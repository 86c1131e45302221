"""A serving tick runs the engine's own programs and nothing beside them.

The manifest's count of small programs a tick (a key split on the host, a
tuple unstacked, a slice taken eagerly: each a launch of its own that cuts the
device's idle gap in two) read 0 on every ledger line of every serving cell
and was retired in PR 52 (``RETIRED`` of ``test_benchmark_manifest.py``).
What it watched is held here, on the CPU: every program a toy engine runs
while it serves must first be compiled, under its own name, and every name
compiled between the first submit and the last tick is one the retired
metric's ``excluding`` pattern took for the engine's
(``tools/describe_idle.ENGINE``, which still prints the count of a capture).
A count of compile requests by name, never a time."""
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import harness  # noqa: E402
from benchmark.tools.describe_idle import ENGINE  # noqa: E402

from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2  # noqa: E402
from deepspeed_tpu.inference.sampling import SamplingParams  # noqa: E402
from deepspeed_tpu.models import get_preset  # noqa: E402
from deepspeed_tpu.models.transformer import init_params  # noqa: E402

COMPILED = []   # (the listener cannot be taken off again: one for the module, read by slices)


def _on(event, secs, fun_name=None, **kw):
    if event.endswith("backend_compile_duration"):
        COMPILED.append(fun_name)


jax.monitoring.register_event_duration_secs_listener(_on)


def _engine(kind):
    if kind == "dense":
        cfg = get_preset("tiny", max_seq_len=128, dtype=jnp.float32)
        params = init_params(jax.random.PRNGKey(0), cfg, dtype=cfg.dtype)
        sizes = dict(prefill_buckets=(16, 32), prefill_chunk=16)
    else:   # the benchmark's ``cfg.latent`` configuration at its rehearsal size
        m = harness.rehearsed(harness.load_json(
            ROOT / "benchmark/configs/dots3_note_l5_e32_serve_1chip.json"), True)
        cfg = harness.module("models", m["model_type"]).transformer_config(
            m, max_seq_len=m["engine"]["max_seq_len"])
        params = init_params(jax.random.PRNGKey(7), cfg)
        sizes = dict(prefill_buckets=(32,), prefill_chunk=32, max_seq_len=256)
    return cfg, InferenceEngineV2(params, cfg, max_seqs=4, num_blocks=64, block_size=8, seed=3,
                                  telemetry=True, **sizes)


def _serve(cfg, eng, temperature, between=None):
    """Chunked packs (cold and over a context), then decode ticks dispatched one
    ahead, from compile caches emptied first: the modules compiled meanwhile, as
    the trace's ``XLA Modules`` line would name them, and the ticks it took."""
    rng = np.random.default_rng(5)
    hi = min(cfg.vocab_size, 255)
    sched = eng.scheduler
    jax.clear_caches()   # a program an earlier test already ran would compile nothing
    first = len(COMPILED)
    for uid, n in enumerate((5, 40, 17)):
        sched.submit(uid + 1, [int(t) for t in rng.integers(1, hi, n)],
                     SamplingParams(temperature=temperature, max_new_tokens=9))
    ticks = 0
    while not sched.idle:
        sched.tick()
        ticks += 1
        if between is not None:
            between(ticks)
        assert ticks < 500
    audit = eng.close()
    assert not any(audit.values()), audit
    return [re.sub(r"^jit\((.*)\)$", r"jit_\1", name) for name in COMPILED[first:]], ticks


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "sampled"])
@pytest.mark.parametrize("kind", ["dense", "latent"])
def test_a_tick_runs_no_program_beside_the_engines_own(kind, temperature):
    modules, ticks = _serve(*_engine(kind), temperature)
    assert ticks >= 10 and modules, (ticks, modules)
    assert {"jit_decode_impl"} < set(modules)   # packs and steps both ran, and were seen
    assert [m for m in modules if not re.search(ENGINE, m)] == []


def test_a_program_beside_the_engines_own_is_seen():
    """The control: one eager operation on a device array between two ticks (what
    a key split on the host was before PR 40) is compiled under a name the
    pattern does not take."""
    carried = jnp.arange(4)

    def stray(tick):
        if tick == 3:
            (carried + tick).block_until_ready()

    modules, _ = _serve(*_engine("dense"), 0.0, between=stray)
    beside = [m for m in modules if not re.search(ENGINE, m)]
    assert len(beside) == 1 and "add" in beside[0], modules
