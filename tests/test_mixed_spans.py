"""The spans of a mixed tick still pair (PR 54, ISSUE 54 point 6): the
benchmark matches a dispatch span to its collect through the span that BOOKS
it, one booking a dispatch, oldest first (``benchmark.xprograms.returned``).
A pack that carries a step is ONE ``prefill_pack`` span, one ``tick_collect``
and one ``engine.pack_emit``, which books the step's rows too: no
``engine.decode_emit`` without a ``decode_tick``, so every ``decode_tick`` of
a recorded run (mixed, decode-only, mixed ...) is paired with its own collect
and its own execution.  CPU: the ORDER of recorded spans; the device's
executions are laid out from it."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark import xprograms  # noqa: E402
from benchmark.xplane import HostEvent  # noqa: E402

from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2  # noqa: E402
from deepspeed_tpu.inference.sampling import SamplingParams  # noqa: E402
from deepspeed_tpu.models import get_preset  # noqa: E402
from deepspeed_tpu.models.transformer import init_params  # noqa: E402

STRETCH = 1e3  # a CPU tick of a ms as a tick of a second: the pairing's slack is a chip's


@pytest.fixture(scope="module")
def recorded():
    """A scheduler run whose executions are mixed, decode-only, mixed, ...:
    request 1 decodes while 2 and then 3 arrive.  Returns the spans as host
    events (start order) and the TRUTH the engine knows: dispatch span id ->
    the end of the collect that fetched that very handle."""
    cfg = get_preset("tiny", max_seq_len=128, dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=cfg.dtype)
    eng = InferenceEngineV2(params, cfg, max_seqs=4, num_blocks=64, block_size=8, seed=3,
                            telemetry=True, prefill_buckets=(16, 32), prefill_chunk=16)
    rec = eng.telemetry.recorder
    last = lambda name: [e for e in rec.chrome_events()
                         if e.get("ph") == "X" and e["name"] == name][-1]
    handles, truth = {}, {}
    for attr, span in (("pack_dispatch", "prefill_pack"), ("decode_dispatch", "decode_tick")):
        def dispatch(*a, real=getattr(eng, attr), span=span, **kw):
            done = real(*a, **kw)
            handles[id(done)] = (done, last(span)["args"]["span_id"])
            return done
        setattr(eng, attr, dispatch)
    fetched = eng._fetched

    def fetch(done):
        waiting = done.sampled is not None
        out = fetched(done)
        if waiting:
            e = last("tick_collect")
            truth[handles[id(done)][1]] = (e["ts"] + e["dur"]) * 1e-6 * STRETCH
        return out

    eng._fetched = fetch
    sched = eng.scheduler
    rng = np.random.default_rng(4)
    prompt = lambda n: [int(t) for t in rng.integers(1, 255, n)]
    samp = SamplingParams(max_new_tokens=16)
    sched.submit(1, prompt(6), samp)
    for n in range(60):
        if n == 3:
            sched.submit(2, prompt(10), samp)   # one cold chunk
        if n == 7:
            sched.submit(3, prompt(40), samp)   # three chunks, two of them on cached pages
        sched.tick()
    assert sched.idle and eng.stats["ahead_drains"] == 0
    events = [e for e in rec.chrome_events() if e.get("ph") == "X"]
    hosts = sorted((HostEvent(e["name"], e["ts"] * 1e-6 * STRETCH,
                              (e["ts"] + e["dur"]) * 1e-6 * STRETCH, e["args"])
                    for e in events), key=lambda h: (h.start, -h.end))
    stats = dict(eng.stats)
    assert not any(eng.close().values())
    return hosts, truth, stats


def _kinds(hosts):
    """'M' a pack that carried live rows, 'P' a pack alone, 'D' a step alone."""
    out = []
    for h in hosts:
        if h.name == "prefill_pack":
            out.append("M" if h.stats["step_rows"] else "P")
        elif h.name == "decode_tick":
            out.append("D")
    return "".join(out)


def test_the_recorded_run_is_mixed_decode_only_mixed(recorded):
    hosts, truth, stats = recorded
    kinds = _kinds(hosts)
    assert "MDDDMMM" in kinds.replace("P", ""), kinds  # 2 arrives; steps; 3's three chunks
    assert kinds.count("M") == stats["mixed_dispatches"] == 4
    assert kinds.count("D") == stats["decode_ticks"] - stats["mixed_dispatches"]
    names = [h.name for h in hosts]
    # one booking a dispatch, under the dispatch's own name: the step a pack
    # carried is booked inside the pack's emit
    assert names.count("engine.pack_emit") == names.count("prefill_pack")
    assert names.count("engine.decode_emit") == names.count("decode_tick")
    assert names.count("tick_collect") == len(truth)


def test_every_dispatch_is_returned_by_its_own_collect(recorded):
    hosts, truth, _ = recorded
    at = xprograms.returned(hosts)
    assert at == truth
    steps = [h for h in hosts if h.name == "decode_tick"]
    assert steps and all(int(h.stats["span_id"]) in at for h in steps)
    for h in hosts:  # a collect returns after its dispatch closed, never before
        if int(h.stats.get("span_id", -1)) in at and h.name in ("prefill_pack", "decode_tick"):
            assert at[int(h.stats["span_id"])] > h.end


@pytest.mark.parametrize("span,module", [("decode_tick", "jit_decode_impl"),
                                         ("prefill_pack", "jit_packed_ctx_impl")])
def test_every_dispatch_pairs_with_its_own_execution(recorded, span, module):
    """One device stream laid out from the recorded order: a program runs
    from when the one before it ended (or its dispatch closed) until just
    before its collect returned.  ``pair`` finds each span's own."""
    hosts, truth, _ = recorded
    at = xprograms.returned(hosts)
    free, runs, own = 0.0, [], {}
    for h in hosts:
        if h.name in ("prefill_pack", "decode_tick"):
            i = int(h.stats["span_id"])
            start = max(h.end, free) + 1e-4
            free = max(truth.get(i, start + 0.2) - 1e-4, start + 1e-4)  # (a chunk alone: never fetched)
            if h.name == span:
                own[i] = len(runs)
                runs.append(xprograms.Execution(module, len(runs), start, free))
    mine = [h for h in hosts if h.name == span]
    found = xprograms.pair(mine, runs, 0.010, at)
    fetched = [h for h in mine if int(h.stats["span_id"]) in at
               or not int(h.stats.get("ahead", 0))]
    assert len(found) == len(fetched) > 3
    for h, e in found:
        assert e.run_id == own[int(h.stats["span_id"])]
