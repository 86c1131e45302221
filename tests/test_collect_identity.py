"""A collect names its dispatch (PR 55): every ``tick_collect`` carries ``of``,
the ``span_id`` of the dispatch span that enqueued what it fetches, and a
``ready`` mark where the runtime called the program's result defined; it
observes into ``serve/collect_wait_ms`` and is logged when it lasts longer
than ``engine_v2.STALL_LOG_S``.  CPU, tiny models: the NAMES and the counts,
never a time (the one long collect is made long with an injected clock)."""
import collections
import logging
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark import harness  # noqa: E402

from deepspeed_tpu.inference import engine_v2  # noqa: E402
from deepspeed_tpu.inference import scheduler as S  # noqa: E402
from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2  # noqa: E402
from deepspeed_tpu.inference.sampling import SamplingParams  # noqa: E402
from deepspeed_tpu.models import get_preset  # noqa: E402
from deepspeed_tpu.models.transformer import init_params  # noqa: E402
from deepspeed_tpu.telemetry import NULL_SPAN, Telemetry  # noqa: E402
from deepspeed_tpu.utils.logging import logger  # noqa: E402

DISPATCHES = ("prefill_pack", "decode_tick")


def _model(kind):
    if kind == "dense":
        cfg = get_preset("tiny", max_seq_len=128, dtype=jnp.float32)
        return kind, cfg, init_params(jax.random.PRNGKey(0), cfg, dtype=cfg.dtype)
    m = harness.rehearsed(harness.load_json(
        ROOT / "benchmark/configs/dots3_note_l5_e32_serve_1chip.json"), True)
    cfg = harness.module("models", m["model_type"]).transformer_config(
        m, max_seq_len=m["engine"]["max_seq_len"])
    return kind, cfg, init_params(jax.random.PRNGKey(7), cfg)


@pytest.fixture(scope="module")
def dense():
    return _model("dense")


@pytest.fixture(scope="module")
def latent():
    return _model("latent")


def _engine(model, **kw):
    kind, cfg, params = model
    base = dict(max_seqs=4, num_blocks=64, block_size=8, seed=3, telemetry=True)
    if kind == "dense":
        base.update(prefill_buckets=(16, 32), prefill_chunk=16)
    else:
        base.update(prefill_buckets=(32,), prefill_chunk=32, max_seq_len=256)
    base.update(kw)
    return InferenceEngineV2(params, cfg, **base)


def _prompts(model, lens, seed=0):
    rng = np.random.default_rng(seed)
    hi = min(model[1].vocab_size, 255)
    return {u + 1: [int(t) for t in rng.integers(1, hi, n)] for u, n in enumerate(lens)}


def _serve(eng, prompts, samp, between=None, late=None):
    """Submit ``prompts`` (``late``: {call: uid} submitted after that call
    instead), tick until idle: {uid: tokens}."""
    sched, late = eng.scheduler, dict(late or {})
    param = lambda u: samp[u] if isinstance(samp, dict) else samp
    for u, p in prompts.items():
        if u not in late.values():
            sched.submit(u, p, param(u))
    n = 0
    while not sched.idle or late:
        sched.tick()
        n += 1
        if n in late:
            u = late.pop(n)
            sched.submit(u, prompts[u], param(u))
        if between is not None:
            between(eng, n)
        assert n < 500
    return {u: sched.result(u) for u in prompts}


def _spans(eng):
    return [e for e in eng.telemetry.recorder.chrome_events() if e.get("ph") == "X"]


def _identity(spans):
    """The invariants of a run's spans; returns (collects, dispatch spans by
    id, the ids a collect names)."""
    dispatches = {e["args"]["span_id"]: e for e in spans if e["name"] in DISPATCHES}
    collects = [e for e in spans if e["name"] == "tick_collect"]
    assert collects
    for c in collects:
        args = c["args"]
        # ... of a dispatch span of the kind its ``what`` names
        assert args["of"] in dispatches, args
        assert dispatches[args["of"]]["name"] == args["what"]
        # ... that was opened before it and left its fetch to it
        assert dispatches[args["of"]]["ts"] < c["ts"]
        assert dispatches[args["of"]]["args"]["synced"] is False
        assert 0.0 <= args["ready_ms"] <= c["dur"] * 1e-3 + 1e-3, args
    named = collections.Counter(c["args"]["of"] for c in collects)
    assert set(named.values()) == {1}  # at most one collect a dispatch
    return collects, dispatches, set(named)


# ---------------------------------------------------------------------------
# the run of the issue: a mixed tick, a drain, a dead row, a preemption
# ---------------------------------------------------------------------------
def _mixed(model):
    """Request 2 arrives while request 1 decodes: its pack carries the step."""
    eng = _engine(model)
    _serve(eng, _prompts(model, (6, 9)), SamplingParams(max_new_tokens=8), late={3: 2})
    assert eng.stats["mixed_dispatches"] >= 1
    return eng


def _drain(model):
    def act(eng, n):
        if n == 4:
            assert eng.scheduler._inflight and eng.scheduler.cancel(2)

    eng = _engine(model)
    _serve(eng, _prompts(model, (6, 9, 7)), SamplingParams(max_new_tokens=10), between=act)
    assert eng.scheduler.drains.get("cancel", 0) >= 1
    assert eng.scheduler.requests[2].state == S.CANCELLED
    return eng


def _dead_row(model):
    """A stop token is seen one call after the next step went out: that
    step's row is dead, and its collect still names the step's span."""
    prompts = _prompts(model, (6, 9))
    free = SamplingParams(max_new_tokens=12)
    clean = _serve(_engine(model), prompts, free)
    samp = {1: SamplingParams(max_new_tokens=12, stop_token=clean[1][4]), 2: free}
    eng = _engine(model)
    _serve(eng, prompts, samp)
    assert eng.stats["ahead_rows_dropped"] == 1
    return eng


def _preemption(model):
    eng = _engine(model, num_blocks=11, kv_watermark=0.0)
    _serve(eng, _prompts(model, (14, 15, 13)), SamplingParams(max_new_tokens=24))
    assert eng.scheduler.stats["preemptions"] >= 1 and eng.scheduler.drains
    return eng


def _enqueued_at_close(model):
    """A step still enqueued when the engine closes: ``close()`` collects."""
    eng = _engine(model)
    sched = eng.scheduler
    sched.submit(1, _prompts(model, (6,))[1], SamplingParams(max_new_tokens=8))
    for _ in range(3):
        sched.tick()
    assert sched._inflight
    return eng


RUNS = {"mixed": _mixed, "drain": _drain, "dead_row": _dead_row,
        "preemption": _preemption, "enqueued_at_close": _enqueued_at_close}


@pytest.mark.parametrize("run", sorted(RUNS))
def test_every_collect_names_the_dispatch_span_it_collects(dense, run):
    eng = RUNS[run](dense)
    hist = eng.telemetry.registry.histogram("serve/collect_wait_ms")
    before_close = hist.count, len([e for e in _spans(eng) if e["name"] == "tick_collect"])
    assert not any(eng.close().values())
    spans = _spans(eng)
    collects, dispatches, named = _identity(spans)
    # a dispatch that fetched inside its own span (a drained tick, today's
    # order) is named by no collect, and every split one that was fetched is
    for i, d in dispatches.items():
        if d["args"].get("synced", True):
            assert i not in named
    assert {c["args"]["what"] for c in collects} <= set(DISPATCHES)
    # one observation a collect, whatever the run held (``close()`` drops
    # the engine's histograms with its namespace)
    assert before_close[0] == before_close[1] > 0


@pytest.mark.parametrize("kind", ["dense", "latent"])
def test_a_pack_that_completes_no_prompt_is_named_by_no_collect(kind, request):
    model = request.getfixturevalue(kind)
    chunk = 16 if kind == "dense" else 32
    eng = _engine(model)
    # one prompt of two whole chunks and a part: the third pack completes it
    _serve(eng, _prompts(model, (2 * chunk + 5,)), SamplingParams(max_new_tokens=4))
    assert not any(eng.close().values())
    collects, dispatches, named = _identity(_spans(eng))
    packs = sorted(i for i, d in dispatches.items() if d["name"] == "prefill_pack")
    assert len(packs) == 3
    assert [i in named for i in packs] == [False, False, True]
    ticks = [i for i, d in dispatches.items() if d["name"] == "decode_tick"]
    assert ticks and all(i in named for i in ticks)


def test_a_mixed_ticks_one_collect_names_the_pack(dense):
    eng = _mixed(dense)
    eng.close()
    collects, dispatches, _ = _identity(_spans(eng))
    mixed = [i for i, d in dispatches.items() if d["args"].get("step_rows")]
    assert mixed
    by = {c["args"]["of"]: c for c in collects}
    assert all(by[i]["args"]["what"] == "prefill_pack" for i in mixed)


def test_the_mirror_of_a_collect_carries_of(dense, monkeypatch):
    """With ``jax_profiler`` on ``Telemetry.span`` mirrors scalar arguments:
    the capture's copy of a collect names its dispatch with no further code."""
    seen = []

    class Annotation:
        def __init__(self, name, **kw):
            seen.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            pass

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    eng = _engine(dense, telemetry=Telemetry(enabled=True, jax_profiler=True))
    _serve(eng, _prompts(dense, (6,)), SamplingParams(max_new_tokens=4))
    eng.close()
    mirrors = [kw for name, kw in seen if name == "tick_collect"]
    ids = {kw["span_id"] for name, kw in seen if name in DISPATCHES}
    assert mirrors and all(kw["of"] in ids and kw["what"] in DISPATCHES for kw in mirrors)


def test_telemetry_off_fetches_the_same_tokens_and_records_nothing(dense):
    prompts = _prompts(dense, (6, 20))
    samp = SamplingParams(max_new_tokens=8)
    on, off = _engine(dense), _engine(dense, telemetry=False)
    assert _serve(on, prompts, samp) == _serve(off, prompts, samp)
    assert off.stats["dispatched_ahead"] == on.stats["dispatched_ahead"] > 0
    assert len(off.telemetry.recorder) == 0
    assert off.telemetry.registry.snapshot() == []
    # the dispatch span an ``Enqueued`` holds is the shared null span
    a = off.mgr.admit(9, prompts[1])
    off.mgr.ensure_capacity(a, 0)
    done = off.pack_dispatch([(a, 0, len(prompts[1]))], samp, split=True, ahead=True)
    assert done.by is NULL_SPAN and done.by.id is None
    first = {}
    off.pack_collect(done, first)
    assert set(first) == {9} and len(off.telemetry.recorder) == 0
    off.flush([9])
    assert not any(on.close().values()) and not any(off.close().values())


# ---------------------------------------------------------------------------
# the line a long collect logs
# ---------------------------------------------------------------------------
class _Clock:
    """``perf_counter`` plus a skew that the next calls add to, one each."""

    def __init__(self):
        self.skew, self.script = 0.0, []

    def __call__(self):
        if self.script:
            self.skew += self.script.pop(0)
        return time.perf_counter() + self.skew


class _Lines(logging.Handler):
    def __init__(self):
        super().__init__(logging.WARNING)
        self.lines = []

    def emit(self, record):
        self.lines.append(record.getMessage())


@pytest.fixture
def warnings():
    h = _Lines()
    logger.addHandler(h)
    yield h.lines
    logger.removeHandler(h)


def test_a_long_collect_is_logged_once_with_its_ready_split(dense, warnings):
    clock = _Clock()
    eng = _engine(dense, telemetry=Telemetry(enabled=True, clock=clock))
    samp = SamplingParams(max_new_tokens=8)
    a = eng.mgr.admit(1, _prompts(dense, (6,))[1])
    eng.mgr.ensure_capacity(a, 0)
    first = {}
    eng.pack_collect(eng.pack_dispatch([(a, 0, 6)], samp, split=True, ahead=True), first)
    assert not warnings  # a plain collect says nothing
    done = eng.decode_dispatch([a], samp, split=True, ahead=True)
    # the collect's three readings: it opens, 1.5 s to ``ready``, 0.25 s more
    clock.script = [0.0, 1.5, 0.25]
    assert set(eng.decode_collect(done)) == {1}
    done = eng.decode_dispatch([a], samp, split=True, ahead=True)
    eng.decode_collect(done)
    assert len(warnings) == 1, warnings
    line = warnings[0]
    tick = [e for e in _spans(eng) if e["name"] == "decode_tick"][0]
    assert f"tick_collect of decode_tick #{tick['args']['span_id']}: " in line
    total, ready = (float(x) for x in
                    line.split(": ")[1].replace("ms, ready after ", "").split(" ms")[0].split())
    assert 1750.0 <= total < 1750.0 + 1e3 * engine_v2.STALL_LOG_S
    assert 1500.0 <= ready <= total - 250.0
    long = [e for e in _spans(eng) if e["name"] == "tick_collect"][1]
    assert long["args"]["ready_ms"] == pytest.approx(ready, abs=0.1)
    eng.flush([1])
    assert not any(eng.close().values())


def test_plain_runs_log_no_collect(dense, warnings):
    for run in (_mixed, _drain, _preemption):
        run(dense).close()
    assert not [w for w in warnings if "tick_collect" in w]
