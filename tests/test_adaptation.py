"""Online autotuning (PR 17): the telemetry-driven controller that retunes
the LIVE serving engine under traffic drift.

The contract under test, layer by layer:

- telemetry: windowed histogram quantiles + counter-rate views (the
  controller's drift signals) are exact and reset cleanly;
- scheduler: ``apply_knobs`` validates at the call site, STAGES under the
  intake lock, and applies only at the tick boundary — ``knob_epoch``
  bumps exactly once per applied batch and a bad batch is dropped whole;
- engine: live-tier knob application is all-or-nothing and re-enabling
  speculation requires a drained scheduler;
- controller: guarded A/B epochs — an injected bad retune must roll back
  and restore the knob; every decision carries its signal snapshot; the
  epoch thread starts/stops idempotently;
- offline registry: ``decode_megastep`` is a first-class knob of
  ``serving_space`` and the roofline (spec pins it to 1, host-tick cost
  amortizes by the fused count);
- wire: the router's per-worker knob push round-trips the socket
  transport with typed refusals;
- lint: importing the controller from a hot path is an astlint violation.
"""
import itertools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.analysis import astlint
from deepspeed_tpu.analysis.schedviz import _stub_scheduler
from deepspeed_tpu.autotuning import roofline, serving_space
from deepspeed_tpu.autotuning.controller import (
    OnlineController,
    attach_controller,
    roofline_rebuild_scorer,
)
from deepspeed_tpu.config.config import (
    AdaptationConfig,
    ConfigError,
    ServeConfig,
)
from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
from deepspeed_tpu.inference.sampling import SamplingParams
from deepspeed_tpu.models import get_preset
from deepspeed_tpu.models.transformer import init_params
from deepspeed_tpu.telemetry import RateView, Telemetry
from deepspeed_tpu.telemetry.registry import MetricsRegistry


@pytest.fixture(scope="module")
def tiny():
    cfg = get_preset("tiny", max_seq_len=128, dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg=cfg, dtype=jnp.float32)
    return cfg, params


def _engine(cfg, params, **kw):
    kw.setdefault("max_seqs", 4)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("block_size", 8)
    kw.setdefault("prefill_buckets", (16, 32, 64))
    kw.setdefault("prefill_budget", 64)
    kw.setdefault("prefill_chunk", 32)
    kw.setdefault("enable_prefix_caching", True)
    return InferenceEngineV2(params, cfg, **kw)


# ---------------------------------------------------------------------------
# telemetry: the drift signals
# ---------------------------------------------------------------------------
def test_histogram_window_views():
    reg = MetricsRegistry()
    h = reg.histogram("serve/ttft_ms")
    for v in (1.0, 2.0, 3.0, 4.0, 100.0):
        h.observe(v)
    assert h.window_count == 5
    q = h.window_quantiles((50, 90))
    assert q["p50"] == 3.0
    assert q["p90"] == 100.0
    assert h.window_mean() == pytest.approx(22.0)
    h.reset()
    assert h.window_count == 0
    assert h.window_quantiles((50,))["p50"] == 0.0


def test_rate_view_counter_rates_and_reset_detection():
    reg = MetricsRegistry()
    c = reg.counter("serve/decode_emitted")
    rv = RateView(c)
    assert rv.sample(0.0) == 0.0  # first sample: no interval yet
    c.inc(100)
    assert rv.sample(2.0) == pytest.approx(50.0)
    c.inc(50)
    assert rv.sample(3.0) > 0.0
    # counter reset (engine rebuild) must not produce a negative rate
    c2 = reg.counter("serve2/decode_emitted")
    rv2 = RateView(c2)
    rv2.sample(0.0)
    c2.inc(10)
    rv2.sample(1.0)
    c2._value = 0  # simulate the reset
    assert rv2.sample(2.0) >= 0.0


# ---------------------------------------------------------------------------
# config: the adaptation block
# ---------------------------------------------------------------------------
def test_adaptation_config_validation():
    AdaptationConfig()  # defaults valid, disabled
    with pytest.raises(ConfigError):
        AdaptationConfig(epoch_s=0.0)
    with pytest.raises(ConfigError):
        AdaptationConfig(guard_epochs=0)
    with pytest.raises(ConfigError):
        AdaptationConfig(regress_tolerance=0.5)
    with pytest.raises(ConfigError):
        AdaptationConfig(ttft_slo_ms=-1.0)
    # ServeConfig coerces a plain dict
    sc = ServeConfig(adaptation={"enabled": True, "epoch_s": 0.1})
    assert isinstance(sc.adaptation, AdaptationConfig)
    assert sc.adaptation.enabled and sc.adaptation.epoch_s == 0.1


# ---------------------------------------------------------------------------
# scheduler: the locked retune surface (host-only stub engine)
# ---------------------------------------------------------------------------
def test_apply_knobs_validates_at_call_site():
    eng, ss = _stub_scheduler()
    with pytest.raises(ValueError, match="unknown"):
        ss.apply_knobs(nonsense=1)
    with pytest.raises(ValueError):  # ConfigError is a ValueError
        ss.apply_knobs(decode_megastep=0)
    with pytest.raises(ValueError):
        ss.apply_knobs(kv_watermark=1.5)
    with pytest.raises(ValueError):
        ss.apply_knobs(prefill_chunk=0)
    # nothing staged by the refused calls
    assert ss._staged_knobs is None and ss.knob_epoch == 0
    eng.close()


def test_apply_knobs_stages_until_tick_boundary():
    eng, ss = _stub_scheduler()
    staged = ss.apply_knobs(decode_megastep=4)
    assert staged == {"decode_megastep": 4}
    # staged, NOT applied: the serve plan and epoch are untouched
    assert ss.serve.decode_megastep == 1 and ss.knob_epoch == 0
    # batches coalesce; the latest value for a knob wins
    ss.apply_knobs(decode_megastep=2, kv_watermark=0.125)
    ss.tick()
    assert ss.knob_epoch == 1
    assert ss.serve.decode_megastep == 2
    assert ss.kv_watermark == 0.125
    k = ss.knobs()
    assert k["decode_megastep"] == 2 and k["knob_epoch"] == 1
    # an empty epoch does not bump
    ss.tick()
    assert ss.knob_epoch == 1
    eng.close()


def test_apply_knobs_bad_batch_dropped_whole_at_boundary():
    eng, ss = _stub_scheduler()
    sp = SamplingParams(temperature=0.0, max_new_tokens=4)
    assert ss.try_submit(1, [1, 2, 3], sp).accepted
    ss.tick()  # request live: spec re-enable must now be refused
    before = ss.knobs()
    ss.apply_knobs(enable_speculation=True, decode_megastep=4)
    ss.tick()  # apply-time failure: batch dropped WHOLE, loop survives
    assert ss.last_knob_error is not None
    assert "drained" in ss.last_knob_error or "idle" in ss.last_knob_error
    after = ss.knobs()
    assert after["decode_megastep"] == before["decode_megastep"]
    assert after["enable_speculation"] is False
    assert ss.knob_epoch == before["knob_epoch"]
    while not ss.idle:
        ss.tick()
    ss.pop_result(1)
    eng.close()


def test_scheduler_signals_shape():
    eng, ss = _stub_scheduler()
    sig = ss.signals()
    for key in ("tick_no", "queue_depth", "running", "shedding",
                "free_blocks", "total_blocks", "headroom_fraction",
                "prefix_hit_rate", "knob_epoch", "preemptions"):
        assert key in sig, key
    assert sig["total_blocks"] > 0
    assert 0.0 <= sig["headroom_fraction"] <= 1.0
    eng.close()


# ---------------------------------------------------------------------------
# engine: live-tier application is all-or-nothing
# ---------------------------------------------------------------------------
def test_engine_apply_knobs_all_or_nothing(tiny):
    cfg, params = tiny
    eng = _engine(cfg, params)
    sched = eng.scheduler
    sp = SamplingParams(temperature=0.0, max_new_tokens=4)
    assert sched.try_submit(1, [1, 2, 3], sp).accepted
    sched.tick()
    chunk = eng.prefill_chunk
    with pytest.raises(ValueError, match="drained"):
        # one bad knob (spec-on while live) refuses the WHOLE batch
        eng.apply_knobs(enable_speculation=True, prefill_chunk=16)
    assert eng.prefill_chunk == chunk and not eng.enable_speculation
    while not sched.idle:
        sched.tick()
    sched.pop_result(1)
    # drained: the same batch now applies
    applied = eng.apply_knobs(enable_speculation=True, prefill_chunk=16)
    assert applied["enable_speculation"] is True
    assert eng.prefill_chunk == 16
    eng.apply_knobs(enable_speculation=False)
    assert eng.close()["blocks_in_use"] == 0


# ---------------------------------------------------------------------------
# controller: guarded A/B retunes on a REAL engine
# ---------------------------------------------------------------------------
def test_controller_rolls_back_injected_bad_retune(tiny):
    cfg, params = tiny
    # time is COUNTED, a millisecond a reading of the clock: a request's TTFT
    # is then the work between its submit and its first token (three times
    # the ticks at a chunk of 8 as at 32), whatever else the machine runs
    readings = itertools.count()
    eng = _engine(cfg, params,
                  telemetry=Telemetry(True, clock=lambda: next(readings) * 1e-3),
                  serve=ServeConfig(adaptation=AdaptationConfig(
                      enabled=True, min_window=2, guard_epochs=1,
                      cooldown_epochs=1, regress_tolerance=1.3,
                      allow_rebuild=False)))
    ctl = attach_controller(eng)
    sched = eng.scheduler
    sp = SamplingParams(temperature=0.0, max_new_tokens=4)
    rng = np.random.default_rng(0)

    def job(uid):
        # UNIQUE prompts: repeats would prefix-cache-hit and hide the
        # crippled chunk entirely
        sched.submit(uid, rng.integers(1, cfg.vocab_size, 48).tolist(), sp)
        while not sched.idle:
            sched.tick()
        sched.pop_result(uid)

    # rehearse BOTH chunk settings so compile time cannot fake a
    # regression, then start a clean measurement window
    for uid, chunk in ((1, 32), (2, 8)):
        sched.apply_knobs(prefill_chunk=chunk)
        job(uid)
    sched.apply_knobs(prefill_chunk=32)
    sched.tick()
    eng.telemetry.reset_window()
    for uid in range(3, 7):  # warm TTFT baseline in the window
        job(uid)
    ctl.inject_retune(_metric="ttft_ms_p90", _better="lower",
                      prefill_chunk=8)
    rollback = None
    for uid in range(10, 34):
        job(uid)
        ctl.step_epoch()
        rollback = next((d for d in ctl.decisions
                         if d["action"] == "rollback"
                         and "prefill_chunk" in d["knobs"]), None)
        if rollback is not None:
            break
    assert rollback is not None, ctl.decisions
    assert rollback["outcome"] == "rolled_back"
    sched.tick()  # land the staged restore
    assert sched.knobs()["prefill_chunk"] == 32
    # every decision carries the signal snapshot that triggered it
    for d in ctl.decisions:
        assert "signals" in d and "knob_epoch" in d["signals"], d
    assert eng.close()["blocks_in_use"] == 0


def test_controller_thread_start_stop_idempotent():
    eng, ss = _stub_scheduler(telemetry=Telemetry(True))
    ctl = OnlineController(
        ss, config=AdaptationConfig(enabled=True, epoch_s=0.005),
        telemetry=eng.telemetry, serve_ns=eng._ns,
        prefill_budget=eng.prefill_budget)
    ctl.start()
    t = ctl._thread
    ctl.start()  # idempotent while running
    assert ctl._thread is t
    deadline = time.time() + 5.0
    while ctl.epoch == 0 and time.time() < deadline:
        time.sleep(0.005)
    assert ctl.epoch > 0, "controller thread never stepped an epoch"
    ctl.stop()
    assert ctl._thread is None
    ctl.stop()  # idempotent after shutdown
    assert ctl.last_error is None
    eng.close()


def test_controller_megastep_climbs_when_decode_bound():
    eng, ss = _stub_scheduler(telemetry=Telemetry(True))
    ctl = OnlineController(
        ss, config=AdaptationConfig(enabled=True, min_window=1,
                                    guard_epochs=1, cooldown_epochs=1,
                                    allow_rebuild=False),
        telemetry=eng.telemetry, serve_ns=eng._ns,
        prefill_budget=eng.prefill_budget)
    sp = SamplingParams(temperature=0.0, max_new_tokens=24)
    for u in range(1, 4):
        assert ss.try_submit(u, [1, 2, 3], sp).accepted
    for _ in range(40):
        if ss.idle:
            break
        ss.tick()
        ctl.step_epoch()
    ups = [d for d in ctl.decisions if d["action"] == "megastep_up"
           and d["outcome"] == "applied"]
    assert ups, ctl.decisions
    assert ss.knobs()["decode_megastep"] > 1
    for u in range(1, 4):
        ss.pop_result(u)
    eng.close()


def test_rebuild_is_proposed_never_executed_by_controller(tiny):
    cfg, params = tiny
    eng, ss = _stub_scheduler(telemetry=Telemetry(True))
    base = {"max_seqs": 4, "num_blocks": 64, "block_size": 8,
            "enable_prefix_caching": True}
    current = {"tp": 1, "serve_replicas": 1, "quant": None}
    scorer = roofline_rebuild_scorer(cfg, base, current, n_devices=1)
    ctl = OnlineController(
        ss, config=AdaptationConfig(enabled=True, min_window=1,
                                    guard_epochs=1, cooldown_epochs=1,
                                    rebuild_hysteresis=1.01),
        telemetry=eng.telemetry, serve_ns=eng._ns,
        prefill_budget=eng.prefill_budget, rebuild_scorer=scorer)
    for _ in range(8):
        ctl.step_epoch()
        if ctl.take_rebuild_proposal() is not None:
            break
    proposals = [d for d in ctl.decisions if d["action"] == "propose_rebuild"]
    # the scorer found a cheaper candidate (int8 weights at least) — the
    # controller PARKED the proposal; the stub engine was never rebuilt
    assert proposals, ctl.decisions
    assert proposals[0]["outcome"] == "proposed"
    assert ctl.take_rebuild_proposal() is None  # pop is one-shot
    eng.close()


# ---------------------------------------------------------------------------
# offline registry: decode_megastep is a first-class knob
# ---------------------------------------------------------------------------
def test_serving_space_registers_decode_megastep():
    space = serving_space()
    names = {k.name for k in space.knobs}
    assert "decode_megastep" in names
    cands = list(space.grid())
    assert any(c["decode_megastep"] > 1 for c in cands)
    # spec pins megastep to 1 (the scheduler collapses it there): the
    # canonicalized grid has NO spec x megastep>1 cross terms
    assert not any(c["spec"] and c["decode_megastep"] > 1 for c in cands)


def test_roofline_megastep_amortizes_host_tick():
    cfg = get_preset("tiny")
    base = {"max_seqs": 8}
    cost = lambda c: roofline.predict_serve_cost(c, cfg, base)
    assert cost({"decode_megastep": 4}) < cost({"decode_megastep": 1})
    assert cost({"decode_megastep": 8}) < cost({"decode_megastep": 4})
    ok, why = roofline.serving_feasible(
        {"tp": 1, "serve_replicas": 1, "decode_megastep": 0}, cfg,
        {"max_seqs": 4, "num_blocks": 64, "block_size": 8}, 8)
    assert not ok and "decode_megastep" in why


# ---------------------------------------------------------------------------
# wire: the router's per-worker knob push
# ---------------------------------------------------------------------------
def test_apply_knobs_over_socket_transport():
    from deepspeed_tpu.config.config import RouterConfig
    from deepspeed_tpu.serving.remote import RemoteWorker
    from deepspeed_tpu.serving.transport import (HeartbeatMonitor,
                                                 RpcClient, WorkerServer,
                                                 dial)

    eng, ss = _stub_scheduler()
    srv = WorkerServer(eng, identity={"worker": 0})
    srv.bind()
    t = threading.Thread(target=srv.serve_socket, daemon=True)
    t.start()
    try:
        c = RpcClient(lambda: dial("127.0.0.1", srv.port, "rpc"))
        reply, _ = c.call({"op": "apply_knobs",
                           "knobs": {"decode_megastep": 4}})
        assert reply["ok"] and reply["staged"] == {"decode_megastep": 4}
        reply, _ = c.call({"op": "tick"})
        reply, _ = c.call({"op": "apply_knobs", "knobs": {}})
        assert reply["ok"] and reply["knobs"]["decode_megastep"] == 4
        # a bad knob surfaces as a TYPED refusal, not a dead worker
        reply, _ = c.call({"op": "apply_knobs",
                           "knobs": {"decode_megastep": 0}})
        assert not reply["ok"]
        assert reply["error"]["kind"] == "internal"
        assert "decode_megastep" in reply["error"]["detail"]
        c.close()
        # the RemoteWorker seam raises the refusal as a ValueError
        mon = HeartbeatMonitor(interval_ms=50.0, lease_ms=1000.0)
        w = RemoteWorker(0, "127.0.0.1", srv.port, mon,
                         config=RouterConfig(n_workers=1))
        with pytest.raises(ValueError, match="refused"):
            w.apply_knobs({"kv_watermark": 2.0})
        assert w.apply_knobs({"kv_watermark": 0.25}) == {
            "kv_watermark": 0.25}
        w.close()
    finally:
        srv.shutdown()
        t.join(timeout=5.0)
        eng.close()


# ---------------------------------------------------------------------------
# lint: the controller must never leak into a hot path
# ---------------------------------------------------------------------------
def test_astlint_flags_controller_import_in_hot_path():
    for src in (
        "from ..autotuning.controller import OnlineController\n",
        "import deepspeed_tpu.autotuning.controller as ctl\n",
        "from ..autotuning import attach_controller\n",
    ):
        out = astlint.lint_source(src, "inference/engine_v2.py")
        assert any(v.rule == "controller-import" for v in out), src
    # benign autotuning imports in hot files stay clean
    ok = astlint.lint_source(
        "from ..autotuning import serving_space\n",
        "inference/engine_v2.py")
    assert not [v for v in ok if v.rule == "controller-import"]
    # the controller import is fine OUTSIDE the hot set
    ok = astlint.lint_source(
        "from .controller import OnlineController\n",
        "autotuning/__init__.py")
    assert not [v for v in ok if v.rule == "controller-import"]
