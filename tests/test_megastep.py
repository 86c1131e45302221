"""Megastep decode (PR 16): device-resident multi-tick serving.

The contract under test: fusing up to ``decode_megastep`` decode-only
ticks into ONE engine burst (one host sync at the burst boundary, stop
detection ON DEVICE) is an invisible optimization — greedy token identity
with per-tick decode, exact stop/max-len truncation mid-burst, and the
full fault-tolerance surface (cancel, deadline, NaN quarantine, zero-leak
teardown) intact at megastep boundaries."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.config.config import ConfigError, RouterConfig, ServeConfig
from deepspeed_tpu.inference import (
    FaultInjector,
    InferenceEngineV2,
    SamplingParams,
)
from deepspeed_tpu.inference import scheduler as S
from deepspeed_tpu.models import get_preset
from deepspeed_tpu.models.transformer import init_params


@pytest.fixture(scope="module")
def tiny():
    # fp32 so greedy token identity cannot flip on bf16 near-ties
    cfg = get_preset("tiny", max_seq_len=128, dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg=cfg, dtype=jnp.float32)
    return cfg, params


def _engine(cfg, params, megastep=1, **kw):
    kw.setdefault("max_seqs", 4)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("block_size", 8)
    kw.setdefault("prefill_buckets", (16, 32, 64))
    kw.setdefault("enable_prefix_caching", True)
    serve = dict(kw.pop("serve", {}))
    serve.setdefault("decode_megastep", megastep)
    serve.setdefault("retry_backoff_ms", 0.0)
    return InferenceEngineV2(params, cfg, serve=serve, **kw)


def _prompts(cfg, n=4, seed=0, shared=12, sfx=4):
    rng = np.random.default_rng(seed)
    sys_prompt = rng.integers(1, cfg.vocab_size, shared).tolist()
    return {u: sys_prompt + rng.integers(1, cfg.vocab_size, sfx).tolist()
            for u in range(1, n + 1)}


def _serve(eng, prompts, samp):
    sched = eng.scheduler
    for u, p in prompts.items():
        assert sched.try_submit(u, p, samp).accepted
    sched.run()
    out = {u: sched.pop_result(u) for u in prompts}
    return out


def _close_leakfree(eng):
    audit = eng.close()
    assert audit["blocks_in_use"] == 0, audit


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------
def test_megastep_config_validation():
    assert ServeConfig(decode_megastep=8).decode_megastep == 8
    with pytest.raises(ConfigError):
        ServeConfig(decode_megastep=0)
    assert RouterConfig(decode_megastep=4).decode_megastep == 4
    with pytest.raises(ConfigError):
        RouterConfig(decode_megastep=-1)


# ---------------------------------------------------------------------------
# the headline gate: megastep decode is greedily token-identical
# ---------------------------------------------------------------------------
def test_megastep_matches_per_tick_greedy(tiny):
    """The tier-1 in-proc identity gate: decode_megastep=4 over a prefix-
    cached arrival workload produces byte-identical greedy results to the
    per-tick baseline, actually fuses bursts, and tears down leak-free."""
    cfg, params = tiny
    samp = SamplingParams(temperature=0.0, max_new_tokens=10)
    prompts = _prompts(cfg, n=4)

    eng1 = _engine(cfg, params, megastep=1)
    want = _serve(eng1, prompts, samp)
    assert all(len(t) == 10 for t in want.values())
    assert eng1.stats["decode_bursts"] == 0
    _close_leakfree(eng1)

    eng4 = _engine(cfg, params, megastep=4)
    got = _serve(eng4, prompts, samp)
    assert got == want, "megastep decode diverged from per-tick greedy"
    stats = dict(eng4.stats)
    assert stats["decode_bursts"] > 0, "megastep run never fused a burst"
    assert stats["burst_ticks"] > stats["decode_bursts"], (
        "bursts fused no extra ticks")
    _close_leakfree(eng4)


def test_megastep_identity_quantized(tiny):
    """int8 weight-quantized serving path under megastep: identical to the
    per-tick quantized run (the quantized jit twin compiles the same burst
    graph)."""
    cfg, params = tiny
    samp = SamplingParams(temperature=0.0, max_new_tokens=8)
    prompts = _prompts(cfg, n=3, seed=1)

    eng1 = _engine(cfg, params, megastep=1, quantize_weights="int8")
    want = _serve(eng1, prompts, samp)
    _close_leakfree(eng1)

    eng4 = _engine(cfg, params, megastep=4, quantize_weights="int8")
    got = _serve(eng4, prompts, samp)
    assert got == want
    assert eng4.stats["decode_bursts"] > 0
    _close_leakfree(eng4)


def test_megastep_identity_tp2(tiny):
    """Megastep under tensor parallelism: the burst jit carries the same
    out-sharding pins as per-tick decode, so tp=2 greedy results stay
    identical too."""
    from deepspeed_tpu.parallel.topology import initialize_mesh

    cfg, params = tiny
    gqa = cfg.replace(num_heads=4, num_kv_heads=2, hidden_size=64,
                      intermediate_size=128)
    gparams = init_params(jax.random.PRNGKey(1), cfg=gqa, dtype=jnp.float32)
    samp = SamplingParams(temperature=0.0, max_new_tokens=8)
    prompts = _prompts(gqa, n=3, seed=2)

    def run(megastep):
        grid = initialize_mesh(devices=jax.devices()[:2], model=2)
        eng = _engine(gqa, gparams, megastep=megastep, grid=grid)
        out = _serve(eng, prompts, samp)
        bursts = eng.stats["decode_bursts"]
        _close_leakfree(eng)
        return out, bursts

    want, _ = run(1)
    got, bursts = run(4)
    assert got == want
    assert bursts > 0


# ---------------------------------------------------------------------------
# on-device termination mid-burst: stop token and length caps
# ---------------------------------------------------------------------------
def test_megastep_stop_token_mid_burst(tiny):
    """A per-request stop token that fires in the MIDDLE of a fused burst
    must truncate exactly where per-tick decode stops — the on-device mask
    freezes the row, the host commits nothing past the stop."""
    cfg, params = tiny
    prompts = _prompts(cfg, n=2, seed=3)

    # free-run first to learn each request's actual 3rd greedy token, then
    # replay with that token as the stop — it fires mid-burst (tick 3 of 4)
    free = SamplingParams(temperature=0.0, max_new_tokens=10)
    eng0 = _engine(cfg, params, megastep=1)
    ref = _serve(eng0, prompts, free)
    _close_leakfree(eng0)
    stop = ref[1][2]

    samp = SamplingParams(temperature=0.0, max_new_tokens=10,
                          stop_token=int(stop))
    eng1 = _engine(cfg, params, megastep=1)
    want = _serve(eng1, prompts, samp)
    _close_leakfree(eng1)

    eng4 = _engine(cfg, params, megastep=4)
    got = _serve(eng4, prompts, samp)
    assert got == want, "stop-token truncation diverged under megastep"
    # request 1 really stopped early AND exactly (stop stripped by result())
    assert got[1] == ref[1][:2], (got[1], ref[1])
    assert eng4.stats["decode_bursts"] > 0
    _close_leakfree(eng4)


def test_megastep_max_new_tokens_mid_burst(tiny):
    """Per-request emission caps that land mid-burst (max_new_tokens not a
    multiple of the fuse count, and DIFFERENT per request) must yield
    exactly-capped results: the caps ride the burst on device."""
    cfg, params = tiny
    prompts = _prompts(cfg, n=3, seed=4)
    budgets = {1: 3, 2: 5, 3: 9}

    def run(megastep):
        eng = _engine(cfg, params, megastep=megastep)
        sched = eng.scheduler
        for u, p in prompts.items():
            assert sched.try_submit(
                u, p, SamplingParams(temperature=0.0,
                                     max_new_tokens=budgets[u])).accepted
        sched.run()
        out = {u: sched.pop_result(u) for u in prompts}
        bursts = eng.stats["decode_bursts"]
        _close_leakfree(eng)
        return out, bursts

    want, _ = run(1)
    got, bursts = run(4)
    assert got == want
    assert bursts > 0
    assert {u: len(t) for u, t in got.items()} == budgets


def test_megastep_max_seq_len_mid_burst(tiny):
    """The engine length cap hitting mid-burst freezes the row on device:
    the sequence never grows past max_seq_len and the results match the
    per-tick run exactly."""
    cfg, params = tiny
    samp = SamplingParams(temperature=0.0, max_new_tokens=32)
    prompts = {1: list(range(2, 18))}  # 16 prompt tokens

    def run(megastep):
        eng = _engine(cfg, params, megastep=megastep, max_seq_len=24)
        out = _serve(eng, prompts, samp)
        _close_leakfree(eng)
        return out

    want = run(1)
    got = run(4)
    assert got == want
    # prompt 16 + first prefill token + 7 decode ticks = 24 = max_seq_len
    assert len(got[1]) == 8


# ---------------------------------------------------------------------------
# fault tolerance at megastep boundaries
# ---------------------------------------------------------------------------
def test_megastep_chaos_cancel_deadline_storm(tiny):
    """Cancels, deadlines, and injected NaN rows landing against a
    megastep-fused scheduler: every request reaches exactly one terminal
    state, the poisoned row quarantines without dragging its batchmates,
    and the pool drains to zero."""
    cfg, params = tiny
    inj = FaultInjector(seed=7).arm("nan_logits", uids=[5], times=1)
    eng = _engine(cfg, params, megastep=4, faults=inj,
                  serve=dict(deadline_ms=60_000.0))
    sched = eng.scheduler
    prompts = _prompts(cfg, n=8, seed=5)
    samp = SamplingParams(temperature=0.0, max_new_tokens=12)
    for u, p in prompts.items():
        dl = 0.5 if u == 7 else None  # request 7: deadline expires mid-run
        assert sched.try_submit(u, p, samp, deadline_ms=dl).accepted
    for _ in range(3):
        sched.tick()
    # cancels land between megasteps (the documented reaction boundary)
    assert sched.cancel(2)
    assert sched.cancel(8)
    sched.run()
    states = {u: sched.requests[u].state for u in prompts}
    assert all(s in S.TERMINAL for s in states.values()), states
    assert states[2] == S.CANCELLED and states[8] == S.CANCELLED
    assert states[5] == S.FAILED  # the quarantined NaN row
    assert states[7] == S.TIMED_OUT
    healthy = [u for u in prompts if u not in (2, 5, 7, 8)]
    assert all(states[u] == S.FINISHED for u in healthy), states
    # healthy survivors are token-identical to a fault-free per-tick run
    ref_eng = _engine(cfg, params, megastep=1)
    for u in healthy:
        assert sched.pop_result(u) == _serve(
            ref_eng, {u: prompts[u]}, samp)[u], u
    for u in (2, 5, 7, 8):
        sched.pop_result(u)
    _close_leakfree(ref_eng)
    _close_leakfree(eng)


def test_megastep_collapses_under_mixed_work(tiny):
    """Adaptive collapse: while a running request is still mid-PREFILL
    (chunked prompt spanning ticks) the plan stays per-tick, so the late
    arrival's TTFT is never stalled behind a long burst; once the tick is
    decode-only, fusing resumes."""
    cfg, params = tiny
    eng = _engine(cfg, params, megastep=8, prefill_chunk=16)
    sched = eng.scheduler
    samp = SamplingParams(temperature=0.0, max_new_tokens=6)
    rng = np.random.default_rng(6)
    assert sched.try_submit(
        1, rng.integers(1, cfg.vocab_size, 8).tolist(), samp).accepted
    sched.tick()  # prefill: no decode rows yet, nothing fused
    assert eng.stats["decode_bursts"] == 0
    # a long chunked arrival: PREFILL spans ticks, pinning decode per-tick
    assert sched.try_submit(
        2, rng.integers(1, cfg.vocab_size, 40).tolist(), samp).accepted
    before = eng.stats["decode_bursts"]
    for _ in range(2):  # 40-token prompt at chunk 16: >= 2 mid-prefill ticks
        sched.tick()
        assert eng.stats["decode_bursts"] == before, (
            "megastep fused while a request was mid-prefill")
    sched.run()
    out = {u: sched.pop_result(u) for u in (1, 2)}
    assert all(len(t) == 6 for t in out.values())
    assert eng.stats["decode_bursts"] > 0  # fused once decode-only
    _close_leakfree(eng)
