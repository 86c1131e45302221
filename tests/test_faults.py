"""Fault-tolerant serving: typed lifecycle states, deadlines, cancellation,
per-request failure isolation, NaN quarantine, transient retry, shed-mode
degradation, watchdog, and the seeded chaos storm (the acceptance suite for
the fault-injection harness in inference/faults.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import (
    FaultInjector,
    InferenceEngineV2,
    InjectedFault,
    SamplingParams,
    finite_guard,
    is_transient,
)
from deepspeed_tpu.inference.faults import is_compile_error
from deepspeed_tpu.inference import scheduler as S
from deepspeed_tpu.models import get_preset
from deepspeed_tpu.models.transformer import init_params


@pytest.fixture(scope="module")
def tiny():
    # fp32 so greedy parity cannot flip on bf16 near-ties
    cfg = get_preset("tiny", max_seq_len=128, dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg=cfg, dtype=jnp.float32)
    return cfg, params


def _engine(cfg, params, **kw):
    kw.setdefault("max_seqs", 4)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("block_size", 8)
    kw.setdefault("prefill_buckets", (16, 32, 64))
    kw.setdefault("serve", dict(retry_backoff_ms=0.0))
    return InferenceEngineV2(params, cfg, **kw)


@pytest.fixture(scope="module")
def ref_engine(tiny):
    """One shared fault-free engine for reference generations — each
    isolation test compares its healthy survivors against this instead of
    building its own baseline engine (sequential generates are independent:
    the scheduler pops every request)."""
    cfg, params = tiny
    return _engine(cfg, params)


def _leakfree(eng):
    alloc = eng.mgr.allocator
    alloc.audit()
    assert not eng.mgr.seqs, eng.mgr.seqs
    in_use = sum(1 for b in range(alloc.total_blocks) if alloc.refcount(b) > 0)
    assert in_use == 0
    assert alloc.free_blocks + alloc.cached_blocks == alloc.total_blocks


# ---------------------------------------------------------------------------
# injector + classifier + finite guard units
# ---------------------------------------------------------------------------
def test_injector_deterministic_seeded_and_budgeted():
    def fires(seed):
        inj = FaultInjector(seed=seed).arm("runner_exception", p=0.3)
        out = []
        for i in range(50):
            try:
                inj.maybe_raise("runner_exception", uids=(i,))
                out.append(0)
            except InjectedFault:
                out.append(1)
        return out

    assert fires(0) == fires(0)  # same seed replays exactly
    assert fires(0) != fires(7)  # different seed, different storm
    # times budget: fires exactly N times, then never again
    inj = FaultInjector().arm("nan_logits", times=2)
    hit = [inj.select("nan_logits", [1, 2]) for _ in range(4)]
    assert hit[0] == [1, 2] and hit[1] == [] and inj.fired("nan_logits") == 2
    # uid scoping: only the targeted request fires
    inj = FaultInjector().arm("runner_exception", uids=[9])
    inj.maybe_raise("runner_exception", uids=(1, 2))  # no overlap: no fire
    with pytest.raises(InjectedFault) as e:
        inj.maybe_raise("runner_exception", uids=(2, 9))
    assert e.value.ctx["uids"] == (2, 9)
    # slow_tick delay + the log records every firing
    inj = FaultInjector().arm("slow_tick", delay_s=0.25, times=1)
    assert inj.delay("slow_tick") == 0.25 and inj.delay("slow_tick") == 0.0
    assert inj.fired() == 1
    # disabled injector is inert
    inj = FaultInjector(enabled=False).arm("runner_exception")
    inj.maybe_raise("runner_exception", uids=(1,))
    assert inj.fired() == 0
    with pytest.raises(ValueError):
        FaultInjector().arm("not_a_point")


def test_transient_classifier():
    assert is_transient(InjectedFault("runner_exception", transient=True))
    assert not is_transient(InjectedFault("runner_exception"))
    assert is_transient(RuntimeError("RESOURCE_EXHAUSTED: out of semaphores"))
    assert is_transient(RuntimeError("device_put transfer stalled"))
    assert not is_transient(RuntimeError("cannot allocate 3 blocks"))
    assert not is_transient(ValueError("bad prompt"))
    # XLA reports HBM/VMEM exhaustion AT COMPILE as RESOURCE_EXHAUSTED too:
    # deterministic, so never transient
    hbm = RuntimeError(
        "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
        "memory in memory space hbm. Used 17.2G of 15.48G hbm.")
    mosaic = RuntimeError("INTERNAL: Mosaic failed to compile TPU kernel: "
                          "scoped vmem limit exceeded")
    for e in (hbm, mosaic):
        assert is_compile_error(e) and not is_transient(e)
    assert not is_compile_error(RuntimeError("RESOURCE_EXHAUSTED: out of semaphores"))
    assert not is_compile_error(InjectedFault("runner_exception"))


def test_compile_failure_stops_the_serve_loop(tiny):
    """A dispatch that fails to COMPILE fails identically for every request
    and every retry: ``run()`` raises it instead of retrying, probing each
    request solo and quarantining the queue behind an exit code 0."""
    cfg, params = tiny
    eng = _engine(cfg, params)
    sched = eng.scheduler
    for u in (1, 2, 3):
        sched.submit(u, [5, 6, 7, 8, 9], SamplingParams(max_new_tokens=4))

    def boom(*a, **kw):
        raise RuntimeError("INTERNAL: Mosaic failed to compile TPU kernel: "
                           "Ran out of memory in memory space vmem")

    def assert_untouched():
        assert not any(r.state == "failed" for r in sched.requests.values())
        assert eng.stats["retries"] == 0 and eng.stats["failed"] == 0
        assert eng.stats["isolation_probes"] == 0

    # (PR 43: the scheduler enqueues through the split bodies on an engine
    # that offers them, and through the back-to-back ones when it drains: a
    # program that cannot compile fails under both names)
    real_prefill, real_dispatch = eng.prefill_entries, eng.prefill_dispatch
    eng.prefill_entries = eng.prefill_dispatch = boom  # the pack cannot compile
    with pytest.raises(RuntimeError, match="Mosaic failed to compile"):
        sched.run()
    assert_untouched()
    eng.prefill_entries, eng.prefill_dispatch = real_prefill, real_dispatch
    eng._decode_tick = eng.decode_dispatch = boom  # now the decode program
    with pytest.raises(RuntimeError, match="Mosaic failed to compile"):
        sched.run()  # (its first call enqueues the pack, then the step)
    assert_untouched()


def test_finite_guard_sentinels_nonfinite_rows():
    logits = jnp.array([[0.1, 0.9, 0.2], [0.5, jnp.nan, 0.1],
                        [jnp.inf, 0.0, 0.0], [0.3, 0.2, 0.1]])
    sampled = jnp.array([1, 0, 0, 0], jnp.int32)
    out = np.asarray(finite_guard(logits, sampled))
    assert out.tolist() == [1, -1, -1, 0]
    # verify-shaped [B, k+1, v]: one bad position poisons its whole row
    lv = jnp.stack([logits[:2], logits[2:]])  # [2, 2, 3]; both rows bad
    sv = jnp.zeros((2, 2), jnp.int32)
    assert np.asarray(finite_guard(lv, sv)).tolist() == [[-1, -1], [-1, -1]]
    ok_rows = jnp.array([0, 3])
    lv_ok = jnp.stack([logits[ok_rows], logits[ok_rows[::-1]]])
    assert (np.asarray(finite_guard(lv_ok, sv)) == 0).all()


# ---------------------------------------------------------------------------
# typed submission outcomes
# ---------------------------------------------------------------------------
def test_typed_submit_rejections_and_raising_compat(tiny):
    cfg, params = tiny
    eng = _engine(cfg, params, max_seqs=1, num_blocks=4)
    sched = eng.scheduler
    samp = SamplingParams(max_new_tokens=4)
    assert sched.try_submit(1, [], samp).reason == S.REJECT_EMPTY_PROMPT
    assert sched.try_submit(1, list(range(200)), samp).reason \
        == S.REJECT_PROMPT_TOO_LONG
    assert sched.try_submit(
        1, list(range(1, 30)), SamplingParams(max_new_tokens=64)
    ).reason == S.REJECT_POOL_IMPOSSIBLE
    res = sched.try_submit(1, [1, 2, 3], samp)
    assert res.accepted and res.reason == S.QUEUED
    assert sched.try_submit(1, [4, 5], samp).reason == S.REJECT_DUPLICATE_UID
    assert sched.try_submit(
        2, [4, 5], SamplingParams(temperature=0.7, max_new_tokens=4)
    ).reason == S.REJECT_SAMPLING_CONFLICT
    # every rejection reason also raises through the compat wrapper
    with pytest.raises(ValueError):
        sched.submit(1, [4, 5], samp)
    # shed-mode backpressure is the one RETRYABLE rejection
    sched._set_shed(True, "test")
    res = sched.try_submit(3, [1, 2], samp)
    assert res.reason == S.RETRY_LATER and not res.accepted
    with pytest.raises(RuntimeError):
        sched.submit(3, [1, 2], samp)
    sched._set_shed(False, "test")
    assert sched.try_submit(3, [1, 2], samp).accepted
    assert eng.stats["shed_rejections"] == 2
    sched.run()
    _leakfree(eng)


# ---------------------------------------------------------------------------
# cancellation from every state
# ---------------------------------------------------------------------------
def test_cancel_from_queue_prefill_decode_and_preempted(tiny):
    cfg, params = tiny
    eng = _engine(cfg, params, max_seqs=3, num_blocks=24,
                  enable_prefix_caching=True, prefill_chunk=16)
    sched = eng.scheduler
    samp = SamplingParams(max_new_tokens=12)
    rng = np.random.default_rng(0)
    long_prompt = [int(t) for t in rng.integers(1, 255, 40)]
    sched.submit(1, [int(t) for t in rng.integers(1, 255, 6)], samp)
    sched.submit(2, long_prompt, samp)  # needs 3 chunked-prefill ticks
    sched.tick()
    assert sched.requests[2].state == "prefill"  # mid-prefill-chunk
    assert sched.cancel(2)
    assert sched.requests[2].state == "cancelled"
    sched.tick()
    assert sched.requests[1].state == "decode"
    # preempted-back-to-queue: force the preemption path, then cancel
    sched._preempt(sched.requests[1])
    assert sched.requests[1].state == "waiting" \
        and sched.requests[1].preemptions == 1
    assert sched.cancel(1)
    # queued-never-admitted
    sched.submit(3, [5, 6, 7], samp)
    assert sched.requests[3].state == "waiting"
    assert sched.cancel(3)
    # cancel is idempotent-safe: terminal and unknown uids return False
    assert not sched.cancel(3) and not sched.cancel(99)
    # decoding request cancels cleanly too
    sched.submit(4, [9, 8, 7], samp)
    sched.tick()
    sched.tick()
    assert sched.requests[4].state == "decode"
    assert sched.cancel(4)
    assert eng.stats["cancelled"] == 4
    assert sched.idle
    _leakfree(eng)
    # partial results of cancelled requests stay readable until popped
    assert isinstance(sched.pop_result(4), list)


# ---------------------------------------------------------------------------
# deadlines (fake clock: deterministic timeouts)
# ---------------------------------------------------------------------------
def test_e2e_and_ttft_deadlines(tiny):
    cfg, params = tiny
    eng = _engine(cfg, params, serve=dict(deadline_ms=5_000.0,
                                          retry_backoff_ms=0.0))
    sched = eng.scheduler
    t = [0.0]
    sched._clock = lambda: t[0]
    samp = SamplingParams(max_new_tokens=6)
    sched.submit(1, [1, 2, 3], samp)  # default 5s e2e deadline
    sched.submit(2, [4, 5, 6], samp, deadline_ms=60_000.0)  # override
    sched.submit(3, [7, 8, 9], samp, deadline_ms=60_000.0,
                 ttft_deadline_ms=2_000.0)
    sched.tick()  # all admitted, first tokens land (ttft met)
    assert sched.requests[3].generated  # first token before the ttft check
    t[0] = 10.0  # 10 s later: req1 e2e-expired, req3's ttft no longer applies
    sched.tick()
    assert sched.requests[1].state == "timed_out"
    assert "e2e deadline" in sched.requests[1].error
    assert sched.requests[2].state == "decode"
    assert sched.requests[3].state == "decode"
    # a queued request that never got a first token trips the TTFT deadline
    sched.submit(4, [2, 2, 2], samp, deadline_ms=60_000.0,
                 ttft_deadline_ms=1_000.0)
    t[0] = 20.0
    sched.tick()
    assert sched.requests[4].state == "timed_out"
    assert "ttft deadline" in sched.requests[4].error
    res = sched.run(wait_for=[2, 3])
    assert len(res[2]) == 6 and len(res[3]) == 6
    assert eng.stats["timed_out"] == 2
    _leakfree(eng)
    # timed-out requests keep partial tokens + the recorded error until popped
    assert isinstance(sched.pop_result(1), list)


# ---------------------------------------------------------------------------
# per-request failure isolation
# ---------------------------------------------------------------------------
def test_fatal_runner_exception_fails_only_victim(tiny, ref_engine):
    cfg, params = tiny
    samp = SamplingParams(max_new_tokens=8)
    rng = np.random.default_rng(3)
    prompts = {u: [int(t) for t in rng.integers(1, 255, 10)]
               for u in (1, 2, 3)}
    ref_out = {u: ref_engine.generate(p, samp) for u, p in prompts.items()}

    # fatal fault scoped to uid 2, firing from the first dispatch: the
    # shared prefill pack raises, isolation probes each entry solo, and
    # only the victim is quarantined
    inj = FaultInjector(seed=0).arm("runner_exception", uids=[2])
    eng = _engine(cfg, params, faults=inj)
    sched = eng.scheduler
    for u, p in prompts.items():
        sched.submit(u, p, samp)
    res = sched.run()
    assert sched.requests[2].state == "failed"
    assert "injected" in sched.requests[2].error
    assert res[1] == ref_out[1] and res[3] == ref_out[3]
    assert eng.stats["failed"] == 1 and eng.stats["isolation_probes"] >= 1
    assert 2 in sched.quarantined
    _leakfree(eng)

    # fatal fault armed only AFTER prefill: the decode batch raises and the
    # decode-side isolation path quarantines the victim mid-generation
    inj2 = FaultInjector(seed=0)
    eng2 = _engine(cfg, params, faults=inj2)
    sched2 = eng2.scheduler
    for u, p in prompts.items():
        sched2.submit(u, p, samp)
    sched2.tick()  # prefill completes fault-free
    assert all(r.state == "decode" for r in sched2.requests.values())
    inj2.arm("runner_exception", uids=[2])
    res2 = sched2.run()
    assert sched2.requests[2].state == "failed"
    assert len(sched2.requests[2].generated) >= 1  # partial progress kept
    assert res2[1] == ref_out[1] and res2[3] == ref_out[3]
    _leakfree(eng2)


def test_transient_runner_exception_retries_and_recovers(tiny, ref_engine):
    cfg, params = tiny
    samp = SamplingParams(max_new_tokens=8)
    rng = np.random.default_rng(4)
    prompts = {u: [int(t) for t in rng.integers(1, 255, 10)] for u in (1, 2)}
    ref_out = {u: ref_engine.generate(p, samp) for u, p in prompts.items()}

    inj = FaultInjector(seed=0).arm("runner_exception", transient=True,
                                    times=3)
    eng = _engine(cfg, params, faults=inj)
    sched = eng.scheduler
    for u, p in prompts.items():
        sched.submit(u, p, samp)
    res = sched.run()
    assert inj.fired() == 3  # the storm actually hit
    assert eng.stats["retries"] >= 3 and eng.stats["failed"] == 0
    assert res == ref_out  # bounded backoff retries are invisible in tokens
    _leakfree(eng)


def test_injected_nan_quarantines_poisoned_row(tiny, ref_engine):
    cfg, params = tiny
    samp = SamplingParams(max_new_tokens=8)
    rng = np.random.default_rng(5)
    prompts = {u: [int(t) for t in rng.integers(1, 255, 10)]
               for u in (1, 2, 3)}
    ref_out = {u: ref_engine.generate(p, samp) for u, p in prompts.items()}

    inj = FaultInjector(seed=0).arm("nan_logits", uids=[2], times=1)
    eng = _engine(cfg, params, faults=inj)
    sched = eng.scheduler
    for u, p in prompts.items():
        sched.submit(u, p, samp)
    res = sched.run()
    assert sched.requests[2].state == "failed"
    assert "non-finite" in sched.requests[2].error
    assert eng.stats["nan_failures"] == 1 and eng.stats["failed"] == 1
    assert res[1] == ref_out[1] and res[3] == ref_out[3]
    _leakfree(eng)


def test_alloc_exhaustion_transient_recovers(tiny):
    cfg, params = tiny
    samp = SamplingParams(max_new_tokens=8)
    rng = np.random.default_rng(6)
    prompts = {u: [int(t) for t in rng.integers(1, 255, 10)] for u in (1, 2)}
    ref = _engine(cfg, params, enable_prefix_caching=True)
    ref_out = {u: ref.generate(p, samp) for u, p in prompts.items()}

    inj = FaultInjector(seed=0).arm("alloc_exhaustion", transient=True,
                                    times=4)
    eng = _engine(cfg, params, enable_prefix_caching=True, faults=inj)
    sched = eng.scheduler
    for u, p in prompts.items():
        sched.submit(u, p, samp)
    res = sched.run()
    assert inj.fired() == 4
    assert eng.stats["failed"] == 0 and sched.stats["preemptions"] == 0
    assert res == ref_out
    _leakfree(eng)


# ---------------------------------------------------------------------------
# degradation: shed mode + watchdog
# ---------------------------------------------------------------------------
def test_shed_mode_queue_depth_cycle_and_chrome_span(tiny):
    cfg, params = tiny
    eng = _engine(cfg, params, max_seqs=2, num_blocks=24, telemetry=True,
                  enable_speculation=True,
                  serve=dict(shed_queue_depth=1, retry_backoff_ms=0.0))
    sched = eng.scheduler
    samp = SamplingParams(max_new_tokens=6)
    rng = np.random.default_rng(7)
    for u in range(1, 6):  # 5 requests into 2 slots: the queue backs up
        sched.submit(u, [int(t) for t in rng.integers(1, 255, 6)], samp)
    sched.tick()
    assert sched.shedding  # waiting depth > 1 flipped shed on
    assert not sched._speculating  # speculation disabled under pressure
    rej = sched.try_submit(50, [1, 2, 3], samp)
    assert rej.reason == S.RETRY_LATER
    res = sched.run()
    assert len(res) == 5 and all(len(v) == 6 for v in res.values())
    assert not sched.shedding  # drained queue exits shed mode
    assert sched._speculating  # and speculation comes back
    assert eng.stats["shed_transitions"] == 2
    assert eng.stats["shed_rejections"] == 1
    assert sched.try_submit(50, [1, 2, 3], samp).accepted
    sched.run()
    # the shed episode is a span on the engine track in the Chrome trace
    events = eng.telemetry.chrome_trace()["traceEvents"]
    assert any(e.get("name") == "shed_mode" for e in events)
    _leakfree(eng)


def test_watchdog_trips_on_slow_ticks(tiny):
    cfg, params = tiny
    inj = FaultInjector(seed=0).arm("slow_tick", delay_s=0.05, times=3)
    eng = _engine(cfg, params, faults=inj,
                  serve=dict(watchdog_tick_ms=1.0, watchdog_grace_ticks=2,
                             retry_backoff_ms=0.0))
    sched = eng.scheduler
    samp = SamplingParams(max_new_tokens=6)
    sched.submit(1, [1, 2, 3], samp)
    res = sched.run()
    assert len(res[1]) == 6  # slow ticks degrade, they do not kill
    assert eng.stats["watchdog_trips"] >= 1
    assert eng.stats["shed_transitions"] >= 1  # entered shed at the trip
    _leakfree(eng)


# ---------------------------------------------------------------------------
# the chaos storm (acceptance): 64 requests (and 16), seeded injection
# of runner exceptions + NaN logits + allocator exhaustion, cancels and
# deadlines, no uninjected request lost, engine alive, zero leaked blocks,
# transitions in counters AND the Chrome trace
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_req", [64, 16])
def test_chaos_storm_64_requests(tiny, n_req):
    cfg, params = tiny
    fatal = [u for u in (3, 17, 41) if u <= n_req]
    nans = [u for u in (5, 23) if u <= n_req]
    cancels = [u for u in (7, 29) if u <= n_req]
    inj = (
        FaultInjector(seed=0)
        .arm("runner_exception", p=0.04, transient=True)
        .arm("runner_exception", uids=fatal)
        .arm("nan_logits", uids=nans, times=len(nans))
        .arm("alloc_exhaustion", p=0.04, transient=True, times=10)
        .arm("slow_tick", p=0.05, delay_s=0.001, times=8)
    )
    eng = _engine(cfg, params, max_seqs=4, num_blocks=48,
                  enable_prefix_caching=True, enable_speculation=True,
                  telemetry=True, faults=inj,
                  serve=dict(deadline_ms=600_000.0, max_retries=4,
                             retry_backoff_ms=0.0, shed_queue_depth=4))
    sched = eng.scheduler
    samp = SamplingParams(temperature=0.0, max_new_tokens=10)
    rng = np.random.default_rng(0)
    shared = rng.integers(1, cfg.vocab_size, 16).tolist()
    prompts = {u: shared + rng.integers(1, cfg.vocab_size, 6).tolist()
               for u in range(1, n_req + 1)}
    # two sacrificial sub-ms deadlines exercise TIMED_OUT inside the storm
    sched.submit(1001, prompts[1], samp, deadline_ms=0.001)
    sched.submit(1002, prompts[2], samp, ttft_deadline_ms=0.001)
    arrivals = np.cumsum(rng.poisson(0.5, n_req))
    submitted = 0
    backlog = []
    cancelled = set()
    for _ in range(5000):
        while submitted < n_req and arrivals[submitted] <= sched.tick_no:
            uid = submitted + 1
            submitted += 1
            r = sched.try_submit(uid, prompts[uid], samp)
            (backlog.append(uid) if r.reason == S.RETRY_LATER
             else None)
        if backlog and not sched.shedding:
            if sched.try_submit(backlog[0], prompts[backlog[0]], samp).accepted:
                backlog.pop(0)
        for uid in cancels:
            if uid in sched.requests and uid not in cancelled \
                    and sched.requests[uid].state not in S.TERMINAL:
                sched.cancel(uid)
                cancelled.add(uid)
        if submitted >= n_req and not backlog and all(
            r.state in S.TERMINAL for r in sched.requests.values()
        ):
            break
        sched.tick()
    else:
        pytest.fail("storm did not converge")
    # every request reached a TYPED terminal state — nothing lost
    states = {u: sched.requests[u].state for u in list(prompts) + [1001, 1002]}
    assert all(s in S.TERMINAL for s in states.values())
    injected = set(fatal) | set(nans) | set(cancels)
    assert all(states[u] == "finished"
               for u in range(1, n_req + 1) if u not in injected)
    assert all(states[u] == "failed" for u in fatal + nans)
    assert all(states[u] == "cancelled" for u in cancels)
    assert states[1001] == "timed_out" and states[1002] == "timed_out"
    # transitions in the counters...
    st = dict(eng.stats)
    assert st["failed"] == len(fatal) + len(nans)
    assert st["nan_failures"] == len(nans)
    assert st["cancelled"] == len(cancels)
    assert st["timed_out"] == 2
    assert st["retries"] > 0
    # ...and on the Chrome trace (typed terminal markers per request uid)
    for u in list(prompts) + [1001, 1002]:
        sched.pop_result(u)
    events = eng.telemetry.chrome_trace()["traceEvents"]
    names = {e["name"] for e in events}
    assert {"failed", "cancelled", "timed_out"} <= names
    # zero-leak allocator invariant after the storm
    _leakfree(eng)


# ---------------------------------------------------------------------------
# a DISARMED injector (rules armed, ``enabled=False``) under the fault
# layer's serve config serves the tokens of an engine that has neither
# ---------------------------------------------------------------------------
def test_disarmed_injector_is_token_identical_to_no_injector(tiny):
    cfg, params = tiny
    samp = SamplingParams(temperature=0.0, max_new_tokens=8)
    rng = np.random.default_rng(0)
    shared = rng.integers(1, cfg.vocab_size, 16).tolist()
    prompts = {u: shared + rng.integers(1, cfg.vocab_size, 8).tolist()
               for u in range(1, 9)}
    inj = (FaultInjector(seed=0, enabled=False)
           .arm("runner_exception", uids=[3])
           .arm("nan_logits", uids=[5])
           .arm("alloc_exhaustion", p=1.0, transient=True))
    outs = []
    for kw in (dict(serve=None),
               dict(faults=inj,
                    serve=dict(deadline_ms=600_000.0, max_retries=3,
                               retry_backoff_ms=0.0, shed_queue_depth=9))):
        eng = _engine(cfg, params, enable_prefix_caching=True, **kw)
        sched = eng.scheduler
        for u, p in prompts.items():
            assert sched.try_submit(u, p, samp).accepted
        sched.run()
        outs.append({u: (sched.requests[u].state, sched.pop_result(u))
                     for u in prompts})
        _leakfree(eng)
    assert outs[0] == outs[1]
    assert all(state == "finished" for state, _ in outs[0].values())
    assert inj.fired() == 0 and not inj.armed()
