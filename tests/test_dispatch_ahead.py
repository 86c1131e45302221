"""A serving tick dispatched one ahead (PR 43): call k of ``ServeScheduler.tick()``
enqueues execution k + 1 before it fetches execution k, a step's input tokens
stay on the device (the engine's chain), and whatever cannot be planned
without the tokens drains.  CPU, tiny dense and ``cfg.latent`` models: the
ORDER and the tokens, never a time."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark import harness  # noqa: E402

from deepspeed_tpu.inference import scheduler as S  # noqa: E402
from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2  # noqa: E402
from deepspeed_tpu.inference.faults import FaultInjector  # noqa: E402
from deepspeed_tpu.inference.sampling import SamplingParams  # noqa: E402
from deepspeed_tpu.models import get_preset  # noqa: E402
from deepspeed_tpu.models.transformer import init_params  # noqa: E402

KINDS = ["dense", "latent"]


def _model(kind):
    if kind == "dense":
        cfg = get_preset("tiny", max_seq_len=128, dtype=jnp.float32)
        return cfg, init_params(jax.random.PRNGKey(0), cfg, dtype=cfg.dtype)
    m = harness.rehearsed(harness.load_json(
        ROOT / "benchmark/configs/dots3_note_l5_e32_serve_1chip.json"), True)
    cfg = harness.module("models", m["model_type"]).transformer_config(
        m, max_seq_len=m["engine"]["max_seq_len"])
    return cfg, init_params(jax.random.PRNGKey(7), cfg)


@pytest.fixture(scope="module", params=KINDS)
def model(request):
    return (request.param,) + _model(request.param)


@pytest.fixture(scope="module")
def dense():
    return ("dense",) + _model("dense")


def _engine(model, **kw):
    kind, cfg, params = model
    base = dict(max_seqs=4, num_blocks=64, block_size=8, seed=3, telemetry=True)
    if kind == "dense":
        base.update(prefill_buckets=(16, 32), prefill_chunk=16)
    else:
        base.update(prefill_buckets=(32,), prefill_chunk=32, max_seq_len=256)
    base.update(kw)
    return InferenceEngineV2(params, cfg, **base)


def _prompts(model, lens=(5, 40, 17), seed=0):
    rng = np.random.default_rng(seed)
    hi = min(model[1].vocab_size, 255)
    return {u + 1: [int(t) for t in rng.integers(1, hi, n)]
            for u, n in enumerate(lens)}


def _back_to_back(sched):
    """Today's order on an engine that offers the split: the reference."""
    sched._back_to_back = lambda: "test"


def _serve(eng, prompts, samp, back_to_back=False, between=None):
    """Submit everything, tick until idle: ({uid: tokens}, [each call's out])."""
    sched = eng.scheduler
    if back_to_back:
        _back_to_back(sched)
    for u, p in prompts.items():
        sched.submit(u, p, samp[u] if isinstance(samp, dict) else samp)
    calls = []
    while not sched.idle:
        calls.append(dict(sched.tick()))
        assert len(sched._inflight) <= 1  # ONE execution's results are held
        if between is not None:
            between(len(calls))
        assert len(calls) < 500
    return {u: sched.result(u) for u in prompts}, calls


def _leakfree(eng):
    audit = eng.close()
    assert not any(audit.values()), audit


def _ticks_named(eng):
    return {tr.uid: (tr.chunk_ticks, tr.emission_ticks)
            for tr in eng.telemetry.finished_traces}


# ---------------------------------------------------------------------------
# the same tokens, in the same calls
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "sampled"])
def test_tokens_equal_generate_and_todays_order_call_by_call(model, temperature):
    samp = SamplingParams(temperature=temperature, max_new_tokens=9)
    prompts = _prompts(model)
    eng = _engine(model)
    got, calls = _serve(eng, prompts, samp)
    assert eng.stats["dispatched_ahead"] > 0 and eng.stats["ahead_drains"] == 0
    assert eng.stats["ahead_rows_dropped"] == 0
    traces = _ticks_named(eng)
    _leakfree(eng)
    ref = _engine(model)
    want, ref_calls = _serve(ref, prompts, samp, back_to_back=True)
    assert ref.stats["dispatched_ahead"] == 0
    # a request's trace names the same tick for every chunk and token: the
    # call that RETURNS the execution they ride (what a benchmark's replay
    # rebuilds the packs and the decode batches from)
    assert traces == _ticks_named(ref) and len(traces) == len(prompts)
    _leakfree(ref)
    # a caller that submits everything and then ticks sees every token in the
    # call it always did (the rng chain saw the same programs in the same order)
    assert got == want and calls == ref_calls
    assert all(len(t) == 9 for t in got.values())
    if temperature == 0.0:
        solo = _engine(model)
        for u, p in prompts.items():
            assert got[u] == solo.generate(p, samp), u
        _leakfree(solo)


def test_generate_leaves_nothing_enqueued(model):
    eng = _engine(model)
    samp = SamplingParams(max_new_tokens=6)
    a = eng.generate(_prompts(model)[1], samp)
    assert len(a) == 6 and eng.scheduler.idle and not eng.scheduler._inflight
    assert eng.generate(_prompts(model)[1], samp) == a
    _leakfree(eng)


# ---------------------------------------------------------------------------
# what is NOT known ahead: a dead row
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "sampled"])
def test_stop_token_inside_the_run_leaves_a_dead_row(model, temperature):
    """The stop is seen one call after the next step went out: that step's
    row is dead.  Nothing past the stop is appended or returned, the others'
    tokens are what they were (under sampling too: a program splits the key
    once whatever its rows, and a row's draw follows the key and its slot),
    and the dead row's pages go back."""
    prompts = _prompts(model, lens=(6, 9))
    free = SamplingParams(temperature=temperature, max_new_tokens=12)
    clean, _ = _serve(_engine(model), prompts, free)
    stop = clean[1][4]  # request 1 stops at its fifth token (or earlier)
    cut = clean[1].index(stop)
    samp = {1: SamplingParams(temperature=temperature, max_new_tokens=12,
                              stop_token=stop), 2: free}
    eng = _engine(model)
    got, calls = _serve(eng, prompts, samp)
    assert got[1] == clean[1][:cut]  # result() strips the stop itself
    assert eng.scheduler.requests[1].generated == clean[1][:cut + 1]
    assert eng.scheduler.requests[1].state == S.FINISHED
    assert got[2] == clean[2]
    assert sum(1 in c for c in calls) == cut + 1  # never returned again
    assert eng.stats["ahead_rows_dropped"] == 1 and eng.stats["ahead_drains"] == 0
    _leakfree(eng)
    ref = _engine(model)
    want, _ = _serve(ref, prompts, samp, back_to_back=True)
    assert got == want
    _leakfree(ref)


def test_dead_rows_pages_go_back_after_the_execution_that_carries_them(dense):
    eng = _engine(dense)
    sched, mgr = eng.scheduler, eng.mgr
    p = _prompts(dense, lens=(6,))[1]
    clean = _engine(dense).generate(p, SamplingParams(max_new_tokens=8))
    stop = clean[3]
    sched.submit(1, p, SamplingParams(max_new_tokens=8, stop_token=stop))
    while sched.requests[1].state != S.FINISHED:
        sched.tick()
    # finished and returned, but the step enqueued ahead still carries it
    assert sched._inflight and 1 in mgr.seqs and not sched.idle
    assert mgr.allocator.free_blocks < mgr.allocator.total_blocks
    assert sched.tick() == {}  # the dead row's result is dropped
    assert 1 not in mgr.seqs and sched.idle
    assert eng.stats["ahead_rows_dropped"] == 1
    assert sched.result(1) == clean[:clean.index(stop)]
    _leakfree(eng)


def test_finite_guard_row_in_flight_fails_alone(model):
    """The finite guard's -1 lands in the chain; the step already enqueued
    reads it clamped, and that row's result is thrown away."""
    prompts = _prompts(model, lens=(6, 9, 7))
    samp = SamplingParams(max_new_tokens=10)
    clean, _ = _serve(_engine(model), prompts, samp)
    eng = _engine(model)
    real, n = eng._decode_jit, [0]

    def poisoned(*args):
        out = real(*args)
        n[0] += 1
        if n[0] == 3:  # the slot of request 2, third step
            slot = eng.mgr.seqs[2].slot
            out = (out[0].at[slot].set(-1),) + tuple(out[1:])
        return out

    eng._decode_jit = poisoned
    got, _ = _serve(eng, prompts, samp)
    sched = eng.scheduler
    assert sched.requests[2].state == S.FAILED
    assert "non-finite" in sched.requests[2].error
    assert got[2] == clean[2][:len(got[2])] and len(got[2]) < 10
    assert got[1] == clean[1] and got[3] == clean[3]
    assert eng.stats["nan_failures"] == 1 and eng.stats["ahead_rows_dropped"] == 1
    _leakfree(eng)


# ---------------------------------------------------------------------------
# what cannot be planned without the tokens drains
# ---------------------------------------------------------------------------
def _drained(model, samp, act, reason, **kw):
    """Run with ``act(eng, call)`` between calls, one ahead and in today's
    order: the same outcome, and the drain counted under ``reason``."""
    prompts = _prompts(model, lens=(6, 9, 7))
    runs = []
    for back_to_back in (False, True):
        eng = _engine(model, **kw)
        tokens, _ = _serve(eng, prompts, samp, back_to_back=back_to_back,
                           between=lambda n, eng=eng: act(eng, n))
        sched = eng.scheduler
        runs.append((tokens, {u: sched.requests[u].state for u in prompts}))
        if not back_to_back:
            assert sched.drains.get(reason, 0) >= 1, sched.drains
            assert eng.stats["ahead_drains"] == sum(sched.drains.values())
        _leakfree(eng)
    return runs


def test_cancel_while_an_execution_is_enqueued_drains(model):
    def act(eng, n):
        if n == 4:
            sched = eng.scheduler
            assert sched._inflight or sched._back_to_back() == "test"
            assert sched.cancel(2)
            assert sched.requests[2].state == S.CANCELLED and not sched._inflight
            assert 2 not in eng.mgr.seqs  # released at once, as it always was

    ahead, today = _drained(model, SamplingParams(max_new_tokens=10), act, "cancel")
    assert ahead[1] == today[1] and ahead[1][2] == S.CANCELLED
    assert ahead[0][1] == today[0][1] and ahead[0][3] == today[0][3]
    # the drain collected the token the enqueued step had sampled for it
    assert ahead[0][2][:len(today[0][2])] == today[0][2]
    assert len(ahead[0][2]) - len(today[0][2]) in (0, 1)


def test_deadline_of_a_running_request_drains(dense):
    t = [0.0]

    def act(eng, n):
        eng.scheduler._clock = lambda: t[0]
        if n == 4:
            t[0] += 10.0  # request 2's deadline passes while it decodes

    samp = SamplingParams(max_new_tokens=10)
    prompts = _prompts(dense, lens=(6, 9, 7))
    runs = []
    for back_to_back in (False, True):
        t[0] = 0.0
        eng = _engine(dense)
        sched = eng.scheduler
        sched._clock = lambda: t[0]
        if back_to_back:
            _back_to_back(sched)
        for u, p in prompts.items():
            sched.try_submit(u, p, samp, deadline_ms=5000.0 if u == 2 else None)
        n = 0
        while not sched.idle:
            sched.tick()
            n += 1
            act(eng, n)
        runs.append({u: (sched.requests[u].state, sched.result(u)) for u in prompts})
        if not back_to_back:
            assert sched.drains == {"expire": 1} and eng.stats["ahead_drains"] == 1
        _leakfree(eng)
    ahead, today = runs
    assert ahead[2][0] == today[2][0] == S.TIMED_OUT
    assert ahead[1] == today[1] and ahead[3] == today[3]
    assert ahead[2][1][:len(today[2][1])] == today[2][1]


def test_pool_pressure_drains_and_preempts_as_it_always_did(dense):
    """A pool too small for every row's growth: the plan ahead cannot pick a
    victim without the tokens, so it collects first; the tokens are those of
    a pool that never ran short."""
    samp = SamplingParams(max_new_tokens=24)
    prompts = _prompts(dense, lens=(14, 15, 13))
    roomy, _ = _serve(_engine(dense), prompts, samp)
    eng = _engine(dense, num_blocks=11, kv_watermark=0.0)
    got, _ = _serve(eng, prompts, samp)
    sched = eng.scheduler
    assert sched.stats["preemptions"] >= 1
    assert sched.drains.get("pool", 0) >= 1
    assert got == roomy
    _leakfree(eng)


def test_a_fault_armed_while_an_execution_is_enqueued_drains(dense):
    inj = FaultInjector(seed=1)

    def act(eng, n):
        if n == 3:
            inj.arm("runner_exception", times=1, transient=True)

    samp = SamplingParams(max_new_tokens=10)
    clean, _ = _serve(_engine(dense), _prompts(dense, lens=(6, 9, 7)), samp)
    ahead, today = _drained(dense, samp, act, "fault", faults=inj,
                            serve=dict(retry_backoff_ms=0.0))
    assert ahead == today and ahead[0] == clean
    assert all(s == S.FINISHED for s in ahead[1].values())


def test_a_failed_dispatch_ahead_is_collected_and_retried_in_todays_order(dense):
    eng = _engine(dense, serve=dict(retry_backoff_ms=0.0))
    real, n = eng.decode_dispatch, [0]

    def flaky(*a, **kw):
        n[0] += 1
        if n[0] == 4:
            raise RuntimeError("transient: device_put hiccup")
        return real(*a, **kw)

    eng.decode_dispatch = flaky
    samp = SamplingParams(max_new_tokens=10)
    prompts = _prompts(dense, lens=(6, 9, 7))
    clean, _ = _serve(_engine(dense), prompts, samp)
    got, _ = _serve(eng, prompts, samp)
    assert got == clean and eng.scheduler.drains == {"dispatch_error": 1}
    _leakfree(eng)


@pytest.mark.parametrize("what", ["speculation", "megastep", "mesh", "retune"])
def test_a_tick_that_needs_the_tokens_keeps_todays_order(dense, what):
    kw = {}
    if what == "speculation":
        kw = dict(enable_speculation=True, enable_prefix_caching=True)
    elif what == "megastep":
        kw = dict(serve=dict(decode_megastep=4))
    eng = _engine(dense, **kw)
    sched = eng.scheduler
    if what == "mesh":
        eng._offload_weights = True  # what ``programs_may_queue`` reads
    samp = SamplingParams(max_new_tokens=10)
    prompts = _prompts(dense, lens=(6, 9, 7))
    clean, _ = _serve(_engine(dense), prompts, samp)
    if what == "retune":
        got, _ = _serve(eng, prompts, samp, between=lambda n: n == 4 and
                        sched.apply_knobs(watchdog_tick_ms=1e9))
        assert sched.drains == {"retune": 1} and sched.knob_epoch == 1
    else:
        got, _ = _serve(eng, prompts, samp)
        assert sched.drains.get(what, 0) >= 1
        if what != "megastep":  # a megastep tick with a prompt waiting goes ahead
            assert eng.stats["dispatched_ahead"] == 0
    assert got == clean
    eng._offload_weights = False
    _leakfree_cached(eng)  # (speculation runs with the prefix cache on)


def test_detach_leaves_a_dead_row_and_close_collects(dense):
    """A handoff carries exactly the tokens the host holds: ``detach`` while
    a step enqueued ahead carries the sequence ends the request at once, that
    step's row is dead, and nobody else's tokens move."""
    samp = SamplingParams(max_new_tokens=10)
    prompts = _prompts(dense, lens=(6, 9))
    clean, _ = _serve(_engine(dense), prompts, samp)
    eng = _engine(dense)
    sched = eng.scheduler
    for u, p in prompts.items():
        sched.submit(u, p, samp)
    for _ in range(3):
        sched.tick()
    assert sched._inflight
    held = list(sched.requests[1].generated)
    assert sched.detach(1) and sched.requests[1].state == S.MIGRATED
    assert sched.requests[1].generated == held == clean[1][:len(held)]
    assert sched._inflight and 1 in eng.mgr.seqs  # the pages wait
    out = sched.tick()
    assert set(out) == {2} and 1 not in eng.mgr.seqs
    assert eng.stats["ahead_rows_dropped"] == 1 and not sched.drains
    sched.tick()
    assert sched._inflight
    _leakfree(eng)  # close() collects, cancels, releases
    assert sched.drains == {"close": 1} and not sched._inflight
    assert sched.requests[2].generated == clean[2][:len(sched.requests[2].generated)]


def test_a_direct_step_collects_what_the_scheduler_enqueued(dense):
    samp = SamplingParams(max_new_tokens=10)
    eng = _engine(dense)
    sched = eng.scheduler
    p = _prompts(dense, lens=(6,))[1]
    clean = _engine(dense).generate(p, samp)
    sched.submit(1, p, samp)
    for _ in range(3):
        sched.tick()
    assert sched._inflight and eng.mgr.seqs[1].pending == 1
    tok = eng.step(SamplingParams(max_new_tokens=10))[1]
    assert not sched._inflight and sched.drains == {"direct_step": 1}
    assert eng.mgr.seqs[1].pending == 0
    assert eng.mgr.seqs[1].tokens[len(p):] == clean[:5] and tok == clean[4]
    assert sched.tick()[1] == clean[3]  # what the drain collected, delivered
    _leakfree(eng)


# ---------------------------------------------------------------------------
# a request submitted while an execution is enqueued
# ---------------------------------------------------------------------------
def test_mid_run_submit_gets_its_first_token_one_call_later(model):
    samp = SamplingParams(max_new_tokens=8)
    prompts = _prompts(model, lens=(6, 7))
    first_call = {}
    for back_to_back in (False, True):
        eng = _engine(model)
        sched = eng.scheduler
        if back_to_back:
            _back_to_back(sched)
        sched.submit(1, prompts[1], samp)
        seen = {1: [], 2: []}
        n = 0
        while not sched.idle or n < 3:
            if n == 3:
                sched.submit(2, prompts[2], samp)
            n += 1
            for u, tok in sched.tick().items():
                seen[u].append((n, tok))
        # no token lost or doubled: one a call, in order, and generate()'s
        for u in (1, 2):
            assert [t for _, t in seen[u]] == sched.result(u)
            calls = [c for c, _ in seen[u]]
            assert calls == list(range(calls[0], calls[0] + 8))
        first_call[back_to_back] = seen[2][0][0]
        solo = _engine(model)
        assert sched.result(2) == solo.generate(prompts[2], samp)
        _leakfree(solo)
        _leakfree(eng)
    assert first_call[True] == 4 and first_call[False] == 5


def test_prefix_hits_of_a_second_turn_over_generated_tokens_unchanged(dense):
    samp = SamplingParams(max_new_tokens=20)
    first = _prompts(dense, lens=(30,))[1]
    stats = []
    for back_to_back in (False, True):
        eng = _engine(dense, enable_prefix_caching=True)
        sched = eng.scheduler
        if back_to_back:
            _back_to_back(sched)
        sched.submit(1, first, samp)
        sched.run()
        answer = sched.pop_result(1)
        sched.submit(2, first + answer + [7, 8, 9], samp)
        sched.run()
        stats.append((eng.mgr.cached_prompt_tokens, eng.mgr.prompt_tokens_total,
                      answer, sched.pop_result(2)))
        _leakfree_cached(eng)
    assert stats[0] == stats[1]
    # the second turn hit every full block of the first turn's prompt AND answer
    assert stats[0][0] == (len(first) + len(stats[0][2]) - 1) // 8 * 8


def _leakfree_cached(eng):
    assert eng.close()["blocks_in_use"] == 0


# ---------------------------------------------------------------------------
# the ORDER, not a time
# ---------------------------------------------------------------------------
def test_steady_decode_enqueues_step_k_plus_1_before_step_k_is_collected(model):
    eng = _engine(model)
    sched = eng.scheduler
    samp = SamplingParams(max_new_tokens=12)
    for u, p in _prompts(model, lens=(6, 7)).items():
        sched.submit(u, p, samp)
    while not sched.idle:
        sched.tick()
        assert len(sched._inflight) <= 1  # at most one un-collected result
    ev = [e for e in eng.telemetry.recorder.chrome_events() if e.get("ph") == "X"]
    steps = [e for e in ev if e["name"] == "decode_tick"]
    collects = [e for e in ev if e["name"] == "tick_collect"
                and e["args"]["what"] == "decode_tick"]
    assert len(steps) == len(collects) == eng.stats["decode_ticks"] == 11
    # every step went out while the execution before it was not fetched (the
    # first one behind the pack that completed the prompts)
    assert [e["args"]["ahead"] for e in steps] == [1] * 11
    packs = [e for e in ev if e["name"] == "prefill_pack"]
    assert [e["args"]["ahead"] for e in packs] == [0] * len(packs)
    assert eng.stats["dispatched_ahead"] == eng.stats["decode_ticks"]
    for k in range(len(steps) - 1):
        dispatch_k1 = steps[k + 1]["ts"] + steps[k + 1]["args"]["dispatch_ms"] * 1e3
        assert dispatch_k1 <= collects[k]["ts"] + collects[k]["dur"]
        assert dispatch_k1 <= collects[k]["ts"]  # before the wait even began
    # a dispatch span covers ITS program's build -> upload -> dispatch and is
    # closed unsynced; the wait and the fetch have a span of their own
    assert all(e["args"].get("synced") is False for e in steps)
    assert all("upload_ms" in e["args"] for e in steps)
    # exactly one upload a dispatch (and a table when a page moved)
    assert eng.stats["dispatch_uploads"] == (
        eng.stats["decode_ticks"] + eng.stats["prefill_dispatches"]
        - eng.stats["mixed_dispatches"] + eng.stats["table_uploads"])
    _leakfree(eng)


def test_a_steady_decode_run_counts_its_steps_ahead(dense):
    """From a scheduler with nothing enqueued on, a run of decode steps alone:
    the first finds no execution before it, every later one does."""
    eng = _engine(dense)
    sched = eng.scheduler
    sched.submit(1, _prompts(dense, lens=(6,))[1], SamplingParams(max_new_tokens=12))
    for _ in range(3):
        sched.tick()
    sched._drain_outside("test")
    assert not sched._inflight and len(sched.requests[1].generated) == 4
    base = {k: eng.stats[k] for k in ("decode_ticks", "dispatched_ahead")}
    sched.run()
    steps = eng.stats["decode_ticks"] - base["decode_ticks"]
    assert steps == 8 and eng.stats["prefill_dispatches"] == 1
    assert eng.stats["dispatched_ahead"] - base["dispatched_ahead"] == steps - 1
    assert len(sched.result(1)) == 12
    _leakfree(eng)


def test_the_chain_feeds_a_step_whose_token_the_host_has_not_seen(dense):
    """The decode program reads a slot's token from the chain where the
    fourth row says so, clamps the guard's -1 there, and hands the chain on
    with the live slots overwritten."""
    eng = _engine(dense)
    sched = eng.scheduler
    sched.submit(1, _prompts(dense, lens=(6,))[1], SamplingParams(max_new_tokens=4))
    seen = []
    real = eng._decode_jit

    def spy(*args):
        seen.append((np.asarray(args[1]).copy(), np.asarray(args[5]).copy()))
        out = real(*args)
        seen[-1] += (np.asarray(out[0]).copy(),)
        return out

    eng._decode_jit = spy
    sched.run()
    slot = 0
    toks = sched.result(1)
    for i, (rows, chain_in, chain_out) in enumerate(seen):
        assert rows.shape == (4, 4) and rows[2].sum() == 1
        # the first step's token is the pack's, not yet fetched either
        assert rows[3, slot] == 1 and rows[0, slot] == 0
        assert chain_in[slot] == toks[i] and chain_out[slot] == toks[i + 1]
    _leakfree(eng)
