"""Serve front end (deepspeed_tpu/serving/): seeded router storm over >= 2
workers (affinity hit-rate >= least-loaded baseline, zero allocator leaks
after drain, greedy token-identity vs a single-engine reference),
prefill/decode disaggregation via the paged-KV handoff (exact and int8
wire), worker-kill re-route + replay, SLO backpressure (retry_after_ms
hints, front-door shed), and dp>1 over-budget prompts served through
replica-local ctx packs (the PR 12 typed reject, retired)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.comm import qcomm
from deepspeed_tpu.config.config import ConfigError, RouterConfig
from deepspeed_tpu.inference import scheduler as sched_mod
from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2, build_serve_engine
from deepspeed_tpu.inference.faults import FaultInjector
from deepspeed_tpu.inference.sampling import SamplingParams
from deepspeed_tpu.models import get_preset
from deepspeed_tpu.models.transformer import forward, init_params
from deepspeed_tpu.serving import build_router
from deepspeed_tpu.serving import handoff as handoff_mod


@pytest.fixture(scope="module")
def tiny():
    # fp32 so greedy token identity cannot flip on bf16 near-ties
    cfg = get_preset("tiny", max_seq_len=256, dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg=cfg, dtype=jnp.float32)
    return cfg, params


SEC = dict(max_seqs=4, num_blocks=96, block_size=8,
           prefill_buckets=[16, 32, 64, 128], max_seq_len=256,
           enable_prefix_caching=True)


def _workload(cfg, n_req=16, seed=0):
    """Mixed traffic: odd uids share a system prompt (affinity population),
    even uids are cold unique prompts (balance population)."""
    rng = np.random.default_rng(seed)
    sys_prompt = rng.integers(1, cfg.vocab_size, 24).tolist()
    out = {}
    for u in range(1, n_req + 1):
        sfx = rng.integers(1, cfg.vocab_size, 8).tolist()
        out[u] = (sys_prompt + sfx if u % 2 else
                  rng.integers(1, cfg.vocab_size, 24).tolist() + sfx)
    return out


def _reference(tiny, prompts, samp):
    cfg, params = tiny
    eng = build_serve_engine(params, cfg, SEC)
    sched = eng.scheduler
    for u, p in prompts.items():
        assert sched.try_submit(u, p, samp).accepted
    sched.run()
    want = {u: sched.pop_result(u) for u in prompts}
    eng.close()
    return want


# ---------------------------------------------------------------------------
# the seeded storm: affinity vs least-loaded, leaks, token identity
# ---------------------------------------------------------------------------
def test_router_storm_affinity_beats_least_loaded(tiny):
    cfg, params = tiny
    samp = SamplingParams(temperature=0.0, max_new_tokens=6)
    prompts = _workload(cfg)
    want = _reference(tiny, prompts, samp)

    hit_rates = {}
    for affinity in (True, False):
        router = build_router(params, cfg, SEC,
                              router=dict(n_workers=2, affinity=affinity))
        # arrival-interleaved submission so placement happens under load
        uids = list(prompts)
        for i in range(0, len(uids), 4):
            for u in uids[i:i + 4]:
                assert router.try_submit(u, prompts[u], samp).accepted
            router.tick()
        out = router.run()
        assert all(out[u] == ("finished", want[u]) for u in prompts), (
            "routed tokens diverged from the single-engine reference")
        hit_rates[affinity] = router.prefix_hit_rate()
        stats = dict(router.stats)
        if affinity:
            assert stats["routed_affinity"] > 0
        else:
            assert stats["routed_affinity"] == 0
        # both workers actually served traffic
        assert all(w.engine.mgr.prompt_tokens_total > 0
                   for w in router.pool.workers)
        # zero-leak drain on EVERY worker
        for audit in router.close():
            assert audit["blocks_in_use"] == 0, audit
    assert hit_rates[True] > 0.0
    assert hit_rates[True] >= hit_rates[False], hit_rates


# ---------------------------------------------------------------------------
# prefill/decode disaggregation: the paged-KV handoff
# ---------------------------------------------------------------------------
# what a served token may fall short of the reference's best logit after
# its pages crossed the wire as int8 (the tiny model's logits have std ~1;
# the benchmark's serving cells judge their tokens by 0.05 to 0.2)
INT8_TOKEN_MARGIN = 0.05


@pytest.mark.parametrize("fmt", ["none", "int8"])
def test_kv_handoff_token_identity(tiny, fmt, monkeypatch):
    """``none`` ships exact pages: greedy token identity.  ``int8`` is a
    lossy wire and the seeded tiny model's top two logits lie 0.0015 apart
    at the 7th token, so it is held to what such a wire can promise: every
    page element within one quantisation step of the exact one, and every
    served token the reference's best at its position or within
    ``INT8_TOKEN_MARGIN`` of it, scored on the served sequence."""
    cfg, params = tiny
    samp = SamplingParams(temperature=0.0, max_new_tokens=8)
    rng = np.random.default_rng(3)
    long_prompt = rng.integers(1, cfg.vocab_size, 48).tolist()
    short = rng.integers(1, cfg.vocab_size, 8).tolist()

    ref = build_serve_engine(params, cfg, SEC)
    want_long = ref.generate(long_prompt, samp)
    want_short = ref.generate(short, samp)
    ref.close()

    shipped = []
    extract = handoff_mod.extract_request

    def extract_beside_exact(engine, uid, fmt="none"):
        # extraction is a read: the exact pages beside the ones that ship
        shipped.append((extract(engine, uid, fmt="none"),
                        extract(engine, uid, fmt=fmt)))
        return shipped[-1][1]

    monkeypatch.setattr(handoff_mod, "extract_request", extract_beside_exact)
    router = build_router(
        params, cfg, SEC,
        router=dict(n_workers=3, prefill_workers=1, disagg_threshold=32,
                    handoff_fmt=fmt),
    )
    router.submit(1, long_prompt, samp)
    router.submit(2, short, samp)
    out = router.run()
    stats = dict(router.stats)
    # the pages that were injected, against the exact ones: identical on the
    # exact wire, within one step of their chunk's scale on the int8 one
    (exact, wire), = shipped
    for (page, _, _, _), (q, s, shape, dtype) in zip(exact.payloads,
                                                     wire.payloads):
        got = qcomm.dequantize_payload(q, s, shape, dtype, fmt)
        if fmt == "none":
            np.testing.assert_array_equal(got, page)
        else:
            step = np.repeat(s, qcomm.DEFAULT_CHUNK)[:page.size]
            assert (np.abs(got - page).reshape(-1) <= step).all()
    # the long prompt went prefill-worker -> migrated at first token
    assert stats["routed_prefill"] == 1
    assert stats["handoffs"] == 1
    assert stats["handoff_wire_bytes"] > 0
    # exact wire accounting: ceil(48/8)=6 pages x bs x hkv x hd, K and V,
    # every layer; fp32 pages ship 4 B/el exact, int8 ~1 B/el + scales
    els = 2 * cfg.num_layers * 6 * 8 * cfg.num_kv_heads * cfg.hd
    if fmt == "none":
        assert stats["handoff_wire_bytes"] == els * 4
    else:
        assert els <= stats["handoff_wire_bytes"] < 1.5 * els
    # migration bookkeeping: MIGRATED on the source, adopted on the target
    src = router.pool.workers[0]
    assert dict(src.scheduler.stats)["migrated"] == 1
    assert sum(dict(w.scheduler.stats)["adopted"]
               for w in router.pool.workers[1:]) == 1
    assert out[2] == ("finished", want_short)  # never left its worker
    state, served = out[1]
    assert state == "finished" and len(served) == len(want_long)
    if fmt == "none":
        assert served == want_long  # greedy token identity through the handoff
    else:
        logits = np.asarray(forward(
            params, jnp.asarray([long_prompt + served]), cfg)[0][0])
        at = logits[len(long_prompt) - 1:-1]
        short_of_best = at.max(axis=-1) - at[np.arange(len(served)), served]
        assert (short_of_best <= INT8_TOKEN_MARGIN).all(), short_of_best
        # and where it first leaves the reference's sequence, the
        # reference's own top two lie that close
        forks = [i for i, (a, b) in enumerate(zip(served, want_long)) if a != b]
        if forks:
            best, second = np.sort(at[forks[0]])[:-3:-1]
            assert best - second <= INT8_TOKEN_MARGIN
    for audit in router.close():
        assert audit["blocks_in_use"] == 0, audit


def test_handoff_publishes_prefix_on_target(tiny):
    """After a migration the destination's cache holds the migrated prefix:
    a follow-up prompt sharing it prefix-hits locally."""
    cfg, params = tiny
    samp = SamplingParams(temperature=0.0, max_new_tokens=4)
    rng = np.random.default_rng(4)
    long_prompt = rng.integers(1, cfg.vocab_size, 48).tolist()
    router = build_router(
        params, cfg, SEC,
        router=dict(n_workers=2, prefill_workers=1, disagg_threshold=32))
    router.submit(1, long_prompt, samp)
    router.run(wait_for=[1])
    assert dict(router.stats)["handoffs"] == 1
    tgt = router.pool.workers[1]
    before = tgt.engine.mgr.cached_prompt_tokens
    # short follow-up (below the disagg threshold) sharing the migrated
    # prefix: affinity routes it to the DECODE worker, where the injected
    # pages were published — it must hit there
    router.submit(2, long_prompt[:24], samp)
    router.run(wait_for=[2])
    assert dict(router.stats)["routed_affinity"] == 1
    assert tgt.engine.mgr.cached_prompt_tokens > before
    for audit in router.close():
        assert audit["blocks_in_use"] == 0, audit


def test_quantized_handoff_pages_stay_out_of_prefix_cache(tiny):
    """int8 handoff pages are lossy roundtrips — they must NOT publish into
    the destination's exact-match prefix cache (a follow-up prefix hit
    would silently decode against off-by-quantization KV)."""
    cfg, params = tiny
    samp = SamplingParams(temperature=0.0, max_new_tokens=4)
    rng = np.random.default_rng(6)
    long_prompt = rng.integers(1, cfg.vocab_size, 48).tolist()
    router = build_router(
        params, cfg, SEC,
        router=dict(n_workers=2, prefill_workers=1, disagg_threshold=32,
                    handoff_fmt="int8"))
    router.submit(1, long_prompt, samp)
    router.run(wait_for=[1])
    assert dict(router.stats)["handoffs"] == 1
    tgt = router.pool.workers[1]
    # the migrated sequence's injected pages carry NO published keys
    assert tgt.engine.mgr.allocator.registrations == 0
    # ... and the lossy migration must not re-point the affinity chain at
    # the target either (it holds nothing hittable): a follow-up sharing
    # the prefix places least-loaded and never hits quantized pages
    router.submit(2, long_prompt[:24], samp)
    router.run(wait_for=[2])
    assert dict(router.stats)["routed_affinity"] == 0
    assert tgt.engine.mgr.cached_prompt_tokens == 0
    for audit in router.close():
        assert audit["blocks_in_use"] == 0, audit


def test_handoff_jits_compile_bounded_shapes(tiny):
    """extract/inject pad page counts to powers of two: migrating prompts of
    many distinct lengths must not compile a fresh program per length — the
    scatter donates the whole pool, so each novel shape would stall every
    worker's tick mid-migration."""
    cfg, params = tiny
    eng = build_serve_engine(params, cfg, SEC)
    try:
        for n in (1, 2, 3, 4, 5, 6, 7):
            blocks = list(range(n))
            pages = eng.extract_kv_blocks(blocks)
            for leaf in jax.tree_util.tree_leaves(pages):
                assert leaf.shape[0] == n  # padding never leaks to callers
            eng.inject_kv_blocks(blocks, pages)
        # page counts 1..7 collapse into pad buckets {1, 2, 4, 8}
        assert eng._kv_gather_jit._cache_size() <= 4
        assert eng._kv_scatter_jit._cache_size() <= 4
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# worker death: re-route + replay from the prompt
# ---------------------------------------------------------------------------
def test_worker_kill_reroutes_and_replays(tiny):
    cfg, params = tiny
    samp = SamplingParams(temperature=0.0, max_new_tokens=8)
    prompts = _workload(cfg, n_req=8, seed=5)
    want = _reference(tiny, prompts, samp)

    inj = FaultInjector(seed=0).arm("worker_kill", uids=[0], after=3, times=1)
    router = build_router(params, cfg, SEC, router=dict(n_workers=2),
                          faults=inj)
    for u, p in prompts.items():
        assert router.try_submit(u, p, samp).accepted
    out = router.run()
    stats = dict(router.stats)
    assert stats["worker_deaths"] == 1
    assert stats["replays"] > 0
    assert not router.pool.workers[0].alive
    # every request — including the replayed ones — finishes with the exact
    # fault-free greedy tokens
    assert all(out[u] == ("finished", want[u]) for u in prompts)
    # dead worker audited clean at kill time; survivor drains clean
    for audit in router.close():
        assert audit["blocks_in_use"] == 0, audit


def test_replay_budget_exhaustion_fails_typed(tiny):
    cfg, params = tiny
    samp = SamplingParams(temperature=0.0, max_new_tokens=4)
    # both workers die; max_replays=0 -> the lost request fails typed
    inj = (FaultInjector(seed=0)
           .arm("worker_kill", uids=[0], after=1, times=1)
           .arm("worker_kill", uids=[1], after=1, times=1))
    router = build_router(params, cfg, SEC,
                          router=dict(n_workers=2, max_replays=0),
                          faults=inj)
    res = router.try_submit(1, [3, 1, 4, 1, 5], samp)
    assert res.accepted
    for _ in range(4):
        router.tick()
    state, toks = router.pop_result(1)
    assert state == "failed" and toks == []
    router.close()


# ---------------------------------------------------------------------------
# SLO backpressure: retry_after_ms + front-door shed
# ---------------------------------------------------------------------------
def test_retry_later_carries_retry_after_hint(tiny):
    cfg, params = tiny
    eng = InferenceEngineV2(
        params, cfg, serve=dict(shed_queue_depth=2),
        **{k: v for k, v in SEC.items()})
    sched = eng.scheduler
    samp = SamplingParams(temperature=0.0, max_new_tokens=4)
    for uid in range(1, 9):
        sched.try_submit(uid, [7] * 40, samp)
    sched.tick()  # queue depth over the shed threshold -> shed mode
    assert sched.shedding
    res = sched.try_submit(99, [7] * 8, samp)
    assert res.reason == sched_mod.RETRY_LATER
    assert res.retry_after_ms is not None and res.retry_after_ms > 0
    # deeper backlog -> larger hint (proportional, not blind-poll constant)
    shallow = sched.retry_after_ms()
    extra = list(sched.waiting)
    sched.waiting.extend(extra)  # artificially double the queue
    assert sched.retry_after_ms() > shallow
    for _ in extra:
        sched.waiting.pop()
    eng.close()


def test_router_front_door_shed(tiny):
    cfg, params = tiny
    samp = SamplingParams(temperature=0.0, max_new_tokens=4)
    # engine sheds instantly (depth 1), router backlog capped at 2
    router = build_router(params, cfg, SEC,
                          router=dict(n_workers=1, shed_queue_depth=2),
                          serve=dict(shed_queue_depth=1))
    # burst-fill the worker queue, then one tick flips its shed detector
    for uid in range(1, 7):
        assert router.try_submit(uid, [5] * 40, samp).accepted
    router.tick()
    assert router.pool.workers[0].shedding
    # shedding worker rejects -> the router absorbs into its backlog until
    # the front-door depth (2) is hit, then the CLIENT gets the typed shed
    shed = None
    for uid in range(7, 12):
        res = router.try_submit(uid, [5] * 40, samp)
        if not res.accepted:
            shed = res
            break
    assert shed is not None, "router never shed at the front door"
    assert shed.reason == sched_mod.RETRY_LATER
    assert shed.retry_after_ms is not None and shed.retry_after_ms > 0
    assert dict(router.stats)["shed_rejections"] >= 1
    router.run()  # the admitted backlog still drains to terminal states
    router.close()


# ---------------------------------------------------------------------------
# dp>1 over-budget close-out, round two: the PR 12 typed reject is RETIRED —
# continuation prefill packs are replica-local now, so over-budget prompts
# queue and serve at any serve_replicas
# ---------------------------------------------------------------------------
@pytest.fixture
def dp2_engine(tiny):
    from deepspeed_tpu.parallel.topology import initialize_mesh

    cfg, params = tiny
    grid = initialize_mesh(devices=jax.devices()[:2], batch=2, model=1)
    eng = InferenceEngineV2(
        params, cfg, grid=grid, serve_replicas=2, max_seqs=4, num_blocks=64,
        block_size=8, prefill_buckets=(16, 32), prefill_budget=32,
        max_seq_len=256)
    yield eng
    eng.close()


def test_dp2_over_budget_prompt_served_token_identical(dp2_engine, tiny):
    """A prompt past the prefill budget on a serve_replicas=2 engine chunks
    into replica-local ctx packs instead of being rejected — and decodes
    exactly what the single-replica engine does."""
    cfg, params = tiny
    samp = SamplingParams(temperature=0.0, max_new_tokens=8)
    prompt = [3, 1, 4, 1, 5, 9, 2, 6] * 4  # 32 + 8 new > budget 32: chunks
    sched = dp2_engine.scheduler
    res = sched.try_submit(1, prompt, samp)
    assert res.accepted, res
    sched.run(wait_for=[1])
    assert sched.requests[1].state == "finished"
    got = sched.pop_result(1)
    solo = InferenceEngineV2(
        params, cfg, max_seqs=4, num_blocks=64, block_size=8,
        prefill_buckets=(16, 32), prefill_budget=32, max_seq_len=256)
    want = solo.generate(prompt, samp)
    solo.close()
    assert got == want
    dp2_engine.mgr.allocator.audit()


def test_dp2_ctx_pack_runs_replica_local(dp2_engine):
    """The engine-level half: a continuation (start > 0) pack on a
    replica-partitioned pool dispatches through the shard_map'd ctx
    attention (no NotImplementedError, KV stays block-affine)."""
    eng = dp2_engine
    seq = eng.mgr.admit(7, [3] * 24)
    eng.mgr.ensure_capacity(seq, 0)
    eng.prefill_entries([(seq, 0, 8)], SamplingParams(temperature=0.0))
    out = eng.prefill_entries([(seq, 8, 24)], SamplingParams(temperature=0.0))
    assert seq.uid in out and out[seq.uid] >= 0
    per = eng.mgr._blocks_per
    r = eng.mgr.replica_of(seq)
    assert all(r * per <= b < (r + 1) * per for b in seq.blocks)
    eng.mgr.release(7)


# ---------------------------------------------------------------------------
# adoption-path validation (the scheduler half of the handoff)
# ---------------------------------------------------------------------------
def test_adopt_prefilled_validation(tiny):
    cfg, params = tiny
    eng = build_serve_engine(params, cfg, SEC)
    sched = eng.scheduler
    samp = SamplingParams(temperature=0.0, max_new_tokens=4)
    pt, ct = eng.mgr.prompt_tokens_total, eng.mgr.cached_prompt_tokens
    ok = sched.adopt_prefilled(1, [5] * 17, n_ctx=16, sampling=samp)
    assert ok.accepted
    # adoption must not move the prefix-hit-rate accounting: the source
    # worker already counted this prompt, and the target never prefills it
    assert (eng.mgr.prompt_tokens_total, eng.mgr.cached_prompt_tokens) \
        == (pt, ct)
    seq = eng.mgr.seqs[1]
    assert seq.seen_tokens == 16 and len(seq.blocks) == 3  # ceil(17/8)
    assert sched.requests[1].state == sched_mod.DECODE
    assert sched.requests[1].generated == [5]
    # duplicate uid + bad n_ctx are typed client errors
    assert sched.adopt_prefilled(1, [5] * 17, 16, samp).reason \
        == sched_mod.REJECT_DUPLICATE_UID
    assert sched.adopt_prefilled(2, [5] * 17, 17, samp).reason \
        == sched_mod.REJECT_EMPTY_PROMPT
    # the adopted request decodes to completion through the normal loop
    sched.run(wait_for=[1])
    assert sched.requests[1].state == sched_mod.FINISHED
    sched.pop_result(1)
    audit = eng.close()
    assert audit["blocks_in_use"] == 0


def test_sampling_conflict_reroutes_not_rejects(tiny):
    """A sampling-triple conflict is per-worker BATCH state: the router
    must try the next candidate (or backlog), never hard-reject the
    client."""
    cfg, params = tiny
    router = build_router(params, cfg, SEC, router=dict(n_workers=2))
    warm = SamplingParams(temperature=0.7, top_k=5, max_new_tokens=16)
    greedy = SamplingParams(temperature=0.0, max_new_tokens=4)
    shared = [9] * 24
    # occupy worker picked for `shared` with a sampled batch (affinity
    # notes that worker for the shared prefix)
    assert router.try_submit(1, shared + [1, 2], warm).accepted
    router.tick()
    # greedy request with the same prefix affinity-routes to the busy
    # worker, conflicts there, and must land on the OTHER worker (or queue)
    res = router.try_submit(2, shared + [3, 4], greedy)
    assert res.accepted, res
    out = router.run()
    assert out[1][0] == "finished" and out[2][0] == "finished"
    assert dict(router.stats)["rejected"] == 0
    router.close()


def test_router_config_validation():
    with pytest.raises(ConfigError):
        RouterConfig(n_workers=0)
    with pytest.raises(ConfigError):
        RouterConfig(n_workers=2, prefill_workers=2)  # no decode worker left
    with pytest.raises(ConfigError):
        RouterConfig(handoff_fmt="int4")
    RouterConfig(n_workers=3, prefill_workers=1, handoff_fmt="int8")


# ---------------------------------------------------------------------------
# what routing buys over replication, and what int8 buys on the wire
# ---------------------------------------------------------------------------
def test_affinity_hits_where_replicated_gated_twin_has_none(tiny):
    """Two in-proc workers behind prefix-affinity routing against ONE
    ``serve_replicas=2`` engine with caching gated off (the replicated
    twin): hits > 0 against exactly 0, the same greedy tokens from both,
    and a telemetry namespace per worker."""
    cfg, params = tiny
    samp = SamplingParams(temperature=0.0, max_new_tokens=6)
    prompts = _workload(cfg, n_req=12)

    router = build_router(params, cfg, SEC, router=dict(n_workers=2))
    for u, p in prompts.items():
        assert router.try_submit(u, p, samp).accepted
    out = router.run()
    assert router.prefix_hit_rate() > 0.0
    assert dict(router.stats)["routed_affinity"] > 0
    assert len({w.ns for w in router.pool.workers}) == 2
    for audit in router.close():
        assert audit["blocks_in_use"] == 0, audit

    twin = build_serve_engine(
        params, cfg, dict(SEC, enable_prefix_caching=False, serve_replicas=2),
        devices=jax.devices()[:2])
    sched = twin.scheduler
    for u, p in prompts.items():
        assert sched.try_submit(u, p, samp).accepted
    sched.run()
    assert twin.mgr.cached_prompt_tokens == 0
    assert all(out[u] == ("finished", sched.pop_result(u)) for u in prompts)
    assert twin.close()["blocks_in_use"] == 0


def test_kv_handoff_int8_wire_under_half_of_exact(tiny):
    """The handoff's bytes on the wire, counted: one migration of the same
    48-token prompt ships under half as many bytes as int8 pages + scales
    as it does exact (whatever the tokens decoded afterwards are)."""
    cfg, params = tiny
    samp = SamplingParams(temperature=0.0, max_new_tokens=2)
    prompt = np.random.default_rng(3).integers(1, cfg.vocab_size, 48).tolist()
    wire = {}
    for fmt in ("none", "int8"):
        router = build_router(
            params, cfg, SEC,
            router=dict(n_workers=2, prefill_workers=1, disagg_threshold=32,
                        handoff_fmt=fmt))
        router.submit(1, prompt, samp)
        assert router.run()[1][0] == "finished"
        stats = dict(router.stats)
        assert stats["handoffs"] == 1
        wire[fmt] = stats["handoff_wire_bytes"]
        for audit in router.close():
            assert audit["blocks_in_use"] == 0, audit
    assert 0 < wire["int8"] < 0.5 * wire["none"], wire
