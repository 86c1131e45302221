"""Replica-affine serving (the un-gating of prefix caching, chunked
prefill and speculation under ``serve_replicas > 1``).

Host-side: prefix-affine admission placement (deepest cached prefix wins
over headroom), ``can_admit_all`` crediting prefix-matched blocks the way
``admit`` actually allocates, randomized R∈{2,4} allocator storms
(block-range affinity, eviction locality, zero-leak drain), per-replica
hit/headroom stats.  Engine: R=2 greedy token identity vs R=1 with
``--quant --spec`` and caching/chunked prefill ON (including an
over-budget prompt served through replica-local ctx packs), per-replica
``serve/replicaN/*`` gauges, and the deterministic-interleaving scenario
for replica-affine admission vs cancel (schedviz bank)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceEngineV2, SamplingParams
from deepspeed_tpu.inference.ragged import StateManager
from deepspeed_tpu.models import get_preset
from deepspeed_tpu.models.transformer import init_params


# ---------------------------------------------------------------------------
# host-only: placement + feasibility (no jit anywhere)
# ---------------------------------------------------------------------------
def _publish(mgr, seq):
    """Pretend the prompt prefilled: reserve its pages, mark them written
    and publish the full-block hash chain (what the engine does per pack)."""
    mgr.ensure_capacity(seq, 0)
    seq.seen_tokens = len(seq.tokens)
    mgr.update_hashes(seq)


def test_prefix_affine_placement_beats_headroom():
    mgr = StateManager(num_blocks=32, block_size=8, max_seqs=4,
                      enable_prefix_caching=True, replicas=2)
    shared = list(range(1, 25))  # 3 full blocks
    a = mgr.admit(1, shared + [90])
    assert mgr.replica_of(a) == 0  # headroom tie -> first group
    _publish(mgr, a)
    mgr.release(1)
    # burn replica 0's headroom below replica 1's
    b = mgr.admit(2, [50] * 16)
    assert mgr.replica_of(b) == 0  # still the tie-break winner
    mgr.ensure_capacity(b, 0)
    avail = [al.available_blocks for al in mgr.allocators]
    assert avail[0] < avail[1]
    # shared-prefix arrival routes to the replica HOLDING the prefix, not
    # the one with more headroom — and actually shares the cached blocks
    c = mgr.admit(3, shared + [91, 92])
    assert mgr.replica_of(c) == 0
    assert c.cached_tokens == 24
    # a cold prompt still balances to the most-headroom replica
    d = mgr.admit(4, [60] * 16)
    assert mgr.replica_of(d) == 1
    for uid in (2, 3, 4):
        mgr.release(uid)
    mgr.allocator.audit()


def test_can_admit_all_credits_active_prefix_matches():
    """The satellite fix: the greedy placement simulation must credit
    prefix-matched blocks instead of charging the full block count —
    otherwise warm-cache batches that ``admit`` would happily place get
    spuriously rejected."""
    mgr = StateManager(num_blocks=16, block_size=8, max_seqs=4,
                      enable_prefix_caching=True, replicas=2)
    shared = list(range(1, 41))  # 5 full blocks
    a = mgr.admit(1, shared)
    mgr.ensure_capacity(a, 0)
    _publish(mgr, a)
    assert mgr.replica_of(a) == 0
    b = mgr.admit(2, [77] * 40)  # fills replica 1 (r0 only has 3 left)
    mgr.ensure_capacity(b, 0)
    assert mgr.replica_of(b) == 1
    # 48-token prompt = 6 blocks: no replica has 6 free...
    assert not mgr.can_admit_all([48])
    # ...but 5 of them are ACTIVELY cached on replica 0 (refcount > 0, so
    # sharing them is free): crediting admits what admit() can place
    prompt = shared + [91] * 8
    assert mgr.can_admit_all([48], [prompt])
    c = mgr.admit(3, prompt)
    mgr.ensure_capacity(c, 0)
    assert mgr.replica_of(c) == 0
    assert c.blocks[:5] == a.blocks  # genuinely shared, not recomputed
    for uid in (1, 2, 3):
        mgr.release(uid)
    mgr.allocator.audit()


def test_can_admit_all_charges_lru_revival_once():
    """Matched blocks parked in the cached LRU leave the available pool on
    revival — charged once for the first sharer, free for the rest (the
    simulation mirrors the allocator exactly)."""
    mgr = StateManager(num_blocks=16, block_size=8, max_seqs=4,
                      enable_prefix_caching=True, replicas=2)
    b = mgr.admit(2, [77] * 40)  # cold filler: lands (and fills) replica 0
    assert mgr.replica_of(b) == 0
    mgr.ensure_capacity(b, 0)
    shared = list(range(1, 41))  # 5 full blocks
    a = mgr.admit(1, shared)  # most headroom now -> replica 1
    assert mgr.replica_of(a) == 1
    _publish(mgr, a)
    mgr.release(1)  # 5 keyed blocks retire to replica 1's LRU
    prompt = shared + [91] * 8  # 6 blocks, 5 cached
    # conservative (no tokens): the second prompt's 6 fresh blocks fit
    # neither replica (r1 down to 2 after the first, r0 holds 3) -> reject
    assert not mgr.can_admit_all([48, 48])
    # credited: first revives 5 LRU blocks + 1 fresh (6), second shares
    # the revived run and adds 1 fresh -> fits
    assert mgr.can_admit_all([48, 48], [prompt, prompt])
    c1 = mgr.admit(3, prompt)
    mgr.ensure_capacity(c1, 0)
    assert mgr.replica_of(c1) == 1
    c2 = mgr.admit(4, prompt)
    mgr.ensure_capacity(c2, 0)
    assert c1.blocks[:5] == c2.blocks[:5]
    mgr.release(2)
    mgr.release(3)
    mgr.release(4)
    mgr.allocator.audit()


def test_eviction_locality_between_replicas():
    """Pressure in one replica's pool evicts only that replica's cache —
    the other replica's published chain keeps serving hits."""
    mgr = StateManager(num_blocks=16, block_size=8, max_seqs=4,
                      enable_prefix_caching=True, replicas=2)
    left = [11] * 24
    right = [22] * 24
    a = mgr.admit(1, left + [1])
    mgr.ensure_capacity(a, 0)
    _publish(mgr, a)
    b = mgr.admit(2, right + [2])  # lands replica 1 (less headroom on 0)
    assert mgr.replica_of(b) == 1
    mgr.ensure_capacity(b, 0)
    _publish(mgr, b)
    mgr.release(1)
    mgr.release(2)
    # a cold 64-token prompt needs the WHOLE of one replica's 8 blocks:
    # placement picks a replica, eviction wipes ITS cache only
    c = mgr.admit(3, [33] * 64)
    mgr.ensure_capacity(c, 0)
    r = mgr.replica_of(c)
    other = 1 - r
    assert mgr.allocators[r].evictions > 0
    assert mgr.allocators[other].evictions == 0
    assert mgr.allocators[other].cached_blocks == 3  # survived intact
    # ...and still serves affinity hits on the untouched replica
    probe = (left if other == 0 else right) + [5, 6]
    d = mgr.admit(4, probe)
    assert mgr.replica_of(d) == other and d.cached_tokens == 24
    mgr.release(3)
    mgr.release(4)
    mgr.allocator.audit()


@pytest.mark.parametrize("replicas", [2, 4])
def test_replica_allocator_randomized_storm(replicas):
    """Randomized admit/publish/release churn with shared-prefix families
    under pool pressure: every live sequence's blocks stay inside its
    owner replica's contiguous range, the per-replica allocators audit
    clean throughout, and the drain leaks nothing."""
    rng = np.random.default_rng(replicas)
    bs = 8
    mgr = StateManager(num_blocks=16 * replicas, block_size=bs,
                      max_seqs=2 * replicas,
                      enable_prefix_caching=True, replicas=replicas)
    families = [[(f + 1) * 10 + (i % 7) for i in range(24)]
                for f in range(3)]
    live = {}
    uid = 0
    per = mgr._blocks_per
    for step in range(300):
        op = rng.random()
        if op < 0.55 and mgr.free_slots:
            uid += 1
            fam = families[int(rng.integers(len(families)))]
            sfx = rng.integers(1, 200, int(rng.integers(1, 12))).tolist()
            prompt = fam + sfx if rng.random() < 0.7 else sfx + [uid]
            if not mgr.can_admit(len(prompt), prompt):
                continue
            seq = mgr.admit(uid, prompt)
            try:
                mgr.ensure_capacity(seq, 0)
            except RuntimeError:
                mgr.release(uid)
                continue
            live[uid] = seq
            if rng.random() < 0.8:
                _publish(mgr, seq)
        elif live:
            victim = int(rng.choice(list(live)))
            mgr.release(victim)
            del live[victim]
        if step % 20 == 0:
            mgr.allocator.audit()
            for seq in live.values():
                r = mgr.replica_of(seq)
                assert all(r * per <= b < (r + 1) * per
                           for b in seq.blocks), (r, seq.blocks)
    for u in list(live):
        mgr.release(u)
    mgr.allocator.audit()
    # zero-leak drain: every block is back to free or cached-LRU
    for a in mgr.allocators:
        assert a.free_blocks + a.cached_blocks == a.total_blocks
    stats = mgr.replica_stats()
    assert len(stats) == replicas
    assert all(0.0 <= s["prefix_hit_rate"] <= 1.0 for s in stats)


# ---------------------------------------------------------------------------
# engine: R=2 vs R=1 greedy token identity with the full feature set
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny():
    # fp32 so greedy identity across shard_map/GSPMD reduction orders
    # cannot flip on bf16 near-ties (same rule as test_inference_tp)
    cfg = get_preset("tiny", max_seq_len=256, dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg=cfg, dtype=jnp.float32)
    return cfg, params


ENGINE_KW = dict(max_seqs=4, num_blocks=64, block_size=8,
                 prefill_buckets=(16, 32), prefill_budget=32,
                 enable_prefix_caching=True, prefill_chunk=16,
                 enable_speculation=True, spec_max_draft=4,
                 quantize_weights="int8")


def _workload(cfg):
    rng = np.random.default_rng(3)
    long_prompt = rng.integers(1, cfg.vocab_size, 50).tolist()  # > budget
    return [
        long_prompt,                    # over-budget: chunked ctx packs
        [7, 8, 9] * 4,                  # repetitive: speculation accepts
        long_prompt[:24] + [5, 6],      # shared prefix: cache hits
        rng.integers(1, cfg.vocab_size, 20).tolist(),  # cold
    ]


def _serve(eng, prompts, max_new=10):
    sched = eng.scheduler
    samp = SamplingParams(temperature=0.0, max_new_tokens=max_new)
    for i, p in enumerate(prompts):
        res = sched.try_submit(i + 1, p, samp)
        assert res.accepted, (i, res)
    sched.run(wait_for=list(range(1, len(prompts) + 1)))
    return {u: sched.pop_result(u) for u in range(1, len(prompts) + 1)}


def test_r2_token_identity_quant_spec_caching(tiny):
    """The acceptance bar: ``--serve-replicas 2 --quant --spec`` with
    prefix caching and chunked prefill ON — no gates, no
    NotImplementedError ctx-pack path — greedy token-identical to R=1 on
    the same workload, with speculation genuinely drafting and the pools
    auditing clean."""
    from deepspeed_tpu.parallel.topology import initialize_mesh

    cfg, params = tiny
    prompts = _workload(cfg)
    base = InferenceEngineV2(params, cfg, **ENGINE_KW)
    want = _serve(base, prompts)
    assert base.stats["spec_drafted"] > 0  # the workload really speculates

    grid = initialize_mesh(devices=jax.devices()[:2], batch=2, model=1)
    eng = InferenceEngineV2(params, cfg, grid=grid, serve_replicas=2,
                            **ENGINE_KW)
    got = _serve(eng, prompts)
    assert got == want, (got, want)
    assert eng.stats["spec_drafted"] > 0
    # every sequence decoded inside its own replica's block range and the
    # partitioned pool drains leak-free
    eng.mgr.allocator.audit()
    stats = eng.replica_stats()
    assert len(stats) == 2
    assert sum(s["spec_drafted"] for s in stats) == eng.stats["spec_drafted"]
    audit = eng.close()
    assert audit["blocks_in_use"] == 0
    base.close()


def test_r2_per_replica_telemetry_gauges(tiny):
    """serve/replicaN/* prefix-hit, pool-headroom and spec-accept gauges
    refresh at tick boundaries on partitioned engines (the imbalance
    surface for the router / future online controller)."""
    from deepspeed_tpu.parallel.topology import initialize_mesh

    cfg, params = tiny
    grid = initialize_mesh(devices=jax.devices()[:2], batch=2, model=1)
    eng = InferenceEngineV2(params, cfg, grid=grid, serve_replicas=2,
                            telemetry=True, **ENGINE_KW)
    shared = [3, 1, 4, 1, 5, 9, 2, 6] * 2
    _serve(eng, [shared + [10 + i] for i in range(3)], max_new=4)
    reg = eng.telemetry.registry
    for r in range(2):
        for name in ("prefix_hit_rate", "pool_headroom", "spec_accept_rate"):
            g = reg.get(f"serve/replica{r}/{name}")
            assert g is not None, (r, name)
            assert 0.0 <= g.value <= 1.0
    # the shared-prefix family landed with affinity: hits are visible on
    # exactly the replica(s) that served them, and aggregate > 0
    hit = [reg.get(f"serve/replica{r}/prefix_hit_rate").value
           for r in range(2)]
    assert max(hit) > 0.0, hit
    rows = eng.replica_stats()
    assert sum(r["cached_prompt_tokens"] for r in rows) > 0
    eng.close()


def test_r2_affine_dispatches_fewer_prompt_tokens_than_gated(tiny):
    """Replica-affine serving against its feature-gated twin at R = 2 on one
    shared-prefix arrival workload (caching, chunked prefill and speculation
    on / all three off): the affine engine DISPATCHES strictly fewer prompt
    tokens — a count, no clock — hits its prefix caches, emits the same
    greedy tokens and reports a row per replica."""
    from deepspeed_tpu.parallel.topology import initialize_mesh

    cfg, params = tiny
    rng = np.random.default_rng(0)
    sys_prompt = rng.integers(1, cfg.vocab_size, 48).tolist()
    prompts = {u: sys_prompt + rng.integers(1, cfg.vocab_size, 8).tolist()
               for u in range(1, 9)}
    arrivals = np.cumsum(rng.poisson(2.0, len(prompts)))
    samp = SamplingParams(temperature=0.0, max_new_tokens=6)
    got = {}
    for affine in (True, False):
        kw = (dict(enable_prefix_caching=True, prefill_chunk=32,
                   enable_speculation=True, spec_max_draft=4) if affine else
              dict(enable_prefix_caching=False, prefill_chunk=None,
                   enable_speculation=False))
        grid = initialize_mesh(devices=jax.devices()[:2], batch=2, model=1)
        eng = InferenceEngineV2(
            params, cfg, grid=grid, serve_replicas=2, max_seqs=4,
            num_blocks=64, block_size=8, max_seq_len=128,
            prefill_buckets=(16, 32, 64), prefill_budget=64, **kw)
        sched = eng.scheduler
        pending = sorted(prompts)
        while pending or not sched.idle:
            while pending and arrivals[len(prompts) - len(pending)] \
                    <= sched.tick_no:
                u = pending.pop(0)
                sched.submit(u, prompts[u], samp)
            sched.tick()
        got[affine] = dict(
            tokens={u: sched.pop_result(u) for u in prompts},
            dispatched=eng.stats["prefill_tokens_dispatched"],
            hit=eng.mgr.cached_prompt_tokens / eng.mgr.prompt_tokens_total,
            rows=eng.replica_stats())
        assert eng.close()["blocks_in_use"] == 0
    aff, gated = got[True], got[False]
    assert all(len(t) == 6 for t in aff["tokens"].values())
    assert aff["tokens"] == gated["tokens"]
    assert gated["dispatched"] == sum(len(p) for p in prompts.values())
    assert aff["dispatched"] < gated["dispatched"], (aff["dispatched"],
                                                     gated["dispatched"])
    assert aff["hit"] > 0.0 and gated["hit"] == 0.0
    assert len(aff["rows"]) == 2
    for row in aff["rows"]:
        assert {"prefix_hit_rate", "headroom", "spec_accept_rate"} <= set(row)


def test_replica_affine_schedviz_scenario():
    """The deterministic-interleaving bank entry: replica-affine admission
    vs cancel on a real replicas=2 StateManager survives a seed sweep
    (and is part of ``schedviz.SCENARIOS``)."""
    from deepspeed_tpu.analysis import schedviz

    assert schedviz.scenario_replica_affine_admission in schedviz.SCENARIOS
    rep = schedviz.explore(schedviz.scenario_replica_affine_admission,
                           seeds=range(6))
    assert rep["passed"], rep["failures"]
