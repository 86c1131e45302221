"""Serving scheduler + prefix-cached paged KV: refcounted allocator
invariants (randomized interleavings, COW), prefix-cache-hit vs cold prefill
token equivalence, chunked prefill, overload with queueing/preemption,
admission anti-starvation, dirty-tracked block-table uploads."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import (
    BlockedAllocator,
    FaultInjector,
    InferenceEngineV2,
    SamplingParams,
    ServeScheduler,
    StateManager,
)
from deepspeed_tpu.inference import scheduler as sched_mod
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
    return InferenceEngineV2(params, cfg, **kw)


# ---------------------------------------------------------------------------
# allocator: refcounts, prefix cache, LRU eviction, COW
# ---------------------------------------------------------------------------
def test_refcounted_allocator_cache_lifecycle():
    a = BlockedAllocator(4)
    [b0, b1] = a.allocate(2)
    a.register(b0, 111)
    a.ref(b0)  # shared
    assert a.refcount(b0) == 2
    a.free([b0])
    assert a.refcount(b0) == 1 and a.lookup(111) == b0
    a.free([b0])  # refcount 0 -> cached LRU, pages intact
    assert a.free_blocks == 2 and a.cached_blocks == 1
    assert a.available_blocks == 3
    # a prefix hit revives the cached block without losing its pages
    hit = a.lookup(111)
    assert hit == b0
    a.ref(hit)
    assert a.refcount(b0) == 1 and a.cached_blocks == 0
    a.free([b0, b1])
    # allocation pressure evicts the LRU block and drops its hash
    got = a.allocate(4)
    assert b0 in got and a.lookup(111) is None and a.evictions == 1
    a.audit()


def test_eviction_cascades_to_cached_descendants():
    """Evicting a cached parent block must invalidate its cached children:
    their keys name the parent's block id, which is about to be reused for
    other content — a lookup through it would serve wrong pages."""
    mgr = StateManager(num_blocks=6, block_size=4, max_seqs=2,
                       enable_prefix_caching=True)
    a = mgr.admit(1, list(range(1, 10)))  # blocks 0,1 full + partial
    mgr.ensure_capacity(a, 0)
    a.seen_tokens = 9
    mgr.update_hashes(a)
    b0, b1 = a.blocks[0], a.blocks[1]
    mgr.release(1)  # both full blocks -> cached LRU (b0 older)
    alloc = mgr.allocator
    assert alloc.cached_blocks >= 2
    # drain the pool so allocation must evict the LRU head (b0)
    got = alloc.allocate(alloc.total_blocks)
    assert b0 in got
    # the child b1 lost its key with the parent (and was freed into `got`)
    assert alloc.key_of(b1) is None and b1 in got
    # a prompt matching the old chain finds NOTHING (no stale hit)
    blocks, _ = mgr._match_prefix(list(range(1, 10)))
    assert blocks == []
    alloc.free(got)
    alloc.audit()


def test_allocator_randomized_invariants():
    """Randomized admit/prefill/decode/release/COW interleavings: refcounts
    always equal ownership counts, no block leaks or double-frees, and a
    write NEVER lands on a page owned by more than one sequence."""
    rng = np.random.default_rng(0)
    bs = 4
    mgr = StateManager(num_blocks=24, block_size=bs, max_seqs=6,
                       enable_prefix_caching=True)
    copies = []
    mgr.cow_hook = lambda src, dst: copies.append((src, dst))
    uid = 0
    live = {}

    def check():
        mgr.allocator.audit()
        owners = {}
        for s in mgr.seqs.values():
            for b in s.blocks:
                owners[b] = owners.get(b, 0) + 1
        for b in range(mgr.allocator.total_blocks):
            assert mgr.allocator.refcount(b) == owners.get(b, 0), b

    for _ in range(400):
        op = rng.choice(["admit", "decode", "release", "cow"])
        if op == "admit" and mgr.free_slots and len(mgr.seqs) < 5:
            uid += 1
            # tiny alphabet -> frequent natural prefix collisions
            prompt = [int(t) for t in rng.integers(0, 3, rng.integers(2, 14))]
            if not mgr.can_admit(len(prompt)):
                continue
            seq = mgr.admit(uid, prompt)
            try:
                mgr.ensure_capacity(seq, 0)
            except RuntimeError:
                mgr.release(uid)
                continue
            seq.seen_tokens = len(seq.tokens)  # simulate completed prefill
            mgr.update_hashes(seq)
            live[uid] = seq
        elif op == "decode" and live:
            seq = live[int(rng.choice(list(live)))]
            try:
                mgr.ensure_capacity(seq, 1)
            except RuntimeError:
                continue
            pos = seq.cur_len  # engine writes cur_len - 1 after the append
            mgr.ensure_writable(seq, pos)
            # THE shared-page invariant: the page being written is
            # exclusively owned (COW must have cloned it otherwise)
            assert mgr.allocator.refcount(seq.blocks[pos // bs]) == 1
            seq.tokens.append(int(rng.integers(0, 3)))
            seq.seen_tokens = seq.cur_len - 1
            mgr.update_hashes(seq)
        elif op == "release" and live:
            u = int(rng.choice(list(live)))
            mgr.release(u)
            del live[u]
        elif op == "cow" and live:
            seq = live[int(rng.choice(list(live)))]
            if seq.blocks:
                i = int(rng.integers(0, len(seq.blocks)))
                before = list(seq.blocks)
                mgr.ensure_writable(seq, i * bs)
                # COW swapped the page only if it was shared; either way the
                # sequence still owns exactly one writable page there
                assert mgr.allocator.refcount(seq.blocks[i]) >= 1
                if seq.blocks[i] != before[i]:
                    assert (before[i], seq.blocks[i]) in copies
        check()
    for u in list(live):
        mgr.release(u)
    check()
    assert mgr.allocator.free_blocks + mgr.allocator.cached_blocks == 24


def test_cow_clones_shared_page_before_write():
    mgr = StateManager(num_blocks=8, block_size=4, max_seqs=2,
                       enable_prefix_caching=True)
    copies = []
    mgr.cow_hook = lambda src, dst: copies.append((src, dst))
    a = mgr.admit(1, [1, 2, 3, 4, 5, 6, 7, 8, 9])  # 2 full blocks + 1
    mgr.ensure_capacity(a, 0)
    a.seen_tokens = 9
    mgr.update_hashes(a)
    b = mgr.admit(2, [1, 2, 3, 4, 5, 6, 7, 8, 2])  # shares both full blocks
    mgr.ensure_capacity(b, 0)
    assert b.cached_tokens == 8 and b.blocks[:2] == a.blocks[:2]
    shared = b.blocks[0]
    assert mgr.allocator.refcount(shared) == 2
    mgr.ensure_writable(b, 0)  # write INTO the shared page -> must clone
    assert copies == [(shared, b.blocks[0])]
    assert b.blocks[0] != shared
    assert a.blocks[0] == shared and mgr.allocator.refcount(shared) == 1
    assert mgr.cow_copies == 1


# ---------------------------------------------------------------------------
# prefix-cache-hit prefill == cold prefill (same logits path, fewer tokens)
# ---------------------------------------------------------------------------
def test_prefix_cache_hit_matches_cold_prefill(tiny):
    cfg, params = tiny
    prefix = [int(t) for t in np.arange(3, 35)]  # 32 tokens = 4 full blocks
    sfx_a, sfx_b = [7, 7, 5, 1], [9, 2, 4, 4]
    samp = SamplingParams(max_new_tokens=5)

    cold = _engine(cfg, params)
    cold_b = cold.generate(prefix + sfx_b, samp)

    hot = _engine(cfg, params, enable_prefix_caching=True)
    hot.generate(prefix + sfx_a, samp)  # populates the block cache
    before = hot.stats["prefill_tokens_dispatched"]
    hot_b = hot.generate(prefix + sfx_b, samp)
    dispatched = hot.stats["prefill_tokens_dispatched"] - before
    assert hot_b == cold_b, (hot_b, cold_b)
    # the 32-token prefix came from cache: >= 50% fewer prompt tokens run
    assert dispatched <= len(prefix + sfx_b) // 2, dispatched
    assert hot.mgr.cached_prompt_tokens >= 32


def test_chunked_prefill_matches_single_shot(tiny):
    cfg, params = tiny
    prompt = [int(t) for t in np.arange(3, 45)]  # 42 tokens
    samp = SamplingParams(max_new_tokens=5)
    ref = _engine(cfg, params).generate(prompt, samp)
    chunked = _engine(cfg, params, prefill_chunk=16)
    assert chunked.generate(prompt, samp) == ref
    # 42 tokens at 16/tick -> 3 prefill dispatches
    assert chunked.stats["prefill_dispatches"] == 3


def test_scheduler_serves_prompt_longer_than_max_bucket(tiny):
    """put() hard-rejects prompts over the largest bucket; the scheduler
    chunks them (the capability long prompts ride on)."""
    cfg, params = tiny
    prompt = [int(t) for t in np.arange(2, 100)]  # 98 > largest bucket 64
    eng = _engine(cfg, params)
    with pytest.raises(ValueError):
        eng.put([1], [prompt])
    out = eng.generate(prompt, SamplingParams(max_new_tokens=4))
    assert len(out) == 4


def test_concurrent_shared_prefix_rematches_late(tiny):
    """Requests submitted TOGETHER still share the prefix: followers are
    admitted while the cold request is writing it, and extend_match swaps
    their unwritten pages for the freshly published cached ones."""
    cfg, params = tiny
    prefix = [int(t) for t in np.arange(3, 35)]  # 32 tokens = 4 blocks
    eng = _engine(cfg, params, max_seqs=4, prefill_chunk=16,
                  enable_prefix_caching=True)
    sched = eng.scheduler
    samp = SamplingParams(max_new_tokens=4)
    for u in range(1, 4):
        sched.submit(u, prefix + [u, u + 1], samp)
    res = sched.run()
    assert len(res) == 3
    # followers 2 and 3 found the whole prefix cached despite being
    # admitted before request 1 finished writing it
    assert eng.mgr.cached_prompt_tokens >= 2 * len(prefix)
    eng.mgr.allocator.audit()


# ---------------------------------------------------------------------------
# scheduler: overload, preemption, starvation, compat
# ---------------------------------------------------------------------------
def test_scheduler_overload_completes_all(tiny):
    """Submitted load far beyond pool capacity: zero failures — every
    request completes via queueing + preemption-by-recompute, with tokens
    identical to an unconstrained engine."""
    cfg, params = tiny
    eng = _engine(cfg, params, max_seqs=3, num_blocks=8,
                  prefill_buckets=(16, 32), enable_prefix_caching=True)
    sched = eng.scheduler
    rng = np.random.default_rng(1)
    prompts = {u: [int(t) for t in rng.integers(1, 255, 14)]
               for u in range(1, 5)}
    samp = SamplingParams(max_new_tokens=24)
    for u, p in prompts.items():
        sched.submit(u, p, samp)  # never throws, though the pool is tiny
    res = sched.run()
    assert sched.stats["finished"] == 4
    assert sched.stats["preemptions"] >= 1  # pool pressure was real
    eng.mgr.allocator.audit()
    big = _engine(cfg, params, prefill_buckets=(16, 32))
    for u, p in prompts.items():
        assert res[u] == big.generate(p, samp), u


def test_scheduler_starvation_bound(tiny):
    """A stream of short prompts cannot starve a queued long prompt: once
    it has waited ``starvation_ticks``, nothing jumps the queue past it."""
    cfg, params = tiny
    eng = _engine(cfg, params, max_seqs=2, num_blocks=8,
                  prefill_buckets=(16, 32))
    sched = ServeScheduler(eng, starvation_ticks=3)
    samp = SamplingParams(max_new_tokens=6)
    rng = np.random.default_rng(2)
    uid = 100
    for _ in range(2):  # shorts occupying the pool first
        uid += 1
        sched.submit(uid, [int(t) for t in rng.integers(1, 255, 6)], samp)
    sched.submit(7, [int(t) for t in rng.integers(1, 255, 40)], samp)  # long
    finished_at = None
    for tick in range(1, 60):
        uid += 1  # one fresh short per tick, forever
        sched.submit(uid, [int(t) for t in rng.integers(1, 255, 6)], samp)
        sched.tick()
        if sched.requests[7].state == "finished":
            finished_at = tick
            break
    assert finished_at is not None, "long prompt starved"
    assert finished_at <= 30, finished_at


def test_submit_validates_but_never_capacity_throws(tiny):
    cfg, params = tiny
    eng = _engine(cfg, params, max_seqs=1, num_blocks=4)
    sched = eng.scheduler
    samp = SamplingParams(max_new_tokens=4)
    with pytest.raises(ValueError):
        sched.submit(1, [], samp)  # empty prompt: invalid
    with pytest.raises(ValueError):
        sched.submit(1, list(range(200)), samp)  # can never fit max_seq_len
    with pytest.raises(ValueError):
        # prompt fits, but prompt + max_new_tokens can never fit the pool
        # even alone — admitting it would eventually kill the whole loop
        sched.submit(1, list(range(1, 30)), SamplingParams(max_new_tokens=64))
    sched.submit(1, [1, 2, 3], samp)
    with pytest.raises(ValueError):
        sched.submit(1, [4, 5], samp)  # duplicate uid
    eng.put([99], [[1, 2]], samp)
    with pytest.raises(ValueError):
        sched.submit(99, [4, 5], samp)  # collides with a put()-admitted uid
    eng.flush([99])
    for u in range(2, 12):  # way past pool capacity: queues, no throw
        sched.submit(u, [1, 2, 3], samp)
    res = sched.run()
    assert len(res) == 11 and all(len(v) > 0 for v in res.values())


def test_generate_does_not_side_drive_put_sequences(tiny):
    """generate() runs through the scheduler: a concurrently put()-admitted
    sequence must not be advanced by it (bare step() used to decode ALL
    active sequences)."""
    cfg, params = tiny
    eng = _engine(cfg, params)
    eng.put([50], [[5, 6, 7, 8]])
    len_before = eng.mgr.seqs[50].cur_len
    eng.generate([9, 8, 7], SamplingParams(max_new_tokens=4))
    assert eng.mgr.seqs[50].cur_len == len_before


# ---------------------------------------------------------------------------
# abort paths: the cancel/timeout/failure twin of the preemption invariant
# test — refcounts return to baseline, the prefix LRU stays consistent,
# no block leaks, from ANY release point
# ---------------------------------------------------------------------------
def test_abort_path_allocator_invariants_randomized_storm(tiny):
    """Randomized cancel / deadline-timeout / injected-failure storm over
    the refcounted COW pool: after every step the allocator audits clean and
    every block's refcount equals its ownership count; after the drain the
    pool is back at baseline (free + cached == total, zero refs)."""
    cfg, params = tiny
    inj = (
        FaultInjector(seed=1)
        .arm("runner_exception", p=0.05, transient=True)
        .arm("runner_exception", p=0.03)  # occasional fatal batch failure
        .arm("nan_logits", p=0.02)
        .arm("alloc_exhaustion", p=0.03, transient=True)
    )
    eng = _engine(cfg, params, max_seqs=4, num_blocks=32,
                  enable_prefix_caching=True, faults=inj,
                  serve=dict(max_retries=2, retry_backoff_ms=0.0))
    sched = eng.scheduler
    t = [0.0]
    sched._clock = lambda: t[0]  # fake clock: deterministic deadline expiry
    samp = SamplingParams(max_new_tokens=8)
    rng = np.random.default_rng(2)
    shared = [int(x) for x in rng.integers(1, 255, 16)]
    mgr = eng.mgr

    def check():
        mgr.allocator.audit()
        owners = {}
        for s in mgr.seqs.values():
            for b in s.blocks:
                owners[b] = owners.get(b, 0) + 1
        for b in range(mgr.allocator.total_blocks):
            assert mgr.allocator.refcount(b) == owners.get(b, 0), b

    uid = 0
    for _ in range(120):
        op = rng.choice(["submit", "cancel", "expire", "tick", "tick"])
        if op == "submit":
            uid += 1
            kw = {}
            if rng.random() < 0.3:  # some requests carry tight deadlines
                kw["deadline_ms"] = float(rng.integers(1, 50))
            p = shared[: int(rng.integers(4, 16))] + [
                int(x) for x in rng.integers(1, 255, int(rng.integers(1, 6)))
            ]
            sched.try_submit(uid, p, samp, **kw)
        elif op == "cancel":
            live = [u for u, r in sched.requests.items()
                    if r.state not in sched_mod.TERMINAL]
            if live:
                sched.cancel(int(rng.choice(live)))
        elif op == "expire":
            t[0] += 0.02  # 20 fake ms: expires the tight-deadline cohort
        else:
            sched.tick()
        check()
    sched.run()  # drain the rest (faults still armed)
    states = {r.state for r in sched.requests.values()}
    assert states <= sched_mod.TERMINAL  # everything reached a typed state
    assert sched.stats["finished"] > 0  # storm didn't just kill everything
    assert eng.stats["cancelled"] + eng.stats["timed_out"] > 0  # aborts real
    for u in list(sched.requests):
        sched.pop_result(u)
    check()
    assert not mgr.seqs
    assert (mgr.allocator.free_blocks + mgr.allocator.cached_blocks
            == mgr.allocator.total_blocks)


# ---------------------------------------------------------------------------
# dirty-tracked block-table upload
# ---------------------------------------------------------------------------
def test_block_table_upload_skipped_when_static(tiny):
    cfg, params = tiny
    # block_size 16: 3-token prompt + 10 decode ticks never grow a page
    eng = _engine(cfg, params, block_size=16, prefill_buckets=(16,),
                  num_blocks=16)
    samp = SamplingParams(max_new_tokens=16)
    eng.put([1], [[5, 6, 7]], samp)
    base = eng.stats["table_uploads"]
    for _ in range(5):
        eng.step(samp)
    # one upload when the first tick saw the fresh table; after that the
    # cached device copy is reused (no page growth)
    assert eng.stats["table_uploads"] - base <= 1
    ticks_before = eng.stats["decode_ticks"]
    for _ in range(3):
        eng.step(samp)
    assert eng.stats["decode_ticks"] - ticks_before == 3
    assert eng.stats["table_uploads"] - base <= 1
    # crossing a page boundary regrows -> exactly one more upload
    for _ in range(10):
        eng.step(samp)
    assert eng.stats["table_uploads"] - base == 2
