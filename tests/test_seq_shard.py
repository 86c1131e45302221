"""Sequence-sharded paged-KV decode (3-D ``batch x seq x model`` serve mesh).

Reference: DeepSpeed-Inference's KV-block management
(``blocked_allocator.py``) never splits one sequence's pool across
devices — a context is bounded by one chip's HBM.  The seq-shard growth
stripes the paged pool over a ``seq`` mesh axis instead: each shard holds
a contiguous slice of the block pool, a sequence's chain round-robins
over the slices (page ``i`` lives on shard ``i % S``), every shard
computes flash-style partial attention against only its local pages, and
the partials merge through an ``S-1``-hop log-sum-exp ring
(``collective_permute`` carrying the ``[B, hq, hd+2]`` accumulator).

Tests pin the four load-bearing claims on the virtual 8-device CPU mesh:

- host-side striping invariants under an allocate/cache/evict storm
  (chain position ``i``'s page provably lives on stripe ``i % S``);
- the admission contract (a prompt over ONE slice's budget is a typed
  ``pool_impossible`` reject carrying the budget it was judged against;
  the same prompt is admitted and served to terminal at ``S=2``);
- the wire shape (exactly ``(S-1) * num_layers`` ring permutes in the
  decode program, sourced from qcomm.py, and NO pool gather);
- end-to-end greedy token identity vs the single-pool engine, including
  through int8 weights, prefix caching, and the megastep burst path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceEngineV2, SamplingParams
from deepspeed_tpu.inference.ragged import BlockedAllocator
from deepspeed_tpu.inference.scheduler import REJECT_POOL_IMPOSSIBLE
from deepspeed_tpu.models import CausalLM, get_preset
from deepspeed_tpu.parallel.topology import initialize_mesh


@pytest.fixture(scope="module")
def gqa_model():
    # fp32: greedy parity across different reduction orders (ring-merged
    # attention partials) must not flip argmax on bf16 near-ties
    cfg = get_preset("tiny", max_seq_len=128, dtype=jnp.float32)
    model = CausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return model, params


# ---------------------------------------------------------------------------
# allocator striping (host side, no mesh)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("stripes", [2, 4])
def test_allocator_striping_storm(stripes):
    """Randomized allocate/extend/register/free/evict storm: every chain
    keeps the ``stripe_of(chain[i]) == i % S`` placement invariant, the
    free lists stay stripe-pure (``audit``), ``can_allocate`` is an exact
    oracle for ``allocate``, and a full drain leaks nothing."""
    rng = np.random.default_rng(0)
    alloc = BlockedAllocator(32, stripes=stripes)
    chains = {}  # uid -> block chain, grown with first_pos threading
    next_uid = 0
    for step in range(400):
        op = rng.integers(0, 3)
        if op == 0:  # start or extend a chain
            if chains and rng.integers(0, 2):
                uid = int(rng.choice(list(chains)))
            else:
                uid = next_uid = next_uid + 1
                chains.setdefault(uid, [])
            chain = chains[uid]
            n = int(rng.integers(1, 5))
            ok = alloc.can_allocate(n, first_pos=len(chain))
            if not ok:
                with pytest.raises(RuntimeError):
                    alloc.allocate(n, first_pos=len(chain))
                continue
            chain.extend(alloc.allocate(n, first_pos=len(chain)))
        elif op == 1 and chains:  # retire a chain (cache a keyed prefix)
            uid = int(rng.choice(list(chains)))
            chain = chains.pop(uid)
            # key a random prefix so retirement populates the cached LRU
            # and later allocations exercise the per-stripe evict path
            for i in range(int(rng.integers(0, len(chain) + 1))):
                alloc.register(chain[i], key=("storm", uid, i),
                               parent=chain[i - 1] if i else None)
            alloc.free(chain)
        elif op == 2 and chains:  # share then release (refcount > 1 path)
            uid = int(rng.choice(list(chains)))
            b = chains[uid][0]
            alloc.ref(b)
            alloc.free([b])
        for uid, chain in chains.items():
            for i, b in enumerate(chain):
                assert alloc.stripe_of(b) == i % stripes, (uid, i, b)
        if step % 25 == 0:
            alloc.audit()
    for chain in chains.values():
        alloc.free(chain)
    alloc.audit()
    assert alloc.available_blocks == alloc.total_blocks


def test_allocator_striping_round_robin_contract():
    """``first_pos`` threading: a chain grown across multiple allocate
    calls round-robins stripes from its CHAIN position, not the call
    boundary — and the stripes must divide the pool."""
    alloc = BlockedAllocator(12, stripes=3)
    chain = alloc.allocate(2, first_pos=0)
    chain += alloc.allocate(4, first_pos=2)
    chain += alloc.allocate(1, first_pos=6)
    assert [alloc.stripe_of(b) for b in chain] == [0, 1, 2, 0, 1, 2, 0]
    with pytest.raises(ValueError):
        BlockedAllocator(10, stripes=3)


# ---------------------------------------------------------------------------
# admission contract (typed reject vs aggregate budget)
# ---------------------------------------------------------------------------
def test_over_one_pool_prompt_typed_reject(gqa_model):
    """A prompt bigger than the pool is rejected with the budget it was
    judged against — the field the capacity router needs to route the
    request to a seq-sharded engine instead of erroring it."""
    model, params = gqa_model
    eng = InferenceEngineV2(params, model.cfg, max_seqs=2, num_blocks=8,
                            block_size=8, prefill_buckets=(32, 64, 128),
                            max_seq_len=120)
    prompt = [(i * 7 + 3) % 50 + 1 for i in range(80)]  # 10 blocks > 8
    res = eng.scheduler.try_submit(1, prompt, SamplingParams(max_new_tokens=8))
    assert not res.accepted and res.reason == REJECT_POOL_IMPOSSIBLE
    assert res.budget_blocks == 8
    assert res.budget_scope == "replica_pool"


def test_over_one_pool_prompt_served_at_s2(gqa_model):
    """The same per-slice capacity with a seq axis to borrow from: the
    80-token prompt (over one slice's 64-token budget, under the 128-token
    aggregate) is admitted, served to terminal, and drains zero-leak."""
    model, params = gqa_model
    grid = initialize_mesh(devices=jax.devices()[:2], seq=2)
    eng = InferenceEngineV2(params, model.cfg, grid=grid, seq_shards=2,
                            max_seqs=2, num_blocks=16, block_size=8,
                            prefill_buckets=(32, 64, 128), max_seq_len=120)
    prompt = [(i * 7 + 3) % 50 + 1 for i in range(80)]
    sched = eng.scheduler
    res = sched.try_submit(1, prompt, SamplingParams(max_new_tokens=8))
    assert res.accepted, res
    sched.run(wait_for=[1])
    assert sched.requests[1].state == "finished", (
        sched.requests[1].state, sched.requests[1].error)
    assert len(sched.pop_result(1)) == 8
    eng.mgr.allocator.audit()
    audit = eng.close()
    assert audit["blocks_in_use"] == 0, audit


# ---------------------------------------------------------------------------
# wire shape: the ring is S-1 permutes per layer, never a pool gather
# ---------------------------------------------------------------------------
def test_decode_hlo_ring_hops_only(gqa_model):
    """The decode program at S=2 carries EXACTLY ``(S-1) * num_layers``
    collective-permutes (the lse-merge ring, attributed to qcomm.py) and
    no other collective — in particular no all-gather: materializing the
    remote pool slices would erase the capacity the axis exists to buy."""
    from deepspeed_tpu.analysis.audit import serve_jit_specs
    from deepspeed_tpu.analysis.hlo import parse_scheduled_hlo

    model, params = gqa_model
    grid = initialize_mesh(devices=jax.devices()[:2], seq=2)
    eng = InferenceEngineV2(params, model.cfg, grid=grid, seq_shards=2,
                            max_seqs=4, num_blocks=64, block_size=8,
                            prefill_buckets=(16, 32))
    spec = serve_jit_specs(eng)["decode"]
    facts = parse_scheduled_hlo(
        spec["jit"].lower(*spec["args"]).compile().as_text())
    live = [c for c in facts.collectives if c.phase != "done"]
    assert [c.kind for c in live] == \
        ["collective-permute"] * model.cfg.num_layers
    assert all(c.source_file == "qcomm.py" for c in live), live
    eng.close()


# ---------------------------------------------------------------------------
# end-to-end token identity (the capability changes capacity, not content)
# ---------------------------------------------------------------------------
def _serve_all(eng, prompts, max_new=8):
    sched = eng.scheduler
    for uid, p in prompts.items():
        assert sched.try_submit(
            uid, p, SamplingParams(temperature=0.0,
                                   max_new_tokens=max_new)).accepted
    sched.run(wait_for=list(prompts))
    out = {u: sched.pop_result(u) for u in prompts}
    stats = dict(eng.stats)
    audit = eng.close()
    assert audit["blocks_in_use"] == 0, audit
    return out, stats


# slow: 12-15 s a case: a solo and a seq-sharded engine served side by side on the virtual mesh
@pytest.mark.slow
@pytest.mark.parametrize("seq,tp", [(2, 1), (2, 2)])
def test_seq_sharded_token_parity(gqa_model, seq, tp):
    """Greedy token identity vs the single-chip engine through the whole
    recovered feature set at once: int8 weights, prefix caching (shared
    prefix prompts), and the megastep decode burst."""
    from deepspeed_tpu.config.config import ServeConfig

    model, params = gqa_model
    kw = dict(max_seqs=4, num_blocks=64, block_size=8,
              prefill_buckets=(16, 32), quantize_weights="int8",
              enable_prefix_caching=True,
              serve=ServeConfig(decode_megastep=4))
    shared = [7, 3, 9, 1, 4, 6, 2, 8]
    prompts = {u: shared + [10 + u, 20 + u, 30 + u] for u in (1, 2, 3)}

    base = InferenceEngineV2(params, model.cfg, **kw)
    want, _ = _serve_all(base, prompts)

    grid = initialize_mesh(devices=jax.devices()[:seq * tp],
                           seq=seq, model=tp)
    eng = InferenceEngineV2(params, model.cfg, grid=grid, seq_shards=seq,
                            **kw)
    got, stats = _serve_all(eng, prompts)
    assert got == want
    assert stats["decode_bursts"] > 0, "megastep burst path never ran"


# slow: 16 s: compiles every hot jit of a speculating int8 engine on a seq2 x tp2 mesh
@pytest.mark.slow
def test_audit_green_at_s2_tp2(gqa_model):
    """The collective-budget audit holds on the 3-D mesh: every hot jit's
    HLO wire bytes match the analytical plan, with the decode/verify ring
    hops ENUMERATED (seq_ring rows) rather than waived."""
    from deepspeed_tpu.analysis.audit import audit_serve_engine

    model, params = gqa_model
    cfg = model.cfg.replace(hidden_size=256, intermediate_size=256,
                            num_heads=4, num_kv_heads=2)
    params = CausalLM(cfg).init_params(jax.random.PRNGKey(0))
    grid = initialize_mesh(devices=jax.devices()[:4], seq=2, model=2)
    eng = InferenceEngineV2(params, cfg, grid=grid, seq_shards=2,
                            quant_comm="int8", comm_tiles=2,
                            max_seqs=2, num_blocks=64, block_size=8,
                            prefill_buckets=(16,), quantize_weights="int8",
                            enable_speculation=True, spec_max_draft=2)
    rep = audit_serve_engine(eng)
    assert rep["engine"]["seq_shards"] == 2
    assert rep["passed"], {
        name: [c for c in j.get("checks", ()) if not c["passed"]]
        for name, j in rep["jits"].items() if not j.get("passed", True)}
    eng.close()
