"""TP-sharded (multi-chip) serving for the v2 engine.

Reference: ``inference/v2/engine_v2.py:93 _initialize_tp_group`` +
``inference/v2/model_implementations/sharding/`` — the v2 engine serves a
model sharded over a TP group.  Here the same capability is a mesh handed to
``InferenceEngineV2``: AutoTP param shardings, a kv-head-sharded block pool,
and the paged attention running per-shard under shard_map.  Tests check
end-to-end token parity between sharded and unsharded serving on the virtual
8-device CPU mesh (the reference's multi-process proxy, SURVEY §4).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceEngineV2, SamplingParams
from deepspeed_tpu.models import CausalLM, get_preset
from deepspeed_tpu.parallel.topology import MODEL_AXIS, initialize_mesh

from conftest import make_grid


@pytest.fixture(scope="module")
def gqa_model():
    # fp32: greedy parity across different reduction orders (TP psum of
    # matmul partials) must not flip argmax on bf16 near-ties
    cfg = get_preset("tiny", max_seq_len=128, dtype=jnp.float32)  # hq=4, hkv=2
    model = CausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return model, params


def _generate_all(eng, prompts, n=6):
    outs = {}
    uids = list(range(1, len(prompts) + 1))
    sampling = SamplingParams(max_new_tokens=n)
    eng.put(uids, prompts, sampling)
    for _ in range(n - 1):
        eng.step(sampling)
    for uid, p in zip(uids, prompts):
        outs[uid] = eng.mgr.seqs[uid].tokens[len(p):][:n]
    eng.flush(uids)
    return outs


@pytest.mark.parametrize("tp", [2, 4])
def test_tp_serving_token_parity(gqa_model, tp):
    """tp=2: kv heads shard (hkv=2).  tp=4: hkv < tp — pool replicates and
    each shard gathers its q heads' kv head (the GQA alignment path)."""
    model, params = gqa_model
    kw = dict(max_seqs=4, num_blocks=64, block_size=8, prefill_buckets=(16, 32))
    prompts = [[3, 1, 4, 1, 5], [2, 7, 1, 8, 2, 8, 1], [9, 9, 8, 2]]

    base = InferenceEngineV2(params, model.cfg, **kw)
    want = _generate_all(base, prompts)

    grid = make_grid(model=tp)
    eng = InferenceEngineV2(params, model.cfg, grid=grid, **kw)
    got = _generate_all(eng, prompts)
    assert got == want, (got, want)


def test_tp_kv_pool_actually_sharded(gqa_model):
    """The capacity claim is real only if each device holds hkv/tp heads of
    the pool — assert the shard shape, not just the spec."""
    model, params = gqa_model
    grid = initialize_mesh(devices=jax.devices()[:2], model=2)
    eng = InferenceEngineV2(params, model.cfg, max_seqs=2, num_blocks=32,
                            block_size=8, prefill_buckets=(16,), grid=grid)
    ck, _ = eng.kv
    # per-LAYER pool buffers: [num_blocks, bs, hkv, hd] each
    spec = ck[0].sharding.spec
    assert spec[2] == MODEL_AXIS
    shard = ck[0].addressable_shards[0].data
    assert shard.shape[2] == model.cfg.num_kv_heads // 2
    # param shardings: at least one leaf is actually split on 'model'
    shardings = jax.tree_util.tree_leaves(eng._param_shardings)
    assert any(MODEL_AXIS in tuple(s.spec) for s in shardings)
    # decode still works and keeps the pool sharded (out_shardings pin)
    eng.put([1], [[3, 1, 4, 1, 5]])
    eng.step()
    ck2, _ = eng.kv
    assert ck2[0].sharding.spec[2] == MODEL_AXIS


def test_tp_serving_rejects_bad_combos(gqa_model):
    model, params = gqa_model
    grid = make_grid(model=2)
    with pytest.raises(ValueError, match="exclusive"):
        InferenceEngineV2(params, model.cfg, grid=grid, offload_weights=True)
    grid3 = initialize_mesh(devices=jax.devices()[:3], model=3)
    with pytest.raises(ValueError, match="divisible"):
        InferenceEngineV2(params, model.cfg, grid=grid3)


def test_2d_batch_model_mesh_token_parity(gqa_model):
    """The 2-D batch x model serve mesh: slots and KV blocks partitioned
    into per-replica groups over 'batch', weights sharded over 'model' —
    greedy decode token-identical to the single-chip engine, with the pool
    actually sharded on its block dim and every sequence's blocks affine to
    its replica's range."""
    model, params = gqa_model
    kw = dict(max_seqs=4, num_blocks=64, block_size=8, prefill_buckets=(16, 32))
    prompts = [[3, 1, 4, 1, 5], [2, 7, 1, 8, 2, 8, 1], [9, 9, 8, 2], [5, 5, 2]]

    base = InferenceEngineV2(params, model.cfg, **kw)
    want = _generate_all(base, prompts)

    grid = initialize_mesh(devices=jax.devices()[:4], batch=2, model=2)
    eng = InferenceEngineV2(params, model.cfg, grid=grid, serve_replicas=2,
                            **kw)
    # pool sharded over the batch axis on its BLOCK dim: half the blocks
    # per replica — the capacity-scaling claim
    ck, _ = eng.kv
    assert ck[0].sharding.spec[0] == "data"  # BATCH_AXIS alias
    assert ck[0].addressable_shards[0].data.shape[0] == 32

    uids = list(range(1, len(prompts) + 1))
    sampling = SamplingParams(max_new_tokens=6)
    eng.put(uids, prompts, sampling)
    # admission balanced across BOTH replica groups, and every block
    # affine to its owner's range (the invariant the in-region block-id
    # translation relies on)
    reps = set()
    for s in eng.mgr.seqs.values():
        r = eng.mgr.replica_of(s)
        reps.add(r)
        per = eng.mgr._blocks_per
        assert all(r * per <= b < (r + 1) * per for b in s.blocks), (
            r, s.blocks)
    assert reps == {0, 1}
    for _ in range(5):
        eng.step(sampling)
    got = {u: eng.mgr.seqs[u].tokens[len(p):][:6]
           for u, p in zip(uids, prompts)}
    eng.flush(uids)
    assert got == want, (got, want)
    # released slots/blocks return to their own groups
    eng.mgr.allocator.audit()
    assert eng.mgr.free_slots == 4


def test_2d_mesh_can_schedule_is_replica_aware(gqa_model):
    """A prompt that fits the SUM of the per-replica pools but no single
    replica must be refused by can_schedule, and a put() that slips past
    anyway must stay all-or-nothing (nothing left admitted)."""
    model, params = gqa_model
    grid = initialize_mesh(devices=jax.devices()[:4], batch=2, model=2)
    eng = InferenceEngineV2(params, model.cfg, grid=grid, serve_replicas=2,
                            max_seqs=4, num_blocks=16, block_size=8,
                            prefill_buckets=(16, 32, 64, 128))
    # 8 blocks per replica; 80 tokens need 10 blocks: aggregate 16 would
    # accept, either replica alone cannot
    assert not eng.can_schedule([80])
    assert eng.can_schedule([40])  # 5 blocks: fits one replica
    # two 40-token prompts land on DIFFERENT replicas (5+5 > 8 on one)
    assert eng.can_schedule([40, 40])
    with pytest.raises(RuntimeError):
        eng.put([1], [[7] * 80], SamplingParams(max_new_tokens=2))
    # nothing leaked: no sequence admitted, all slots free
    assert not eng.mgr.seqs and eng.mgr.free_slots == 4
    eng.mgr.allocator.audit()


def test_2d_mesh_rejects_bad_wiring(gqa_model):
    model, params = gqa_model
    kw = dict(max_seqs=4, num_blocks=64, block_size=8, prefill_buckets=(16,))
    # replicas without a matching batch-axis grid
    grid = make_grid(model=2)  # leftover fills data=4, not 2
    with pytest.raises(ValueError, match="batch"):
        InferenceEngineV2(params, model.cfg, grid=grid, serve_replicas=2, **kw)
    grid2 = initialize_mesh(devices=jax.devices()[:4], batch=2, model=2)
    with pytest.raises(ValueError, match="divide"):
        InferenceEngineV2(params, model.cfg, grid=grid2, serve_replicas=2,
                          max_seqs=3, num_blocks=64, block_size=8,
                          prefill_buckets=(16,))
    # prefix caching / chunked prefill / speculation construct fine at
    # R>1 now — replica-affine serving retired the old NotImplementedError
    # gate (tests/test_replica_affinity.py covers the behavior end to end)
    eng = InferenceEngineV2(params, model.cfg, grid=grid2, serve_replicas=2,
                            enable_prefix_caching=True, prefill_chunk=16,
                            enable_speculation=True, **kw)
    assert eng.enable_prefix_caching and eng.enable_speculation


def test_tp_serving_with_quantized_weights(gqa_model):
    """TP x int8 serving (the multi-chip capacity combo): sharded compressed
    weights must generate exactly like single-device compressed weights."""
    model, params = gqa_model
    kw = dict(max_seqs=2, num_blocks=64, block_size=8, prefill_buckets=(16,))
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    samp = SamplingParams(max_new_tokens=5)
    solo = InferenceEngineV2(
        params, model.cfg, quantize_weights="int8", **kw
    ).generate(prompt, samp)
    grid = make_grid(model=2)
    eng = InferenceEngineV2(
        params, model.cfg, grid=grid, quantize_weights="int8", **kw
    )
    got = eng.generate(prompt, samp)
    assert got == solo, (got, solo)
    # at least one compressed payload is actually split on 'model'
    from deepspeed_tpu.ops.quantizer import ServingQuant

    qs = [
        l for l in jax.tree_util.tree_leaves(
            eng.params, is_leaf=lambda x: isinstance(x, ServingQuant)
        )
        if isinstance(l, ServingQuant)
    ]
    assert qs, "no quantized leaves survived TP placement"
    assert any(
        MODEL_AXIS in jax.tree_util.tree_flatten(
            tuple(q.q.sharding.spec)
        )[0]
        for q in qs
    )
