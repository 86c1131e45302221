"""zero.Init analogue + streamed HF import tests (VERDICT r3 item 3).

Reference: runtime/zero/partition_parameters.py:824 (zero.Init),
tests/unit/runtime/zero/test_zero_context*.py.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import CausalLM, get_preset
from deepspeed_tpu.runtime import zero


def _shard_fraction(arr) -> float:
    """max per-device shard size / global size."""
    global_size = math.prod(arr.shape) or 1
    return max(
        math.prod(s.data.shape) or 1 for s in arr.addressable_shards
    ) / global_size


def test_initialize_materializes_params_sharded():
    """initialize(model=...) must build params directly into fsdp shards —
    large leaves never fully materialize on one device."""
    cfg = get_preset("tiny", max_seq_len=32).replace(
        hidden_size=128, intermediate_size=256
    )
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=CausalLM(cfg),
        config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 3, "param_persistence_threshold": 0},
        },
        mesh=deepspeed_tpu.initialize_mesh(fsdp=8),
    )
    # every big leaf of the live master tree is 1/8-sharded
    big = [
        l for l in jax.tree_util.tree_leaves(engine.state.params)
        if l.size >= 128 * 128
    ]
    assert big
    for leaf in big:
        assert _shard_fraction(leaf) <= 1 / 8 + 1e-6, leaf.shape


def test_init_sharded_params_direct():
    cfg = get_preset("tiny").replace(hidden_size=128, intermediate_size=256)
    model = CausalLM(cfg)
    grid = deepspeed_tpu.initialize_mesh(fsdp=8)
    key = jax.random.PRNGKey(0)
    shapes = jax.eval_shape(model.init_params, key)
    from deepspeed_tpu.config.config import parse_config

    c = parse_config({"zero_optimization": {"stage": 3}})
    plan = zero.plan_sharding(shapes, c.zero_optimization, grid.spec)
    params = zero.init_sharded_params(model.init_params, key, plan, grid.mesh)
    # numerics identical to a dense init (same PRNG stream)
    dense = model.init_params(key)
    for a, b in zip(
        jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(dense)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6)


def test_zero_init_context_manager():
    cfg = get_preset("tiny").replace(hidden_size=128)
    model = CausalLM(cfg)
    grid = deepspeed_tpu.initialize_mesh(fsdp=8)
    with zero.Init({"zero_optimization": {"stage": 3}}, grid) as zi:
        params = zi.materialize(model.init_params, jax.random.PRNGKey(0))
    emb = params["embed"]["embedding"]
    assert _shard_fraction(emb) <= 1 / 8 + 1e-6


def test_opt_state_specs_match_by_path_not_shape():
    """Two same-shaped params with different TP specs must give their Adam
    moments different layouts (VERDICT r2 weak #8)."""
    import optax
    from jax.sharding import PartitionSpec as P

    shapes = {
        "a": jax.ShapeDtypeStruct((16, 32), jnp.float32),
        "b": jax.ShapeDtypeStruct((16, 32), jnp.float32),
    }
    from deepspeed_tpu.config.config import parse_config

    c = parse_config({"zero_optimization": {"stage": 0}})
    rules = [(r"^a$", P(None, "model")), (r"^b$", P("model", None))]
    grid = deepspeed_tpu.initialize_mesh(model=8)
    plan = zero.plan_sharding(shapes, c.zero_optimization, grid.spec, rules)
    opt = optax.adam(1e-3)
    params = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    opt_shapes = jax.eval_shape(opt.init, params)
    shardings = plan.opt_state_shardings(grid.mesh, opt_shapes)
    mu = shardings[0].mu
    assert mu["a"].spec == P(None, "model")
    assert mu["b"].spec == P("model", None)


def test_streamed_hf_import_matches_dense(tmp_path):
    from deepspeed_tpu.checkpoint.hf_import import (
        export_hf_checkpoint,
        load_hf_checkpoint,
        load_hf_checkpoint_sharded,
    )
    from deepspeed_tpu.config.config import parse_config

    cfg = get_preset("tiny", max_seq_len=32).replace(
        hidden_size=128, intermediate_size=256, num_kv_heads=4
    )
    model = CausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(1))
    export_hf_checkpoint(params, cfg, str(tmp_path))

    dense, cfg_d = load_hf_checkpoint(str(tmp_path))
    grid = deepspeed_tpu.initialize_mesh(fsdp=8)
    c = parse_config({"zero_optimization": {"stage": 3}})
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    plan = zero.plan_sharding(shapes, c.zero_optimization, grid.spec)
    streamed, cfg_s = load_hf_checkpoint_sharded(str(tmp_path), plan, grid.mesh, cfg=cfg)

    flat_d = jax.tree_util.tree_leaves(dense)
    flat_s = jax.tree_util.tree_leaves(streamed)
    assert len(flat_d) == len(flat_s)
    for a, b in zip(flat_d, flat_s):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0)
    # streamed leaves are actually sharded
    emb = streamed["embed"]["embedding"]
    assert _shard_fraction(emb) <= 1 / 8 + 1e-6


def test_streamed_import_through_initialize(tmp_path):
    """initialize(model=<hf dir>) end-to-end: streamed weights, trains."""
    from deepspeed_tpu.checkpoint.hf_import import export_hf_checkpoint

    cfg = get_preset("tiny", max_seq_len=32).replace(
        hidden_size=128, intermediate_size=256, num_kv_heads=4
    )
    params = CausalLM(cfg).init_params(jax.random.PRNGKey(2))
    export_hf_checkpoint(params, cfg, str(tmp_path))

    engine, _, _, _ = deepspeed_tpu.initialize(
        model=str(tmp_path),
        config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 3, "param_persistence_threshold": 0},
        },
        mesh=deepspeed_tpu.initialize_mesh(fsdp=8),
    )
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (8, 33)).astype(np.int32)}
    l0 = float(engine.train_batch(batch))
    l1 = float(engine.train_batch(batch))
    assert np.isfinite([l0, l1]).all() and l1 < l0
