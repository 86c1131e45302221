"""LoRA tests (reference: tests/unit/linear/ semantics)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.linear import LoRACausalLM, LoRAConfig, optimized_linear
from deepspeed_tpu.models import CausalLM, get_preset


def _lora_engine(r=4, lr=1e-2):
    cfg = get_preset("tiny", max_seq_len=32)
    model = LoRACausalLM(CausalLM(cfg), LoRAConfig(lora_r=r))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "adamw", "params": {"lr": lr, "weight_decay": 0.1}},
        },
        mesh=deepspeed_tpu.initialize_mesh(data=8),
    )
    return engine, model, cfg


def test_lora_init_shapes_and_identity():
    engine, model, cfg = _lora_engine()
    params = engine.state.params
    assert set(params) == {"base", "lora"}
    for group in params["lora"].values():
        assert group["a"].shape[-1] == 4 and group["b"].shape[-2] == 4
        # B starts at zero: adapter is initially the identity
        assert float(jnp.abs(group["b"]).max()) == 0.0
    # merged == base at init
    merged = model.merge(params)
    for a, b in zip(
        jax.tree_util.tree_leaves(merged), jax.tree_util.tree_leaves(params["base"])
    ):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=1e-6
        )


def test_lora_trains_and_base_stays_frozen():
    engine, model, cfg = _lora_engine()
    base_before = jax.tree_util.tree_map(np.asarray, engine.state.params["base"])
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (16, 33)).astype(np.int32)}
    losses = [float(engine.train_batch(batch)) for _ in range(8)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    # base untouched (even with weight_decay in the optimizer)
    for before, after in zip(
        jax.tree_util.tree_leaves(base_before),
        jax.tree_util.tree_leaves(engine.state.params["base"]),
    ):
        np.testing.assert_array_equal(before, np.asarray(after))
    # adapters moved
    moved = any(
        float(jnp.abs(g["b"]).max()) > 0
        for g in engine.state.params["lora"].values()
    )
    assert moved


def test_lora_optimizer_state_is_masked():
    """Frozen leaves carry no Adam moments — the LoRA memory win."""
    engine, _, _ = _lora_engine()
    import optax

    leaves = jax.tree_util.tree_leaves(engine.state.opt_state)
    n_state = sum(l.size for l in leaves if hasattr(l, "size"))
    n_lora = sum(
        l.size for l in jax.tree_util.tree_leaves(engine.state.params["lora"])
    )
    n_base = sum(
        l.size for l in jax.tree_util.tree_leaves(engine.state.params["base"])
    )
    # mu+nu for lora only (plus scalar counts), nothing for base
    assert n_state < 2 * n_lora + 64
    assert n_state < n_base  # sanity: far below full-model state


# slow: 11 s: trains LoRA, merges, then builds and serves an engine on the merged tree
@pytest.mark.slow
def test_lora_export_merged_deploys():
    engine, model, cfg = _lora_engine()
    rng = np.random.default_rng(1)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (16, 33)).astype(np.int32)}
    for _ in range(3):
        engine.train_batch(batch)
    merged = model.export_merged(engine.state.params)
    # merged weights run in the plain model with identical loss
    plain = CausalLM(cfg)
    l_plain = float(plain.loss_fn(
        jax.tree_util.tree_map(lambda x: x.astype(cfg.dtype), merged),
        {"input_ids": jnp.asarray(batch["input_ids"])},
    ))
    l_lora = float(model.loss_fn(
        engine.state.params, {"input_ids": jnp.asarray(batch["input_ids"])},
    ))
    assert abs(l_plain - l_lora) < 5e-2


def test_optimized_linear_functional():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(4, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    a = jnp.asarray(rng.normal(size=(16, 2)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(2, 8)), jnp.float32)
    out = optimized_linear(x, w, a, b, scale=0.5)
    ref = x @ w + (x @ a) @ b * 0.5
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_lora_base_has_no_fp32_master():
    """Frozen base leaves keep bf16 storage — no fp32 master copy."""
    engine, _, _ = _lora_engine()
    for leaf in jax.tree_util.tree_leaves(engine.state.params["base"]):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert leaf.dtype == jnp.bfloat16, leaf.dtype
    for group in engine.state.params["lora"].values():
        assert group["a"].dtype == jnp.float32
