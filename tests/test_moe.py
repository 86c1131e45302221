"""MoE gating + layer tests (reference: tests/unit/moe/test_moe.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import CausalLM, get_preset, init_params
from deepspeed_tpu.models.transformer import forward
from deepspeed_tpu.moe.sharded_moe import capacity_for, top1_gating, topk_gating
from deepspeed_tpu.parallel.sharding import set_current_mesh
from deepspeed_tpu.parallel.topology import initialize_mesh


def test_capacity_formula():
    assert capacity_for(64, 4, 1, 1.0) == 16
    assert capacity_for(64, 4, 2, 1.0) == 32
    assert capacity_for(8, 8, 1, 1.0, min_capacity=4) == 4  # floor


def test_top1_gating_routes_every_token_with_slack():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(32, 4)), jnp.float32)
    g = top1_gating(logits, capacity_factor=4.0)
    # plenty of capacity: nothing dropped, each token exactly one slot
    assert float(g.dropped_fraction) == 0.0
    assert np.all(np.asarray(jnp.sum(g.dispatch, axis=(1, 2))) == 1)
    # combine weight for each token == its top prob
    probs = jax.nn.softmax(logits, axis=-1)
    got = np.asarray(jnp.sum(g.combine, axis=(1, 2)))
    np.testing.assert_allclose(got, np.asarray(jnp.max(probs, axis=-1)), atol=1e-6)


def test_capacity_drops_overflow():
    # all tokens want expert 0; capacity caps what gets through
    logits = jnp.full((16, 4), -10.0).at[:, 0].set(10.0)
    g = top1_gating(logits, capacity_factor=1.0)  # cap = 4
    assert int(jnp.sum(g.dispatch)) == 4
    assert float(g.dropped_fraction) == pytest.approx(12 / 16)


def test_top2_weight_normalization():
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(16, 4)), jnp.float32)
    g = topk_gating(logits, k=2, capacity_factor=4.0)
    # combine weights of each token sum to 1 (renormalized top-2)
    sums = np.asarray(jnp.sum(g.combine, axis=(1, 2)))
    np.testing.assert_allclose(sums, 1.0, atol=1e-5)


def test_second_choice_queues_behind_first():
    # expert 0 is everyone's first choice, expert 1 everyone's second;
    # with cap=4 the 2nd-choice queue for expert 1 must start at its own 0
    logits = jnp.tile(jnp.asarray([[5.0, 3.0, -5.0, -5.0]]), (8, 1))
    g = topk_gating(logits, k=2, capacity_factor=1.0, min_capacity=4)
    # cap = ceil(8*2*1/4)=4: 4 tokens through expert0, 4 through expert1
    per_expert = np.asarray(jnp.sum(g.dispatch, axis=(0, 2)))
    assert per_expert[0] == 4 and per_expert[1] == 4


def test_top2_renormalizes_after_drop():
    """A token whose 2nd choice is dropped keeps full weight on its 1st
    (reference top2gating: denominator computed post-capacity-mask)."""
    # 8 tokens: first 4 pick experts (0,1); last 4 pick (2,1). cap=4 for
    # expert 1 fills with the first 4 tokens' 2nd choices... make expert 1
    # overflow: all 8 tokens' 2nd choice is expert 1, cap = 8*2/4 = 4.
    l = np.full((8, 4), -10.0, np.float32)
    l[:4, 0] = 5.0
    l[4:, 2] = 5.0
    l[:, 1] = 3.0  # everyone's 2nd choice
    g = topk_gating(jnp.asarray(l), k=2, capacity_factor=1.0, min_capacity=1)
    sums = np.asarray(jnp.sum(g.combine, axis=(1, 2)))
    # expert 1 cap = 4: the 4 tokens that got both choices sum to 1;
    # the 4 that lost expert-1 still sum to 1 via renormalised 1st choice
    np.testing.assert_allclose(sums, 1.0, atol=1e-5)
    per_expert = np.asarray(jnp.sum(g.dispatch, axis=(0, 2)))
    assert per_expert[1] == 4  # overflow dropped


def test_aux_loss_uniform_vs_skewed():
    rng = np.random.default_rng(2)
    uniform = jnp.asarray(rng.normal(size=(256, 4)) * 0.01, jnp.float32)
    skewed = jnp.full((256, 4), -10.0).at[:, 0].set(10.0)
    g_u = top1_gating(uniform, capacity_factor=2.0)
    g_s = top1_gating(skewed, capacity_factor=2.0)
    assert float(g_u.aux_loss) < float(g_s.aux_loss)
    assert float(g_u.aux_loss) == pytest.approx(1.0, abs=0.05)  # balanced -> E*(1/E^2)*E = 1


# slow: 12 s: an expert-parallel engine's step on the 8-device mesh
@pytest.mark.slow
def test_moe_model_forward_and_train():
    cfg = get_preset("tiny_moe")
    model = CausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    logits, _, aux = forward(params, jnp.zeros((2, 16), jnp.int32), cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert float(aux) > 0.0

    config = {
        "train_micro_batch_size_per_gpu": 4,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 3e-3}},
        "zero_optimization": {"stage": 0},
        "bf16": {"enabled": True},
        "steps_per_print": 1000,
    }
    engine, _, _, _ = ds.initialize(model=model, config=config)
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 64, (1, 8 * 4, 17), dtype=np.int64)}
    first = float(engine.train_batch(batch))
    for _ in range(15):
        loss = float(engine.train_batch(batch))
    assert loss < first * 0.8, (first, loss)


def test_moe_expert_parallel_mesh():
    grid = initialize_mesh(expert=4, fsdp=2)
    set_current_mesh(grid.mesh)
    try:
        cfg = get_preset("tiny_moe")
        model = CausalLM(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        batch = {"input_ids": jnp.asarray(rng.integers(0, 64, (4, 17)))}
        # parity: loss identical with and without the expert mesh
        loss_mesh = float(jax.jit(model.loss_fn)(params, batch))
        set_current_mesh(None)
        loss_plain = float(jax.jit(model.loss_fn)(params, batch))
        # bf16 compute: sharded reduction order differs slightly
        assert abs(loss_mesh - loss_plain) < 5e-3, (loss_mesh, loss_plain)
    finally:
        set_current_mesh(None)
