"""Eigenvalue + progressive layer drop tests (reference runtime/eigenvalue.py,
runtime/progressive_layer_drop.py)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.runtime.eigenvalue import Eigenvalue
from deepspeed_tpu.runtime.progressive_layer_drop import (
    ProgressiveLayerDrop,
    layer_keep_mask,
)


def test_eigenvalue_quadratic_exact():
    """For L(p) = 0.5 p^T A p the dominant Hessian eigenvalue is max eig(A)."""
    rng = np.random.default_rng(0)
    m = rng.normal(size=(6, 6))
    a = m @ m.T  # PSD with distinct eigenvalues
    a_j = jnp.asarray(a, jnp.float32)

    def loss_fn(p, batch, rng_):
        return 0.5 * p["w"] @ a_j @ p["w"]

    est, vec = Eigenvalue(max_iter=200, tol=1e-5).compute_eigenvalue(
        loss_fn, {"w": jnp.zeros((6,), jnp.float32)}, None
    )
    true = float(np.linalg.eigvalsh(a).max())
    assert abs(est - true) / true < 1e-2, (est, true)


def test_eigenvalue_on_model_loss_runs():
    from deepspeed_tpu.models import CausalLM, get_preset

    cfg = get_preset("tiny", max_seq_len=16).replace(num_layers=1, dtype=jnp.float32)
    model = CausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    batch = {"input_ids": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 17)))}
    est, _ = Eigenvalue(max_iter=8).compute_eigenvalue(model.loss_fn, params, batch)
    assert np.isfinite(est)


def test_pld_schedule_matches_reference_math():
    pld = ProgressiveLayerDrop(theta=0.5, gamma=0.001)
    assert pld.get_theta() == 1.0
    for step in (0, 100, 1000, 10000):
        got = pld.update_state(step)
        want = (1 - 0.5) * math.exp(-0.001 * step) + 0.5
        assert abs(got - want) < 1e-9
        assert abs(float(pld.theta_at(step)) - want) < 1e-6
    assert pld.get_state()["progressive_layer_drop"] is True


def test_layer_keep_mask_and_forward_identity():
    from deepspeed_tpu.models import CausalLM, get_preset
    from deepspeed_tpu.models.transformer import forward

    mask = layer_keep_mask(jax.random.PRNGKey(0), 8, theta=0.0)
    assert mask[0] == 1.0  # first layer always kept
    full = layer_keep_mask(jax.random.PRNGKey(0), 8, theta=1.0)
    np.testing.assert_array_equal(np.asarray(full), np.ones(8))

    cfg = get_preset("tiny", max_seq_len=16).replace(dtype=jnp.float32)
    model = CausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(1))
    tokens = jnp.asarray(np.random.default_rng(2).integers(0, 256, (2, 16)))
    keep_all = jnp.ones((cfg.num_layers,), jnp.float32)
    drop_all_but_first = jnp.zeros((cfg.num_layers,), jnp.float32).at[0].set(1.0)
    l_full, _, _ = forward(params, tokens, cfg, layer_keep=keep_all)
    l_none, _, _ = forward(params, tokens, cfg)
    np.testing.assert_allclose(np.asarray(l_full), np.asarray(l_none), atol=1e-5)
    l_dropped, _, _ = forward(params, tokens, cfg, layer_keep=drop_all_but_first)
    assert not np.allclose(np.asarray(l_dropped), np.asarray(l_full))
