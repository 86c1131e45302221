"""A causal WINDOW in flash attention (``ops/pallas/flash_attention.py``,
``flash_kernel.py``): the band-masked XLA body and the interpreted Pallas
kernels (the block-sparse family walking ``window_band`` with one mask term on
the band's far edge) against a dense float32 mask, forward and the three
gradients; the edge exact; ``window=0`` the program it was."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import flash_kernel as fk
from deepspeed_tpu.ops.pallas import record_dispatch
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

S, HQ, HKV, D, BLOCK = 384, 4, 2, 64, 128


def _qkv(seed=0, s=S):
    rng = np.random.default_rng(seed)
    mk = lambda h: jnp.asarray(rng.standard_normal((2, s, h, D)), jnp.float32)
    return mk(HQ), mk(HKV), mk(HKV)


def dense(q, k, v, window):
    """Every score, masked by ``0 <= i - j < window`` (0: causal alone)."""
    s = q.shape[1]
    k, v = (jnp.repeat(t, HQ // HKV, axis=2) for t in (k, v))
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") * D ** -0.5
    back = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    ok = (back >= 0) & (back < window) if window else back >= 0
    p = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")


@pytest.fixture
def interpreted():
    fk.set_interpret(True)
    yield
    fk.set_interpret(False)


WINDOWS = [1, BLOCK, BLOCK + 1, 200, S, S + 7]


@pytest.mark.parametrize("window", WINDOWS)
def test_the_band_body_matches_a_dense_mask_forward_and_backward(window):
    q, k, v = _qkv(1)
    f = lambda fn: lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))
    got = jax.value_and_grad(f(lambda q, k, v: flash_attention(q, k, v, window=window)),
                             (0, 1, 2))(q, k, v)
    ref = jax.value_and_grad(f(lambda q, k, v: dense(q, k, v, window)), (0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("window", WINDOWS)
def test_the_interpreted_kernels_match_a_dense_mask_forward_and_backward(interpreted, window):
    q, k, v = _qkv(2)
    f = lambda fn: lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))
    with record_dispatch() as log:
        got = jax.value_and_grad(f(lambda q, k, v: flash_attention(q, k, v, window=window)),
                                 (0, 1, 2))(q, k, v)
    assert [d["kernel"] for d in log if d["ran"]][0] == "flash_fwd"
    ref = jax.value_and_grad(f(lambda q, k, v: dense(q, k, v, window)), (0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("kernels", [False, True], ids=["band_body", "kernels"])
@pytest.mark.parametrize("window", [1, BLOCK, BLOCK + 1])
def test_the_windows_edge_is_exact(kernels, window):
    """Values one-hot in the key's position: the output's support IS the set of
    keys a query saw, and the gradient to a key's value says which queries did."""
    fk.set_interpret(kernels)
    try:
        q, k, _ = _qkv(3)
        pos, w = jnp.arange(S), min(window, D)
        # value row j carries a mark in column j % D: distinct inside a window <= D
        v = jnp.zeros((2, S, HKV, D)).at[:, pos, :, pos % D].set(1.0)
        out = np.asarray(flash_attention(q, k, v, window=w))
        for i in (0, w - 1, w, S - 1):
            seen = {c for c in range(D) if out[0, i, 0, c] > 0}
            assert seen == {j % D for j in range(max(0, i - w + 1), i + 1)}
        # ... and for the window itself: which queries a key's value reaches
        j = 5
        inside = jax.grad(lambda v: flash_attention(q, k, v, window=window)[0, j + window - 1, 0, 0])(
            jnp.ones((2, S, HKV, D)))[0, j, 0, 0]
        outside = jax.grad(lambda v: flash_attention(q, k, v, window=window)[0, j + window, 0, 0])(
            jnp.ones((2, S, HKV, D)))[0, j, 0, 0]
        assert float(inside) > 0 and float(outside) == 0.0
    finally:
        fk.set_interpret(False)


def test_a_band_visits_every_block_its_window_touches_and_no_other():
    for s, block, window in [(1024, 128, 1), (1024, 128, 128), (1024, 128, 129),
                             (8192, 512, 1024), (8192, 1024, 1024), (2048, 256, 700)]:
        band = fk.window_band(s, block, window)
        i, j = np.indices((s, s))
        pairs = (i - j >= 0) & (i - j < window)
        touched = pairs.reshape(s // block, block, s // block, block).any(axis=(1, 3))
        assert (band == touched).all()


def test_window_zero_is_the_program_it_was(interpreted):
    q, k, v = _qkv(4, s=256)
    f = lambda **kw: jax.make_jaxpr(jax.grad(
        lambda q: jnp.sum(flash_attention(q, k, v, **kw))))(q)
    assert str(f()) == str(f(window=0)) == str(f(window=256))
    assert "flash_sparse" not in str(f()) and "flash_sparse_fwd" in str(f(window=128))


def test_a_window_no_block_serves_takes_the_band_body(interpreted):
    q, k, v = _qkv(5, s=192)  # no block of 128 divides 192
    with record_dispatch() as log:
        flash_attention(q, k, v, window=64)
    assert [d["ran"] for d in log] == [False]
