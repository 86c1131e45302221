"""The bodies between a latent-attention layer's projections
(``ops/latent_attention.py``) and the Pallas kernel for the selector's scores
(``ops/pallas/index_scores.py``), against their plainest forms."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import latent_attention as la
from deepspeed_tpu.ops.pallas import index_scores as ik


def _scores(q_i, w, k_i, q_pos, scale):
    s = jnp.einsum("cjk,cj->ck", jax.nn.relu(jnp.einsum("cjd,kd->cjk", q_i, k_i)), w) * scale
    return jnp.where(jnp.arange(k_i.shape[0])[None, :] <= q_pos[:, None], s, -jnp.inf)


def test_index_scores_in_key_blocks_skips_dead_blocks():
    c, j, d, k, kb = 8, 3, 16, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q_i, w, k_i = (jax.random.normal(ks[0], (c, j, d)), jax.random.normal(ks[1], (c, j)),
                   jax.random.normal(ks[2], (k, d)))
    q_pos = jnp.arange(20, 28)  # live keys end inside block 1
    calls = []

    def key_block(b):
        calls.append(b)
        return jax.lax.dynamic_slice_in_dim(k_i, b * kb, kb)

    got = la.index_scores(q_i, w, q_pos, key_block, (jnp.max(q_pos) + kb) // kb, kb, k, 0.5)
    want = _scores(q_i, w, k_i, q_pos, 0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)
    assert np.isneginf(np.asarray(got)[:, 32:]).all()  # blocks 2 and 3: never scored


@pytest.mark.parametrize("n_live", [5, 16, 17, 40, 70, 100, 128])
def test_topk_at_a_narrower_width_selects_the_same_keys(monkeypatch, n_live):
    """``select_topk(n_live=)`` sorts the narrowest static width that holds
    every live key (no sort while they all fit in k): the same set as the
    whole row's ``lax.top_k``."""
    monkeypatch.setattr(la, "TOPK_STEP", 32)
    k, width = 16, 128
    sc = jax.random.normal(jax.random.PRNGKey(n_live), (6, width))
    sc = jnp.where(jnp.arange(width)[None, :] < n_live - jnp.arange(6)[:, None] % 3, sc, -jnp.inf)
    vals, idx = jax.jit(lambda s, n: la.select_topk(s, k, n))(sc, n_live)
    ref_vals, ref_idx = jax.lax.top_k(sc, k)
    for row in range(6):
        live = np.isfinite(np.asarray(vals[row]))
        assert live.sum() == min(k, int(np.isfinite(np.asarray(sc[row])).sum()))
        assert set(np.asarray(idx[row])[live]) == set(np.asarray(ref_idx[row])[np.isfinite(ref_vals[row])])
        np.testing.assert_array_equal(np.asarray(sc[row])[np.asarray(idx[row])[live]],
                                      np.asarray(vals[row])[live])


def test_sparse_attention_in_query_blocks_is_attention_over_the_selected(monkeypatch):
    monkeypatch.setattr(la, "Q_BLOCK", 4)
    c, h, w, r, n, k = 12, 3, 10, 8, 40, 6
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, rows = jax.random.normal(ks[0], (c, h, w)), jax.random.normal(ks[1], (n, w))
    idx = jax.random.randint(ks[2], (c, k), 0, n)
    valid = jnp.arange(k)[None, :] < (1 + jnp.arange(c) % k)[:, None]
    got = la.sparse_attention(q, idx, valid, lambda ix: rows[ix], r, 0.3)
    for t in range(c):
        keys = rows[idx[t][valid[t]]]
        p = jax.nn.softmax(jnp.einsum("hw,kw->hk", q[t], keys) * 0.3, axis=-1)
        np.testing.assert_allclose(np.asarray(got[t]), np.asarray(p @ keys[:, :r]),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pos", [3, 4, 5, 11])
def test_window_edges(pos):
    """Window 5 = the query's own position and the 4 before it."""
    h, w, r, n = 2, 6, 4, 12
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    q, keys = jax.random.normal(ks[0], (1, 1, h, w)), jax.random.normal(ks[1], (1, n, w))
    key_pos = jnp.arange(n)[None, :]
    got = la.window_attention(q, jnp.array([[pos]]), keys, key_pos, 5, r, 1.0)[0, 0]
    lo = max(pos - 4, 0)
    seen = keys[0, lo: pos + 1]
    p = jax.nn.softmax(jnp.einsum("hw,kw->hk", q[0, 0], seen), axis=-1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(p @ seen[:, :r]), rtol=1e-5, atol=1e-5)


@pytest.fixture
def interpreted():
    with ik.interpreted():
        yield


def test_paged_index_scores_kernel_reads_its_pages(interpreted):
    g, c, j, d, bs, p, nb = 2, 16, 4, 32, 8, 6, 20
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (g, c, j, d))
    w = jax.random.normal(ks[1], (g, c, j))
    pages = jax.random.normal(ks[2], (nb, bs, d))
    tables = jax.random.permutation(ks[3], nb)[: g * p].reshape(g, p)
    live = jnp.array([6, 3])
    got = ik.paged_index_scores(q, w, pages, tables, live, 0.5)
    keys = pages[tables].reshape(g, p * bs, d)
    want = jnp.einsum("gcjk,gcj->gck", jax.nn.relu(jnp.einsum("gcjd,gkd->gcjk", q, keys)), w) * 0.5
    want = want.at[1, :, 3 * bs:].set(0.0)  # past a group's live pages: not computed
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert ik.supports(128, 64, 128, 128) and not ik.supports(12, 4, 32, 8)
