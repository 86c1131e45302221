"""The Pallas kernel that attends a group's SELECTED rows by walking its pages
whole (``ops/pallas/selected_attention.py``, interpret mode), the mask it is
handed (``latent_attention.selected_mask``) and the gate that chooses between
it and the gathered body (``latent_runner._attend_selected``), against the
gathered body on the same picks."""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import latent_runner as lr
from deepspeed_tpu.ops import latent_attention as la
from deepspeed_tpu.ops.pallas import record_dispatch
from deepspeed_tpu.ops.pallas import selected_attention as sa

C, H, W, R, BS, P, NB, K = 16, 4, 24, 16, 8, 10, 64, 12


@pytest.fixture
def interpreted():
    with sa.interpreted():
        yield


def _causal(scores, q_pos):
    return jnp.where(jnp.arange(scores.shape[-1])[None, None, :] <= q_pos[:, :, None],
                     scores, -jnp.inf)


def _groups(lasts, seed=0):
    """Groups of C queries ending at ``lasts`` (a negative one: a page of
    padding, every row at position 0), their pages scattered over the pool."""
    g = len(lasts)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (g, C, H, W))
    pages = jax.random.normal(ks[1], (NB, BS, W))
    tables = jax.random.permutation(ks[2], NB)[: g * P].reshape(g, P)
    last = jnp.asarray(lasts)
    q_pos = jnp.where(last[:, None] < 0, 0,
                      jnp.maximum(last[:, None] - (C - 1) + jnp.arange(C)[None, :], 0))
    scores = _causal(jax.random.normal(ks[3], (g, C, P * BS)), q_pos)
    return q, pages, tables, q_pos, scores


def _gathered(q, pages, tables, vals, ix):
    def group(q, table, vals, ix):
        own = pages[table].reshape(P * BS, W)
        return la.sparse_attention(q, ix, vals > -jnp.inf, lambda r: own[r], R, 0.3)
    return jnp.stack([group(*xs) for xs in zip(q, tables, vals, ix)])


def _picked_sets(vals, ix, width):
    out = np.zeros(vals.shape[:-1] + (width,), np.int8)
    for at in np.ndindex(*vals.shape[:-1]):
        out[at][np.asarray(ix[at])[np.isfinite(np.asarray(vals[at]))]] = 1
    return out


# first page only; fewer than k live keys; exactly k; several times k; padding
LASTS = [5, K - 2, K - 1, 41, 6 * K + 3, -1]


@pytest.mark.parametrize("tq,kp", [(16, 1), (8, 4), (4, 3)])
def test_the_kernel_is_attention_over_the_picked_rows(interpreted, monkeypatch, tq, kp):
    monkeypatch.setattr(sa, "TQ", tq)
    monkeypatch.setattr(sa, "KP", kp)
    q, pages, tables, q_pos, scores = _groups(LASTS)
    vals, ix = jax.vmap(lambda s: la.select_topk(s, K))(scores)
    live = jnp.max(q_pos, axis=1) // BS + 1
    got = sa.selected_attention(q, la.selected_mask(scores, vals, ix), pages, tables, live, R, 0.3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_gathered(q, pages, tables, vals, ix)),
                               rtol=2e-5, atol=2e-5)


def test_a_group_handed_no_live_pages_is_skipped(interpreted):
    """Its pages are never read (they hold NaN here) and the other groups'
    outputs are what they are without it."""
    q, pages, tables, q_pos, scores = _groups([30, 50, 20])
    vals, ix = jax.vmap(lambda s: la.select_topk(s, K))(scores)
    mask = la.selected_mask(scores, vals, ix)
    live = jnp.max(q_pos, axis=1) // BS + 1
    want = sa.selected_attention(q, mask, pages, tables, live, R, 0.3)
    poisoned = pages.at[tables[1]].set(jnp.nan)
    got = sa.selected_attention(q, mask, poisoned, tables, live.at[1].set(0), R, 0.3)
    for g in (0, 2):
        np.testing.assert_array_equal(np.asarray(got[g]), np.asarray(want[g]))


@pytest.mark.parametrize("case", ["random", "fewer_than_k", "ties_at_the_threshold",
                                  "all_equal", "narrow_width", "no_sort_width"])
def test_the_mask_is_exactly_what_select_topk_picked(monkeypatch, case):
    monkeypatch.setattr(la, "TOPK_STEP", 32)
    width, n_live = 128, None
    sc = jax.random.normal(jax.random.PRNGKey(3), (2, 6, width))
    q_pos = jnp.asarray([[20, 40, 60, 80, 100, 127], [3, 5, 11, 12, 13, 127]])
    if case == "fewer_than_k":
        q_pos = jnp.minimum(q_pos, K - 3)
    elif case == "ties_at_the_threshold":
        # five keys share the K-th largest score: the lower positions are taken
        sc = sc.at[:, :, jnp.asarray([2, 9, 10, 17, 19])].set(jnp.sort(sc, axis=-1)[..., -K][..., None])
    elif case == "all_equal":
        sc = jnp.full_like(sc, 0.25)
    elif case == "narrow_width":
        q_pos, n_live = jnp.minimum(q_pos, 60), 61
    elif case == "no_sort_width":
        q_pos, n_live = jnp.minimum(q_pos, K - 1), K  # select_topk returns the first k, unsorted
    sc = _causal(sc, q_pos)
    vals, ix = jax.vmap(lambda s: la.select_topk(s, K, n_live))(sc)
    want = _picked_sets(vals, ix, width)
    assert (want.sum(-1) == np.minimum(np.asarray(q_pos) + 1, K)).all()
    np.testing.assert_array_equal(np.asarray(la.selected_mask(sc, vals, ix)), want)


def _layer_state(lasts, seed=1):
    """What ``_attend_selected`` takes for a pack: a cache of latent and index
    pages, queries, and ``s`` (the fields of ``cfg.latent`` it reads)."""
    g = len(lasts)
    j, d = 2, 8
    q, lat, tables, q_pos, _ = _groups(lasts, seed)
    ks = jax.random.split(jax.random.PRNGKey(seed + 10), 3)
    idx = jax.random.normal(ks[0], (NB, BS, d))
    q_i, w = jax.random.normal(ks[1], (g, C, j, d)), jax.random.normal(ks[2], (g, C, j))
    s = SimpleNamespace(full=SimpleNamespace(row=W - 3, kv_rank=R, scale=0.3), index_heads=j,
                        index_dim=d, index_topk=K, index_scale=0.5)
    return s, q[..., : W - 3], q_i, w, q_pos, tables, lat, idx


@pytest.mark.parametrize("dense_max", [0, 41, 42, 1 << 30])
def test_either_side_of_the_gate_is_the_same_attention(monkeypatch, dense_max):
    """Groups under ``DENSE_KEYS_MAX`` walk their pages, the others gather
    their rows, in one call: the same outputs and picks as the gathered body
    alone (the gate closed: not interpreted, not on a TPU)."""
    lasts = [7, 41, 41 + C, 79]
    s, q_abs, q_i, w, q_pos, tables, lat, idx = _layer_state(lasts)
    real = jnp.ones(q_pos.shape, bool)

    def attend():
        picked, probe = [], []
        o = lr._attend_selected(s, q_abs, q_i, w, q_pos, tables, lat, idx, real, picked, probe)
        return o, picked[0], probe[0]

    monkeypatch.setattr(la, "DENSE_KEYS_MAX", dense_max)
    with sa.interpreted(), record_dispatch() as log:
        got, n, probe = attend()
    assert [d["ran"] for d in log if d["kernel"] == "selected_attn"] == [True]
    with record_dispatch() as log:
        want, n_want, probe_want = attend()
    assert [(d["ran"], d["reason"]) for d in log if d["kernel"] == "selected_attn"] == [
        (False, "not on a TPU")]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    assert int(n) == int(n_want) == sum(min(p + 1, K) for p in np.asarray(q_pos).ravel())
    np.testing.assert_array_equal(np.asarray(probe["index_picked"]),
                                  np.asarray(probe_want["index_picked"]))


def test_the_shape_gate():
    assert sa.supports(128, 128, 640, 512, 128)
    assert not sa.supports(128, 128, 576, 512, 128)   # rows not whole lanes
    assert not sa.supports(128, 128, 640, 512, 64)    # a page narrower than a lane row
    assert not sa.supports(24, 128, 640, 512, 128)    # no whole query tiles
    with sa.interpreted():
        assert sa.supports(8, 4, 24, 16, 8)
