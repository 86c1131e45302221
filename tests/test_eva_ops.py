"""``ops/eva.py`` alone: a chunk's summary against a float64 loop and the
uncached attention against a
query-by-query loop over the two key sets (its own window's exact keys, one
summary per chunk of every window before it) in ONE softmax."""
import numpy as np
import pytest

import jax.numpy as jnp

from deepspeed_tpu.ops import eva


def _loop_summary(k, v, phi, mu):
    """One chunk [C, H, D] in float64, a head at a time."""
    k, v, phi, mu = (np.asarray(a, np.float64) for a in (k, v, phi, mu))
    ks, vs = np.zeros(k.shape[1:]), np.zeros(v.shape[1:])
    for h in range(k.shape[1]):
        score = np.array([phi[h] @ k[m, h] for m in range(k.shape[0])])
        a = np.exp(score - score.max())
        a /= a.sum()
        ks[h] = sum(a[m] * k[m, h] for m in range(k.shape[0])) + mu[h]
        vs[h] = sum(a[m] * v[m, h] for m in range(k.shape[0]))
    return ks, vs


@pytest.mark.parametrize("chunk,heads,dim", [(4, 2, 8), (16, 3, 16), (1, 2, 8)])
def test_summarise_is_the_float64_loop(chunk, heads, dim):
    rng = np.random.default_rng([chunk, heads])
    k, v = (rng.normal(size=(5, chunk, heads, dim)).astype(np.float32) for _ in range(2))
    phi, mu = (rng.normal(size=(heads, dim)).astype(np.float32) for _ in range(2))
    ks, vs = eva.summarise(jnp.asarray(k), jnp.asarray(v), jnp.asarray(phi), jnp.asarray(mu))
    assert ks.shape == vs.shape == (5, heads, dim)
    for n in range(5):
        want_k, want_v = _loop_summary(k[n], v[n], phi, mu)
        assert np.abs(np.asarray(ks)[n] - want_k).max() <= 1e-5
        assert np.abs(np.asarray(vs)[n] - want_v).max() <= 1e-5


def test_a_chunk_of_one_row_is_the_row_and_the_offset():
    rng = np.random.default_rng(1)
    k, v = (rng.normal(size=(3, 1, 2, 8)).astype(np.float32) for _ in range(2))
    phi, mu = (rng.normal(size=(2, 8)).astype(np.float32) for _ in range(2))
    ks, vs = eva.summarise(k, v, phi, mu)
    assert np.abs(np.asarray(ks) - (k[:, 0] + mu)).max() <= 1e-6
    assert np.abs(np.asarray(vs) - v[:, 0]).max() <= 1e-6


def test_the_offset_goes_to_the_key_alone_and_a_zero_phi_pools_by_the_mean():
    rng = np.random.default_rng(2)
    k, v = (rng.normal(size=(4, 2, 8)).astype(np.float32) for _ in range(2))
    mu = rng.normal(size=(2, 8)).astype(np.float32)
    ks, vs = eva.summarise(k, v, np.zeros((2, 8), np.float32), mu)
    assert np.abs(np.asarray(ks) - (k.mean(0) + mu)).max() <= 1e-6
    assert np.abs(np.asarray(vs) - v.mean(0)).max() <= 1e-6


def test_summaries_come_back_in_the_rows_dtype_pooled_in_float32():
    rng = np.random.default_rng(3)
    k, v = (jnp.asarray(rng.normal(size=(2, 4, 2, 8)), jnp.bfloat16) for _ in range(2))
    phi, mu = (jnp.asarray(rng.normal(size=(2, 8)), jnp.float32) for _ in range(2))
    ks, vs = eva.summarise(k, v, phi, mu)
    assert ks.dtype == vs.dtype == jnp.bfloat16
    want_k, _ = eva.summarise(k.astype(jnp.float32), v.astype(jnp.float32), phi, mu)
    assert np.abs(np.asarray(ks, np.float32) - np.asarray(want_k)).max() <= 2e-2


def _loop_attention(q, k, v, phi, mu, window, chunk):
    """Query by query, head by head, float64: the two sets and one softmax."""
    n, heads, dim = q.shape
    out = np.zeros((n, heads, dim))
    sums = {c: _loop_summary(k[c * chunk:(c + 1) * chunk], v[c * chunk:(c + 1) * chunk], phi, mu)
            for c in range(n // chunk)}
    for i in range(n):
        own = [j for j in range(i + 1) if j // window == i // window]
        earlier = [c for c in sums if c * chunk // window < i // window]
        for h in range(heads):
            keys = [np.asarray(k[j, h], np.float64) for j in own] + [sums[c][0][h] for c in earlier]
            vals = [np.asarray(v[j, h], np.float64) for j in own] + [sums[c][1][h] for c in earlier]
            s = np.array([np.asarray(q[i, h], np.float64) @ key for key in keys]) * dim ** -0.5
            p = np.exp(s - s.max())
            out[i, h] = sum(w * val for w, val in zip(p / p.sum(), vals))
    return out


@pytest.mark.parametrize("n", [5, 16, 17, 40, 53])
def test_attend_uncached_is_the_query_by_query_loop(n):
    """8-position windows of 2-position chunks: sequences that end inside the
    first window, on an edge, one past it, and six windows in with a last chunk
    that is not whole."""
    window, chunk = 8, 2
    rng = np.random.default_rng(n)
    q, k, v = (rng.normal(size=(n, 2, 8)).astype(np.float32) for _ in range(3))
    phi, mu = (0.5 * rng.normal(size=(2, 8)).astype(np.float32) for _ in range(2))
    got = np.asarray(eva.attend_uncached(q[None], k[None], v[None], phi, mu, window, chunk))[0]
    assert np.abs(got - _loop_attention(q, k, v, phi, mu, window, chunk)).max() <= 1e-5


def test_a_query_never_sees_its_own_windows_summaries_nor_a_later_key():
    """Moving a key the query may not see (a later one, an earlier window's
    exact row given the SAME summary, ...) leaves the output as it was; moving
    a seen one does not."""
    window, chunk, n = 8, 2, 20
    rng = np.random.default_rng(9)
    q, k, v = (rng.normal(size=(1, n, 2, 8)).astype(np.float32) for _ in range(3))
    phi, mu = (rng.normal(size=(2, 8)).astype(np.float32) for _ in range(2))
    base = np.asarray(eva.attend_uncached(q, k, v, phi, mu, window, chunk))
    later = v.copy()
    later[0, 13] += 1.0  # position 13: window 1
    moved = np.asarray(eva.attend_uncached(q, k, later, phi, mu, window, chunk))
    assert np.abs(moved - base)[0, :13].max() == 0.0          # no earlier query sees it
    assert np.abs(moved - base)[0, 13:16].max() > 1e-3        # its own window's later queries do
    assert np.abs(moved - base)[0, 16:].max() > 1e-4          # the next window through its summary
