"""Latent attention over EVERY cached row (``ops/latent_attention.py``:
``dense_attention_pack``, ``dense_attention_step``: absorbed, as the cache
keeps one row a key) against plain multi-head attention on the
same rows, YaRN's table and the softmax scale against the formula written out,
and the group-limited routing's corner cases."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import latent as lm
from deepspeed_tpu.models.latent import LatentAttn, Yarn
from deepspeed_tpu.ops import latent_attention as la

H, R, NOPE, ROPE, V, C = 3, 64, 16, 8, 16, 8   # a latent wider than a head
A = LatentAttn(num_heads=H, q_rank=32, kv_rank=R, nope_dim=NOPE, rope_dim=ROPE, v_dim=V,
               rope_theta=1e4, scale_factor=1.3, gate=False)
LANES = 128


def _plain_mha(q, rows, w_uk, w_uv, q_pos, a):
    """q [T, H, nope + rope] at positions ``q_pos`` over ``rows`` [K, row] at
    positions 0..K-1: keys and values decompressed per head, causal, float64."""
    q, rows = np.asarray(q, np.float64), np.asarray(rows, np.float64)
    uk = np.asarray(w_uk, np.float64).reshape(a.kv_rank, a.num_heads, a.nope_dim)
    uv = np.asarray(w_uv, np.float64).reshape(a.kv_rank, a.num_heads, a.v_dim)
    k = np.concatenate([np.einsum("kr,rhn->khn", rows[:, :a.kv_rank], uk),
                        np.broadcast_to(rows[:, None, a.kv_rank:a.row],
                                        (rows.shape[0], a.num_heads, a.rope_dim))], -1)
    v = np.einsum("kr,rhv->khv", rows[:, :a.kv_rank], uv)
    s = np.einsum("qhe,khe->hqk", q, k) * a.scale
    s = np.where(np.arange(rows.shape[0])[None, None, :] <= np.asarray(q_pos)[None, :, None],
                 s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("hqk,khv->qhv", p / p.sum(-1, keepdims=True), v)


def _pages(rows, table, n_blocks):
    """``rows`` [K, row] laid into pages of ``C`` by ``table``, zeros past the row."""
    lat = np.zeros((n_blocks, C, LANES), np.float32)
    for p, b in enumerate(table[: -(-rows.shape[0] // C)]):
        part = rows[p * C:(p + 1) * C]
        lat[b, :part.shape[0], :rows.shape[1]] = part
    return jnp.asarray(lat)


def _weights(key):
    ks = jax.random.split(key, 2)
    return (jax.random.normal(ks[0], (R, H * NOPE)) / np.sqrt(R),
            jax.random.normal(ks[1], (R, H * V)) / np.sqrt(R))


def _absorbed(q, w_uk):
    """Queries as projected [..., H, nope + rope] -> ``[q_nope W_uk ; q_rope]``,
    zeros from the row's width up to the pages' lanes."""
    q_n = jnp.einsum("...hn,rhn->...hr", q[..., :NOPE], w_uk.reshape(R, H, NOPE))
    q = jnp.concatenate([q_n, q[..., NOPE:]], axis=-1)
    return jnp.pad(q, ((0, 0),) * (q.ndim - 1) + ((0, LANES - A.row),))


def _values(o, w_uv):
    return np.asarray(jnp.einsum("...hr,rhv->...hv", o, w_uv.reshape(R, H, V)))


@pytest.mark.parametrize("tokens", [16, 24, 64])
@pytest.mark.parametrize("key_block", [16, 512], ids=["small_blocks", "one_block"])
def test_pack_body_is_plain_mha_over_each_sequences_own_rows(monkeypatch, tokens, key_block):
    """A pack of two sequences side by side (one behind 24 cached rows that
    ANOTHER call wrote, on pages that interleave with the other's), in blocks of
    keys smaller than a sequence or larger: the heads' values of plain MHA over
    each sequence's own rows."""
    monkeypatch.setattr(la, "DENSE_KEY_BLOCK", key_block)
    g = tokens // C
    ga = (g + 1) // 2          # sequence a takes the larger half, b the rest
    ks = jax.random.split(jax.random.PRNGKey(tokens), 4)
    w_uk, w_uv = _weights(ks[0])
    start_a = 24                                   # a: 24 rows cached, a multiple of the page
    n_a, n_b = ga * C - 3, (g - ga) * C            # a's last page is ragged
    rows_a = np.asarray(jax.random.normal(ks[1], (start_a + n_a, A.row)))
    rows_b = np.asarray(jax.random.normal(ks[2], (n_b, A.row)))
    q = jax.random.normal(ks[3], (g, C, H, NOPE + ROPE))
    pages = 12
    tables = np.full((4, pages), -1, np.int32)
    tables[2, :pages] = 2 * np.arange(pages) + 1   # slot 2 = a, odd blocks
    tables[1, :pages] = 2 * np.arange(pages)       # slot 1 = b, even blocks
    lat = _pages(rows_a, tables[2], 2 * pages) + _pages(rows_b, tables[1], 2 * pages)
    slot = np.asarray([2] * ga + [1] * (g - ga))
    live = jnp.ones((g,), bool)
    pos_a = start_a + np.arange(ga * C)
    pos_a[n_a:] = 0                                # padding rows: position 0, never read
    q_pos = jnp.asarray(np.concatenate([pos_a, np.arange(n_b)]).reshape(g, C).astype(np.int32))
    got = _values(jax.jit(lambda *a: la.dense_attention_pack(*a, A))(
        _absorbed(q, w_uk), lat, jnp.asarray(tables[slot]), live, q_pos), w_uv)
    flat = np.asarray(q).reshape(g * C, H, -1)
    want_a = _plain_mha(flat[:n_a], rows_a, w_uk, w_uv, pos_a[:n_a], A)
    want_b = _plain_mha(flat[ga * C:], rows_b, w_uk, w_uv, np.arange(n_b), A)
    got = got.reshape(g * C, H, V)
    np.testing.assert_allclose(got[:n_a], want_a, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got[ga * C:], want_b, rtol=2e-4, atol=2e-5)
    assert np.isfinite(got).all()                  # padding rows too


def test_a_dead_page_of_the_pack_comes_back_zeros_between_two_live_ones():
    """A page of the pack that holds no sequence (``live`` false) reaches no
    key and reads zeros, whatever its table and positions say; the pages beside
    it are what they are without it."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    w_uk, _ = _weights(ks[0])
    lat = jax.random.normal(ks[1], (6, C, LANES))
    q_abs = _absorbed(jax.random.normal(ks[2], (3, C, H, NOPE + ROPE)), w_uk)
    tables = jnp.asarray([[0, 1, 2], [0, 1, 2], [3, 4, 5]], jnp.int32)
    q_pos = jnp.asarray([np.arange(C), C + np.arange(C), np.arange(C)], jnp.int32)
    run = lambda live: np.asarray(la.dense_attention_pack(
        q_abs, lat, tables, jnp.asarray(live), q_pos, A))
    got, all_live = run([True, False, True]), run([True, True, True])
    assert np.all(got[1] == 0) and np.abs(all_live[1]).max() > 0
    np.testing.assert_array_equal(got[[0, 2]], all_live[[0, 2]])


def test_a_kind_without_a_gate_is_the_gated_kind_at_gate_one():
    """``attn_output`` of a kind that has no head gate (``gate`` None) is the
    gated kind's at a gate of 1: through ``W_uv`` a head, then ``W_o``."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    _, w_uv = _weights(ks[0])
    aw = {"w_uv": w_uv, "wo": jax.random.normal(ks[1], (H * V, 24))}
    o = jax.random.normal(ks[2], (5, H, R))
    got = lm.attn_output(aw, o, None, A)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(lm.attn_output(aw, o, jnp.ones((5, H)), A)))
    np.testing.assert_allclose(np.asarray(got), _values(o, w_uv).reshape(5, -1) @ np.asarray(aw["wo"]),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("key_block", [16, 512])
def test_step_body_is_the_pack_body_is_plain_mha(monkeypatch, key_block):
    """One query a slot, absorbed, over pages up to its length (an idle slot
    beside them): the same values as plain MHA and as the pack body on a pack
    of those single rows' pages."""
    monkeypatch.setattr(la, "DENSE_KEY_BLOCK", key_block)
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    w_uk, w_uv = _weights(ks[0])
    lens = [37, 0, 8, 61]
    pages = 8
    tables = np.full((4, pages), -1, np.int32)
    rows, lat = [], jnp.zeros((4 * pages, C, LANES), jnp.float32)
    for b, n in enumerate(lens):
        tables[b] = b + 4 * np.arange(pages)
        rows.append(np.asarray(jax.random.normal(ks[1 + b % 3], (max(n, 1), A.row))) * (1 + b))
        if n:
            lat = lat + _pages(rows[-1], tables[b], 4 * pages)
    q = jax.random.normal(ks[4], (4, H, NOPE + ROPE))
    q_abs = _absorbed(q, w_uk)
    o = jax.jit(lambda *a: la.dense_attention_step(*a, A))(
        q_abs, lat, jnp.asarray(tables), jnp.asarray(lens))
    got = _values(o, w_uv)
    # ... and the pack body on groups whose ONE real query is the slot's last row
    pos = np.zeros((4, C), np.int32)
    pos[:, 0] = np.maximum(np.asarray(lens) - 1, 0)
    packed = la.dense_attention_pack(
        jnp.zeros((4, C, H, LANES)).at[:, 0].set(q_abs), lat, jnp.asarray(tables),
        jnp.asarray(lens) > 0, jnp.asarray(pos), A)
    np.testing.assert_allclose(_values(packed[:, 0], w_uv), got, rtol=2e-5, atol=2e-6)
    for b, n in enumerate(lens):
        if n == 0:
            assert np.all(got[b] == 0)
            continue
        want = _plain_mha(np.asarray(q)[b:b + 1], rows[b][:n], w_uk, w_uv, [n - 1], A)
        np.testing.assert_allclose(got[b], want[0], rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("pages_a_step", [1, 4, 8])
def test_the_decode_kernel_walks_each_slots_pages_up_to_its_length(monkeypatch, pages_a_step):
    """``ops/pallas/latent_decode.py`` in interpret mode against the XLA body:
    slots of unequal lengths on interleaved pages, one idle (zeros), one of a
    single key, one that ends in the middle of a step and one on its edge."""
    from deepspeed_tpu.ops.pallas import latent_decode as dk

    monkeypatch.setattr(dk, "KP", pages_a_step)
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    b, pages = 6, 12
    lat = jax.random.normal(ks[0], (b * pages, C, LANES))
    q = jax.random.normal(ks[1], (b, H, LANES))
    tables = jnp.asarray(np.arange(b)[:, None] + b * np.arange(pages)[None, :], jnp.int32)
    lens = jnp.asarray([37, 0, 8, 96, 1, 64])
    with dk.interpreted():
        got = jax.jit(lambda *a: dk.latent_decode(*a, R, A.scale))(q, lat, tables, lens)
    want = la.dense_attention_step(q, lat, tables, lens, A)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)
    assert np.all(np.asarray(got[1]) == 0)
    assert dk.supports(128, 640, 512, 128) or dk.interpret() is False  # the served widths' gate
    assert not dk.supports(128, 576, 512, 128) and not dk.supports(12, 640, 512, 128)


def _yarn_by_hand(r, theta, factor, orig, fast, slow):
    """transformers' DeepseekV2YarnRotaryEmbedding, written out."""
    dim = lambda turns: r * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))
    lo, hi = max(math.floor(dim(fast)), 0), min(math.ceil(dim(slow)), r - 1)
    inv = [theta ** (-2 * i / r) for i in range(r // 2)]
    ramp = [min(max((i - lo) / (hi - lo), 0.0), 1.0) for i in range(r // 2)]
    return [f / factor * m + f * (1 - m) for f, m in zip(inv, ramp)], lo, hi


def test_yarn_on_the_rope_key_and_the_softmax_scale_against_the_formula():
    """DeepSeek-V2's table (theta 1e4 on 64 dims, factor 40 over 4096, beta 32 /
    1): which of the 32 pairs are left, ramped and divided; cos / sin x 1.0; the
    scale ``192^-1/2 x (0.1 x 0.707 x ln 40 + 1)^2``; and ``attn_inputs`` rotates
    the queries' rope dims and the ONE rope key a token with that table."""
    y = Yarn(factor=40.0, original_max=4096, beta_fast=32.0, beta_slow=1.0, attention_factor=1.0)
    inv, lo, hi = _yarn_by_hand(64, 1e4, 40.0, 4096, 32.0, 1.0)
    assert (lo, hi) == (10, 23)
    ramp = lm.yarn_ramp(64, 1e4, y)
    assert np.all(ramp[:11] == 0) and np.all(ramp[23:] == 1) and np.all(np.diff(ramp[10:24]) > 0)
    mscale = 0.1 * 0.707 * math.log(40.0) + 1.0
    a = LatentAttn(num_heads=2, q_rank=16, kv_rank=32, nope_dim=128, rope_dim=64, v_dim=128,
                   rope_theta=1e4, rope_scaling=y, scale_factor=mscale ** 2, gate=False)
    assert a.scale == pytest.approx(192 ** -0.5 * 1.5896, rel=1e-4)
    assert a.scale == pytest.approx(0.07217 * mscale ** 2, rel=1e-4)

    class Cfg:
        hidden_size, norm_eps = 24, 1e-6

        class latent:
            rescale_lora = False

    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    aw = {"w_dq": jax.random.normal(ks[0], (24, 16)), "q_norm": jnp.ones(16),
          "w_uq": jax.random.normal(ks[1], (16, 2 * 192)),
          "w_dkv": jax.random.normal(ks[2], (24, 96)), "kv_norm": jnp.ones(32),
          "w_uk": jnp.zeros((32, 2 * 128))}
    h = jax.random.normal(ks[3], (5, 24))
    pos = jnp.asarray([0, 1, 900, 5000, 60000])
    _, q, row, gate = lm.attn_inputs(aw, h, pos, a, Cfg)
    assert gate is None and q.shape == (5, 2, 96) and row.shape == (5, 96)  # absorbed: [32 | 64]
    # the angle in float32, as the program forms it: at position 60 000 a float32
    # angle is exact to ~4e-3 rad, which is the program's to keep, not this test's
    ang = (np.asarray(pos, np.float32)[:, None] * np.asarray(inv, np.float32)[None, :]
           ).astype(np.float64)

    def rotated(x):
        x1, x2 = np.asarray(x, np.float64)[..., :32], np.asarray(x, np.float64)[..., 32:]
        c, s = np.cos(ang), np.sin(ang)
        if x1.ndim == 3:
            c, s = c[:, None], s[:, None]
        return np.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)

    k_r = (h @ aw["w_dkv"])[:, 32:]
    np.testing.assert_allclose(np.asarray(row[:, 32:]), rotated(k_r), rtol=2e-3, atol=2e-3)
    c_q = lm.rms(h @ aw["w_dq"], aw["q_norm"], 1e-6)
    q_r = (c_q @ aw["w_uq"]).reshape(5, 2, 192)[..., 128:]
    np.testing.assert_allclose(np.asarray(q[..., 32:]), rotated(q_r), rtol=2e-3, atol=2e-3)
    # without the scaling the far positions rotate otherwise: the table is read
    plain = lm.attn_inputs(aw, h, pos, LatentAttn(2, 16, 32, 128, 64, 128, 1e4, gate=False), Cfg)[2]
    assert float(jnp.abs(plain[3:, 32:] - row[3:, 32:]).max()) > 0.1
