"""``ops/ssm.py``: the chunked scan, the one-step recurrence and the causal
convolution of a Mamba-2 mixer against the recurrence written one token at a
time.  float32, CPU, small."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import latent as lm
from deepspeed_tpu.ops import ssm

H, P, R, N, K, L = 4, 8, 2, 16, 4, 8  # heads, channels, groups, state, conv taps, chunk
MB = lm.Mamba(num_heads=H, head_dim=P, n_groups=R, state=N, conv=K, chunk=L)
TOL = 1e-5


def _inputs(seed, t):
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.standard_normal(s), jnp.float32)
    dt = jnp.asarray(r.uniform(0.001, 0.3, (t, H)), jnp.float32)
    a = -jnp.asarray(r.uniform(1.0, 16.0, (H,)), jnp.float32)
    return f(t, H, P), dt, a, f(t, R, N), f(t, R, N)


# the kernels' plain bodies and the references as ONE program a shape each (op by
# op, every pad, slice and scan of theirs compiles on its own)
ssm_scan, ssm_step = jax.jit(ssm.ssm_scan), jax.jit(ssm.ssm_step)


@jax.jit
def _recurrence(x, dt, a, b, c, s0):
    """One token at a time: (y [T, H, P], the state after the last token)."""
    bh, ch = jnp.repeat(b, H // R, axis=1), jnp.repeat(c, H // R, axis=1)

    def token(s, t):
        x_t, dt_t, b_t, c_t = t
        s = jnp.exp(dt_t * a)[:, None, None] * s + (dt_t[:, None] * x_t)[..., None] * b_t[:, None]
        return s, jnp.sum(s * c_t[:, None], -1)

    s, y = jax.lax.scan(token, s0, (x, dt, bh, ch))
    return y, s


def _chunked(x, dt, a, b, c, s0, t):
    """``ssm_scan`` over ``t`` tokens padded to whole chunks, from ``s0``."""
    g = -(-t // L)
    pad = lambda v: jnp.pad(v, ((0, g * L - t),) + ((0, 0),) * (v.ndim - 1)
                            ).reshape(g, L, *v.shape[1:])
    loaded = jnp.broadcast_to(s0, (g, H, P, N))
    y, states = ssm_scan(pad(x), pad(dt), a, pad(b), pad(c), loaded, jnp.arange(g) > 0)
    return y.reshape(g * L, H, P)[:t], states[-1]


@pytest.mark.parametrize("t", [1, L - 1, L, L + 1, 3 * L + 5])
@pytest.mark.parametrize("with_state", [False, True], ids=["from_zero", "from_a_state"])
def test_chunked_scan_is_the_recurrence(t, with_state):
    """Lengths that are not whole chunks (the padding's dt = 0 leaves the
    state as it was), with and without an initial state."""
    x, dt, a, b, c = _inputs(t, t)
    s0 = jnp.asarray(np.random.default_rng(9).standard_normal((H, P, N)), jnp.float32) \
        if with_state else jnp.zeros((H, P, N), jnp.float32)
    y_ref, s_ref = _recurrence(x, dt, a, b, c, s0)
    y, s = _chunked(x, dt, a, b, c, s0, t)
    assert np.abs(np.asarray(y) - np.asarray(y_ref)).max() <= TOL * max(1.0, float(jnp.abs(y_ref).max()))
    assert np.abs(np.asarray(s) - np.asarray(s_ref)).max() <= TOL * max(1.0, float(jnp.abs(s_ref).max()))


def test_several_segments_in_one_call_each_from_its_own_state():
    """Chunks [A0 A1 | B0 | C0 C1 C2] in one call: A continues a kept state,
    B and C start from zeros; nothing leaks across a boundary."""
    lens, r = (2 * L, L - 3, 2 * L + 2), np.random.default_rng(3)
    s_a = jnp.asarray(r.standard_normal((H, P, N)), jnp.float32)
    starts = [s_a, jnp.zeros((H, P, N)), jnp.zeros((H, P, N))]
    parts = [_inputs(10 + i, n) for i, n in enumerate(lens)]
    a = parts[0][2]
    cat, loaded, cont = [[] for _ in range(4)], [], []
    for (x, dt, _, b, c), n, s0 in zip(parts, lens, starts):
        g = -(-n // L)
        for dst, v in zip(cat, (x, dt, b, c)):
            dst.append(jnp.pad(v, ((0, g * L - n),) + ((0, 0),) * (v.ndim - 1)))
        loaded += [s0] * g
        cont += [False] + [True] * (g - 1)
    x, dt, b, c = (jnp.concatenate(v).reshape(-1, L, *v[0].shape[1:]) for v in cat)
    y, states = ssm_scan(x, dt, a, b, c, jnp.stack(loaded), jnp.asarray(cont))
    y, at = y.reshape(-1, H, P), 0
    for (xi, dti, _, bi, ci), n, s0 in zip(parts, lens, starts):
        g = -(-n // L)
        y_ref, s_ref = _recurrence(xi, dti, a, bi, ci, s0)
        assert np.abs(np.asarray(y[at * L: at * L + n]) - np.asarray(y_ref)).max() <= 1e-4
        assert np.abs(np.asarray(states[at + g - 1]) - np.asarray(s_ref)).max() <= 1e-4
        at += g


def test_step_is_one_step_of_the_recurrence_and_idle_states_keep_their_bits():
    r = np.random.default_rng(4)
    s = jnp.asarray(r.standard_normal((3, H, P, N)), jnp.float32)
    x, dt, a, b, c = _inputs(5, 3)
    active = jnp.asarray([True, False, True])
    y, new = ssm_step(s, x, dt, a, b, c, active)
    for i in range(3):
        y_ref, s_ref = _recurrence(x[i:i + 1], dt[i:i + 1], a, b[i:i + 1], c[i:i + 1], s[i])
        assert np.abs(np.asarray(y[i]) - np.asarray(y_ref[0])).max() <= TOL * 10
        if active[i]:
            assert np.abs(np.asarray(new[i]) - np.asarray(s_ref)).max() <= TOL * 10
        else:
            assert np.array_equal(np.asarray(new[i]), np.asarray(s[i]))


def _conv_reference(rows, w, b):
    """out_t = silu(b + sum_j w_j * in_{t-K+1+j}), zeros before the first row."""
    padded = jnp.pad(rows, ((K - 1, 0), (0, 0)))
    return jax.nn.silu(b + sum(w[j] * padded[j:j + rows.shape[0]] for j in range(K)))


@pytest.mark.parametrize("edge", [K - 2, K - 1, K, L, L + K - 2])
def test_conv_tail_across_a_cut(edge):
    """A sequence cut at ``edge``: the part after the cut, fed the tail the
    part before it left, equals the uncut convolution; so do single steps."""
    r, t, cw = np.random.default_rng(edge), 3 * L, 6
    rows = jnp.asarray(r.standard_normal((t, cw)), jnp.float32)
    w, b = (jnp.asarray(r.standard_normal(s), jnp.float32) for s in ((K, cw), (cw,)))
    want = np.asarray(_conv_reference(rows, w, b))
    zeros = jnp.zeros((1, K - 1, cw))
    first, ext = ssm.conv_chunks(zeros, rows[None, :edge], w, b)
    tail = ext[:, edge:edge + K - 1]  # the last K - 1 rows that came in
    rest, _ = ssm.conv_chunks(tail, rows[None, edge:], w, b)
    assert np.abs(np.concatenate([first[0], rest[0]]) - want).max() <= TOL
    for i in range(edge, edge + 3):  # ... and token by token from the same tail
        out, tail = ssm.conv_step(tail, rows[None, i], w, b)
        assert np.abs(np.asarray(out[0]) - want[i]).max() <= TOL


def test_mixer_in_chunks_equals_the_mixer_in_steps():
    """``mamba_chunks`` on a prompt (its last chunk partial) and then
    ``mamba_step`` for three tokens = ``mamba_chunks`` over all of it."""
    d, n, r = 16, 2 * L + 3, np.random.default_rng(6)
    spec = lm.LatentSpec(layer_kinds=("mamba",), full=None, sliding=None, index_heads=0,
                         index_dim=0, index_topk=0, first_dense=0, n_routed=1, n_held=1,
                         held_offset=0, experts_per_tok=1, moe_width=8, n_shared=1, mamba=MB)
    w = {k: jnp.asarray(r.standard_normal(s) / np.sqrt(s[0] if len(s) > 1 else 1), jnp.float32)
         for k, s in lm._single_shapes(d, spec, "mamba").items()}
    h = jnp.asarray(r.standard_normal((n + 3, d)), jnp.float32)

    def chunks(rows):
        t = rows.shape[0]
        g = -(-t // L)
        hp = jnp.pad(rows, ((0, g * L - t), (0, 0))).reshape(g, L, d)
        valid = (jnp.arange(g * L) < t).reshape(g, L)
        out, states, tails = lm.mamba_chunks(
            w, hp, valid, jnp.arange(g) > 0, jnp.zeros((g, K - 1, MB.conv_width)),
            jnp.zeros((g, H, P, N)), MB, 1e-5)
        return out.reshape(g * L, d)[:t], states[-1:], tails[-1:]

    whole, _, _ = chunks(h)
    out, state, tail = chunks(h[:n])
    assert np.abs(np.asarray(out) - np.asarray(whole[:n])).max() <= 1e-4
    for i in range(n, n + 3):
        y, state, tail = lm.mamba_step(w, h[i:i + 1], jnp.asarray([True]), tail, state, MB, 1e-5)
        assert np.abs(np.asarray(y[0]) - np.asarray(whole[i])).max() <= 1e-4


# (heads, channels a head, groups, state): two groups and a state WIDER than the head, as
# the block of two parallel mixers runs them (32 x 128, 2 groups, state 256: N = 2 P, 16
# heads a group), beside the other proportions that keep 2 groups
WIDE_STATES = [(8, 4, 2, 8), (32, 2, 2, 4), (6, 2, 2, 16), (4, 8, 2, 32)]


def _wide_inputs(seed, t, h, p, r, n):
    g = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(g.standard_normal(s), jnp.float32)
    dt = jnp.asarray(g.uniform(0.001, 0.3, (t, h)), jnp.float32)
    return f(t, h, p), dt, -jnp.asarray(g.uniform(1.0, 16.0, (h,)), jnp.float32), f(t, r, n), f(t, r, n)


@jax.jit
def _wide_recurrence(x, dt, a, b, c, s0):
    """``_recurrence`` at any (H, P, R, N): head ``h`` reads group ``h // (H / R)``."""
    rep = x.shape[1] // b.shape[1]
    bh, ch = jnp.repeat(b, rep, axis=1), jnp.repeat(c, rep, axis=1)

    def token(s, t):
        x_t, dt_t, b_t, c_t = t
        s = jnp.exp(dt_t * a)[:, None, None] * s + (dt_t[:, None] * x_t)[..., None] * b_t[:, None]
        return s, jnp.sum(s * c_t[:, None], -1)

    s, y = jax.lax.scan(token, s0, (x, dt, bh, ch))
    return y, s


@pytest.mark.parametrize("shape", WIDE_STATES, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("t", [L - 1, 2 * L + 3])
def test_chunked_scan_at_two_groups_and_a_state_wider_than_the_head(shape, t):
    h, p, r, n = shape
    assert r == 2 and n > p
    x, dt, a, b, c = _wide_inputs(t + h, t, *shape)
    s0 = jnp.asarray(np.random.default_rng(1).standard_normal((h, p, n)), jnp.float32)
    y_ref, s_ref = _wide_recurrence(x, dt, a, b, c, s0)
    g = -(-t // L)
    pad = lambda v: jnp.pad(v, ((0, g * L - t),) + ((0, 0),) * (v.ndim - 1)
                            ).reshape(g, L, *v.shape[1:])
    y, states = ssm_scan(pad(x), pad(dt), a, pad(b), pad(c),
                         jnp.broadcast_to(s0, (g, h, p, n)), jnp.arange(g) > 0)
    scale = lambda v: TOL * max(1.0, float(jnp.abs(v).max()))
    assert np.abs(np.asarray(y.reshape(g * L, h, p)[:t]) - np.asarray(y_ref)).max() <= scale(y_ref)
    assert np.abs(np.asarray(states[-1]) - np.asarray(s_ref)).max() <= scale(s_ref)


@pytest.mark.parametrize("shape", WIDE_STATES, ids=lambda s: "x".join(map(str, s)))
def test_step_at_two_groups_and_a_state_wider_than_the_head(shape):
    h, p, r, n = shape
    s = jnp.asarray(np.random.default_rng(2).standard_normal((3, h, p, n)), jnp.float32)
    x, dt, a, b, c = _wide_inputs(7, 3, *shape)
    active = jnp.asarray([False, True, True])
    y, new = ssm_step(s, x, dt, a, b, c, active)
    for i in range(3):
        y_ref, s_ref = _wide_recurrence(x[i:i + 1], dt[i:i + 1], a, b[i:i + 1], c[i:i + 1], s[i])
        assert np.abs(np.asarray(y[i]) - np.asarray(y_ref[0])).max() <= TOL * 10
        want = s_ref if active[i] else s[i]
        assert np.abs(np.asarray(new[i]) - np.asarray(want)).max() <= (TOL * 10 if active[i] else 0)


# ONE group: every head reads the same B and C (Granite 4.0-H's 128 heads x 64 at state 128)
ONE_GROUP = (8, 4, 1, 16)  # (heads, channels, groups, state)


@pytest.mark.parametrize("t", [L - 1, 2 * L + 3])
def test_chunked_scan_at_one_group(t):
    h, p, r, n = ONE_GROUP
    x, dt, a, b, c = _wide_inputs(t + 3, t, *ONE_GROUP)
    assert b.shape == (t, 1, n)
    s0 = jnp.asarray(np.random.default_rng(4).standard_normal((h, p, n)), jnp.float32)
    y_ref, s_ref = _wide_recurrence(x, dt, a, b, c, s0)
    g = -(-t // L)
    pad = lambda v: jnp.pad(v, ((0, g * L - t),) + ((0, 0),) * (v.ndim - 1)
                            ).reshape(g, L, *v.shape[1:])
    y, states = ssm_scan(pad(x), pad(dt), a, pad(b), pad(c),
                         jnp.broadcast_to(s0, (g, h, p, n)), jnp.arange(g) > 0)
    scale = lambda v: TOL * max(1.0, float(jnp.abs(v).max()))
    assert np.abs(np.asarray(y.reshape(g * L, h, p)[:t]) - np.asarray(y_ref)).max() <= scale(y_ref)
    assert np.abs(np.asarray(states[-1]) - np.asarray(s_ref)).max() <= scale(s_ref)


def test_step_at_one_group():
    h, p, r, n = ONE_GROUP
    s = jnp.asarray(np.random.default_rng(6).standard_normal((3, h, p, n)), jnp.float32)
    x, dt, a, b, c = _wide_inputs(8, 3, *ONE_GROUP)
    active = jnp.asarray([True, False, True])
    y, new = ssm_step(s, x, dt, a, b, c, active)
    for i in range(3):
        y_ref, s_ref = _wide_recurrence(x[i:i + 1], dt[i:i + 1], a, b[i:i + 1], c[i:i + 1], s[i])
        assert np.abs(np.asarray(y[i]) - np.asarray(y_ref[0])).max() <= TOL * 10
        want = s_ref if active[i] else s[i]
        assert np.abs(np.asarray(new[i]) - np.asarray(want)).max() <= (TOL * 10 if active[i] else 0)


def test_a_mixers_constant_multipliers_are_the_scaled_projections():
    """``Mamba.in_multiplier`` on the input, ``multipliers`` over the five segments
    z | x | B | C | dt of ``W_in``'s output and ``out_multiplier`` on ``W_out``'s:
    the mixer with them equals the mixer without on weights scaled alike."""
    import dataclasses

    d, n, r = 16, L + 5, np.random.default_rng(8)
    mup = (0.9, 0.8, 0.7, 0.6, 0.5)
    scaled = dataclasses.replace(MB, in_multiplier=0.25, multipliers=mup, out_multiplier=0.4)
    assert scaled.in_scale.shape == (MB.in_width,) and MB.in_scale is None
    assert list(scaled.in_scale[[0, H * P, 2 * H * P, 2 * H * P + R * N, -1]]) == \
        [np.float32(v) for v in mup]
    spec = lm.LatentSpec(layer_kinds=("mamba",), full=None, sliding=None, index_heads=0,
                         index_dim=0, index_topk=0, first_dense=0, n_routed=1, n_held=1,
                         held_offset=0, experts_per_tok=1, moe_width=8, n_shared=1, mamba=MB)
    w = {k: jnp.asarray(r.standard_normal(s) / np.sqrt(s[0] if len(s) > 1 else 1), jnp.float32)
         for k, s in lm._single_shapes(d, spec, "mamba").items()}
    folded = {**w, "w_in": w["w_in"] * 0.25 * scaled.in_scale, "w_out": w["w_out"] * 0.4}
    h = jnp.asarray(r.standard_normal((2, L, d)), jnp.float32)
    valid = (jnp.arange(2 * L) < n).reshape(2, L)
    args = (valid, jnp.asarray([False, True]), jnp.zeros((2, K - 1, MB.conv_width)),
            jnp.zeros((2, H, P, N)))
    got = lm.mamba_chunks(w, h, *args, scaled, 1e-5)
    want = lm.mamba_chunks(folded, h, *args, MB, 1e-5)
    for a, b in zip(got, want):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() <= 1e-5
    tail, state = got[2][-1:], got[1][-1:]
    y1 = lm.mamba_step(w, h[1, :1], jnp.asarray([True]), tail, state, scaled, 1e-5)
    y2 = lm.mamba_step(folded, h[1, :1], jnp.asarray([True]), tail, state, MB, 1e-5)
    for a, b in zip(y1, y2):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() <= 1e-5
