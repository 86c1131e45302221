"""The gated delta rule (Gated DeltaNet) in the two forms serving needs.  Per
value head (its keys ``Dk`` wide, its values ``Dv``; ``Hv / Hk`` value heads
read one key head), with a matrix state ``S`` [Dk, Dv] (key x value), float32:

    S'  = exp(g_t) S_{t-1}                               g_t <= 0: the decay
    S_t = S' + k_t (x) ( beta_t (v_t - S'^T k_t) )       the delta rule
    o_t = S_t^T q_t

``q`` and ``k`` come in as the recurrence consumes them (``k`` of unit length,
``q`` of length ``Dk^-1/2``: the caller normalises).

- ``gdn_scan``: CHUNKS of ``L`` tokens in the chunked (WY) form.  With
  ``gamma_t`` the decay summed inside the chunk, ``A = strict_lower(beta_i
  (k_i . k_j) exp(gamma_i - gamma_j))``, ``W = (I + A)^-1 diag(beta) (K o
  exp gamma)`` and ``U = (I + A)^-1 diag(beta) V`` (ONE product with the
  explicit inverse, built by blocks: see below), a chunk maps its incoming
  state LINEARLY, ``S_out = (exp(gamma_L) I - Kd^T W) S_in +
  Kd^T U`` with ``Kd = K o exp(gamma_L - gamma)``: both terms are matmuls made
  for every chunk at once, ONE state is handed over per chunk, and the outputs
  ``O = (Q o exp gamma) S_in + tril(Q K^T o exp(gamma_i - gamma_j)) (U - W
  S_in)`` are matmuls again.  A chunk takes its incoming state from the chunk
  before it (``cont``) or from ``loaded`` (its sequence's kept state, zeros for
  a sequence's first chunk), so chunks of several sequences share one call.
- ``gdn_step``: the recurrence itself, one token a sequence, on a batch of
  kept states, updated where ``active`` and left bit-identical elsewhere.

``(I + A)^-1`` of a chunk (``_solve_unit_lower``): the diagonal blocks of
``SOLVE_BLOCK`` rows are inverted by forward substitution on the identity, every
block of every chunk and head in one array (``SOLVE_BLOCK - 1`` serial steps in
all), and adjacent pairs are merged, ``[[X11, 0], [-X22 (A21 X11), X22]]``, a
level a time, each level two batched matmuls.  That is as exact as substitution
where the keys of a chunk are alike or the same (``cond(I + A)`` ~157:
2.4e-7 of the largest entry against 4.1e-7 in a float32 emulation, ISSUE 46).
The nilpotent series ``(I - A)(I + A^2)(I + A^4)...`` is NOT: its powers grow
combinatorially and cancel (3.1e-4 at keys 0.9 alike, 1e+29 at identical
keys).  ``jax.lax.linalg.triangular_solve`` on ``A`` was an explicit inverse on
the chip too (XLA's ``InvertDiagBlocksLowerTriangular`` and one matmul), but
built by a 128-step row loop: 1.37 ms a call at the serving shape against 0.19
(my chip run, PR 46).  A chunk that ``SOLVE_BLOCK`` does not cut into a
power-of-two count of blocks, two or more (no served chunk: the tests' 4, 8 and
16), is one block and stays with ``triangular_solve``: substitution alone.

A token with ``g = 0`` and ``beta = 0`` leaves the state as it was: that is
how padding rows are kept out.  Everything is float32 inside, whatever the
state is KEPT in (``S.dtype``: float32 in serving; a benchmark's control casts
it down).  Plain XLA bodies under ``jax.named_scope`` (``gdn_scan``,
``gdn_step``); the short convolution before them is ``ops/ssm.py``'s.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST  # float32 operands stay float32 on the MXU
# Rows of a diagonal block inverted by substitution.  At the serving shape (4 chunks x
# 32 heads, L = 128, 256 right-hand sides) on a v5e, the solve alone / the whole scan
# (tools/gdn_scan_curves.py; my chip run, PR 46): 8 -> 0.212 / 0.477 ms, 16 -> 0.190 /
# 0.457, 32 -> 0.557 / 0.830 (a step is latency until its row product outgrows it);
# ``triangular_solve`` 1.365 alone.
SOLVE_BLOCK = 16


def _diagonal_blocks(a, size):
    """a [..., L, L] -> its L / size diagonal blocks [..., L / size, size, size]."""
    return jnp.stack([a[..., i:i + size, i:i + size] for i in range(0, a.shape[-1], size)], axis=-3)


def _substitute(d, r):
    """(I + d) x = r by rows, forward: d [..., n, n] strictly lower triangular (what
    stands on and above its diagonal must be zero), r [..., n, m] -> x [..., n, m]."""
    rows = d.ndim - 2

    def write_row(i, x):  # row i of d is zero from column i on: it meets final rows of x only
        d_i = jax.lax.dynamic_index_in_dim(d, i, axis=rows, keepdims=False)
        r_i = jax.lax.dynamic_index_in_dim(x, i, axis=rows, keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(
            x, r_i - jnp.einsum("...j,...jk->...k", d_i, x, precision=_HI), i, axis=rows)

    return jax.lax.fori_loop(1, d.shape[-1], write_row, r)


def _solve_unit_lower(a, rhs):
    """(I + a)^-1 rhs for a [..., L, L] strictly lower triangular, rhs [..., L, M]."""
    l = a.shape[-1]
    count = l // SOLVE_BLOCK
    if l % SOLVE_BLOCK or count < 2 or count & (count - 1):
        # one block: XLA's substitution, bit for bit what every chunk ran before PR 46 (another
        # rounding order moves a near-tie of tests/benchmark's float32 logits check past its 1e-4)
        return jax.lax.linalg.triangular_solve(a, rhs, left_side=True, lower=True, unit_diagonal=True)
    size = SOLVE_BLOCK
    d = _diagonal_blocks(a, size)
    x = _substitute(d, jnp.broadcast_to(jnp.eye(size, dtype=a.dtype), d.shape))
    while size < l:  # [[X11, 0], [-X22 (A21 X11), X22]] of every adjacent pair
        pairs = x.reshape(*x.shape[:-3], -1, 2, size, size)
        x11, x22 = pairs[..., 0, :, :], pairs[..., 1, :, :]
        a21 = _diagonal_blocks(a, 2 * size)[..., size:, :size]
        x21 = -jnp.einsum("...ij,...jk->...ik", x22,
                          jnp.einsum("...ij,...jk->...ik", a21, x11, precision=_HI), precision=_HI)
        x = jnp.concatenate([jnp.concatenate([x11, jnp.zeros_like(x11)], axis=-1),
                             jnp.concatenate([x21, x22], axis=-1)], axis=-2)
        size *= 2
    return jnp.einsum("...ij,...jd->...id", x[..., 0, :, :], rhs, precision=_HI)


def gdn_scan(q, k, v, g, beta, loaded, cont):
    """q, k [G, L, Hk, Dk], v [G, L, Hv, Dv], g [G, L, Hv] (<= 0; 0 at
    padding), beta [G, L, Hv] (0 at padding), loaded [G, Hv, Dk, Dv] the state
    each chunk starts from unless ``cont`` [G] bool says it continues the chunk
    before it.  Returns (o [G, L, Hv, Dv] float32, the state after each chunk
    [G, Hv, Dk, Dv] float32)."""
    with jax.named_scope("gdn_scan"):
        f32 = jnp.float32
        q, k, v, g, beta = (t.astype(f32) for t in (q, k, v, g, beta))
        n, l, hk, dk = q.shape
        hv, dv = v.shape[2], v.shape[3]
        heads = lambda t: jnp.repeat(jnp.moveaxis(t, 1, 2), hv // hk, axis=1)  # [G, Hv, L, Dk]
        q, k = heads(q), heads(k)
        v = jnp.moveaxis(v, 1, 2)                          # [G, Hv, L, Dv]
        g, beta = jnp.moveaxis(g, 1, 2), jnp.moveaxis(beta, 1, 2)      # [G, Hv, L]
        cum = jnp.cumsum(g, axis=-1)                       # gamma, inclusive
        total = cum[..., -1]                               # [G, Hv]
        lower = jnp.arange(l)[:, None] >= jnp.arange(l)[None, :]
        # exp(gamma_i - gamma_j) for i >= j (<= 1), 0 above the diagonal
        decay = jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :], -jnp.inf))
        kk = jnp.einsum("ghid,ghjd->ghij", k, k, precision=_HI)
        a = jnp.where(lower & ~jnp.eye(l, dtype=bool), beta[..., None] * kk * decay, 0.0)
        rhs = beta[..., None] * jnp.concatenate([k * jnp.exp(cum)[..., None], v], axis=-1)
        wu = _solve_unit_lower(a, rhs)                     # (I + A) [W | U] = rhs
        w, u = wu[..., :dk], wu[..., dk:]                  # [G, Hv, L, Dk], [G, Hv, L, Dv]
        k_end = k * jnp.exp(total[..., None] - cum)[..., None]
        carry = jnp.exp(total)[..., None, None] * jnp.eye(dk, dtype=f32) \
            - jnp.einsum("ghld,ghle->ghde", k_end, w, precision=_HI)   # [G, Hv, Dk, Dk]
        add = jnp.einsum("ghld,ghlv->ghdv", k_end, u, precision=_HI)   # [G, Hv, Dk, Dv]

        def hand_over(s_prev, xs):
            own, cont_g, m, plus = xs
            s_in = jnp.where(cont_g, s_prev, own.astype(f32))
            s_out = jnp.einsum("hde,hev->hdv", m, s_in, precision=_HI) + plus
            return s_out, (s_in, s_out)

        _, (s_in, s_out) = jax.lax.scan(
            hand_over, jnp.zeros((hv, dk, dv), f32), (loaded, cont, carry, add))
        fresh = u - jnp.einsum("ghld,ghdv->ghlv", w, s_in, precision=_HI)      # V'
        qk = jnp.einsum("ghid,ghjd->ghij", q, k, precision=_HI) * decay
        o = jnp.einsum("ghld,ghdv->ghlv", q * jnp.exp(cum)[..., None], s_in, precision=_HI) \
            + jnp.einsum("ghij,ghjv->ghiv", qk, fresh, precision=_HI)
        return jnp.moveaxis(o, 1, 2), s_out


def gdn_step(s, q, k, v, g, beta, active):
    """s [B, Hv, Dk, Dv] kept states, q, k [B, Hk, Dk], v [B, Hv, Dv], g and
    beta [B, Hv], active [B] bool -> (o [B, Hv, Dv] float32, states: updated
    where ``active``, the kept bits elsewhere)."""
    with jax.named_scope("gdn_step"):
        f32 = jnp.float32
        q, k, v, g, beta = (t.astype(f32) for t in (q, k, v, g, beta))
        hv = v.shape[1]
        heads = lambda t: jnp.repeat(t, hv // t.shape[1], axis=1)      # [B, Hv, Dk]
        q, k = heads(q), heads(k)
        decayed = jnp.exp(g)[..., None, None] * s.astype(f32)
        seen = jnp.sum(decayed * k[..., None], axis=2)                 # S'^T k  [B, Hv, Dv]
        new = decayed + k[..., None] * (beta[..., None] * (v - seen))[:, :, None, :]
        o = jnp.sum(new * q[..., None], axis=2)
        kept = jnp.where(active[:, None, None, None], new.astype(s.dtype), s)
        return o, kept
