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
  exp gamma)`` and ``U = (I + A)^-1 diag(beta) V`` (ONE triangular solve, by
  forward substitution: stable where the keys of a chunk are alike), a chunk
  maps its incoming state LINEARLY, ``S_out = (exp(gamma_L) I - Kd^T W) S_in +
  Kd^T U`` with ``Kd = K o exp(gamma_L - gamma)``: both terms are matmuls made
  for every chunk at once, ONE state is handed over per chunk, and the outputs
  ``O = (Q o exp gamma) S_in + tril(Q K^T o exp(gamma_i - gamma_j)) (U - W
  S_in)`` are matmuls again.  A chunk takes its incoming state from the chunk
  before it (``cont``) or from ``loaded`` (its sequence's kept state, zeros for
  a sequence's first chunk), so chunks of several sequences share one call.
- ``gdn_step``: the recurrence itself, one token a sequence, on a batch of
  kept states, updated where ``active`` and left bit-identical elsewhere.

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
        wu = jax.lax.linalg.triangular_solve(  # (I + A) [W | U] = rhs: the diagonal is not read
            a, rhs, left_side=True, lower=True, unit_diagonal=True)
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
