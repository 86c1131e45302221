"""Mamba-2's state-space recurrence in the two forms serving needs, with its
short causal convolution.  Per head ``h`` (``P`` channels, state ``N`` wide;
``B`` and ``C`` are shared by the heads of a group):

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        S [P, N], float32
    y_t = S_t C_t                                        (the caller adds D x_t)

- ``ssm_scan``: CHUNKS of ``L`` tokens in the chunked (SSD) form: inside a
  chunk the products ``C B^T``, ``M x`` and the chunk's own contribution to
  the state are matmuls; ONE state is handed over per chunk.  A chunk takes
  its incoming state from the chunk before it (``cont``) or from ``loaded``
  (its sequence's kept state, zeros for a sequence's first chunk), so chunks
  of several sequences share one call, each scanned from its own state.
- ``ssm_step``: the recurrence itself, one token a sequence, on a batch of
  kept states, updated where ``active`` and left bit-identical elsewhere.
- ``conv_chunks`` / ``conv_step``: ``out_t = silu(b + sum_j w_j * in_{t-K+1+j})``
  depthwise over the ``K - 1`` rows before a chunk (a token) and its own.

A token with ``dt = 0`` leaves the state as it was: that is how padding rows
are kept out.  Everything is float32 inside, whatever the state is KEPT in
(``S.dtype``: float32 in serving; a benchmark's control casts it down).  Plain XLA
bodies under ``jax.named_scope`` (``ssm_scan``, ``ssm_step``, ``ssm_conv``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST  # float32 operands stay float32 on the MXU


def conv_chunks(prev, rows, w, b):
    """prev [G, K-1, C] the rows before each chunk, rows [G, L, C], w [K, C],
    b [C] -> (out [G, L, C] float32, ext [G, L+K-1, C]: ``prev`` and ``rows``
    joined, of which a chunk's new tail is a slice)."""
    with jax.named_scope("ssm_conv"):
        k, l = w.shape[0], rows.shape[1]
        ext = jnp.concatenate([prev.astype(rows.dtype), rows], axis=1)
        acc = b.astype(jnp.float32)
        for j in range(k):
            acc = acc + w[j].astype(jnp.float32) * ext[:, j:j + l].astype(jnp.float32)
        return jax.nn.silu(acc), ext


def conv_step(tail, row, w, b):
    """tail [B, K-1, C], row [B, C] -> (out [B, C] float32, new tail)."""
    with jax.named_scope("ssm_conv"):
        win = jnp.concatenate([tail, row[:, None].astype(tail.dtype)], axis=1)
        acc = b.astype(jnp.float32) + jnp.sum(
            w.astype(jnp.float32)[None] * win.astype(jnp.float32), axis=1)
        return jax.nn.silu(acc), win[:, 1:]


def ssm_scan(x, dt, a, b, c, loaded, cont):
    """x [G, L, H, P], dt [G, L, H] (>= 0; 0 at padding), a [H] (< 0),
    b, c [G, L, R, N] (``R`` groups, head ``h`` reads group ``h // (H / R)``),
    loaded [G, H, P, N] the state each chunk starts from unless ``cont`` [G]
    bool says it continues the chunk before it.  Returns (y [G, L, H, P]
    float32, the state after each chunk [G, H, P, N] float32)."""
    with jax.named_scope("ssm_scan"):
        g, l, h, p = x.shape
        r, n = b.shape[2], b.shape[3]
        f32 = jnp.float32
        x, dt, b, c = (t.astype(f32) for t in (x, dt, b, c))
        la = dt * a.astype(f32)                       # log decay a token, <= 0
        cum = jnp.cumsum(la, axis=1)                  # [G, L, H], inclusive
        total = cum[:, -1]                            # [G, H]
        xh = x.reshape(g, l, r, h // r, p)
        heads = lambda t: t.reshape(g, l, r, h // r)
        # inside a chunk: y_t += sum_{s<=t} (C_t.B_s) exp(cum_t - cum_s) dt_s x_s
        cb = jnp.einsum("gtrn,gsrn->grts", c, b, precision=_HI)
        diff = heads(cum)[:, :, None] - heads(cum)[:, None]            # [G, t, s, R, Hr]
        causal = (jnp.arange(l)[:, None] >= jnp.arange(l)[None, :])[None, :, :, None, None]
        m = jnp.exp(jnp.where(causal, diff, -jnp.inf)) * heads(dt)[:, None]
        m = m * jnp.moveaxis(cb, 1, 3)[..., None]                      # [G, t, s, R, Hr]
        y = jnp.einsum("gtsrh,gsrhp->gtrhp", m, xh, precision=_HI)
        # the chunk's own contribution to its last state, from a zero start
        w_end = jnp.exp(total[:, None] - cum) * dt                     # [G, L, H]
        local = jnp.einsum("gsrhp,gsrn->grhpn", xh * heads(w_end)[..., None], b,
                           precision=_HI).reshape(g, h, p, n)

        def hand_over(s_prev, xs):
            own, cont_g, decay, add = xs
            s_in = jnp.where(cont_g, s_prev, own.astype(f32))
            s_out = jnp.exp(decay)[:, None, None] * s_in + add
            return s_out, (s_in, s_out)

        _, (s_in, s_out) = jax.lax.scan(
            hand_over, jnp.zeros((h, p, n), f32), (loaded, cont, total, local))
        # what the incoming state adds: y_t += exp(cum_t) C_t . S_in
        y = y + jnp.einsum("gtrn,grhpn->gtrhp", c, s_in.reshape(g, r, h // r, p, n),
                           precision=_HI) * jnp.exp(heads(cum))[..., None]
        return y.reshape(g, l, h, p), s_out


def ssm_step(s, x, dt, a, b, c, active):
    """s [B, H, P, N] kept states, x [B, H, P], dt [B, H], a [H], b, c
    [B, R, N], active [B] bool -> (y [B, H, P] float32, states: updated where
    ``active``, the kept bits elsewhere)."""
    with jax.named_scope("ssm_step"):
        bsz, h, p, n = s.shape
        r = b.shape[1]
        f32 = jnp.float32
        x, dt, b, c = (t.astype(f32) for t in (x, dt, b, c))
        grouped = lambda t: t.reshape(bsz, r, h // r, *t.shape[2:])
        decay = grouped(jnp.exp(dt * a.astype(f32)))[..., None, None]
        dx = grouped(dt[..., None] * x)[..., None]                     # [B, R, Hr, P, 1]
        new = decay * grouped(s.astype(f32)) + dx * b[:, :, None, None, :]
        y = jnp.sum(new * c[:, :, None, None, :], axis=-1)
        kept = jnp.where(active[:, None, None, None], new.reshape(s.shape).astype(s.dtype), s)
        return y.reshape(bsz, h, p), kept
