"""Grouped-query attention over a WINDOW whose keys live in a ring a slot
(``inference/latent_runner.py``: a ``wattn`` layer's ``wk`` / ``wv``), plain XLA.

A ring is ``rc`` pages of a pool ``[slots * rc, page, Hkv, hd]``, slot ``n``'s
from page ``n * rc``: position ``p`` lives in row ``p % page`` of the slot's
page ``(p // page) % rc``.  Which position a row holds follows from the
reader's own position (a ring's page ``i`` holds the latest page ``P <= pos //
page`` of the sequence with ``P % rc == i``, and a row past ``pos`` is masked by
causality), so a stale row is never taken for a key.  The callers
name the scope (``window_attn``); nothing here is a kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .latent_attention import _MASKED  # finite: a padding row's softmax is uniform, not NaN


def _attend(q, q_pos, keys, values, key_pos, window: int, probe=None):
    """q [G, C, Hq, hd] at q_pos [G, C] over keys, values [G, K, Hkv, hd] at
    key_pos [G, K] (negative: no key): a query sees key ``j`` iff ``0 <= q_pos -
    j < window``.  Scores and softmax float32.  Returns [G, C, Hq, hd].
    ``probe`` (a list) is handed what the mask let each query see: how many
    keys and the oldest one's position, [G * C]."""
    g, c, hq, hd = q.shape
    hkv = keys.shape[2]
    s = jnp.einsum("gckrd,gnkd->gkrcn", q.reshape(g, c, hkv, hq // hkv, hd), keys,
                   preferred_element_type=jnp.float32) * hd ** -0.5
    d = q_pos[:, :, None] - key_pos[:, None, :]
    ok = (d >= 0) & (d < window) & (key_pos[:, None, :] >= 0)
    if probe is not None:
        far = jnp.iinfo(jnp.int32).max
        probe.append({"window_seen": jnp.sum(ok, -1, dtype=jnp.int32).reshape(-1),
                      "window_oldest": jnp.min(jnp.where(ok, key_pos[:, None, :], far),
                                               -1).reshape(-1)})
    p = jax.nn.softmax(jnp.where(ok[:, None, None], s, _MASKED), axis=-1)
    o = jnp.einsum("gkrcn,gnkd->gckrd", p.astype(values.dtype), values,
                   preferred_element_type=jnp.float32)
    return o.reshape(g, c, hq, hd).astype(q.dtype)


def ring_attention(q, q_pos, k_ring, v_ring, slot, page0, rc: int, window: int, probe=None):
    """Groups of queries (q [G, C, Hq, hd] at q_pos [G, C], all of slot ``slot``
    [G] and on its page ``page0`` [G]) over that page and the look-back's pages
    before it, out of the rings their rows were written to first.  A pack's
    group is a page of queries; a decode tick's is one row, its slot's own."""
    bs = k_ring.shape[1]
    back = -(-(window - 1) // bs)
    pages = page0[:, None] - jnp.arange(back, -1, -1)[None, :]        # [G, back + 1]
    at = slot[:, None] * rc + pages % rc
    key_pos = jnp.where(pages[..., None] >= 0, pages[..., None] * bs + jnp.arange(bs), -1)
    flat = lambda a: a.reshape(a.shape[0], -1, *a.shape[3:])
    return _attend(q, q_pos, flat(k_ring[at]), flat(v_ring[at]), flat(key_pos), window, probe)


def ring_write_rows(ring, rows, pos, active):
    """One new row a live slot (rows [B, Hkv, hd] at ``pos`` [B]) into row
    ``pos % R`` of its ring; idle slots are dropped from the scatter."""
    n, bs = ring.shape[:2]
    b = rows.shape[0]
    rc = n // b
    page = jnp.where(active, jnp.arange(b) * rc + (pos // bs) % rc, n)
    return ring.at[page, pos % bs].set(rows.astype(ring.dtype), mode="drop")
