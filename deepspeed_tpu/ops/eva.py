"""EVA attention (Zheng et al., ICLR 2023, as EvaByte configures it), plain XLA:
a query attends the exact keys of its own WINDOW of positions and, for every
window before it, one learned SUMMARY row per chunk of ``chunk`` positions.

``summarise`` makes the summaries: per chunk and head a softmax-pooling of the
chunk's (rotated) keys under a learned vector ``phi``, the pooled key shifted by
a learned ``mu``, the values pooled by the same weights.  A summary row has a
key's and a value's format, so the serving runner keeps it where a key and a
value were (``inference/latent_runner.py``: one page of summaries replaces a
closed window's pages) and attends exact rows and summaries in ONE softmax
through the paged kernels it already has.  ``attend_uncached`` is the same
mathematics with every sequence its own keys (the uncached forward's, and what
the serving tests are held to).

The callers name the scopes (``eva_summarise``, ``eva_attend``); nothing here
is a kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .latent_attention import _MASKED  # finite: a padding row's softmax is uniform, not NaN


def summarise(k, v, phi, mu):
    """Whole chunks of rows -> one summary row each.  k, v [..., C, H, D] (the
    keys rotated), phi, mu [H, D].  ``a = softmax over the chunk's C rows of
    (phi_h . k)`` (no scale inside it), ``k~ = sum a k + mu``, ``v~ = sum a v``;
    float32 inside, results in k's dtype: (k~, v~) [..., H, D]."""
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    a = jax.nn.softmax(jnp.einsum("...chd,hd->...ch", kf, phi.astype(jnp.float32)), axis=-2)
    pooled = lambda x: jnp.einsum("...ch,...chd->...hd", a, x)
    return ((pooled(kf) + mu.astype(jnp.float32)).astype(k.dtype),
            pooled(vf).astype(v.dtype))


def attend_uncached(q, k, v, phi, mu, window: int, chunk: int):
    """b sequences from position 0: q, k, v [b, n, H, D] (q and k rotated) ->
    [b, n, H, D].  Query ``i`` sees key ``j`` iff ``j <= i`` lies in ``i``'s
    window, and the summary of chunk ``c`` iff the chunk's window lies before
    ``i``'s; one softmax over both sets, float32."""
    b, n, h, d = q.shape
    pad = -n % chunk
    chunks = lambda x: jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        b, (n + pad) // chunk, chunk, h, d)
    ks, vs = summarise(chunks(k), chunks(v), phi, mu)              # [b, n / chunk, H, D]
    i = jnp.arange(n)
    exact = (i[None, :] <= i[:, None]) & (i[None, :] // window == i[:, None] // window)
    # a chunk never straddles a window (``chunk`` divides ``window``): its first
    # position says which window it lies in
    closed = (jnp.arange(ks.shape[1]) * chunk)[None, :] // window < i[:, None] // window
    keys = jnp.concatenate([k, ks], axis=1)
    vals = jnp.concatenate([v, vs], axis=1)
    ok = jnp.concatenate([exact, closed], axis=1)                   # [n, n + n / chunk]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, keys, preferred_element_type=jnp.float32) * d ** -0.5
    p = jax.nn.softmax(jnp.where(ok[None, None], s, _MASKED), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(vals.dtype), vals,
                      preferred_element_type=jnp.float32).astype(q.dtype)
