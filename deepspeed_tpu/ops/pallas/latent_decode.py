"""Pallas kernel for a decode tick's latent attention over EVERY cached row
(DeepSeek-V2's MLA in its absorbed form): one query a slot walks that slot's
live pages WHOLE, where the allocator put them, up to the slot's length.

The XLA body (``ops/latent_attention.py:dense_attention_step``) gathers a block
of every slot's rows before it scores them (three passes over a row where the
algorithm needs one), for all the slots the engine has, idle or not, as far as
the LONGEST one reaches: 26.8 ms a tick of 7 live slots at 6.5% of its roofline
(my chip run, PR 45).  The 128 heads of a slot share its rows, so a slot's
query is an MXU tile by itself (``[H, W] x [W, keys]``), and the work is on the
chip's ridge (2176 FLOPs a key and head for 1152 B a key): what is left to do
is to read each live row once.

TPU design (``selected_attention.py``'s, less the mask and the query tiles):
- grid = (slots, key steps), key steps innermost and ``arbitrary``; a step is
  ``KP`` pages.  The block tables and the slots' lengths are prefetched
  scalars, the ``KP`` page operands' ``BlockSpec`` index maps look ``table[b,
  step * KP + j]`` up: no row is copied;
- work bounded by length: steps past a slot's last live page skip their
  compute and repeat that step's pages, which the pipeline recognises and does
  not fetch again; an idle slot (length 0) costs its grid steps and nothing
  else, and its output is zeros;
- a flash kernel's running max, sum and float32 accumulator in VMEM scratch;
  keys at or past the slot's length weigh exactly 0.

``supports()`` gates dispatch as the other latent kernels' do; the XLA body is
the fallback and the ground truth.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .index_scores import interpret, interpreted  # noqa: F401  (one switch for the three)

_MASKED = -1e30  # latent_attention._MASKED: finite, so max and exp stay finite
KP = 8           # pages a step: a slot of 24 slots x 65 steps costs its grid steps even when idle


def supports(h: int, w: int, r_kv: int, bs: int) -> bool:
    """On the chip whole 128-lane tiles of keys, row lanes and value lanes, and
    the heads a whole number of sublane tiles."""
    if interpret():
        return True
    return bs % 128 == 0 and w % 128 == 0 and r_kv % 128 == 0 and h % 16 == 0


def _kernel(lens_ref, tables_ref, q_ref, *rest, kp: int, bs: int, r_kv: int, scale: float):
    pages, (o_ref, m_sc, l_sc, acc_sc) = rest[:kp], rest[kp:]
    b, i = pl.program_id(0), pl.program_id(1)
    n = lens_ref[b]
    steps = (n + kp * bs - 1) // (kp * bs)

    @pl.when(i == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, _MASKED, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    @pl.when(i < steps)
    def _():
        q = q_ref[...]                                            # [H, W]
        keys = jnp.concatenate([p[...] for p in pages], axis=0)   # [kp*bs, W]
        s = jax.lax.dot_general(q, keys, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        at = i * (kp * bs) + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(at < n, s, _MASKED)
        m_old = m_sc[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.where(at < n, jnp.exp(s - m_new), 0.0)
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[...] = alpha * acc_sc[...] + jnp.dot(
            p.astype(keys.dtype), keys[:, :r_kv], preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)).astype(o_ref.dtype)


def latent_decode(q_abs, pages, tables, lens, r_kv: int, scale: float):
    """q_abs [B, H, W] (``[q_nope W_uk ; q_rope]``, zeros past the row), pages
    [nb, bs, W], tables [B, P] int32 (non-negative), lens [B] int32: keys a slot
    attends (0: an idle slot).  Key ``s`` of slot ``b`` is row ``s % bs`` of page
    ``tables[b, s // bs]``.  Returns [B, H, r_kv] in q_abs's dtype: softmax over
    the slot's first ``lens[b]`` keys of ``scale * q . row``, times the rows'
    first ``r_kv`` lanes; zeros for an idle slot."""
    b, h, w = q_abs.shape
    nb, bs, _ = pages.shape
    kp = min(KP, tables.shape[1])
    tables = jnp.pad(tables, ((0, 0), (0, -tables.shape[1] % kp)))
    steps = tables.shape[1] // kp

    def step(bi, i, n):  # steps past the last live one repeat it
        return jnp.minimum(i, jnp.maximum((n[bi] + kp * bs - 1) // (kp * bs) - 1, 0))

    def page(j):
        return pl.BlockSpec(
            (None, bs, w), lambda bi, i, n, tab: (tab[bi, step(bi, i, n) * kp + j], 0, 0))

    return pl.pallas_call(
        lambda *refs: _kernel(*refs, kp=kp, bs=bs, r_kv=r_kv, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, steps),
            in_specs=[pl.BlockSpec((None, h, w), lambda bi, i, n, tab: (bi, 0, 0)),
                      *[page(j) for j in range(kp)]],
            out_specs=pl.BlockSpec((None, h, r_kv), lambda bi, i, n, tab: (bi, 0, 0)),
            scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, r_kv), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, r_kv), q_abs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret(),
        name="latent_decode",
    )(lens.astype(jnp.int32), tables.astype(jnp.int32), q_abs, *[pages] * kp)
