"""Pallas kernel for attention over SELECTED rows of a paged latent cache
(DeepSeek-V3.2's sparse attention in MLA's absorbed form) that never gathers a
row: a group of ``C`` queries of one sequence walks that sequence's live pages
WHOLE, where the allocator put them, and the selection arrives as a mask.

The XLA body (``ops/latent_attention.py:sparse_attention``) gathers each
query's ``k`` picked rows: 14.5 ns a 1280-byte row on a v5e, the price of
issuing a row copy and nine times the price of moving it (ledger, PR 31).  The
128 heads share a row, and a context of a few times ``k`` has a fifth or more
of its keys picked, so reading every page at the memory's speed and masking
what was not picked costs ``context / k`` times the needed FLOPs on an MXU
that is idle while the gather runs, and no row copy.  That is the cheaper
schedule up to a context the caller measures (``latent_attention.
DENSE_KEYS_MAX``); past it, and for a decode tick's single rows, the gathered
body stays.

TPU design:
- grid = (groups, query tiles, key steps), key steps innermost and
  ``arbitrary``; a step is ``kp`` pages.  The block table and each group's
  count of live pages are prefetched scalars, and the ``kp`` page operands'
  ``BlockSpec`` index maps look ``table[g, step * kp + j]`` up: no ``lat[table]``
  copy exists for these groups;
- work bounded by length: steps past a group's last live page skip their
  compute and repeat that step's pages and mask, which the pipeline
  recognises and does not fetch again.  A group handed 0 live pages costs its
  query tiles' fetch and nothing else, and its output is not written: the
  caller takes the other body's for it;
- a query tile is ``tq`` queries x ``H`` heads folded into the MXU's M
  dimension: scores ``[tq*H, W] x [W, kp*bs]``, weighted sum ``[tq*H, kp*bs] x
  [kp*bs, r_kv]`` over the rows' first ``r_kv`` lanes, with a flash kernel's
  running max, sum and float32 accumulator in VMEM scratch.  A step of several
  pages amortises the accumulator's rescale (4 x the size of one page's
  scores) over them.  Score blocks never reach HBM;
- the mask ``[G, C, keys]`` int8 has a query's keys along lanes; a query's row
  of it becomes an additive ``0 / -1e30`` row that is broadcast over the
  query's ``H`` score rows (sublanes): one add a score vreg.  An unpicked
  key's weight is ``exp(-1e30 - max)`` = exactly 0, as in the gathered body.

``supports()`` gates dispatch as ``index_scores.supports`` does; the gathered
XLA body remains the long-context path, the fallback and the ground truth.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .index_scores import interpret, interpreted  # noqa: F401  (one switch for the pair)

_MASKED = -1e30  # latent_attention._MASKED: finite, so max and exp stay finite
# Tiles tried at 12 288 keys (my chip run, PR 32; ms a 128-query group, share of
# the MXU peak): 32 x 4 2.70 / 87%, 16 x 4 2.86, 16 x 8 2.86, 32 x 8 3.39, 16 x 2 3.27
TQ = 32  # queries a tile (x heads = the MXU's M): keys are re-read C / TQ times
KP = 4   # pages a step: the accumulator is rescaled once for all of them
VMEM_LIMIT = 100 << 20  # of a v5e's 128 MiB; a 32 x 4 tile's buffers need ~55 MiB


def supports(c: int, h: int, w: int, r_kv: int, bs: int) -> bool:
    """Whole query tiles; on the chip whole 128-lane tiles of keys, row lanes
    and value lanes, and a query's heads a whole number of sublane tiles."""
    tq = min(TQ, c)
    if c % tq:
        return False
    if interpret():
        return True
    return bs % 128 == 0 and w % 128 == 0 and r_kv % 128 == 0 and h % 16 == 0 and tq % 16 == 0


def _kernel(live_ref, tables_ref, q_ref, mask_ref, *rest, kp: int, tq: int, heads: int,
            r_kv: int, scale: float):
    pages, (o_ref, s_sc, m_sc, l_sc, acc_sc) = rest[:kp], rest[kp:]
    g, i = pl.program_id(0), pl.program_id(2)
    steps = (live_ref[g] + kp - 1) // kp

    @pl.when(i == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, _MASKED, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    @pl.when(i < steps)
    def _():
        q = q_ref[...]  # [tq*H, W]
        keys = jnp.concatenate([p[...] for p in pages], axis=0)  # [kp*bs, W]
        s = jax.lax.dot_general(q, keys, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        bias = (mask_ref[...].astype(jnp.float32) - 1.0) * -_MASKED  # [tq, kp*bs]: 0 / -1e30
        for t in range(tq):  # a query's mask row over its H score rows
            rows = slice(t * heads, (t + 1) * heads)
            s_sc[rows, :] = s[rows, :] + bias[t:t + 1, :]
        s = s_sc[...]
        m_old = m_sc[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new)
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[...] = alpha * acc_sc[...] + jnp.dot(
            p.astype(keys.dtype), keys[:, :r_kv], preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when((i == pl.num_programs(2) - 1) & (steps > 0))
    def _():
        o_ref[...] = (acc_sc[...] / l_sc[...]).astype(o_ref.dtype)


def selected_attention(q_abs, mask, pages, tables, live_pages, r_kv: int, scale: float):
    """q_abs [G, C, H, W], mask [G, C, P * bs] int8 (1 = the query attends the
    key), pages [nb, bs, W], tables [G, P] int32 (non-negative), live_pages [G]
    int32: pages of the group that hold a key some query of it picked (0 = not
    this kernel's group).  Key ``s`` of group ``g`` is row ``s % bs`` of page
    ``tables[g, s // bs]``.  Returns [G, C, H, r_kv] in q_abs's dtype: softmax
    over each query's masked keys of ``scale * q . row``, times the rows' first
    ``r_kv`` lanes; a group with 0 live pages is left unwritten (undefined).
    Every query of a live group has a picked key among its live pages."""
    g, c, h, w = q_abs.shape
    nb, bs, _ = pages.shape
    tq, kp = min(TQ, c), min(KP, tables.shape[1])
    tables = jnp.pad(tables, ((0, 0), (0, -tables.shape[1] % kp)))
    mask = jnp.pad(mask, ((0, 0), (0, 0), (0, tables.shape[1] * bs - mask.shape[2])))
    steps = tables.shape[1] // kp
    m = tq * h

    def step(gi, i, live):  # steps past the last live one repeat it
        return jnp.minimum(i, jnp.maximum((live[gi] + kp - 1) // kp - 1, 0))

    def page(j):
        return pl.BlockSpec(
            (None, bs, w), lambda gi, qi, i, live, tab: (tab[gi, step(gi, i, live) * kp + j], 0, 0))

    res = pl.pallas_call(
        lambda *refs: _kernel(*refs, kp=kp, tq=tq, heads=h, r_kv=r_kv, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(g, c // tq, steps),
            in_specs=[
                pl.BlockSpec((None, m, w), lambda gi, qi, i, live, tab: (gi, qi, 0)),
                pl.BlockSpec((None, tq, kp * bs),
                             lambda gi, qi, i, live, tab: (gi, qi, step(gi, i, live))),
                *[page(j) for j in range(kp)],
            ],
            out_specs=pl.BlockSpec((None, m, r_kv), lambda gi, qi, i, live, tab: (gi, qi, 0)),
            scratch_shapes=[pltpu.VMEM((m, kp * bs), jnp.float32),
                            pltpu.VMEM((m, 1), jnp.float32),
                            pltpu.VMEM((m, 1), jnp.float32),
                            pltpu.VMEM((m, r_kv), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((g, c * h, r_kv), q_abs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret(),
        name="selected_attn",
    )(live_pages.astype(jnp.int32), tables.astype(jnp.int32), q_abs.reshape(g, c * h, w), mask,
      *[pages] * kp)
    return res.reshape(g, c, h, r_kv)
