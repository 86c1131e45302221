"""Pallas kernel for a prefill pack's latent attention over EVERY cached row in
the DECOMPRESSED form (DeepSeek-V2's MLA as the published model computes it):
a RUN of consecutive query pages of one sequence walks that sequence's live
pages WHOLE, where the allocator put them, and ``W_uk`` / ``W_uv`` are applied
to a block of latent rows ONCE for all of the run's queries.

The absorbed walk (``selected_attention.py``) folds the queries through
``W_uk`` first and scores them against the rows themselves: ``2 (2 r_kv +
rope)`` FLOPs a (query, key) pair and head, 2304 on DeepSeek-V2's 640-lane
rows, which that kernel runs at ~86% of the MXU's peak and cannot run faster.
Here a key and head costs ``2 r_kv (nope + v)`` once (its ``k = c W_uk[h]``
and ``v = c W_uv[h]``) and a pair and head ``2 (nope + rope) + 2 v`` after
that: from ``latent_attention.crossing()`` queries a run (171 at these widths)
the cheaper form, at 2048 queries a third of the walk's matmul work.  The
softmax's vector work (one exponential a pair and head) is the same in both.

TPU design:
- grid = (head blocks, runs), runs innermost and ``arbitrary``.  A run is
  ``(first page of queries, pages of queries, first position)``; the runs and
  their block tables are prefetched scalars.  A step holds the WHOLE pack's
  queries and outputs of its ``HB`` heads (fetched once a head block, the runs
  share the buffers) and a dead run (0 pages) costs its grid step alone;
- the keys are walked by a loop INSIDE the step, its trip count the run's own
  reach (``first position + queries``, in steps of ``KP`` pages): no grid step
  exists for a key block no query of the run sees.  The pages stay in HBM and
  are copied through the block table into a double-buffered VMEM tile, step
  ``i + 1`` in flight while step ``i`` computes (``paged_attention.py``'s
  idiom): no ``lat[table]`` copy exists;
- per key step and head: ONE matmul ``rows[:, :r_kv] x [W_uk[h] | W_uv[h]]``
  gives the step's keys and values (bf16, as the model's own are); the score
  operand is ``[k_nope | rows[:, r_kv:]]``, the rope key with the row's zero pad
  behind it, so every slice is a whole lane tile (the queries arrive padded
  with zeros to the same width).  Then every query tile of the run that sees a
  key of the step: ``s = scale * q . k`` in float32, a flash kernel's running
  max, sum and float32 accumulator in VMEM scratch (all of the pack's rows, a
  head block's heads), ``acc += p v`` with ``p`` cast to bf16.  Neither the
  scores nor the decompressed keys reach HBM;
- causal by position: a tile whose last position lies before the step's first
  key is not visited; only a tile that straddles the diagonal builds a mask
  (an iota compare); every other tile runs the body with no mask in it.

Query tiles are laid over the PACK (``TQ`` rows from row 0), not over the
run.  A tile the run starts or ends inside is visited ``TAIL`` rows at a time,
as far as the run needs it (a question's two pages cost 256 rows of scores,
not 1024); where even that reaches past the run's ends, the other rows take
positions before its first or past its last, land in scratch rows no live run
reads (a run initialises its own), and are never written out: the run's rows
alone are, a page at a time.  A row outside every live run comes back zeros:
the caller takes the other body's for it.

``supports()`` gates dispatch as the other latent kernels' do; the absorbed
bodies (``selected_attn``, ``latent_attention.dense_attention_pack``) remain
the path of short runs, the fallback and the ground truth.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .index_scores import interpret, interpreted  # noqa: F401  (one switch for the four)

_MASKED = -1e30  # latent_attention._MASKED: finite, so max and exp stay finite
# Tiles tried (my chip runs, PR 47; ``tools/latent_prefill_curves.py``: ms of ONE layer's run
# of 2048 queries x 128 heads whose last query sits at key 16 384; the absorbed walk 52.7):
# TQ x KP x HB 128 x 8 x 4 36.6, 256 x 4 x 4 38.6, 256 x 8 x 4 28.4, 256 x 16 x 4 26.8,
# 512 x 8 x 4 25.1, 512 x 16 x 4 26.5, 512 x 8 x 2 25.3, 1024 x 4 x 2 39.8, 1024 x 8 x 2
# 24.4-24.7 (75-76% of the MXU's peak on the FLOPs it does; 80.5% at 49 152 keys, 71.5
# ms for the walk's 159.7), 1024 x 12 x 2 25.5, 1024 x 16 x 2 26.6, 2048 x 8 x 2 25.0;
# heads a step 1 / 2 / 4 at 1024 x 8: 24.9 / 24.7 / 24.5 (the pages' re-reads hide).  The
# MXU, not the softmax's vector work, bounds it: a longer query tile feeds a loaded key tile
# more rows, and the diagonal's coarser skip costs less than that gains (at 4096 keys 1024
# and 512 read 6.78 and 7.13, 2048 7.48).  A SHORTER run at 16 384 keys, by the rows of a
# partial tile visited at a time (TAIL none / 512 / 256 / 128; the walk): 256 queries
# 14.9 / 9.9 / 7.7 / 8.7 (9.2; from an odd page of the pack 15.0 / - / 10.8 / 8.9),
# 640 queries 15.1 / 15.2 / 13.8 / 15.0 (18.9), 1152 queries 25.2 / 20.2 / 18.1 / 17.0
# (31.6), 2048 queries the same 24.5 for all.  Heads laid side by side along lanes (no
# transposes around the call, a static head loop) read the same in the kernel and 0.3 ms
# MORE around it: XLA re-lays ``[T, H, w]`` as ``[T, H w]`` by a copy no cheaper than the
# transpose.
TQ = 1024  # queries a tile (one head's: the MXU's M)
TAIL = 256  # ... of a tile the run starts or ends inside: the rows it needs are visited this many at a time
KP = 8     # pages a key step: keys and values are decompressed, and the accumulators rescaled, once for all of them
HB = 2     # heads a grid step: the pages are read H / HB times a run
VMEM_LIMIT = 100 << 20  # of a v5e's 128 MiB


def supports(t: int, h: int, lanes: int, r_kv: int, nope: int, v: int, bs: int) -> bool:
    """Whole query tiles and head blocks; on the chip whole 128-lane tiles of
    keys a page, of the row's latent and rope parts and of a head's key and
    value, and query tiles of whole sublane tiles."""
    tq, hb = min(TQ, t), min(HB, h)
    if t % tq or tq % min(TAIL, tq) or h % hb or t % bs:
        return False
    if interpret():
        return True
    return (bs % 128 == 0 and r_kv % 128 == 0 and (lanes - r_kv) % 128 == 0
            and nope % 128 == 0 and v % 128 == 0 and tq % 16 == 0)


def _kernel(runs_ref, tables_ref, q_ref, w_ref, lat_hbm, o_ref, buf, sem, k_sc, v_sc, m_sc, l_sc,
            acc_sc, *, kp: int, bs: int, tq: int, ts: int, hb: int, r_kv: int, nope: int,
            scale: float):
    r = pl.program_id(1)
    g0, n, p0 = runs_ref[r, 0], runs_ref[r, 1], runs_ref[r, 2]
    kb = kp * bs
    row0, row_end = g0 * bs, (g0 + n) * bs
    shift = p0 - row0             # a pack row's position: row + shift
    steps = jax.lax.div(p0 + n * bs + kb - 1, kb)
    t_end = jax.lax.div(row_end + tq - 1, tq)

    def pages(i, slot, fn):
        for j in range(kp):
            fn(pltpu.make_async_copy(lat_hbm.at[tables_ref[r, i * kp + j]],
                                     buf.at[slot, pl.ds(j * bs, bs)], sem.at[slot]))

    def tile(h, row, size: int, k0, masked: bool):
        rows = pl.ds(pl.multiple_of(row, size), size)
        s = jax.lax.dot_general(q_ref[h, rows, :], k_sc[...], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            q_at = row + shift + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_at = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_at <= q_at, s, _MASKED)
        m_old = m_sc[h, rows, :]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new)  # a masked key: exp(-1e30 - max) = exactly 0 (key 0 came first)
        l_sc[h, rows, :] = alpha * l_sc[h, rows, :] + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[h, rows, :] = alpha * acc_sc[h, rows, :] + jnp.dot(
            p.astype(v_sc.dtype), v_sc[...], preferred_element_type=jnp.float32)
        m_sc[h, rows, :] = m_new

    def key_step(i, _):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < steps)
        def _():
            pages(i + 1, 1 - slot, lambda c: c.start())

        pages(i, slot, lambda c: c.wait())
        k0 = i * kb
        # the first tile with a row at or past the step's first key
        t_first = jax.lax.div(jnp.maximum(row0, k0 - shift), tq)

        def head(h, _):
            kv = jnp.dot(buf[slot, :, :r_kv], w_ref[h], preferred_element_type=jnp.float32)
            k_sc[:, :nope] = kv[:, :nope].astype(k_sc.dtype)
            k_sc[:, nope:] = buf[slot, :, r_kv:]
            v_sc[...] = kv[:, nope:].astype(v_sc.dtype)

            def rows(row, size: int):
                straddles = k0 + kb - 1 > row + shift  # a key of the step past the first row

                @pl.when(straddles)
                def _():
                    tile(h, row, size, k0, True)

                @pl.when(jnp.logical_not(straddles))
                def _():
                    tile(h, row, size, k0, False)

            def visit(t, _):
                whole = (t * tq >= row0) & ((t + 1) * tq <= row_end)

                @pl.when(whole)
                def _():
                    rows(t * tq, tq)

                @pl.when(jnp.logical_not(whole))
                def _():  # the run starts or ends inside: the part of the tile it needs
                    first = jax.lax.div(jnp.maximum(t * tq, jnp.maximum(row0, k0 - shift)), ts)
                    last = jax.lax.div(jnp.minimum((t + 1) * tq, row_end) + ts - 1, ts)

                    def part(u, _):
                        rows(u * ts, ts)
                        return 0

                    jax.lax.fori_loop(first, last, part, 0)

                return 0

            return jax.lax.fori_loop(t_first, t_end, visit, 0)

        return jax.lax.fori_loop(0, hb, head, 0)

    @pl.when(r == 0)
    def _():  # a row of no run comes back zeros
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(n > 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, _MASKED, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)
        pages(0, 0, lambda c: c.start())
        jax.lax.fori_loop(0, steps, key_step, 0)

        def write(j, _):  # the run's own rows, a page of queries at a time
            h = jax.lax.div(j, n)
            rows = pl.ds(pl.multiple_of(row0 + jax.lax.rem(j, n) * bs, bs), bs)
            o_ref[h, rows, :] = (acc_sc[h, rows, :] / l_sc[h, rows, :]).astype(o_ref.dtype)
            return 0

        jax.lax.fori_loop(0, hb * n, write, 0)


def latent_prefill(q, w_ukv, pages, tables, runs, r_kv: int, scale: float):
    """q [H, T, nope + lanes - r_kv] (a head's ``[q_nope ; q_rope]``, zeros past
    it: the width of ``[k_nope ; row[r_kv:]]``), the pack's ``T`` rows in
    pages of ``bs``; w_ukv [H, r_kv, nope + v] (``[W_uk[h] | W_uv[h]]``); pages
    [nb, bs, lanes] (a row ``[c_kv ; k_rope]``, zeros past it); tables [R, P]
    int32 (non-negative) each run's block table; runs [R, 3] int32: a run's
    first page of the pack, its pages (0: no run) and its first query's
    position, a multiple of ``bs``: row ``i`` of the run is at that position +
    ``i`` and attends the keys up to its own.  Key ``s`` of run ``r`` is row ``s
    % bs`` of page ``tables[r, s // bs]``.  Returns [H, T, v] in q's dtype:
    softmax over each query's keys of ``scale * q . [row[:r_kv] W_uk[h] ;
    row[r_kv:]]``, times ``row[:r_kv] W_uv[h]``; zeros for the rows outside
    every run."""
    tq = min(TQ, q.shape[1])
    tiles = (tq, min(TAIL, tq), min(KP, tables.shape[1]), min(HB, q.shape[0]))
    return _call(q, w_ukv, pages, tables, runs, r_kv=r_kv, scale=scale, tiles=tiles,
                 interpreted=interpret())


@functools.partial(jax.jit, static_argnames=("r_kv", "scale", "tiles", "interpreted"))
def _call(q, w_ukv, pages, tables, runs, *, r_kv: int, scale: float, tiles, interpreted: bool):
    """``latent_prefill`` at the tiles and the interpret switch it was called
    under.  Jitted on them, so that the layers of one program, and every branch
    that holds the call, trace and lower the kernel ONCE: a trace and lowering
    is ~0.5 s of a process's set-up whether the compile cache hits or not (five
    layers of cell 9's pack: 2.5 s of its warm-up; my chip runs, PR 47)."""
    h, t, wq = q.shape
    nb, bs, lanes = pages.shape
    nope = wq - (lanes - r_kv)
    v = w_ukv.shape[-1] - nope
    tq, ts, kp, hb = tiles
    tables = jnp.pad(tables, ((0, 0), (0, -tables.shape[1] % kp)))
    kb = kp * bs
    heads = lambda width: pl.BlockSpec((hb, t, width), lambda hi, ri, runs, tab: (hi, 0, 0))
    return pl.pallas_call(
        lambda *refs: _kernel(*refs, kp=kp, bs=bs, tq=tq, ts=ts, hb=hb, r_kv=r_kv, nope=nope,
                              scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(h // hb, runs.shape[0]),
            in_specs=[heads(wq),
                      pl.BlockSpec((hb, r_kv, nope + v), lambda hi, ri, runs, tab: (hi, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],  # the pages stay in HBM
            out_specs=heads(v),
            scratch_shapes=[pltpu.VMEM((2, kb, lanes), pages.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.VMEM((kb, wq), pages.dtype),
                            pltpu.VMEM((kb, v), pages.dtype),
                            pltpu.VMEM((hb, t, 1), jnp.float32),
                            pltpu.VMEM((hb, t, 1), jnp.float32),
                            pltpu.VMEM((hb, t, v), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((h, t, v), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpreted,
        name="latent_prefill",
    )(runs.astype(jnp.int32), tables.astype(jnp.int32), q, w_ukv, pages)
