"""Pallas paged-attention decode kernel.

The serving-performance core the reference implements as CUDA blocked flash
attention over the ragged KV cache (``inference/v2/kernels/ragged_ops/
atom_builder`` + blocked attention; FastGen's throughput claim lives here).

Iteration space (one kernel invocation, no grid: q and out sit whole in VMEM;
``ctx_attention.py`` walks its pack the same way since PR 25):

- **a scalar loop over the B slots** gathers the live ones (``len > 0``); a dead
  slot costs that one compare, no DMA and no compute, and its output row is
  finite zeros (the runners hand an inactive slot length 0; the dense body
  gives the same zeros, so the two stay each other's ground truth);
- **a loop over the live rows**, and per row **a loop with a dynamic trip count
  over key tiles of ``kpt`` pages** (``_tile_pages``: as many whole pages as
  ``_TILE_BYTES`` of K hold, from the call's shapes alone): each page below
  ``ceil(len / bs)`` is fetched from the HBM pool by its table id with
  ``make_async_copy`` into a double-buffered VMEM tile, tile i + 1 in flight
  while tile i is computed, and the first tile of the NEXT live row in flight
  during this row's last (the buffers alternate across rows: no bubble at a
  row's start).  Work (compute AND DMA) follows the rows' live pages, not
  ``max_pages`` nor the slot count.

**No transpose.**  A page is ``[bs, hkv, hd]`` in the pool, so a tile arrives
as rows ``(key, kv head)`` of ``hd`` lanes, and it is used as it lands: ONE
matmul scores all ``hq`` query heads against all ``kpt * bs * hkv`` rows, a
static per-column key index (``key_s``: the column's key where the column's
kv head is the query row's, ``_NO_KEY`` elsewhere) masks the other heads'
columns and the keys past the row's length in one compare, and ONE matmul
folds the probabilities (exactly 0 in the masked columns) into ``[hq, hd]``.
That spends ``hkv`` times the exp work of a head-major layout and no relayout
at all: the kernel it replaces transposed K and V head-major every page and
ran 2 x hkv matmuls of ``g`` rows x ``bs`` keys a page; in Mosaic's own count
a 512-key tile of 8 kv heads is ~3 600 operations this way and ~10 000 with
the transposes.  A sub-32-bit tile is fetched into ``uint32`` scratch through
a view of the pool's dtype (same bytes), so it loads as whole packed vregs.
Online softmax in fp32; GQA without repeating kv.  The tail of a row's last
tile is masked out of the scores AND zeroed in V before the weighted sum (an
unfetched VMEM region may hold NaN bits, and ``0 * NaN`` is NaN).

The jnp gather path (inference/paged.py) remains the fallback + ground
truth; ``supports()`` gates dispatch exactly like ops/pallas/flash_kernel.
``_decode_kernel_packed`` (head sizes under 128; runs in no benchmark cell)
still walks a page a grid step.

On the chip (TPU v5e, PR 34; PERF.md §6).  Alone, one call
(``tools/paged_decode_curves.py``; the kernel it replaces beside it):

    shape (live rows x keys of slots)        before    128    256    512   1024 keys a tile
    chat  10 x ~1.8k of 64, block 32, 8 kv   0.315   0.123  0.110  0.112  0.107 ms (75% of its roofline at 256)
    docs   8 x 1-4k  of 64, block 32, 8 kv   0.281   0.108  0.098  0.097  0.101
    nemo 128 x ~1k  of 128, block 128, 2 kv  0.864   0.552  0.372  0.276  0.256    (66% at 1024)
    no live row of 64                        0.071   0.013  0.012  0.012  0.013

The optimum follows a tile's BYTES (its rows = keys x kv heads), not its keys:
512 KiB of K is 256 keys of Mistral's pages and 1024 of Nemotron's.  In the
cells (traced runs): ``mistral7b_chat_rate`` 0.325 -> 0.070 ms a call, 28% ->
84% of its roofline (9 live rows of 64), the decode program 15.28 -> 11.19 ms;
``mistral7b_docs_closed`` ~0.17 -> ~0.044 ms (0.353 -> 0.096 s of a 4 s
capture); ``nemotron3_super_reasoning_closed`` 1.066 -> 0.298 ms for its one
attention block (128 live rows).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_NO_KEY = 1 << 30  # the key index of a column another kv head owns

_INTERPRET = False

# bytes of K a key tile holds (a whole number of pages, fetched into one
# double-buffered VMEM tile); sets tile shapes only, the loops' trip counts
# come from seq_lens
_TILE_BYTES = 512 * 1024

# what ``supports`` admits by ``_vmem_estimate``, and what the kernel asks of
# Mosaic: twice that, for the temporaries the estimate does not see
_VMEM_BUDGET = 16 * 1024 * 1024
_VMEM_LIMIT = 2 * _VMEM_BUDGET


def set_interpret(value: bool) -> None:
    global _INTERPRET
    _INTERPRET = bool(value)


def _packed_mode(hd: int, hkv: int) -> bool:
    """Sub-128 head dims route through the PACKED kernel: KV pages are
    viewed as ``[bs, hkv*hd]`` (kv heads side-by-side on the 128-lane minor
    dim) so the per-page DMA stays tile-aligned, and the query matrix is
    laid out block-diagonally over the packed lanes — cross-head lanes hold
    zeros, so one full-lane MXU dot computes every head's scores exactly
    (r4 VERDICT weak #1: hd=64 used to fall back to the dense gather)."""
    return hd % 128 != 0 and (hkv * hd) % 128 == 0 and hd % 8 == 0


def supports(q, cache_k, logits_soft_cap) -> bool:
    b, hq, hd = q.shape
    nb, bs, hkv, _ = cache_k.shape
    if logits_soft_cap is not None:
        return False
    # Mosaic requires the per-page DMA slice's minor dim aligned to the
    # (2,128) tiling on hardware: lone hd=64 fails with "Slice shape along
    # dimension 3 must be aligned to tiling (128)"; the packed layout
    # restores alignment whenever hkv*hd is a lane multiple.  Interpret
    # mode (CPU tests) has no such constraint.
    if _INTERPRET:
        if hd % 8 or hd < 8:
            return False
    elif hd % 128 and not _packed_mode(hd, hkv):
        return False
    if hq % hkv:
        return False
    if not _INTERPRET and not _packed_mode(hd, hkv):
        # q and out sit whole in VMEM beside the key tiles
        isz = jnp.dtype(cache_k.dtype).itemsize
        if _vmem_estimate(b, hq, hkv, hd, bs, isz) > _VMEM_BUDGET:
            return False
    return True


def _tile_pages(bs: int, hkv: int, hd: int, isz: int, p: Optional[int] = None) -> int:
    """Pages a key tile (``kpt``), from the call's shapes alone: as many whole
    pages as ``_TILE_BYTES`` of K hold, at most the table's width."""
    kpt = max(1, _TILE_BYTES // (bs * hkv * hd * isz))
    return kpt if p is None else min(p, kpt)


def tile_keys(cache_k, block_table) -> int:
    """Keys a tile of the kernel walks for this pool and table (what the
    dispatcher notes, so a trace says which tile ran)."""
    _, bs, hkv, hd = cache_k.shape
    if not _INTERPRET and _packed_mode(hd, hkv):
        return bs  # the packed kernel still walks a page a step
    return bs * _tile_pages(bs, hkv, hd, jnp.dtype(cache_k.dtype).itemsize,
                            block_table.shape[1])


def _vmem_estimate(b, hq, hkv, hd, bs, isz):
    cols = _tile_pages(bs, hkv, hd, isz) * bs * hkv
    return (
        2 * b * hq * hd * isz       # q and out, whole
        + 2 * 2 * cols * hd * isz   # double-buffered K and V tiles
        + 4 * hq * cols             # the columns' key index (int32)
        + 4 * 4 * hq * cols         # scores / probabilities (f32) and copies
    )


def _decode_kernel(
    lens_ref,    # [B] int32 SMEM — length INCLUDING the current token; 0 = no row
    tables_ref,  # [B, P] int32 SMEM — ids clipped into the pool
    q_ref,       # [B, hq, hd] VMEM, whole
    k_hbm,       # [num_blocks, bs*hkv, hd] HBM — a page's (key, kv head) rows
    v_hbm,
    o_ref,       # [B, hq, hd] VMEM, whole
    kbuf,        # [2, cols | cols/2, hd] VMEM — double-buffered key tile
    vbuf,
    sem,         # DMA semaphores [2, 2]
    live_s,      # [B] int32 SMEM — the live slots, in order
    key_s,       # [hq, cols] int32 VMEM — a column's key within the tile
    *,
    scale: float,
    bs: int,
    hkv: int,
    kpt: int,
):
    n_slots, p_max = tables_ref.shape
    _, hq, hd = q_ref.shape
    g = hq // hkv
    kt = kpt * bs    # keys a tile
    pr = bs * hkv    # rows of a page: (key, kv head), the pool's own order
    cols = kt * hkv  # rows of a tile = columns of its scores
    dtype = k_hbm.dtype
    # sub-32-bit pages land in uint32 scratch through a ``dtype`` view: the
    # bytes are the pool's, and a tile then loads as whole packed vregs (a
    # scratch of ``dtype`` itself loads half-filled ones and repacks each)
    words = kbuf.dtype != dtype

    def tile_ref(buf):
        return buf.bitcast(dtype) if words else buf

    def tile_of(buf, slot):
        return pltpu.bitcast(buf[slot], dtype) if words else buf[slot]

    # column c of a tile is key c // hkv under kv head c % hkv (no transpose:
    # the tile stays as fetched), and a query row scores its own kv head's
    # columns only: every other column gets a key index no length reaches
    col = jax.lax.broadcasted_iota(jnp.int32, (hq, cols), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (hq, cols), 0)
    key_s[...] = jnp.where(col % hkv == row // g, col // hkv, _NO_KEY)
    o_ref[...] = jnp.zeros_like(o_ref)  # a dead slot's row: finite zeros

    def _compact(b, n):
        live = lens_ref[b] > 0

        @pl.when(live)
        def _():
            live_s[n] = b
        return n + live.astype(jnp.int32)

    # a dead slot costs this scalar compare: no DMA, no compute
    n_live = jax.lax.fori_loop(0, n_slots, _compact, 0)

    def row_len(b):
        # the table's width bounds a row, as in the dense body
        return jnp.minimum(lens_ref[b], p_max * bs)

    def tile_dma(b, i, slot, fn):
        """Start or await the live pages of key tile ``i`` of slot ``b``."""
        n_pages = (row_len(b) + bs - 1) // bs
        for j in range(kpt):
            pg = i * kpt + j

            @pl.when(pg < n_pages)
            def _():
                pid = tables_ref[b, pg]
                rows = pl.ds(j * pr, pr)
                fn(pltpu.make_async_copy(
                    k_hbm.at[pid], tile_ref(kbuf).at[slot, rows], sem.at[slot, 0]))
                fn(pltpu.make_async_copy(
                    v_hbm.at[pid], tile_ref(vbuf).at[slot, rows], sem.at[slot, 1]))

    @pl.when(n_live > 0)
    def _():
        tile_dma(live_s[0], 0, 0, lambda c: c.start())

    def _row(r, t0):
        """Live row ``r``; ``t0`` tiles were walked before it (the buffers
        alternate across rows, so the next row's first tile is in flight
        while this row's last is computed)."""
        b = live_s[r]
        ln = row_len(b)
        n_kt = (ln + kt - 1) // kt
        q = q_ref[b]  # [hq, hd]

        def _tile(i, carry):
            m_prev, l_prev, acc = carry
            slot = jax.lax.rem(t0 + i, 2)

            @pl.when(i + 1 < n_kt)
            def _():
                tile_dma(b, i + 1, 1 - slot, lambda c: c.start())

            @pl.when((i + 1 == n_kt) & (r + 1 < n_live))
            def _():
                tile_dma(live_s[r + 1], 0, 1 - slot, lambda c: c.start())

            tile_dma(b, i, slot, lambda c: c.wait())
            left = ln - i * kt  # the row's keys from this tile's first on

            @pl.when(left < kt)
            def _():
                # the tail of the row's last tile was not fetched (or lies
                # past the length): what the buffer holds there may be NaN
                # bits, and 0 * NaN must not reach the accumulator
                # (a scratch row holds ``pack`` tile rows, all of one key:
                # ``hkv`` is a multiple of it)
                pack = cols // vbuf.shape[1]
                rows = jax.lax.broadcasted_iota(jnp.int32, vbuf.shape[1:], 0)
                vbuf[slot] = jnp.where(rows < left * hkv // pack, vbuf[slot], 0)

            s = jax.lax.dot_general(
                q, tile_of(kbuf, slot), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [hq, cols]
            s = jnp.where(key_s[...] < left, s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # the tile's first key is live under every kv head, so m_new is
            # finite and a masked column's exp is exactly 0
            p = jnp.exp(s - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(dtype), tile_of(vbuf, slot), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [hq, hd]: another head's columns weigh 0
            return m_new, l_new, acc * alpha + pv

        init = (
            jnp.full((hq, 1), NEG_INF, jnp.float32),
            jnp.zeros((hq, 1), jnp.float32),
            jnp.zeros((hq, hd), jnp.float32),
        )
        # dynamic trip count: work (compute AND DMA) follows the row's live
        # pages, not the table's width
        _, l_fin, acc = jax.lax.fori_loop(0, n_kt, _tile, init)
        o_ref[b] = (acc / l_fin).astype(o_ref.dtype)
        return t0 + n_kt

    jax.lax.fori_loop(0, n_live, _row, 0)


def _decode_kernel_packed(
    lens_ref,  # [B] int32 (scalar prefetch, SMEM)
    tables_ref,  # [B, P] int32 (scalar prefetch, SMEM)
    q_ref,  # [1, hq, hkv*hd] VMEM — block-diagonal over packed lanes
    k_hbm,  # [num_blocks, bs, hkv*hd] ANY (packed view of the pool)
    v_hbm,
    o_ref,  # [1, hq, hkv*hd] VMEM — caller slices its head's lanes out
    k_buf,  # [2, bs, hkv*hd] VMEM scratch (double buffer)
    v_buf,
    sem,
    *,
    scale: float,
    bs: int,
    max_pages: int,
):
    b = pl.program_id(0)
    seq_len = lens_ref[b]
    n_pages = jnp.maximum((seq_len + bs - 1) // bs, 1)

    def copy_page(i, slot):
        page = tables_ref[b, i]
        pltpu.make_async_copy(k_hbm.at[page], k_buf.at[slot], sem.at[slot, 0]).start()
        pltpu.make_async_copy(v_hbm.at[page], v_buf.at[slot], sem.at[slot, 1]).start()

    def wait_page(i, slot):
        page = tables_ref[b, i]
        pltpu.make_async_copy(k_hbm.at[page], k_buf.at[slot], sem.at[slot, 0]).wait()
        pltpu.make_async_copy(v_hbm.at[page], v_buf.at[slot], sem.at[slot, 1]).wait()

    copy_page(0, 0)
    qp = q_ref[0]  # [hq, hkv*hd], zeros off the owning head's lanes
    hq = qp.shape[0]

    def body(i, carry):
        m_prev, l_prev, acc = carry
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_pages)
        def _():
            copy_page(i + 1, jax.lax.rem(i + 1, 2))

        wait_page(i, slot)
        kb = k_buf[slot]  # [bs, hkv*hd]
        vb = v_buf[slot]
        # one full-lane dot: block-diagonal q zeroes cross-head lanes, so
        # s[row, t] = q_row . k[t, row's head lanes] exactly
        s = jax.lax.dot_general(
            qp, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [hq, bs]
        pos = i * bs + jax.lax.broadcasted_iota(jnp.int32, (hq, bs), 1)
        s = jnp.where(pos < seq_len, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [hq, hkv*hd] — every head's lanes filled; caller selects
        return m_new, l_new, acc * alpha + pv

    init = (
        jnp.full((qp.shape[0], 1), NEG_INF, jnp.float32),
        jnp.zeros((qp.shape[0], 1), jnp.float32),
        jnp.zeros(qp.shape, jnp.float32),
    )
    _, l_fin, acc = jax.lax.fori_loop(0, n_pages, body, init)
    o_ref[0] = (acc / jnp.maximum(l_fin, 1e-30)).astype(o_ref.dtype)


def _paged_decode_packed(q, cache_k, cache_v, safe_tables, lens, scale):
    b, hq, hd = q.shape
    nb, bs, hkv, _ = cache_k.shape
    p = safe_tables.shape[1]
    g = hq // hkv
    w = hkv * hd
    # block-diagonal q over the packed lanes: row i owns head i//g's slice
    lane = jnp.arange(w)[None, :]
    owner = (jnp.arange(hq) // g)[:, None]
    q_rep = jnp.concatenate([q.reshape(b, hq, hd)] * hkv, axis=-1)  # tile lanes
    qp = jnp.where((lane // hd) == owner, q_rep, 0)
    kernel = functools.partial(
        _decode_kernel_packed, scale=scale, bs=bs, max_pages=p
    )
    out = pl.pallas_call(
        kernel,
        name="paged_decode_packed",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, hq, w), lambda bi, lens, tables: (bi, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, hq, w), lambda bi, lens, tables: (bi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, bs, w), cache_k.dtype),
                pltpu.VMEM((2, bs, w), cache_v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, w), q.dtype),
        interpret=_INTERPRET,
    )(
        lens, safe_tables, qp,
        cache_k.reshape(nb, bs, w), cache_v.reshape(nb, bs, w),
    )
    # select each row's owning-head lanes (outside the kernel: plain jnp)
    out4 = out.reshape(b, hq, hkv, hd)
    idx = (jnp.arange(hq) // g)[None, :, None, None]
    return jnp.take_along_axis(out4, jnp.broadcast_to(idx, (b, hq, 1, hd)), axis=2)[
        :, :, 0
    ]


def paged_attention_decode_kernel(
    q: jnp.ndarray,  # [B, hq, hd]
    cache_k: jnp.ndarray,  # [num_blocks, bs, hkv, hd]
    cache_v: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, P] int32 (-1 padded)
    seq_lens: jnp.ndarray,  # [B] int32, length INCLUDING current token; 0 = no row
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Kernel entry.  A row of length 0 is no row: it costs no DMA and no
    compute and comes back as finite zeros."""
    b, hq, hd = q.shape
    nb, bs, hkv, _ = cache_k.shape
    scale = float(scale) if scale is not None else float(hd) ** -0.5
    lens = seq_lens.astype(jnp.int32)
    # ids clipped into the pool like the dense body's: only pages under
    # ceil(len / bs) are fetched, and those are the sequence's own
    safe_tables = jnp.clip(block_table, 0, nb - 1).astype(jnp.int32)

    if not _INTERPRET and _packed_mode(hd, hkv):
        out = _paged_decode_packed(q, cache_k, cache_v, safe_tables, lens, scale)
        return jnp.where((lens > 0)[:, None, None], out, 0)

    kpt = _tile_pages(bs, hkv, hd, jnp.dtype(cache_k.dtype).itemsize,
                      block_table.shape[1])
    return _paged_decode(q, cache_k, cache_v, safe_tables, lens,
                         scale=scale, kpt=kpt, interpret=_INTERPRET)


# jitted so the layers of one program, which all call it with the same
# shapes, trace and lower the kernel once between them
@functools.partial(jax.jit, static_argnames=("scale", "kpt", "interpret"))
def _paged_decode(q, cache_k, cache_v, safe_tables, lens, *, scale, kpt, interpret):
    b, hq, hd = q.shape
    nb, bs, hkv, _ = cache_k.shape
    dtype = cache_k.dtype
    cols = kpt * bs * hkv
    # a tile of a sub-32-bit pool is fetched into uint32 words (see the
    # kernel) where a word holds rows of one key and a page is whole (8, 128)
    # word tiles; the interpreter cannot write through such a view
    pack = 4 // jnp.dtype(dtype).itemsize
    words = pack > 1 and not interpret and hkv % pack == 0 and (bs * hkv) % (8 * pack) == 0
    tile = pltpu.VMEM((2, cols // pack, hd), jnp.uint32) if words \
        else pltpu.VMEM((2, cols, hd), dtype)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)  # kv pools stay in HBM
    kernel = functools.partial(_decode_kernel, scale=scale, bs=bs, hkv=hkv, kpt=kpt)
    return pl.pallas_call(
        kernel,
        name="paged_decode",
        in_specs=[smem, smem, vmem, hbm, hbm],
        out_specs=vmem,
        out_shape=jax.ShapeDtypeStruct((b, hq, hd), q.dtype),
        scratch_shapes=[
            tile, tile,
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((b,), jnp.int32),
            pltpu.VMEM((hq, cols), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(
        # a page's rows in the pool's own order, (key, kv head): the same bytes
        lens, safe_tables, q,
        cache_k.reshape(nb, bs * hkv, hd), cache_v.reshape(nb, bs * hkv, hd),
    )
