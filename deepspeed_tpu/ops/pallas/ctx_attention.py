"""Pallas packed-suffix context-attention kernel (prefill / verify path).

The decode kernel (ops/pallas/paged_attention.py) covers single-token
attention; this module covers the OTHER hot attention path — the
packed-suffix body every chunked prefill, prefix-cache-hit serve, and
speculative-verify forward rides (``inference/paged.py
paged_attention_packed_ctx``).  The jnp dense body gathers **all P pages
per segment** and materializes O(T * P * bs) logits; this kernel's work
follows the pack's live (segment rows x cached keys) and nothing else.

Iteration space (one kernel invocation, no grid: the table's page
dimension and its empty slots are not steps):

- **a loop over the N slot rows** in which an empty slot (``slen == 0``)
  costs one scalar compare — no DMA, no compute;
- per live segment, **row tiles** of ``tq`` pack rows from ``start`` to
  ``start + slen`` (``tq * g`` MXU rows a kv head, ``_ROW_TILE_M``); the
  tile's queries are folded once into per-kv-head ``[tq*g, hd]`` slabs and
  its fp32 ``(m, l, acc)`` lives in VMEM scratch until the tile is done,
  then leaves for the outputs once, masked to the segment's own rows
  (verify packs start mid-tile; the last tile is clamped to the pack);
- per row tile, **a loop with a dynamic trip count over the cached
  context**: ``ceil(ctx_pages / K)`` key tiles of K pages (``_KEY_TILE``
  keys), each page fetched from the HBM pool by its table id with
  ``make_async_copy`` into a double-buffered VMEM tile, tile i + 1 in
  flight while tile i is computed.  Ids outside ``[0, nb)`` (another seq
  shard's pages under striping) and pages past ``ceil(ctx_len / bs)`` are
  never fetched and their keys masked; the mid-page tail of the last
  context page is masked at ``pos < ctx_len``;
- then **the pack's own causal keys ride the same reduction**: key tiles
  of ``tq`` pack rows up to and including the row tile's own (a cold
  ``ctx_len = 0`` pack degenerates to plain causal attention).  One
  softmax spans [cached context | in-pack causal segment], so a suffix
  prefill over cached context is numerically the cold full-prompt prefill.

Per key tile the K/V tile goes head-major once (``[K, hkv, hd] -> [hkv,
K, hd]``) and each kv head does two 2-D matmuls, ``[tq*g, hd] x [hd, K]``
and ``[tq*g, K] x [K, hd]``: GQA without repeating kv, the accumulator
touched once per key tile.  ``logits_soft_cap`` is FUSED (cap * tanh(s /
cap) before masking) — unlike the decode kernel, a gemma-2 config does not
fall back to the dense body here.  ``partial=True`` returns the
un-normalized flash triple ``(acc, m, l)`` — the seq-shard region merges S
of these with the same log-sum-exp ring as decode, and ``include_pack``
charges the pack's fresh keys to seq shard 0 only.  Tile shapes come from
the call's shapes (``_tiles``); trip counts from ``ctx_lens``, the segment
spans and the table's ids.

Segment layout contract (the engine's pack builders guarantee it, same
assumption the dense body's buffer-index causality already makes): each
segment's valid rows are one CONTIGUOUS run in the pack, in position
order; ``segment_ids`` is 1-based per slot row with 0 = padding.

The jnp body (inference/paged.py) stays the fallback + ground truth;
``supports()`` gates dispatch exactly like the decode/flash kernels and
``set_interpret`` runs the kernel on CPU for parity tests.  Hardware
requires ``hd % 128 == 0`` (the packed-lane trick the decode kernel uses
for hd < 128 is not built here yet — those shapes fall back).  The kernel
asks Mosaic for ``_VMEM_LIMIT`` and ``supports()`` admits what
``_vmem_estimate`` puts under half of it: T = 256 at hq 32 / hkv 8 / hd
128 bf16 is estimated at 20.8 MiB, T = 512 at 28.0 (taken), T = 1024 at 42.5
(declined).

On the chip (TPU v5e, PR 25; PERF.md §6): one call at the serving benchmark's
shape (T = 256, 64 slots x 128 table pages) takes 0.15 ms in the document
cell and 0.09 ms in the chat cell, 21% and 14% of its roofline, where the
64 x 128-step grid of the kernel it replaces took 11.4 ms whatever the pack
held.  Alone: 0.07 / 0.18 / 0.32 ms over 256 / 2048 / 3712 cached keys, 0.41
ms for eight 32-row segments (each pays a whole row tile).  Mosaic compiles
it in ~6-10 s a shape (58 s before).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

_INTERPRET = False

# context keys walked per loop step (a whole number of pages, fetched as one
# double-buffered VMEM tile) and MXU rows per row tile (tq pack rows x g query
# heads of one kv head); both only set tile shapes, the loops' trip counts
# come from ctx_lens / slens
_KEY_TILE = 512
_ROW_TILE_M = 512

# what ``supports`` admits by ``_vmem_estimate``, and what the kernel asks of
# Mosaic (``vmem_limit_bytes``): twice that, for the temporaries the estimate
# does not see (v5e has 128 MiB of VMEM; the default scoped limit is 16)
_VMEM_BUDGET = 32 * 1024 * 1024
_VMEM_LIMIT = 2 * _VMEM_BUDGET


def set_interpret(value: bool) -> None:
    global _INTERPRET
    _INTERPRET = bool(value)


def _pad_len(t: int) -> int:
    """Pack rows padded to a sublane multiple."""
    return -(-t // 8) * 8


def _tiles(t_pad: int, g: int, bs: int, p: int):
    """(tq, kpt): pack rows per row tile and context pages per key tile,
    from the shapes alone."""
    tq = min(t_pad, max(16, _ROW_TILE_M // g // 16 * 16))
    kpt = max(1, min(p, _KEY_TILE // bs))
    return tq, kpt


def _vmem_estimate(t_pad, hq, hkv, hd, bs, p, isz):
    g = hq // hkv
    tq, kpt = _tiles(t_pad, g, bs, p)
    m, kt = tq * g, max(kpt * bs, tq)
    lanes = lambda n: -(-n // 128) * 128
    return (
        t_pad * (hq + 2 * hkv) * hd * isz       # resident q + pack k/v
        + 4 * t_pad * hq * hd                   # fp32 acc output
        + 2 * 4 * t_pad * lanes(hq)             # m, l outputs (lane-padded)
        + 2 * 2 * kpt * bs * hkv * hd * isz     # double-buffered k/v key tile
        + hkv * m * hd * (isz + 4)              # per-head q rows + fp32 acc tile
        + 2 * 4 * m * lanes(hkv)                # running m, l of the row tile
        + 2 * kt * hkv * hd * isz               # head-major copy of a key tile
        + 4 * 4 * m * kt                        # scores / probabilities (f32)
    )


def fits_vmem(t: int, hq: int, hkv: int, hd: int, bs: int, pages: int, isz: int) -> bool:
    """``supports``' verdict on VMEM from the shapes alone: a pack of ``t`` rows
    at ``hq`` query / ``hkv`` K / V heads of ``hd``, pages of ``bs`` rows of
    ``isz``-byte elements, a context table ``pages`` wide.  For a caller that
    lays its cache out before there is an array to show ``supports``."""
    return _vmem_estimate(_pad_len(t), hq, hkv, hd, bs, pages, isz) <= _VMEM_BUDGET


def supports(q, cache_k, ctx_tables) -> bool:
    """Shape/layout gate for kernel dispatch (soft cap is fused, so unlike
    the decode kernel a ``logits_soft_cap`` config stays on the kernel)."""
    t, hq, hd = q.shape
    nb, bs, hkv, _ = cache_k.shape
    if hq % hkv:
        return False
    if ctx_tables.ndim != 2 or ctx_tables.shape[1] < 1:
        return False
    if _INTERPRET:
        # CPU parity tests: no Mosaic tiling constraint, just a sane lane
        return hd >= 8 and hd % 8 == 0
    if hd % 128:
        return False
    return fits_vmem(t, hq, hkv, hd, bs, ctx_tables.shape[1], jnp.dtype(cache_k.dtype).itemsize)


def _ctx_kernel(
    tables_ref,  # [N, P] int32 SMEM — raw, may be -1 / out of range
    lens_ref,    # [N] int32 — cached-context length per segment
    starts_ref,  # [N] int32 — first pack row of the segment
    slens_ref,   # [N] int32 — valid pack rows of the segment
    flags_ref,   # [1] int32 — include_pack (seq-shard charge-to-shard-0)
    q_ref,       # [T_pad, hq, hd] VMEM
    kp_ref,      # [T_pad, hkv, hd] VMEM — the pack's fresh keys
    vp_ref,
    ck_hbm,      # [nb, bs, hkv, hd] HBM pool
    cv_hbm,
    acc_ref,     # [T_pad, hq, hd] f32 out — weighted-V accumulator
    m_ref,       # [T_pad, hq] f32 out — running max
    l_ref,       # [T_pad, hq] f32 out — running sum-exp
    kbuf,        # [2, kpt, bs, hkv, hd] VMEM — double-buffered key tile
    vbuf,
    sem,         # DMA semaphores [2, 2]
    q2_s,        # [hkv, tq*g, hd] — the row tile's queries, one slab a kv head
    acc_s,       # [hkv, tq*g, hd] f32 — the row tile's accumulator
    m_s,         # [tq*g, hkv] f32 — its running max (kv head in lanes)
    l_s,         # [tq*g, hkv] f32
    *,
    scale: float,
    soft_cap: Optional[float],
    nb: int,
    tq: int,
):
    t_pad, hq, hd = q_ref.shape
    hkv = kp_ref.shape[1]
    g = hq // hkv
    mrows = tq * g
    n_slots, p_max = tables_ref.shape
    _, kpt, bs = kbuf.shape[:3]
    kt = kpt * bs

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    # a key tile's pages past the context (or another shard's) are never
    # fetched: what the buffers hold there is masked out of the scores, but
    # 0 * NaN in the PV product would not be, so they start finite
    kbuf[...] = jnp.zeros_like(kbuf)
    vbuf[...] = jnp.zeros_like(vbuf)
    include_pack = flags_ref[0] > 0

    def _fold(x3):
        """[tq, g, hd] -> [tq*g, hd]; a sublane count the dtype's packing
        does not divide goes through f32."""
        if x3.dtype.itemsize < 4 and g % (4 // x3.dtype.itemsize):
            return x3.astype(jnp.float32).reshape(mrows, hd).astype(x3.dtype)
        return x3.reshape(mrows, hd)

    def _update(h, kh, vh, k_ok):
        """Fold one key tile into the running softmax of kv head ``h``:
        kh/vh [K, hd]; k_ok [1 | tq*g, K] key mask."""
        s = jax.lax.dot_general(
            q2_s[h], kh, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [tq*g, K]
        if soft_cap is not None:
            s = soft_cap * jnp.tanh(s / soft_cap)
        s = jnp.where(k_ok, s, NEG_INF)
        m_old = m_s[:, h:h + 1]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        # keyless rows' exp(NEG_INF - NEG_INF) = 1 must not pollute l/acc
        p = jnp.where(k_ok, jnp.exp(s - m_new), 0.0)
        l_s[:, h:h + 1] = l_s[:, h:h + 1] * alpha \
            + jnp.sum(p, axis=1, keepdims=True)
        m_s[:, h:h + 1] = m_new
        acc_s[h] = acc_s[h] * alpha + jax.lax.dot_general(
            p.astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def _all_heads(k3, v3, k_ok):
        """k3/v3 [K, hkv, hd] as fetched; heads go to the major dim once a
        tile so each head's [K, hd] slab is a plain index."""
        kT = k3.transpose(1, 0, 2)
        vT = v3.transpose(1, 0, 2)
        for h in range(hkv):
            _update(h, kT[h], vT[h], k_ok)

    def _segment(n, _):
        slen = slens_ref[n]

        @pl.when(slen > 0)
        def _live():
            start = starts_ref[n]
            seg_end = start + slen
            ln = lens_ref[n]
            n_pages = jnp.minimum((ln + bs - 1) // bs, p_max)
            n_kt = (n_pages + kpt - 1) // kpt

            def page_of(i, j):
                """(pool id, live) of page j of context tile i; ids outside
                [0, nb) belong to another seq shard and are never fetched."""
                pg = i * kpt + j
                pid = tables_ref[n, jnp.minimum(pg, p_max - 1)]
                return pid, (pg < n_pages) & (pid >= 0) & (pid < nb)

            def tile_dma(i, slot, fn):
                for j in range(kpt):
                    pid, ok = page_of(i, j)

                    @pl.when(ok)
                    def _():
                        fn(pltpu.make_async_copy(
                            ck_hbm.at[pid], kbuf.at[slot, j], sem.at[slot, 0]))
                        fn(pltpu.make_async_copy(
                            cv_hbm.at[pid], vbuf.at[slot, j], sem.at[slot, 1]))

            def _row_tile(r, _):
                row0 = start + r * tq                 # the tile's own rows are
                row1 = jnp.minimum(row0 + tq, seg_end)  # [row0, row1)
                base = jnp.minimum(row0, t_pad - tq)  # loaded from base
                for h in range(hkv):
                    q2_s[h] = _fold(q_ref[pl.ds(base, tq), h * g:(h + 1) * g, :])
                acc_s[...] = jnp.zeros_like(acc_s)
                m_s[...] = jnp.full_like(m_s, NEG_INF)
                l_s[...] = jnp.zeros_like(l_s)

                # ---- cached context: n_kt tiles of kpt pages, tile i + 1 in
                # flight while tile i is computed ----
                @pl.when(n_kt > 0)
                def _():
                    tile_dma(0, 0, lambda c: c.start())

                def _ctx_tile(i, _):
                    slot = jax.lax.rem(i, 2)

                    @pl.when(i + 1 < n_kt)
                    def _():
                        tile_dma(i + 1, 1 - slot, lambda c: c.start())

                    tile_dma(i, slot, lambda c: c.wait())
                    pos = i * kt + jax.lax.broadcasted_iota(jnp.int32, (1, kt), 1)
                    k_ok = jnp.zeros((1, kt), jnp.bool_)
                    any_ok = False
                    for j in range(kpt):
                        _, ok = page_of(i, j)
                        k_ok |= ok & (pos >= (i * kpt + j) * bs) \
                            & (pos < (i * kpt + j + 1) * bs)
                        any_ok |= ok
                    k_ok &= pos < ln  # mid-page tail of the last context page

                    @pl.when(any_ok)
                    def _():
                        _all_heads(kbuf[slot].reshape(kt, hkv, hd),
                                   vbuf[slot].reshape(kt, hkv, hd), k_ok)
                    return 0

                jax.lax.fori_loop(0, n_kt, _ctx_tile, 0)

                # ---- the pack's own causal keys, same reduction: key tile c
                # holds rows [start + c*tq, +tq) of the segment, c <= r ----
                rid = base + jax.lax.broadcasted_iota(
                    jnp.int32, (mrows, 1), 0) // g

                def _pack_tile(c, _):
                    k0 = start + c * tq
                    kbase = jnp.minimum(k0, t_pad - tq)
                    kj = kbase + jax.lax.broadcasted_iota(jnp.int32, (1, tq), 1)
                    # packed order == position order within a segment, so
                    # causality by buffer index + the contiguous span is exact
                    k_ok = (kj >= k0) & (kj < jnp.minimum(k0 + tq, seg_end)) \
                        & (kj <= rid)
                    _all_heads(kp_ref[pl.ds(kbase, tq)], vp_ref[pl.ds(kbase, tq)],
                               k_ok)
                    return 0

                jax.lax.fori_loop(
                    0, jnp.where(include_pack, r + 1, 0), _pack_tile, 0)

                # ---- the tile's rows leave for the outputs once ----
                rows = base + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
                mine = (rows >= row0) & (rows < row1)  # [tq, 1]
                for h in range(hkv):
                    hs = slice(h * g, (h + 1) * g)
                    a3 = acc_s[h].reshape(tq, g, hd)
                    old = acc_ref[pl.ds(base, tq), hs, :]
                    acc_ref[pl.ds(base, tq), hs, :] = jnp.where(
                        mine[..., None], a3, old)
                    for src, dst in ((m_s, m_ref), (l_s, l_ref)):
                        # [tq*g, 1] -> [tq, g]: through the lanes of a
                        # [tq, g, hd] broadcast, the shape the reshape above
                        # already takes
                        x = jnp.broadcast_to(src[:, h:h + 1], (mrows, hd))
                        x = jnp.max(x.reshape(tq, g, hd), axis=-1)
                        dst[pl.ds(base, tq), hs] = jnp.where(
                            mine, x, dst[pl.ds(base, tq), hs])
                return 0

            jax.lax.fori_loop(0, (slen + tq - 1) // tq, _row_tile, 0)

        return 0

    jax.lax.fori_loop(0, n_slots, _segment, 0)


def paged_attention_packed_ctx_kernel(
    q: jnp.ndarray,        # [T, hq, hd] — packed suffix tokens
    k: jnp.ndarray,        # [T, hkv, hd] — the pack's fresh keys
    v: jnp.ndarray,
    segment_ids: jnp.ndarray,  # [T] int32, slot + 1, 0 = padding
    cache_k: jnp.ndarray,  # [num_blocks, bs, hkv, hd]
    cache_v: jnp.ndarray,
    ctx_tables: jnp.ndarray,  # [N, P] int32 (-1 padded / OOR under striping)
    ctx_lens: jnp.ndarray,    # [N] int32 — cached-context length per slot
    scale: Optional[float] = None,
    logits_soft_cap: Optional[float] = None,
    include_pack=None,     # traced bool; None = True (single-shard caller)
    partial: bool = False,
):
    """Kernel entry.  ``partial=False`` returns the normalized [T, hq, hd]
    output (pad rows — ``segment_ids == 0`` — come back exactly 0);
    ``partial=True`` returns the fp32 flash triple ``(acc, m, l)`` for the
    seq-shard log-sum-exp ring merge."""
    t, hq, hd = q.shape
    bs, hkv = cache_k.shape[1:3]
    tq, kpt = _tiles(_pad_len(t), hq // hkv, bs, ctx_tables.shape[1])
    if include_pack is None:
        include_pack = True
    return _packed_ctx(
        q, k, v, segment_ids, cache_k, cache_v, ctx_tables, ctx_lens,
        jnp.asarray(include_pack),
        scale=float(scale) if scale is not None else float(hd) ** -0.5,
        cap=float(logits_soft_cap) if logits_soft_cap is not None else None,
        partial=bool(partial), tq=tq, kpt=kpt, interpret=_INTERPRET,
    )


# jitted so the layers of one program, which all call it with the same
# shapes, trace and lower the kernel once between them
@functools.partial(jax.jit, static_argnames=(
    "scale", "cap", "partial", "tq", "kpt", "interpret"))
def _packed_ctx(q, k, v, segment_ids, cache_k, cache_v, ctx_tables, ctx_lens,
                include_pack, *, scale, cap, partial, tq, kpt, interpret):
    t, hq, hd = q.shape
    nb, bs, hkv, _ = cache_k.shape
    n = ctx_tables.shape[0]
    t_pad = _pad_len(t)
    if t_pad != t:
        zpad = ((0, t_pad - t), (0, 0), (0, 0))
        q, k, v = jnp.pad(q, zpad), jnp.pad(k, zpad), jnp.pad(v, zpad)

    # contiguous segment spans from the 1-based ids (empty segment: len 0,
    # start parked at t so its row/key ranges are empty)
    ids = segment_ids.astype(jnp.int32)
    onehot = ids[None, :] == (jnp.arange(n, dtype=jnp.int32) + 1)[:, None]
    slens = jnp.sum(onehot, axis=1).astype(jnp.int32)
    ar = jnp.arange(t, dtype=jnp.int32)
    starts = jnp.min(jnp.where(onehot, ar[None, :], t), axis=1).astype(jnp.int32)
    flags = include_pack.astype(jnp.int32).reshape(1)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)  # kv pools stay in HBM
    mrows = tq * (hq // hkv)
    kernel = functools.partial(
        _ctx_kernel, scale=scale, soft_cap=cap, nb=nb, tq=tq)
    acc, m, l = pl.pallas_call(
        kernel,
        name="packed_ctx",
        in_specs=[smem] * 5 + [vmem] * 3 + [hbm] * 2,
        out_specs=[vmem] * 3,
        out_shape=[
            jax.ShapeDtypeStruct((t_pad, hq, hd), jnp.float32),
            jax.ShapeDtypeStruct((t_pad, hq), jnp.float32),
            jax.ShapeDtypeStruct((t_pad, hq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, kpt, bs, hkv, hd), cache_k.dtype),
            pltpu.VMEM((2, kpt, bs, hkv, hd), cache_v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((hkv, mrows, hd), q.dtype),
            pltpu.VMEM((hkv, mrows, hd), jnp.float32),
            pltpu.VMEM((mrows, hkv), jnp.float32),
            pltpu.VMEM((mrows, hkv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(
        ctx_tables.astype(jnp.int32), ctx_lens.astype(jnp.int32),
        starts, slens, flags, q, k, v, cache_k, cache_v,
    )
    if partial:
        return acc[:t], m[:t], l[:t]
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out[:t].astype(q.dtype)
