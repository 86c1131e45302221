"""Pallas packed-suffix context-attention kernel (prefill / verify path).

The decode kernel (ops/pallas/paged_attention.py) covered single-token
attention; this module covers the OTHER hot attention path — the
packed-suffix body every chunked prefill, prefix-cache-hit serve, and
speculative-verify forward rides (``inference/paged.py
paged_attention_packed_ctx``).  The jnp dense body gathers **all P pages
per segment** and materializes O(T * P * bs) logits; this kernel streams
exactly the live pages and keeps the working set at one VMEM tile.

TPU design (mirrors the decode kernel, generalized to packed segments):

- grid = (pack_segments, max_ctx_pages) with the per-slot ``ctx_tables``
  row as a **prefetched scalar operand**: each page step's BlockSpec index
  map looks up ``ctx_tables[n, i]`` and routes exactly that segment's page
  from the HBM pool into VMEM — pages the segment doesn't own are never
  touched.
- **length-bounded work**: steps past ``ceil(ctx_len / block_size)`` skip
  all compute (``pl.when``) and their index map repeats the segment's last
  live page, which Pallas's pipeline recognizes and elides the DMA — HBM
  traffic and FLOPs scale with the TRUE cached context, not the table
  width (the dense body's O(T * P * bs) gather).
- **one online-softmax accumulator spanning [cached context | in-pack
  causal segment]**: the fp32 running (m, l, acc) lives in VMEM-resident
  output blocks across the whole grid; the final grid step of each
  segment folds the pack's fresh causal keys into the SAME reduction, so
  a suffix prefill over cached context is numerically the single softmax
  the dense body computes (and the cold ``ctx_len = 0`` pack degenerates
  to plain causal attention).
- **mid-page segment starts**: ``ctx_lens`` need not be page-aligned — a
  verify pack begins at the decode head, so the last context page is row-
  masked at ``pos < ctx_len`` and the pack's own rows enter through the
  in-pack half (the ``write_spec_kv`` layout).
- GQA via the non-head-repeated kv layout: scores batch over the kv-head
  dim (a static python unroll of 2-D/3-D dots per kv head), pages are
  never head-repeated in VMEM.  ``logits_soft_cap`` is FUSED
  (cap * tanh(s / cap) before masking) — unlike the decode kernel, a
  gemma-2 config does not fall back to the dense body here.
- ``partial=True`` returns the un-normalized flash triple
  ``(acc, m, l)`` — the seq-shard region merges S of these with the same
  log-sum-exp ring as decode, and ``include_pack`` (a prefetched scalar)
  charges the pack's fresh keys to seq shard 0 only.

Segment layout contract (the engine's pack builders guarantee it, same
assumption the dense body's buffer-index causality already makes): each
segment's valid rows are one CONTIGUOUS run in the pack, in position
order; ``segment_ids`` is 1-based per slot row with 0 = padding.

The jnp body (inference/paged.py) stays the fallback + ground truth;
``supports()`` gates dispatch exactly like the decode/flash kernels and
``set_interpret`` runs the kernel on CPU for parity tests.  Hardware
requires ``hd % 128 == 0`` (the packed-lane trick the decode kernel uses
for hd < 128 is not built here yet — those shapes fall back); a VMEM
budget guard routes oversized packs (resident q/acc + the pack-logits
tile) back to the dense body rather than overflowing VMEM.
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

# pack-stage key-tile width: the in-pack causal logits are computed in
# [T, g, _BLOCK_PACK] tiles so the pack temporaries stay bounded by the
# tile, not O(T^2) (packs are padded up to a tile multiple)
_BLOCK_PACK = 256

# hardware VMEM budget for the resident blocks (q + pack kv + fp32
# accumulator + page double-buffer + one pack-logits tile); packs whose
# estimate exceeds it fall back to the dense body instead of overflowing
_VMEM_BUDGET = 10 * 1024 * 1024


def set_interpret(value: bool) -> None:
    global _INTERPRET
    _INTERPRET = bool(value)


def _pad_len(t: int) -> int:
    """Pack rows padded to a sublane multiple, and to a whole number of
    pack-stage key tiles once the pack outgrows one tile."""
    if t <= _BLOCK_PACK:
        return -(-t // 8) * 8
    return -(-t // _BLOCK_PACK) * _BLOCK_PACK


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
    t_pad = _pad_len(t)
    isz = jnp.dtype(cache_k.dtype).itemsize
    g = hq // hkv
    est = (
        t_pad * (hq + 2 * hkv) * hd * isz      # resident q + pack k/v
        + 4 * t_pad * hq * (hd + 2)            # fp32 acc + m + l outputs
        + 4 * bs * hkv * hd * isz              # double-buffered page DMA
        + 8 * t_pad * g * min(t_pad, _BLOCK_PACK)  # pack-logits tile (f32 x2)
    )
    return est <= _VMEM_BUDGET


def _ctx_kernel(
    tables_ref,  # [N, P] int32 (scalar prefetch, SMEM) — raw, may be -1/OOR
    lens_ref,    # [N] int32 — cached-context length per segment
    starts_ref,  # [N] int32 — first pack row of the segment
    slens_ref,   # [N] int32 — valid pack rows of the segment
    flags_ref,   # [1] int32 — include_pack (seq-shard charge-to-shard-0)
    q_ref,       # [T_pad, hq, hd] VMEM (resident across the grid)
    kp_ref,      # [T_pad, hkv, hd] VMEM — the pack's fresh keys
    vp_ref,
    kpg_ref,     # [1, bs, hkv, hd] VMEM — this step's context page
    vpg_ref,
    acc_ref,     # [T_pad, hq, hd] f32 out — online weighted-V accumulator
    m_ref,       # [T_pad, hq] f32 out — running max
    l_ref,       # [T_pad, hq] f32 out — running sum-exp
    *,
    scale: float,
    soft_cap: Optional[float],
    bs: int,
    nb: int,
    bkp: int,
):
    n = pl.program_id(0)
    i = pl.program_id(1)
    n_steps = pl.num_programs(1)
    t_pad, hq, hd = q_ref.shape
    hkv = kp_ref.shape[1]
    g = hq // hkv
    ln = lens_ref[n]
    n_pages = (ln + bs - 1) // bs
    start = starts_ref[n]
    slen = slens_ref[n]

    @pl.when((n == 0) & (i == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    rows = jax.lax.broadcasted_iota(jnp.int32, (t_pad, 1), 0)
    # segments write disjoint rows, so one global (m, l, acc) triple serves
    # every segment — each update is masked to this segment's rows
    in_seg = (rows >= start) & (rows < start + slen)  # [T, 1]

    def _capped(s):
        if soft_cap is None:
            return s
        return soft_cap * jnp.tanh(s / soft_cap)

    def _online_update(h, s3, k_ok, vals):
        """Fold one key tile into the running softmax of kv-head ``h``.

        s3 [T_pad, g, K] f32 scores (pre-mask); k_ok broadcastable key
        mask; vals [K, hd] values.  Rows outside the segment keep their
        state (masked write)."""
        hs = slice(h * g, (h + 1) * g)
        m_old = m_ref[:, hs]        # [T, g]
        l_old = l_ref[:, hs]
        a_old = acc_ref[:, hs, :]   # [T, g, hd]
        s3 = jnp.where(k_ok, s3, NEG_INF)
        m_new = jnp.maximum(m_old, jnp.max(s3, axis=-1))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s3 - m_new[..., None])
        # keyless rows' exp(NEG_INF - NEG_INF) = 1 must not pollute l/acc
        p = jnp.where(k_ok, p, 0.0)
        l_new = l_old * alpha + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p.astype(vals.dtype), vals, (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [T, g, hd]
        a_new = a_old * alpha[..., None] + pv
        m_ref[:, hs] = jnp.where(in_seg, m_new, m_old)
        l_ref[:, hs] = jnp.where(in_seg, l_new, l_old)
        acc_ref[:, hs, :] = jnp.where(in_seg[..., None], a_new, a_old)

    # ---- context page step: skipped entirely past ceil(ctx_len / bs) and
    # for pages another seq shard owns (id outside [0, nb)) ----
    page_raw = tables_ref[n, i]
    page_ok = (i < n_pages) & (page_raw >= 0) & (page_raw < nb)

    @pl.when(page_ok)
    def _ctx_page():
        kb = kpg_ref[0]  # [bs, hkv, hd]
        vb = vpg_ref[0]
        pos = i * bs + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bs), 2)
        k_ok = pos < ln  # mid-page tail of the last context page masks off
        for h in range(hkv):
            qh = q_ref[:, h * g:(h + 1) * g, :]  # [T, g, hd]
            s3 = jax.lax.dot_general(
                qh, kb[:, h, :], (((2,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [T, g, bs]
            _online_update(h, _capped(s3), k_ok, vb[:, h, :])

    # ---- in-pack causal stage, fused into the SAME reduction on the
    # segment's last grid step (cold packs with zero context pages land
    # here directly) ----
    include_pack = flags_ref[0] > 0

    @pl.when((i == n_steps - 1) & (slen > 0) & include_pack)
    def _pack():
        n_kt = t_pad // bkp  # static

        def tile(kt, _):
            j0 = kt * bkp
            kj = j0 + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bkp), 2)
            # packed order == position order within a segment, so causality
            # by buffer index + the contiguous segment span is exact
            k_ok = (kj >= start) & (kj < start + slen) \
                & (rows[:, :, None] >= kj)  # [T, 1, bkp]
            kc = kp_ref[pl.ds(j0, bkp), :, :]
            vc = vp_ref[pl.ds(j0, bkp), :, :]
            for h in range(hkv):
                qh = q_ref[:, h * g:(h + 1) * g, :]
                s3 = jax.lax.dot_general(
                    qh, kc[:, h, :], (((2,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale  # [T, g, bkp]
                _online_update(h, _capped(s3), k_ok, vc[:, h, :])
            return 0

        jax.lax.fori_loop(0, n_kt, tile, 0)


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
    nb, bs, hkv, _ = cache_k.shape
    n, p = ctx_tables.shape
    scale = float(scale) if scale is not None else float(hd) ** -0.5
    cap = float(logits_soft_cap) if logits_soft_cap is not None else None
    t_pad = _pad_len(t)
    bkp = min(t_pad, _BLOCK_PACK)
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
    if include_pack is None:
        flags = jnp.ones((1,), jnp.int32)
    else:
        flags = jnp.asarray(include_pack).astype(jnp.int32).reshape(1)

    def page_map(n_, i_, tables, lens, st, sl, fl):
        # live steps route the owned page; elided steps repeat the
        # segment's last live page so the pipeline skips the DMA
        n_pages = (lens[n_] + bs - 1) // bs
        j = jnp.minimum(i_, jnp.maximum(n_pages - 1, 0))
        return jnp.clip(tables[n_, j], 0, nb - 1), 0, 0, 0

    const3 = lambda n_, i_, *s: (0, 0, 0)
    const2 = lambda n_, i_, *s: (0, 0)
    kernel = functools.partial(
        _ctx_kernel, scale=scale, soft_cap=cap, bs=bs, nb=nb, bkp=bkp
    )
    acc, m, l = pl.pallas_call(
        kernel,
        name="packed_ctx",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n, p),
            in_specs=[
                pl.BlockSpec((t_pad, hq, hd), const3),
                pl.BlockSpec((t_pad, hkv, hd), const3),
                pl.BlockSpec((t_pad, hkv, hd), const3),
                pl.BlockSpec((1, bs, hkv, hd), page_map),
                pl.BlockSpec((1, bs, hkv, hd), page_map),
            ],
            out_specs=[
                pl.BlockSpec((t_pad, hq, hd), const3),
                pl.BlockSpec((t_pad, hq), const2),
                pl.BlockSpec((t_pad, hq), const2),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((t_pad, hq, hd), jnp.float32),
            jax.ShapeDtypeStruct((t_pad, hq), jnp.float32),
            jax.ShapeDtypeStruct((t_pad, hq), jnp.float32),
        ],
        interpret=_INTERPRET,
    )(
        ctx_tables.astype(jnp.int32), ctx_lens.astype(jnp.int32),
        starts, slens, flags, q, k, v, cache_k, cache_v,
    )
    if partial:
        return acc[:t], m[:t], l[:t]
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out[:t].astype(q.dtype)
