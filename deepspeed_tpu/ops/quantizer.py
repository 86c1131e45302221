"""Quantization ops: symmetric int8 and fp8 with per-group scales.

Public API over the Pallas kernels (``ops/pallas/quant_kernel.py``) with a
jnp reference path for odd shapes / CPU; the counterpart of the reference's
``deepspeed/ops/quantizer`` + ``ops/fp_quantizer`` front-ends over
``csrc/quantization`` and ``csrc/fp_quantizer``.

All functions operate on arbitrary-shape arrays; quantization groups are
rows of the ``[-1, group_size]`` flattening (group_size defaults to the
trailing dimension), matching the reference's contiguous-group scheme
(quantize.cu processes ``elems_per_group`` runs).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..comm import qcomm
from .pallas import quant_kernel, quant_matmul as quant_mm_kernel


class QuantizedTensor(NamedTuple):
    data: jnp.ndarray  # int8 or fp8, original shape
    scales: jnp.ndarray  # fp32 [groups]
    group_size: int
    orig_dtype: jnp.dtype


def _grouped(x: jnp.ndarray, group_size: Optional[int]) -> Tuple[jnp.ndarray, int]:
    n = x.size
    gs = group_size or (x.shape[-1] if x.ndim else n)
    if n % gs:
        # Degenerate fallback: one scale for the whole tensor. Loudly coarser
        # than the caller asked for — warn instead of silently ignoring it.
        from ..utils.logging import warning_once

        warning_once(
            f"quantizer: tensor size {n} not divisible by group_size {gs}; "
            "falling back to a SINGLE quantization group for the whole tensor"
        )
        gs = n
    return x.reshape(n // gs, gs), gs


def _use_pallas(x2d) -> bool:
    return (
        jax.default_backend() == "tpu" and quant_kernel.supports(x2d)
    ) or quant_kernel._INTERPRET


def quantize_int8(x: jnp.ndarray, group_size: Optional[int] = None) -> QuantizedTensor:
    """Symmetric int8: q = round(x / s), s = amax/127 per group."""
    orig_dtype = x.dtype
    x2d, gs = _grouped(x, group_size)
    if _use_pallas(x2d):
        q, s = quant_kernel.quantize_int8(x2d)
    else:
        xf = x2d.astype(jnp.float32)
        amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
        s = (jnp.maximum(amax, 1e-12) / 127.0)[..., 0]
        q = jnp.clip(jnp.round(xf / s[..., None]), -127, 127).astype(jnp.int8)
    return QuantizedTensor(q.reshape(x.shape), s, gs, orig_dtype)


def dequantize(qt: QuantizedTensor, dtype=None) -> jnp.ndarray:
    dtype = dtype or qt.orig_dtype
    q2d = qt.data.reshape(-1, qt.group_size)
    if qt.data.dtype == jnp.int8 and _use_pallas(q2d):
        out = quant_kernel.dequantize_int8(q2d, qt.scales, out_dtype=dtype)
    else:
        out = (q2d.astype(jnp.float32) * qt.scales[..., None]).astype(dtype)
    return out.reshape(qt.data.shape)


def quantize_fp8(
    x: jnp.ndarray, dtype=jnp.float8_e4m3fn, group_size: Optional[int] = None
) -> QuantizedTensor:
    """Scaled fp8 cast (e4m3 default; e5m2 for gradients à la fp_quantizer)."""
    orig_dtype = x.dtype
    x2d, gs = _grouped(x, group_size)
    if _use_pallas(x2d):
        q, s = quant_kernel.quantize_fp8(x2d, dtype=dtype)
    else:
        xf = x2d.astype(jnp.float32)
        amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
        s = (jnp.maximum(amax, 1e-12) / float(jnp.finfo(dtype).max))[..., 0]
        q = (xf / s[..., None]).astype(dtype)
    return QuantizedTensor(q.reshape(x.shape), s, gs, orig_dtype)


def fake_quantize_int8(x: jnp.ndarray, group_size: Optional[int] = None) -> jnp.ndarray:
    """quantize→dequantize in one call (the reference's fake_quantizer.cu,
    used by compression's QAT path)."""
    return dequantize(quantize_int8(x, group_size))


# ---------------------------------------------------------------------------
# quantized-weight serving (reference csrc/fp_quantizer + inference/v2
# cuda_linear FP6/quantized GEMMs; blogs/deepspeed-fp6)
# ---------------------------------------------------------------------------
class ServingQuant(NamedTuple):
    """A kernel ``[..., in, out]`` stored compressed for serving: ``q`` in
    int8 / fp8 with ONE fp32 scale per output channel.  Per-output-channel
    scaling makes the dequant exact as a POST-matmul multiply —
    ``(x @ q) * s`` — so the matmul reads the compressed bytes (half the
    HBM traffic of bf16, the resource decode is bound by) and the scale
    rides the output, never a materialized bf16 weight copy."""

    q: jnp.ndarray  # int8 or float8_e4m3fn, same shape as the original
    s: jnp.ndarray  # fp32 [out]


def quantize_serving_weight(w: jnp.ndarray, fmt: str = "int8") -> ServingQuant:
    """Per-output-channel symmetric compression of a ``[..., in, out]``
    kernel (``fmt``: 'int8' | 'fp8').  Only the contraction dim (``in``,
    axis -2) folds into each scale: stacked-layer kernels ``[L, in, out]``
    get independent ``[L, out]`` scales that slice with the layer."""
    xf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=w.ndim - 2)  # [..., out]
    if fmt == "int8":
        s = jnp.maximum(amax, 1e-12) / 127.0
        q = jnp.clip(jnp.round(xf / s[..., None, :]), -127, 127).astype(jnp.int8)
    elif fmt == "fp8":
        fmax = float(jnp.finfo(jnp.float8_e4m3fn).max)
        s = jnp.maximum(amax, 1e-12) / fmax
        q = (xf / s[..., None, :]).astype(jnp.float8_e4m3fn)
    else:
        raise ValueError(f"quantize_weights format {fmt!r} (int8|fp8)")
    return ServingQuant(q=q, s=s.astype(jnp.float32))


# Serving-matmul policy.  The fused-kernel decision used to be a process-
# global ``set_fused_serving`` switch (a TP engine pinned EVERY later engine
# in the process to the jnp body); it is now per-call state carried by a
# :class:`ServingContext` the engine threads through ``serving_mm``.
class ServingContext(NamedTuple):
    """Per-engine serving-matmul policy, threaded through ``serving_mm``.

    ``mesh``/``axis``/``size`` describe the tensor-parallel model axis (the
    ``model`` axis of ``parallel.topology``); ``size <= 1`` or ``mesh is
    None`` means single-chip dispatch.  ``kv_cols``: whether the kv
    projections' out-features may shard on the model axis (requires
    ``num_kv_heads % size == 0`` — sub-head sharding is never produced; the
    model runner passes ``kind='rep'`` for wk/wv otherwise).  ``fused``:
    tri-state kernel gate — None = auto (fused kernel whenever the local
    shapes qualify), False = jnp bodies everywhere (how tests reach the
    reference), True = same as auto (the kernel still refuses unsupported
    shapes).

    ``comm_fmt``/``comm_tiles``: the row-parallel partial-sum TRANSPORT
    policy (comm/qcomm.py).  ``comm_fmt`` 'none' (default) keeps the exact
    ``lax.psum`` — bit-identical to pre-qcomm serving; 'int8'/'fp8' ship
    the [B, hidden] partials as quantized payload + per-chunk fp32 scales
    (EQuARX reduce-scatter → re-quantize → all-gather, fp32 carry
    accumulation — lossy, see README for where exactness holds).
    ``comm_tiles`` > 1 decomposes each row-parallel matmul output into
    that many free-dim tiles, each reduced independently so tile i's
    collective overlaps tile i+1's compute in the schedule (T3-style) —
    volume-neutral, composes with either format."""

    mesh: object = None
    axis: str = "model"  # parallel.topology.MODEL_AXIS
    size: int = 1
    kv_cols: bool = True
    fused: Optional[bool] = None
    comm_fmt: str = "none"
    comm_tiles: int = 1

    @property
    def tp(self) -> bool:
        return self.mesh is not None and self.size > 1


def _mm_local(x2d, w, bias, fused: Optional[bool]):
    """Single-device dispatch: fused Pallas kernel on qualifying shapes
    (unless ``fused is False``), else the jnp reference body — exactly the
    math ``serving_mm`` has always computed."""
    if isinstance(w, ServingQuant):
        if fused is not False and quant_mm_kernel.supports_int8(x2d, w.q):
            return quant_mm_kernel.quant_matmul(x2d, w.q, w.s, bias=bias)
        y = x2d @ w.q.astype(x2d.dtype)
        y = (y * w.s.astype(jnp.float32)).astype(x2d.dtype)
        return y if bias is None else y + bias
    if (
        fused is not False
        and w.row_shards == 1
        and quant_mm_kernel.supports_fp6(x2d, w.packed, w.in_dim)
    ):
        return quant_mm_kernel.quant_matmul_fp6(
            x2d, w.packed, w.s, w.in_dim, bias=bias
        )
    codes = _fp6_unpack(w.packed, w.in_dim, w.row_shards)
    y = x2d @ _fp6_decode(codes, x2d.dtype)
    y = (y * w.s.astype(jnp.float32)).astype(x2d.dtype)
    return y if bias is None else y + bias


def _shard_kind(w, kind: str, ctx: ServingContext) -> str:
    """Downgrade ``kind`` to 'rep' (replicated-compute region) when the
    requested partition does not divide — the same divisibility conditions
    ``auto_tp.infer_tp_rules`` applies, so the region specs always match
    the GSPMD placement of the weight and no weight collective is ever
    inserted at the region boundary."""
    if isinstance(w, ServingQuant):
        k_dim, n_dim = w.q.shape[-2], w.q.shape[-1]
        packed_ok = True
    else:
        k_dim, n_dim = w.in_dim, w.packed.shape[-1]
        # the quarter-strided FP6 pack is only row-splittable when it was
        # packed per K-chunk for exactly this many shards (engine passes
        # row_parallel_shards=tp at quantize time)
        packed_ok = w.row_shards == ctx.size and w.packed.shape[-2] % ctx.size == 0
    if kind == "col" and n_dim % ctx.size:
        return "rep"
    if kind == "row" and (k_dim % ctx.size or not packed_ok):
        return "rep"
    return kind


def _shard_mm(x2d, w, bias, kind: str, ctx: ServingContext):
    """One fused matmul as a manual ``shard_map`` region over the model
    axis (the same fully-manual pattern backing the paged-attention TP
    path — a ``pallas_call`` has no GSPMD partitioning rule, so the
    partitioner would gather the full weight per shard; the manual region
    keeps the compressed bytes sharded and runs the kernel per shard).

    - ``col`` (qkv / up / gate / head): weight, per-output-channel scales
      and bias all sharded on out-features; x replicated.  No collective —
      the output stays sharded on its last dim.
    - ``row`` (o / down): in-features sharded, fused kernel per shard on
      its K-slice, one ``psum`` over the partial products.  The scale is a
      per-OUT-channel multiplier, so applying it in each shard's epilogue
      commutes with the reduction; ``bias`` is added once post-reduce by
      the caller (``serving_mm``), never per shard.
    - ``rep``: replicated compute (kv projections when ``num_kv_heads``
      does not divide the axis; indivisible dims) — still a manual region
      so the kernel never meets the GSPMD partitioner.
    """
    from jax.sharding import PartitionSpec as P

    from ..parallel.sharding import shard_map_compat

    ax = ctx.axis
    fused = ctx.fused
    is_fp6 = isinstance(w, ServingQuantFP6)
    if is_fp6:
        w_leaves = (w.packed, w.s)
        rebuild = lambda p, s, in_dim, shards: ServingQuantFP6(p, s, in_dim, shards)
        w_specs = {
            "col": (P(None, None, ax), P(ax)),
            "row": (P(None, ax, None), P(None)),
            "rep": (P(None, None, None), P(None)),
        }[kind]
    else:
        w_leaves = (w.q, w.s)
        w_specs = {
            "col": (P(None, ax), P(ax)),
            "row": (P(ax, None), P(None)),
            "rep": (P(None, None), P(None)),
        }[kind]
    x_spec = P(None, ax) if kind == "row" else P(None, None)
    out_spec = P(None, ax) if kind == "col" else P(None, None)
    # col/rep fuse the (sharded/replicated) bias into the local epilogue;
    # row adds it once post-psum in the caller
    fuse_bias = bias is not None and kind != "row"
    n_sh = ctx.size

    def _slice_out(local_w, lo, hi):
        """View of the local kernel restricted to out-channels [lo, hi) —
        both formats keep out-features as the TRAILING dim, so the slice is
        contiguous and the per-out-channel scales slice with it."""
        if is_fp6:
            return rebuild(
                local_w.packed[..., lo:hi], local_w.s[..., lo:hi],
                local_w.in_dim, local_w.row_shards,
            )
        return ServingQuant(local_w.q[..., lo:hi], local_w.s[..., lo:hi])

    def body(xl, wl, sl, *rest):
        bl = rest[0] if rest else None
        if is_fp6:
            local_in = w.in_dim // n_sh if kind == "row" else w.in_dim
            # a per-chunk pack sliced to one chunk IS a standard pack
            local_w = rebuild(wl, sl, local_in, 1)
        else:
            local_w = ServingQuant(wl, sl)
        tiles = max(int(ctx.comm_tiles), 1) if kind == "row" else 1
        n_out = (local_w.packed if is_fp6 else local_w.q).shape[-1]
        if tiles > 1 and n_out >= tiles:
            # T3-style fine-grained overlap: the local GEMM decomposes into
            # free-dim (out-channel) tiles, each a SEPARATE matmul whose
            # partial sums reduce independently — tile i's transport has no
            # data dependence on tile i+1's matmul, so the scheduler can
            # run them concurrently (asserted on scheduled HLO in
            # tests/test_overlap_hlo.py).  Tiling the free dim keeps total
            # wire volume at exactly one [B, N] payload; tiling the
            # contraction dim instead would ship a full-width partial per
            # tile.  Volume-neutral, composes with the quantized transport.
            tile_n = -(-n_out // tiles)
            outs = []
            for i in range(tiles):
                lo = i * tile_n
                hi = min(lo + tile_n, n_out)
                if lo >= hi:
                    break
                y_i = _mm_local(xl, _slice_out(local_w, lo, hi), None, fused)
                # per-tile transport through qcomm (tiles=1: THIS loop is
                # the tiling) — exact lax.psum in passthrough, quantized
                # EQuARX all-reduce otherwise; routing the passthrough
                # through qcomm too keeps the fmt='none' A/B lever and the
                # auditor's source-based transport attribution universal
                outs.append(qcomm.q_psum_tiled(
                    y_i, ax, ctx.comm_fmt, tiles=1, world=n_sh,
                    out_dtype=y_i.dtype,
                ))
            return jnp.concatenate(outs, axis=-1)
        y = _mm_local(xl, local_w, bl, fused)
        if kind == "row":
            # partial-sum transport (comm/qcomm.py): exact lax.psum in
            # passthrough, quantized EQuARX all-reduce in int8/fp8
            y = qcomm.q_psum_tiled(
                y, ax, ctx.comm_fmt, tiles=1, world=n_sh,
                out_dtype=y.dtype,
            )
        return y

    in_specs = (x_spec,) + w_specs
    operands = (x2d,) + w_leaves
    if fuse_bias:
        in_specs += (P(ax) if kind == "col" else P(None),)
        operands += (bias,)
    y = shard_map_compat(
        body, ctx.mesh, in_specs=in_specs, out_specs=out_spec
    )(*operands)
    if bias is not None and not fuse_bias:
        y = y + bias
    return y


def serving_mm(
    x: jnp.ndarray,
    w,
    bias: Optional[jnp.ndarray] = None,
    kind: str = "col",
    ctx: Optional[ServingContext] = None,
) -> jnp.ndarray:
    """``x @ w (+ bias)`` where ``w`` may be a :class:`ServingQuant`
    (int8/fp8) or :class:`ServingQuantFP6`.

    On TPU (or under the Pallas interpreter) qualifying shapes route
    through the fused dequant-matmul kernels (``ops/pallas/quant_matmul``):
    the compressed bytes are the ONLY weight HBM traffic, decode happens in
    the kernel's operand-load stage, and the per-output-channel scale (and
    ``bias``) fuse into the fp32 epilogue.  Elsewhere the jnp body runs —
    same math, XLA-fused, bit-stable with the pre-kernel path.

    ``ctx`` (:class:`ServingContext`) carries the per-engine policy: with
    an active TP mesh the call runs inside a manual shard_map region over
    the model axis — ``kind`` 'col' (out-features sharded, no collective),
    'row' (in-features sharded + one psum), or 'rep' (replicated compute)
    — so multi-chip serving keeps in-kernel dequantization instead of the
    old process-global ``set_fused_serving(False)`` pin.  Unquantized ``w``
    ignores ``kind``/mesh and stays on the GSPMD path."""
    if isinstance(w, (ServingQuant, ServingQuantFP6)):
        fused = ctx.fused if ctx is not None else None
        if ctx is not None and ctx.tp:
            lead = x.shape[:-1]
            x2d = x.reshape(-1, x.shape[-1])
            y = _shard_mm(x2d, w, bias, _shard_kind(w, kind, ctx), ctx)
            return y.reshape(*lead, y.shape[-1])
        return _mm_local(x, w, bias, fused)
    y = x @ w
    return y if bias is None else y + bias


class ServingQuantFP6:
    """FP6 (e2m3) serving weight: four 6-bit codes bit-packed into three
    uint8 byte PLANES ``[..., 3, in/4, out]`` + one fp32 scale per output
    channel — 0.75 bytes/weight, the reference's TC-FPx format class
    (``csrc/fp_quantizer``, blogs/deepspeed-fp6).  The pack is
    QUARTER-STRIDED: packed row ``r`` carries the codes of weight rows
    ``(r, K/4+r, K/2+r, 3K/4+r)``, so the fused Pallas kernel
    (``ops/pallas/quant_matmul.py``) decodes each quarter with pure
    elementwise bit arithmetic and contracts it against the matching
    ``x[:, i*K/4:(i+1)*K/4]`` slice — no row interleave, no strided loads.
    Decode is pure vector arithmetic (no codebook gather): sign/exp/
    mantissa fields reassemble in the compute dtype inside the matmul.

    ``row_shards > 1`` (tensor-parallel row-parallel layers — o/down
    projections): the quarter-stride is applied independently within each
    of ``row_shards`` contiguous K-chunks, laid out chunk-after-chunk along
    the packed dim.  Sharding the packed planes on that dim then hands each
    model shard a standalone valid pack of its contiguous K-slice — the
    contiguous slice is exactly what the row-parallel activation sharding
    produces, which the GLOBAL quarter-stride would not match (its quarters
    interleave rows from all shards)."""

    def __init__(self, packed, s, in_dim: int, row_shards: int = 1):
        self.packed = packed  # [..., 3, in/4, out] uint8 byte planes
        self.s = s  # [..., out] fp32
        self.in_dim = int(in_dim)
        self.row_shards = int(row_shards)

    def tree_flatten(self):
        return (self.packed, self.s), (self.in_dim, self.row_shards)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)


jax.tree_util.register_pytree_node(
    ServingQuantFP6,
    lambda x: x.tree_flatten(),
    ServingQuantFP6.tree_unflatten,
)

_FP6_MAX = 7.5  # e2m3: (1 + 7/8) * 2^2


def _fp6_encode(x: jnp.ndarray) -> jnp.ndarray:
    """|x| <= 7.5 (pre-scaled) -> 6-bit e2m3 codes (uint8, low 6 bits)."""
    sign = (x < 0).astype(jnp.uint8)
    a = jnp.clip(jnp.abs(x), 0.0, _FP6_MAX)
    # normal range needs e_real in [0, 2]; below 1.0 is subnormal (e=0)
    e_real = jnp.clip(jnp.floor(jnp.log2(jnp.maximum(a, 1e-12))), 0.0, 2.0)
    sub = a < 1.0
    m = jnp.where(sub, jnp.round(a * 8.0), jnp.round((a / 2.0**e_real - 1.0) * 8.0))
    e = jnp.where(sub, 0.0, e_real + 1.0)
    # mantissa carry: m == 8 rolls into the next exponent
    carry = m >= 8.0
    m = jnp.where(carry, 0.0, m)
    e = jnp.where(carry, e + 1.0, e)
    over = e > 3.0
    e = jnp.where(over, 3.0, e)
    m = jnp.where(over, 7.0, m)
    return (
        (sign << 5)
        | (e.astype(jnp.uint8) << 3)
        | m.astype(jnp.uint8)
    )


def _fp6_decode(code: jnp.ndarray, dtype) -> jnp.ndarray:
    s = (code >> 5) & 1
    e = ((code >> 3) & 3).astype(jnp.float32)
    m = (code & 7).astype(jnp.float32)
    mag = jnp.where(e == 0, m / 8.0, (1.0 + m / 8.0) * (2.0 ** (e - 1.0)))
    return (jnp.where(s == 1, -mag, mag)).astype(dtype)


def _fp6_pack(codes: jnp.ndarray, row_shards: int = 1) -> jnp.ndarray:
    """[..., in, out] 6-bit codes -> [..., 3, in/4, out] byte planes
    (in % 4 == 0), quarter-strided: packed row ``r`` holds the codes of
    rows ``(r, K/4+r, K/2+r, 3K/4+r)`` so the fused kernel's unpack needs
    no row interleave (see :class:`ServingQuantFP6`).  ``row_shards > 1``
    quarter-strides each contiguous K-chunk independently and concatenates
    the chunk packs along the packed dim (the TP row-parallel layout)."""
    if row_shards > 1:
        *lead, n, out = codes.shape
        chunked = _fp6_pack(codes.reshape(*lead, row_shards, n // row_shards, out))
        # [..., R, 3, n/(4R), out] -> [..., 3, R, n/(4R), out] -> [..., 3, n/4, out]
        chunked = jnp.moveaxis(chunked, -4, -3)
        return chunked.reshape(*lead, 3, n // 4, out)
    *lead, n, out = codes.shape
    c = codes.reshape(*lead, 4, n // 4, out)
    c0, c1, c2, c3 = c[..., 0, :, :], c[..., 1, :, :], c[..., 2, :, :], c[..., 3, :, :]
    b0 = (c0 << 2) | (c1 >> 4)
    b1 = ((c1 & 0xF) << 4) | (c2 >> 2)
    b2 = ((c2 & 0x3) << 6) | c3
    return jnp.stack([b0, b1, b2], axis=-3)


def _fp6_unpack(packed: jnp.ndarray, in_dim: int, row_shards: int = 1) -> jnp.ndarray:
    if row_shards > 1:
        *lead, _, k4, out = packed.shape
        chunked = packed.reshape(*lead, 3, row_shards, k4 // row_shards, out)
        chunked = jnp.moveaxis(chunked, -3, -4)  # [..., R, 3, k4/R, out]
        codes = _fp6_unpack(chunked, in_dim // row_shards)  # [..., R, in/R, out]
        return codes.reshape(*lead, in_dim, out)
    b0, b1, b2 = packed[..., 0, :, :], packed[..., 1, :, :], packed[..., 2, :, :]
    c0 = b0 >> 2
    c1 = ((b0 & 0x3) << 4) | (b1 >> 4)
    c2 = ((b1 & 0xF) << 2) | (b2 >> 6)
    c3 = b2 & 0x3F
    # quarters concatenate back in row order (quarter-strided pack)
    return jnp.concatenate([c0, c1, c2, c3], axis=-2)


def quantize_serving_weight_fp6(
    w: jnp.ndarray, row_shards: int = 1
) -> ServingQuantFP6:
    """Per-output-channel FP6 compression of a ``[..., in, out]`` kernel
    (in % 4 == 0).  ``row_shards``: pack per contiguous K-chunk for TP
    row-parallel sharding (requires in % (4 * row_shards) == 0)."""
    if w.shape[-2] % (4 * row_shards):
        raise ValueError(
            f"fp6 packing needs in-dim % {4 * row_shards} == 0 "
            f"(row_shards={row_shards}), got {w.shape}"
        )
    xf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=w.ndim - 2)  # [..., out]
    s = jnp.maximum(amax, 1e-12) / _FP6_MAX
    codes = _fp6_encode(xf / s[..., None, :])
    return ServingQuantFP6(
        _fp6_pack(codes, row_shards), s.astype(jnp.float32), w.shape[-2],
        row_shards,
    )


_SERVING_QUANT_PATHS = (
    "attn/wq", "attn/wk", "attn/wv", "attn/wo",
    "mlp/w_up", "mlp/w_gate", "mlp/w_down",
    "lm_head/kernel",
)
# row-parallel under TP serving: in-features shard on the model axis
_SERVING_ROW_PATHS = ("attn/wo", "mlp/w_down")


def quantize_serving_params(params, fmt: str = "int8",
                            row_parallel_shards: int = 1):
    """Compress the big matmul kernels of a CausalLM tree for serving
    (``fmt``: 'int8' | 'fp8' | 'fp6'); embeddings (gathers) and norms stay
    in the original dtype.  Returns the mixed tree — ``serving_mm``
    consumes it transparently.

    ``row_parallel_shards``: TP model-axis size — FP6 row-parallel kernels
    (o/down projections) are packed per K-chunk so their byte planes shard
    cleanly on in-features (see :class:`ServingQuantFP6`); int8/fp8 layouts
    are chunk-agnostic and ignore it."""
    from ..runtime.zero import path_str

    def leaf(kp, x):
        p = path_str(kp)
        if getattr(x, "ndim", 0) >= 2 and any(p.endswith(t) for t in _SERVING_QUANT_PATHS):
            if fmt == "fp6":
                shards = (row_parallel_shards
                          if any(p.endswith(t) for t in _SERVING_ROW_PATHS)
                          else 1)
                return quantize_serving_weight_fp6(x, shards)
            return quantize_serving_weight(x, fmt)
        return x

    return jax.tree_util.tree_map_with_path(leaf, params)


def tree_nbytes(tree) -> int:
    return sum(
        x.size * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(tree)
        if hasattr(x, "dtype")
    )
