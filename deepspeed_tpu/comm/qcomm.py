"""Quantized collectives with error feedback — the general int8/fp8 layer.

Generalizes the 1-bit machinery of :mod:`comm.compressed` (sign + per-chunk
l1 scale, worker/server error feedback) into multi-bit transports the three
hot paths share:

- **ZeRO-3 / ZeRO++**: per-layer weight all-gathers (`q_all_gather`) and the
  2-hop quantized gradient reduce (`q_reduce_scatter` — chunk → quantize →
  ``all_to_all`` → fp32 dequant-sum, the reference's
  ``all_to_all_quant_reduce`` shape) with optional LoCo-style error feedback.
- **TP serving**: the row-parallel partial-sum transport
  (`q_all_reduce` / `q_psum_tiled`) — EQuARX-style (arXiv:2506.17615)
  reduce-scatter → re-quantize → all-gather, so BOTH wire hops carry int8/fp8
  while the reduction itself accumulates in fp32 carry chunks.
- **MoE**: dispatch/combine `q_all_to_all` over the expert axis.

Every function takes ``fmt`` in ``('none', 'int8', 'fp8')``: ``'none'`` is
an EXACT passthrough onto the plain ``lax`` collective (zero extra ops — the
A/B lever every call site keeps), so quantized transport is always
opt-in per call.  Payload dtypes on the wire are ``s8`` / ``f8e4m3fn`` plus
one fp32 scale per ``chunk`` elements; the scheduled-HLO tests
(tests/test_overlap_hlo.py) assert those dtypes on the actual wire ops.

Accumulation discipline (the guard rail): a reduction over ``W`` ranks of
int8 values spans ``W * 127`` — far outside int8 — so reducing collectives
ALWAYS dequantize to fp32 carry chunks before summing and re-quantize only
for the second wire hop.  Requesting integer accumulation
(``accum='int8'``/``'fp8'``) raises :class:`QCommOverflowError` instead of
silently losing precision; ``accum='fp32'`` (default) is the carry path.

Error feedback (gradient paths): pass ``error`` (a persistent fp32 buffer
shaped like ``x``) and the quantization residual of THIS call rides out as
``new_error`` — add it back in before the next call's quantization
(1-bit Adam's compensation, multi-bit).  Activations (TP psum) typically
run without error state; exactness there is the passthrough mode's job.

All functions must be called INSIDE ``shard_map`` over ``axis_name``.
"""
from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp

from ..parallel.sharding import collective_axis_size as _axis_size

AxisNames = Union[str, Sequence[str]]

FORMATS = ("none", "int8", "fp8")
_FP8_DTYPE = jnp.float8_e4m3fn
_FMT_MAX = {"int8": 127.0, "fp8": 448.0}  # e4m3fn finfo max
_FMT_BYTES = {"none": 4, "int8": 1, "fp8": 1}
DEFAULT_CHUNK = 256  # elements per fp32 scale on the wire


class QCommError(ValueError):
    """Typed configuration error of the quantized-collective layer."""


class QCommOverflowError(QCommError):
    """A reducing collective was asked to accumulate in an integer/fp8
    format: ``W`` int8 addends span ``W * 127``, outside the format's range,
    so the sum would silently saturate.  Reductions must accumulate through
    the fp32 carry path (``accum='fp32'``, the default)."""


def _check_fmt(fmt: str) -> str:
    if fmt not in FORMATS:
        raise QCommError(f"qcomm format {fmt!r} — expected one of {FORMATS}")
    return fmt


def _check_reduce(fmt: str, accum: str, axis_name: AxisNames, op: str) -> None:
    _check_fmt(fmt)
    if accum == "fp32":
        return
    if accum not in FORMATS:
        raise QCommError(
            f"qcomm accum {accum!r} — expected 'fp32' (carry) of {FORMATS}"
        )
    # 'none' payloads reduce exactly in fp32 anyway; quantized payloads have
    # no safe narrow accumulation at any world size > 1 (and W is static, so
    # refuse at trace time rather than saturate at run time)
    if fmt != "none":
        raise QCommOverflowError(
            f"{op}: accumulating {fmt} payloads in {accum!r} over the "
            f"{axis_name!r} axis would overflow the format's range "
            f"(W addends of magnitude up to {_FMT_MAX[fmt]:.0f}); use "
            "accum='fp32' — the carry path dequantizes per-rank payloads "
            "and sums in fp32 before re-quantizing the second hop"
        )


# ---------------------------------------------------------------------------
# per-chunk quantization of a flat buffer
# ---------------------------------------------------------------------------
def _pad_to(flat: jnp.ndarray, mult: int) -> jnp.ndarray:
    n = flat.shape[0]
    pad = (-n) % mult
    return jnp.pad(flat, (0, pad)) if pad else flat


def _q_chunks(flat: jnp.ndarray, fmt: str, chunk: int):
    """fp32 [n] (n % chunk == 0) -> (payload [n/chunk, chunk], scales)."""
    buf = flat.reshape(-1, chunk)
    amax = jnp.max(jnp.abs(buf), axis=-1)
    s = jnp.maximum(amax, 1e-12) / _FMT_MAX[fmt]
    if fmt == "int8":
        q = jnp.clip(jnp.round(buf / s[:, None]), -127, 127).astype(jnp.int8)
    else:
        q = (buf / s[:, None]).astype(_FP8_DTYPE)
    return q, s.astype(jnp.float32)


def _dq_chunks(q: jnp.ndarray, s: jnp.ndarray) -> jnp.ndarray:
    """payload [..., G, chunk] + scales [..., G] -> fp32 [..., G, chunk]."""
    return q.astype(jnp.float32) * s[..., None]


def _residual(flat: jnp.ndarray, q, s) -> jnp.ndarray:
    return flat - _dq_chunks(q, s).reshape(-1)


def wire_bytes(op: str, n_elements: int, fmt: str, world: int,
               chunk: int = DEFAULT_CHUNK,
               none_bytes_per_el: int = 4) -> int:
    """Per-device payload bytes ONE call puts on the wire (payload + fp32
    scales), for the telemetry accounting.  ``op``: 'all_gather' |
    'reduce_scatter' | 'all_reduce' | 'all_to_all'.  ``n_elements`` is the
    FULL logical tensor (for all_to_all: this rank's local buffer).  Exact
    passthrough ('none') counts fp32 payload and no scales.  Counts what a
    device SENDS on a ring: (W-1)/W of the buffer per hop, twice for
    all_reduce (reduce-scatter + all-gather)."""
    _check_fmt(fmt)
    # 'none' ships the compute dtype (``none_bytes_per_el`` — bf16 serving
    # psums are 2 bytes/el); quantized formats are 1 byte/el + scales
    per_el = none_bytes_per_el if fmt == "none" else _FMT_BYTES[fmt]
    scale_b = 0 if fmt == "none" else 4 * (-(-n_elements // chunk))
    body = n_elements * per_el + scale_b
    if op == "all_gather":
        return body * (world - 1) // world
    if op == "reduce_scatter":
        return body * (world - 1) // world
    if op == "all_reduce":
        # reduce-scatter + all-gather, both quantized
        return 2 * (body * (world - 1) // world)
    if op == "all_to_all":
        return body * (world - 1) // world
    if op == "collective_permute":
        # point-to-point: every device sends the FULL buffer once per call
        # (no (W-1)/W ring discount — there is no ring decomposition to
        # amortize; ``world`` is accepted for signature symmetry only)
        return body
    raise QCommError(f"wire_bytes op {op!r}")


# ---------------------------------------------------------------------------
# collectives
# ---------------------------------------------------------------------------
def ring_permute(x: jnp.ndarray, axis_name: AxisNames,
                 world: Optional[int] = None) -> jnp.ndarray:
    """One nearest-neighbour ring hop: rank ``i`` sends ``x`` to rank
    ``(i + 1) % world`` and receives rank ``(i - 1) % world``'s buffer.

    The point-to-point primitive of the seq-sharded decode ring
    (``inference/paged.py``): the ``[B, hq, hd+2]`` flash accumulator
    travels exactly ``world - 1`` hops, each fully counted by
    ``wire_bytes('collective_permute', ...)`` — no (W-1)/W ring discount,
    a permute ships its whole payload.  Exact (no quantized variant: the
    accumulator is an fp32 running max/denominator/weighted sum, and
    requantizing partials per hop would compound error ``world`` times).

    Must run inside a ``shard_map`` region over ``axis_name``.  ``world``
    defaults to the live axis size.
    """
    if world is None:
        from ..parallel.sharding import collective_axis_size

        world = collective_axis_size(axis_name)
    perm = [(i, (i + 1) % world) for i in range(world)]
    return jax.lax.ppermute(x, axis_name, perm)


def q_all_gather(
    x: jnp.ndarray,
    axis_name: AxisNames,
    fmt: str = "int8",
    *,
    axis: int = 0,
    tiled: bool = False,
    chunk: int = DEFAULT_CHUNK,
    out_dtype=None,
) -> jnp.ndarray:
    """All-gather with a quantized wire payload (the ZeRO-3/qwZ weight
    gather: each rank's shard travels int8/fp8 + per-chunk fp32 scales and
    dequantizes on arrival).  Exact in ``fmt='none'``.  ``axis``/``tiled``
    follow ``lax.all_gather`` semantics."""
    _check_fmt(fmt)
    out_dtype = out_dtype or x.dtype
    if fmt == "none":
        # cast BEFORE the gather: a bf16 compute gather of an fp32 master
        # shard must ship 2 bytes/el, not gather wide and narrow after
        return jax.lax.all_gather(
            x.astype(out_dtype), axis_name, axis=axis, tiled=tiled
        )
    n = x.size
    flat = _pad_to(x.reshape(-1).astype(jnp.float32), chunk)
    q, s = _q_chunks(flat, fmt, chunk)
    q_all = jax.lax.all_gather(q, axis_name)  # [W, G, chunk] — narrow wire
    s_all = jax.lax.all_gather(s, axis_name)  # [W, G]
    full = _dq_chunks(q_all, s_all).reshape(q_all.shape[0], -1)[:, :n]
    full = full.reshape((q_all.shape[0],) + x.shape).astype(out_dtype)
    if tiled:
        return jnp.concatenate([full[i] for i in range(full.shape[0])], axis=axis)
    return jnp.moveaxis(full, 0, axis) if axis else full


def q_reduce_scatter(
    x: jnp.ndarray,
    axis_name: AxisNames,
    fmt: str = "int8",
    *,
    scatter_axis: int = 0,
    mean: bool = False,
    error: Optional[jnp.ndarray] = None,
    chunk: int = DEFAULT_CHUNK,
    accum: str = "fp32",
    world: Optional[int] = None,
):
    """Reduce-scatter whose wire payload is quantized per destination chunk
    (qgZ: split → quantize → ``all_to_all`` → fp32 dequant-sum).  ``x`` is
    this rank's full-size partial; returns this rank's fully reduced shard
    (``x.shape`` with ``scatter_axis`` divided by ``W``), in fp32.

    ``error``: persistent error-feedback buffer shaped like ``x`` (fp32);
    when given, it is added before quantization and the call returns
    ``(shard, new_error)`` — the residual to carry into the next step.
    Without ``error`` the return is just ``shard``.

    ``accum`` must stay ``'fp32'`` (see :class:`QCommOverflowError`)."""
    _check_reduce(fmt, accum, axis_name, "q_reduce_scatter")
    w = world or _axis_size(axis_name)
    if x.shape[scatter_axis] % w:
        raise QCommError(
            f"q_reduce_scatter: dim {scatter_axis} ({x.shape[scatter_axis]}) "
            f"must divide the axis size {w}"
        )
    xf = x.astype(jnp.float32)
    comp = xf + error if error is not None else xf
    if fmt == "none":
        out = jax.lax.psum_scatter(
            comp, axis_name, scatter_dimension=scatter_axis, tiled=True
        )
        out = out / w if mean else out
        if error is not None:
            return out, jnp.zeros_like(xf)
        return out
    # [W, ...piece]: leading axis = destination rank.  Each piece pads to a
    # chunk multiple INDEPENDENTLY so scale groups never straddle a
    # destination boundary (the all_to_all split must stay piece-aligned).
    pieces = jnp.stack(jnp.split(comp, w, axis=scatter_axis))
    piece_elems = pieces[0].size
    flat2 = pieces.reshape(w, -1)
    pad = (-piece_elems) % chunk
    if pad:
        flat2 = jnp.pad(flat2, ((0, 0), (0, pad)))
    gpr = flat2.shape[1] // chunk  # scale groups per piece
    q, s = _q_chunks(flat2.reshape(-1), fmt, chunk)
    if error is not None:
        new_error = _residual(flat2.reshape(-1), q, s)
        new_error = new_error.reshape(w, -1)[:, :piece_elems]
        new_error = new_error.reshape(pieces.shape)
        new_error = jnp.concatenate(
            [new_error[i] for i in range(w)], axis=scatter_axis
        )
    recv_q = jax.lax.all_to_all(
        q.reshape(w, gpr, chunk), axis_name, split_axis=0, concat_axis=0,
        tiled=True,
    ).reshape(w, gpr, chunk)
    recv_s = jax.lax.all_to_all(
        s.reshape(w, gpr), axis_name, split_axis=0, concat_axis=0, tiled=True
    ).reshape(w, gpr)
    # fp32 carry: dequantize every rank's payload and sum in fp32
    total = jnp.sum(_dq_chunks(recv_q, recv_s), axis=0).reshape(-1)[:piece_elems]
    out = total.reshape(pieces.shape[1:])
    out = out / w if mean else out
    if error is not None:
        return out, new_error
    return out


def q_all_reduce(
    x: jnp.ndarray,
    axis_name: AxisNames,
    fmt: str = "int8",
    *,
    mean: bool = False,
    error: Optional[jnp.ndarray] = None,
    chunk: int = DEFAULT_CHUNK,
    accum: str = "fp32",
    world: Optional[int] = None,
):
    """All-reduce as quantized reduce-scatter → re-quantize → quantized
    all-gather (EQuARX): both wire hops carry int8/fp8 + per-chunk scales,
    the reduction itself runs in fp32 carry chunks on the scatter side.
    Exact ``lax.psum``/``pmean`` in ``fmt='none'``.

    ``error`` compensates the FIRST hop's quantization of this rank's
    partial (worker-side feedback); the second hop's residual belongs to the
    reduced value, which no single rank owns across steps — gradient paths
    that need full compensation should reduce-scatter (their consumer is
    sharded anyway).  Returns ``out`` or ``(out, new_error)``."""
    _check_reduce(fmt, accum, axis_name, "q_all_reduce")
    if fmt == "none":
        xf = x.astype(jnp.float32)
        # drain any pending error-feedback residual into the exact
        # reduction (same contract as q_reduce_scatter's passthrough) so
        # flipping int8 -> 'none' mid-run never drops compensated mass
        comp = xf + error if error is not None else xf
        out = (jax.lax.pmean(comp, axis_name) if mean
               else jax.lax.psum(comp, axis_name))
        if error is not None:
            return out, jnp.zeros_like(xf)
        return out
    w = world or _axis_size(axis_name)
    n = x.size
    flat = _pad_to(x.reshape(-1).astype(jnp.float32), w * chunk)
    res = q_reduce_scatter(
        flat, axis_name, fmt, mean=mean, world=w,
        error=(_pad_to(error.reshape(-1), w * chunk) if error is not None else None),
        chunk=chunk, accum=accum,
    )
    if error is not None:
        shard, new_error = res
        new_error = new_error[:n].reshape(x.shape)
    else:
        shard = res
    full = q_all_gather(shard, axis_name, fmt, tiled=True, chunk=chunk,
                        out_dtype=jnp.float32)
    out = full[:n].reshape(x.shape)
    if error is not None:
        return out, new_error
    return out


def q_all_to_all(
    x: jnp.ndarray,
    axis_name: AxisNames,
    fmt: str = "int8",
    *,
    split_axis: int = 0,
    concat_axis: int = 0,
    chunk: int = DEFAULT_CHUNK,
    out_dtype=None,
    world: Optional[int] = None,
) -> jnp.ndarray:
    """All-to-all with quantized payload (the MoE dispatch/combine wire:
    each destination's slab is quantized independently, so scales travel
    with their slab).  Non-reducing — no accumulation concern.

    Differentiable via a straight-through estimator: the quantize→dequant
    on the wire has zero derivative, so a custom VJP treats it as identity
    and routes the cotangent through the TRANSPOSED all-to-all (split and
    concat axes swapped) at the same wire format — without this, training
    through a quantized dispatch/combine (MoE EP) would get all-zero
    expert gradients."""
    _check_fmt(fmt)
    out_dtype = out_dtype or x.dtype
    if fmt == "none":
        return jax.lax.all_to_all(
            x, axis_name, split_axis=split_axis, concat_axis=concat_axis,
            tiled=True,
        ).astype(out_dtype)
    w = world or _axis_size(axis_name)
    if x.shape[split_axis] % w:
        raise QCommError(
            f"q_all_to_all: split dim {split_axis} ({x.shape[split_axis]}) "
            f"must divide the axis size {w}"
        )
    in_dtype = x.dtype

    @jax.custom_vjp
    def a2a(v):
        return _q_a2a_impl(v, axis_name, fmt, split_axis, concat_axis,
                           chunk, out_dtype, w)

    def fwd(v):
        return a2a(v), None

    def bwd(_, g):
        # STE: quantization ~ identity; the all-to-all transposes (the
        # slab that went rank r -> rank d comes back d -> r), still on the
        # narrow wire
        return (_q_a2a_impl(g, axis_name, fmt, concat_axis, split_axis,
                            chunk, in_dtype, w),)

    a2a.defvjp(fwd, bwd)
    return a2a(x)


def _q_a2a_impl(x, axis_name, fmt, split_axis, concat_axis, chunk,
                out_dtype, w):
    pieces = jnp.stack(jnp.split(x.astype(jnp.float32), w, axis=split_axis))
    piece_shape = pieces.shape[1:]
    piece_elems = pieces[0].size
    flat2 = pieces.reshape(w, -1)
    pad = (-piece_elems) % chunk
    if pad:
        flat2 = jnp.pad(flat2, ((0, 0), (0, pad)))
    gpr = flat2.shape[1] // chunk
    q, s = _q_chunks(flat2.reshape(-1), fmt, chunk)
    recv_q = jax.lax.all_to_all(
        q.reshape(w, gpr, chunk), axis_name, split_axis=0, concat_axis=0,
        tiled=True,
    ).reshape(w, gpr, chunk)
    recv_s = jax.lax.all_to_all(
        s.reshape(w, gpr), axis_name, split_axis=0, concat_axis=0, tiled=True
    ).reshape(w, gpr)
    deq = _dq_chunks(recv_q, recv_s).reshape(w, -1)[:, :piece_elems]
    deq = deq.reshape((w,) + piece_shape).astype(out_dtype)
    return jnp.concatenate([deq[i] for i in range(w)], axis=concat_axis)


def q_psum_tiled(
    y: jnp.ndarray,
    axis_name: AxisNames,
    fmt: str = "none",
    *,
    tiles: int = 1,
    chunk: int = DEFAULT_CHUNK,
    out_dtype=None,
    world: Optional[int] = None,
) -> jnp.ndarray:
    """The TP row-parallel partial-sum transport, T3-style: the matmul
    output ``y`` ([B, N] per-shard partial products) reduces tile by tile
    along its LAST (free/output) dim, each tile an independent
    ``q_all_reduce`` — so tile i's collective overlaps tile i+1's epilogue
    and the surrounding compute in the compiler's schedule (asserted in
    tests/test_overlap_hlo.py).

    Tiling the free dim keeps total wire volume EXACTLY one [B, N] payload
    (tiling the contraction K instead would psum a full [B, N] partial per
    tile — T x the bytes — so the sub-GEMM boundary goes on the output dim,
    which is also where T3 slices its fused GEMM + reduce-scatter).

    ``fmt='none', tiles=1`` is bit-identical to the plain ``lax.psum`` this
    replaces (the passthrough every call site keeps A/B-able).  Quantized
    formats reduce through the fp32 carry path per tile; int8 transport of
    fp32 partials is lossy — callers gate it on the path's documented error
    tolerance (decode logits argmax tolerates it; see README)."""
    _check_fmt(fmt)
    out_dtype = out_dtype or y.dtype
    tiles = max(int(tiles), 1)
    if tiles == 1 and fmt == "none":
        return jax.lax.psum(y, axis_name)
    n = y.shape[-1]
    tiles = min(tiles, n)
    # static tile split: pad N up so tiles are equal-size (XLA-friendly)
    tile_n = -(-n // tiles)
    outs = []
    for i in range(tiles):
        lo = i * tile_n
        sl = y[..., lo : min(lo + tile_n, n)]
        if sl.shape[-1] == 0:
            continue
        if fmt == "none":
            outs.append(jax.lax.psum(sl, axis_name))
        else:
            outs.append(
                q_all_reduce(sl, axis_name, fmt, chunk=chunk,
                             world=world).astype(out_dtype)
            )
    out = jnp.concatenate(outs, axis=-1) if len(outs) > 1 else outs[0]
    return out.astype(out_dtype)


def error_like(x) -> jnp.ndarray:
    """Zero-initialized error-feedback buffer for ``x`` (fp32, same shape).
    Persist it across steps and thread it through ``error=``."""
    return jnp.zeros(getattr(x, "shape", ()), jnp.float32)


# ---------------------------------------------------------------------------
# host-side payload codec (the paged-KV handoff wire format)
# ---------------------------------------------------------------------------
def quantize_payload(arr, fmt: str, chunk: int = DEFAULT_CHUNK):
    """Encode a host array into qcomm's per-chunk-scale wire format:
    ``(payload, scales)`` where ``payload`` is int8 (or fp8-as-uint8 bytes)
    of ``arr`` flattened into ``chunk``-element groups and ``scales`` is one
    fp32 amax scale per group — exactly the layout the collectives put on
    the wire, but computed in numpy so a ROUTER process packing a paged-KV
    handoff never touches a device.  ``fmt='none'`` passes through
    ``(arr, None)``.  Decode with :func:`dequantize_payload`."""
    import numpy as np

    _check_fmt(fmt)
    if fmt == "none":
        return np.asarray(arr), None
    flat = np.asarray(arr, np.float32).reshape(-1)
    pad = (-flat.shape[0]) % chunk
    if pad:
        flat = np.pad(flat, (0, pad))
    buf = flat.reshape(-1, chunk)
    amax = np.max(np.abs(buf), axis=-1)
    s = (np.maximum(amax, 1e-12) / _FMT_MAX[fmt]).astype(np.float32)
    if fmt == "int8":
        q = np.clip(np.round(buf / s[:, None]), -127, 127).astype(np.int8)
    else:
        # fp8 payloads cross the host boundary as their raw e4m3 bytes;
        # ml_dtypes (a jax dependency) casts in PURE numpy — the codec must
        # never touch a device (a router process packing a handoff has none)
        import ml_dtypes

        q = (buf / s[:, None]).astype(ml_dtypes.float8_e4m3fn).view(np.uint8)
    return q, s


def dequantize_payload(q, s, shape, dtype, fmt: str,
                       chunk: int = DEFAULT_CHUNK):
    """Decode a :func:`quantize_payload` pair back into an array of
    ``shape``/``dtype``.  Exact inverse layout: dequantized fp32 groups are
    un-padded and reshaped; ``fmt='none'`` casts the passthrough payload."""
    import numpy as np

    _check_fmt(fmt)
    if fmt == "none":
        return np.asarray(q).reshape(shape).astype(dtype)
    if fmt == "int8":
        buf = q.astype(np.float32) * s[:, None]
    else:
        import ml_dtypes

        buf = q.view(ml_dtypes.float8_e4m3fn).astype(np.float32) * s[:, None]
    n = int(np.prod(shape))
    return buf.reshape(-1)[:n].reshape(shape).astype(dtype)


def payload_wire_bytes(n_elements: int, fmt: str, chunk: int = DEFAULT_CHUNK,
                       none_bytes_per_el: int = 2) -> int:
    """Bytes ONE :func:`quantize_payload` encoding puts on a wire (payload
    + fp32 scales) — the handoff counterpart of :func:`wire_bytes` (which
    counts ring-collective sends, not point-to-point transfers).
    ``none_bytes_per_el`` defaults to 2: passthrough KV pages ship in the
    cache compute dtype (bf16)."""
    _check_fmt(fmt)
    if fmt == "none":
        return n_elements * none_bytes_per_el
    return n_elements * _FMT_BYTES[fmt] + 4 * (-(-n_elements // chunk))
