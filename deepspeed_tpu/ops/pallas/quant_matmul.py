"""Pallas fused dequant-matmul: quantized weights decoded IN the matmul.

The TPU-native counterpart of the reference's quantized-GEMM kernels
(``inference/v2/kernels/core_ops/cuda_linear/`` — the TC-FPx FP6 GEMM — and
``csrc/fp_quantizer/quantize.cu``): a blocked matmul whose operand-load
stage unpacks and dequantizes the weight tile directly in VMEM, so the only
weight bytes that ever cross HBM are the compressed ones.  Dequantizing
*outside* the matmul (the plain ``x @ q.astype`` path) forfeits exactly the
memory-bandwidth win quantization exists for — decode-time serving matmuls
are weight-bandwidth-bound, and EQuARX (arxiv 2506.17615) reports the same
inside XLA: quantization only accelerates when the decode fuses into the
consuming op instead of materializing.

Two kernels, one schedule (grid ``(M/bm, N/bn, K-blocks)``, K innermost so
the fp32 VMEM accumulator survives across K steps; per-output-channel scale
and optional bias fuse into the epilogue on the last K step):

- **int8 / fp8** (``quant_matmul``): the weight tile loads as int8 (or
  float8_e4m3fn — a real TPU dtype) and widens to the compute dtype in
  VMEM, feeding the MXU.  1 byte/weight of HBM traffic vs 2 for bf16.
- **FP6 e2m3** (``quant_matmul_fp6``): four 6-bit codes ride three uint8
  byte PLANES (``ops/quantizer.py`` packs quarter-strided: plane bytes
  ``b0/b1/b2`` at packed row r carry the codes of weight rows
  ``(r, K/4+r, K/2+r, 3K/4+r)``).  The kernel loads the three plane tiles
  (0.75 bytes/weight), reassembles sign/exponent/mantissa with integer
  bit-arithmetic on the VPU, and issues four quarter-K MXU contractions —
  the quarter-strided grouping is what makes the unpack pure elementwise
  ops: no sublane interleave, no strided loads, each decoded quarter
  contracts against its own ``x[:, i*K/4 : (i+1)*K/4]`` slice (routed by
  BlockSpec index maps, never materialized).

Both kernels accumulate in fp32 regardless of compute dtype.  The jnp
reference bodies (``ref_*``) are the ground truth for parity tests and the
CPU fallback; ``set_interpret(True)`` runs the real kernels through the
Pallas interpreter so the tier-1 CPU lane exercises the kernel bodies.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_INTERPRET = False


def set_interpret(value: bool) -> None:
    global _INTERPRET
    _INTERPRET = bool(value)


def enabled() -> bool:
    """Whether the fused kernels can run here at all (real TPU, or the
    interpreter for CPU parity tests)."""
    return jax.default_backend() == "tpu" or _INTERPRET


def _pick_block(n: int, preferred) -> Optional[int]:
    for b in preferred:
        if n % b == 0:
            return b
    return None


def _pad_rows(x2d: jnp.ndarray, multiple: int = 8):
    """Pad the M dim up to a sublane multiple (decode batches are tiny)."""
    m = x2d.shape[0]
    m_pad = -(-m // multiple) * multiple
    if m_pad != m:
        x2d = jnp.pad(x2d, ((0, m_pad - m), (0, 0)))
    return x2d, m


# ---------------------------------------------------------------------------
# int8 / fp8: convert-in-operand-load
# ---------------------------------------------------------------------------
def supports_int8(x: jnp.ndarray, q: jnp.ndarray) -> bool:
    """Static applicability: 2D weight, lane-aligned K and N."""
    if not enabled() or q.ndim != 2:
        return False
    k, n = q.shape
    return x.shape[-1] == k and k % 128 == 0 and n % 128 == 0


def _qmm_kernel(x_ref, q_ref, s_ref, *rest, out_dtype, has_bias, n_k):
    if has_bias:
        b_ref, o_ref, acc = rest
    else:
        o_ref, acc = rest
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    xb = x_ref[...]
    # the dequant IS the operand load: compressed bytes arrive in VMEM and
    # widen to the compute dtype right before the MXU
    wb = q_ref[...].astype(xb.dtype)
    acc[...] += jax.lax.dot_general(
        xb, wb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(kk == n_k - 1)
    def _():
        y = acc[...] * s_ref[...]  # [bm, bn] * [1, bn] per-channel scale
        if has_bias:
            y = y + b_ref[...]
        o_ref[...] = y.astype(out_dtype)


def quant_matmul(
    x: jnp.ndarray,
    q: jnp.ndarray,
    s: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,
    block_m: Optional[int] = None,
    block_n: int = 256,
    block_k: int = 512,
) -> jnp.ndarray:
    """``(x @ q) * s (+ bias)`` with ``q`` int8/fp8 decoded in-kernel.

    x: [..., K] (any leading shape); q: [K, N]; s: [N] fp32; bias: [N].
    Returns [..., N] in x.dtype with fp32 accumulation.
    """
    lead = x.shape[:-1]
    k, n = q.shape
    x2d = x.reshape(-1, k)
    x2d, m = _pad_rows(x2d)
    m_pad = x2d.shape[0]
    bm = block_m or _pick_block(m_pad, (256, 128, 64, 32, 16, 8))
    bn = _pick_block(n, (block_n, 256, 128))
    bk = _pick_block(k, (block_k, 512, 256, 128))
    grid = (m_pad // bm, n // bn, k // bk)
    has_bias = bias is not None
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
    ]
    operands = [x2d, q, s.astype(jnp.float32).reshape(1, n)]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        operands.append(bias.astype(jnp.float32).reshape(1, n))
    out = pl.pallas_call(
        functools.partial(
            _qmm_kernel, out_dtype=x.dtype, has_bias=has_bias, n_k=k // bk
        ),
        name="quant_matmul_int8",
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=_INTERPRET,
    )(*operands)
    return out[:m].reshape(*lead, n)


def ref_quant_matmul(x, q, s, bias=None):
    """jnp reference body — the exact math ``serving_mm`` always ran:
    dequantize-then-matmul with the scale applied post-matmul in fp32."""
    y = (x @ q.astype(x.dtype)) * s.astype(jnp.float32)
    y = y.astype(x.dtype)
    if bias is not None:
        y = y + bias
    return y


# ---------------------------------------------------------------------------
# FP6 e2m3: bit-unpack-in-operand-load
# ---------------------------------------------------------------------------
def supports_fp6(x: jnp.ndarray, planes: jnp.ndarray, in_dim: int) -> bool:
    """planes [3, K/4, N]; K/4 must be lane/grid-alignable."""
    if not enabled() or planes.ndim != 3 or planes.shape[0] != 3:
        return False
    k4, n = planes.shape[1], planes.shape[2]
    return (
        x.shape[-1] == in_dim
        and in_dim == 4 * k4
        and k4 % 128 == 0
        and n % 128 == 0
    )


def _fp6_decode_block(c: jnp.ndarray, dtype) -> jnp.ndarray:
    """int32 6-bit e2m3 codes -> values, pure VPU arithmetic (no gather).
    mag = m/8 for e==0 (subnormal), else (1+m/8)*2^(e-1); 2^(e-1) comes
    from an integer shift, not a transcendental."""
    sign = (c >> 5) & 1
    e = (c >> 3) & 3
    m = (c & 7).astype(jnp.float32)
    pow2 = (jnp.left_shift(jnp.int32(1), e)).astype(jnp.float32) * 0.5
    mag = jnp.where(e == 0, m * 0.125, (1.0 + m * 0.125) * pow2)
    return jnp.where(sign == 1, -mag, mag).astype(dtype)


def _fp6_mm_kernel(*refs, out_dtype, has_bias, n_k):
    if has_bias:
        (x0, x1, x2, x3, p0, p1, p2, s_ref, b_ref, o_ref, acc) = refs
    else:
        (x0, x1, x2, x3, p0, p1, p2, s_ref, o_ref, acc) = refs
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    # three byte planes -> four code quarters (pure bit arithmetic; the
    # quarter-strided pack means NO row interleave is needed afterwards)
    b0 = p0[0].astype(jnp.int32)
    b1 = p1[0].astype(jnp.int32)
    b2 = p2[0].astype(jnp.int32)
    c0 = b0 >> 2
    c1 = ((b0 & 0x3) << 4) | (b1 >> 4)
    c2 = ((b1 & 0xF) << 2) | (b2 >> 6)
    c3 = b2 & 0x3F
    for x_ref, c in ((x0, c0), (x1, c1), (x2, c2), (x3, c3)):
        xb = x_ref[...]
        # e2m3 has <= 4 significant bits: exact in bf16 and fp32 alike
        wb = _fp6_decode_block(c, xb.dtype)
        acc[...] += jax.lax.dot_general(
            xb, wb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kk == n_k - 1)
    def _():
        y = acc[...] * s_ref[...]
        if has_bias:
            y = y + b_ref[...]
        o_ref[...] = y.astype(out_dtype)


def quant_matmul_fp6(
    x: jnp.ndarray,
    planes: jnp.ndarray,
    s: jnp.ndarray,
    in_dim: int,
    bias: Optional[jnp.ndarray] = None,
    block_m: Optional[int] = None,
    block_n: int = 256,
    block_k4: int = 256,
) -> jnp.ndarray:
    """``(x @ dequant_fp6(planes)) * s (+ bias)`` with the 6-bit unpack in
    the kernel's operand-load stage.

    x: [..., K]; planes: [3, K/4, N] uint8 (quarter-strided pack); s: [N].
    """
    lead = x.shape[:-1]
    k4, n = planes.shape[1], planes.shape[2]
    k = in_dim
    x2d = x.reshape(-1, k)
    x2d, m = _pad_rows(x2d)
    m_pad = x2d.shape[0]
    bm = block_m or _pick_block(m_pad, (256, 128, 64, 32, 16, 8))
    bn = _pick_block(n, (block_n, 256, 128))
    bk4 = _pick_block(k4, (block_k4, 256, 128))
    n_k = k4 // bk4
    grid = (m_pad // bm, n // bn, n_k)
    has_bias = bias is not None
    # x quarter slices ride index maps: quarter i of K-step kk is the block
    # at column offset i*K/4 + kk*bk4 — four views of one buffer, no copies
    in_specs = [
        pl.BlockSpec(
            (bm, bk4), lambda i, j, kk, q=qi: (i, q * n_k + kk)
        )
        for qi in range(4)
    ]
    # the three byte planes are three block-views of the packed array
    in_specs += [
        pl.BlockSpec((1, bk4, bn), lambda i, j, kk, p=pi: (p, kk, j))
        for pi in range(3)
    ]
    in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
    operands = [x2d] * 4 + [planes] * 3 + [s.astype(jnp.float32).reshape(1, n)]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        operands.append(bias.astype(jnp.float32).reshape(1, n))
    out = pl.pallas_call(
        functools.partial(
            _fp6_mm_kernel, out_dtype=x.dtype, has_bias=has_bias, n_k=n_k
        ),
        name="quant_matmul_fp6",
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=_INTERPRET,
    )(*operands)
    return out[:m].reshape(*lead, n)
