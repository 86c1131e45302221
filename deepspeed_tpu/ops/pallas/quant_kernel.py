"""Pallas block quantization kernels: int8 (symmetric) and fp8.

TPU-native counterpart of the reference's CUDA quantization suite
(``csrc/quantization/{quantize.cu,dequantize.cu,quant_reduce.cu}``, 2,920
LoC, and ``csrc/fp_quantizer/*``): per-group symmetric scaling with the
amax/127 rule, fused scale-compute + cast in one VMEM pass.  Groups are
rows of the flattened [groups, group_size] view (the reference quantizes
contiguous partitions the same way).

The fp8 path targets ``float8_e4m3fn`` / ``float8_e5m2`` — real dtypes on
TPU, so "packing" is just a cast; scaling still matters (e4m3 maxes at
448).  Odd shapes fall back to the jnp reference implementation in
``ops/quantizer.py`` (same math, XLA-fused) — the ``is_compatible``-style
split the op_builder UX uses everywhere else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_INTERPRET = False


def set_interpret(value: bool) -> None:
    global _INTERPRET
    _INTERPRET = bool(value)


def _quant_int8_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _dequant_int8_kernel(q_ref, s_ref, o_ref, *, out_dtype):
    q = q_ref[...].astype(jnp.float32)
    o_ref[...] = (q * s_ref[...]).astype(out_dtype)


# fp32 working tile per grid step.  The kernel holds the input block, its
# fp32 widening, the quantized block and the pipeline's double buffers at
# once; 1 MiB of fp32 keeps that under Mosaic's 16 MiB default scoped VMEM.
_TILE_BYTES = 1024 * 1024


def _block_rows(g: int, n: int) -> int:
    """Largest power-of-two row block (<= 256) that divides ``g`` and keeps
    the fp32 tile inside ``_TILE_BYTES``; < 32 means no tile-aligned block
    exists (int8/fp8 rows pack 32 to a sublane tile)."""
    bm = 256
    while bm > 1 and (g % bm or bm * n * 4 > _TILE_BYTES):
        bm //= 2
    return bm


def supports(x2d) -> bool:
    g, n = x2d.shape
    return n % 128 == 0 and _block_rows(g, n) >= 32


def quantize_int8(x2d: jnp.ndarray):
    """[G, N] -> (int8 [G, N], fp32 scales [G]); one scale per row/group.

    Scales travel through the kernel as a [G, 1] column: Mosaic tiles a 1-D
    fp32 operand differently from XLA (T(256) vs T(1024)) and refuses the
    call, while a 2-D block with a full-extent minor dim is layout-exact."""
    g, n = x2d.shape
    bm = _block_rows(g, n)
    q, s = pl.pallas_call(
        _quant_int8_kernel,
        name="quant_int8",
        grid=(g // bm,),
        in_specs=[pl.BlockSpec((bm, n), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g, n), jnp.int8),
            jax.ShapeDtypeStruct((g, 1), jnp.float32),
        ],
        interpret=_INTERPRET,
    )(x2d)
    return q, s[:, 0]


def dequantize_int8(q2d: jnp.ndarray, scales: jnp.ndarray, out_dtype=jnp.bfloat16):
    g, n = q2d.shape
    bm = _block_rows(g, n)
    return pl.pallas_call(
        functools.partial(_dequant_int8_kernel, out_dtype=out_dtype),
        name="quant_dequant_int8",
        grid=(g // bm,),
        in_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((g, n), out_dtype),
        interpret=_INTERPRET,
    )(q2d, scales.astype(jnp.float32)[:, None])


def _quant_fp8_kernel(x_ref, q_ref, s_ref, *, fp8_dtype, fp8_max):
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / fp8_max
    q_ref[...] = (x / scale).astype(fp8_dtype)
    s_ref[...] = scale


def quantize_fp8(x2d: jnp.ndarray, dtype=jnp.float8_e4m3fn):
    """[G, N] -> (fp8 [G, N], fp32 scales [G])."""
    g, n = x2d.shape
    bm = _block_rows(g, n)
    fp8_max = float(jnp.finfo(dtype).max)
    q, s = pl.pallas_call(
        functools.partial(_quant_fp8_kernel, fp8_dtype=dtype, fp8_max=fp8_max),
        name="quant_fp8",
        grid=(g // bm,),
        in_specs=[pl.BlockSpec((bm, n), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g, n), dtype),
            jax.ShapeDtypeStruct((g, 1), jnp.float32),
        ],
        interpret=_INTERPRET,
    )(x2d)
    return q, s[:, 0]
