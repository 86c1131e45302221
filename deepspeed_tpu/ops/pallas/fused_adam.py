"""Pallas fused AdamW: one VMEM pass over flat (p, g, m, v) buffers.

Counterpart of the reference's multi-tensor-apply fused Adam
(``csrc/adam/multi_tensor_adam.cu`` + ``fused_adam_frontend.cpp``): instead
of CUDA chunk lists, the pytree is raveled once (``ravel_pytree``) and the
kernel walks tile-sized blocks of the flat buffers — the same "touch every
element once" guarantee.  XLA usually fuses the optax chain to within noise
of this; the kernel exists for the cases where the update is issued over
very many small tensors and fusion boundaries show up in the profile
(benchmark before switching — ops/optimizers.py keeps XLA as default).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.flatten_util import ravel_pytree

_INTERPRET = False


def set_interpret(value: bool) -> None:
    global _INTERPRET
    _INTERPRET = bool(value)


def _adamw_kernel(p_ref, g_ref, m_ref, v_ref, lr_ref, t_ref,
                  np_ref, nm_ref, nv_ref, *, b1, b2, eps, wd):
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * g * g
    t = t_ref[0].astype(jnp.float32)
    lr = lr_ref[0]
    mhat = m / (1.0 - b1**t)
    vhat = v / (1.0 - b2**t)
    upd = mhat / (jnp.sqrt(vhat) + eps) + wd * p
    np_ref[...] = (p - lr * upd).astype(np_ref.dtype)
    nm_ref[...] = m
    nv_ref[...] = v


def fused_adamw_flat(
    p: jnp.ndarray, g: jnp.ndarray, m: jnp.ndarray, v: jnp.ndarray,
    lr: jnp.ndarray, step: jnp.ndarray,
    b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8, wd: float = 0.0,
    block: int = 1 << 16,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Flat fp32 buffers [N] (N % 128 == 0) -> (new_p, new_m, new_v)."""
    n = p.size
    bs = min(block, n)
    while n % bs:
        bs //= 2
    kernel = functools.partial(_adamw_kernel, b1=b1, b2=b2, eps=eps, wd=wd)
    scalar = pl.BlockSpec((1,), lambda i: (0,))
    blk = pl.BlockSpec((bs,), lambda i: (i,))
    return pl.pallas_call(
        kernel,
        name="fused_adam",
        grid=(n // bs,),
        in_specs=[blk, blk, blk, blk, scalar, scalar],
        out_specs=[blk, blk, blk],
        out_shape=[
            jax.ShapeDtypeStruct((n,), p.dtype),
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
        ],
        interpret=_INTERPRET,
    )(p, g, m, v, lr.reshape(1), step.reshape(1))


def fused_adamw_tree(params, grads, m_tree, v_tree, lr, step, **kw):
    """Pytree front-end: ravel → fused kernel → unravel."""
    pf, unravel = ravel_pytree(params)
    gf, _ = ravel_pytree(grads)
    mf, _ = ravel_pytree(m_tree)
    vf, _ = ravel_pytree(v_tree)
    np_, nm, nv = fused_adamw_flat(
        pf.astype(jnp.float32), gf.astype(jnp.float32), mf, vf,
        jnp.asarray(lr, jnp.float32), jnp.asarray(step, jnp.int32), **kw
    )
    return unravel(np_), unravel(nm), unravel(nv)
