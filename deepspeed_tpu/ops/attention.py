"""Attention ops: reference implementation + dispatch.

The reference ships many attention bodies (training kernels
``csrc/transformer/``, inference v1 ``csrc/transformer/inference/``, ragged
blocked flash attention ``inference/v2/kernels/ragged_ops``, Ulysses wrapping
any local attention ``deepspeed/sequence/layer.py:311``).  On TPU there is one
logical op — scaled dot-product attention with GQA — realised as:

- ``dot_product_attention``: pure-jnp reference body.  XLA already fuses this
  well; it is the fallback everywhere and the ground truth in kernel tests.
- ``flash_attention`` (ops/pallas/flash_attention.py): Pallas blockwise
  online-softmax kernel for long sequences on real TPU.
- ring / Ulysses wrappers (deepspeed_tpu/sequence/) compose *around* either
  body.

All bodies share the [batch, seq, heads, head_dim] layout and support GQA by
``num_q_heads % num_kv_heads == 0`` head-group broadcasting (reference GQA
handling: sequence/layer.py:111 uneven_heads_all2all).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


def repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """[b, s, h_kv, d] -> [b, s, h_kv * n_rep, d] by head-group broadcast."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d))
    return k.reshape(b, s, h * n_rep, d)


def make_causal_mask(q_len: int, kv_len: int, q_offset=0, dtype=jnp.float32):
    """Additive causal mask allowing query i to attend kv j <= i + offset.

    ``q_offset`` supports decode (q positions start at kv_len - q_len) and
    blockwise attention (ring/fpdt chunk offsets).
    """
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    kv_pos = jnp.arange(kv_len)[None, :]
    neg = jnp.asarray(jnp.finfo(dtype).min, dtype)
    return jnp.where(q_pos >= kv_pos, jnp.asarray(0.0, dtype), neg)


def dot_product_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    q_offset: int | jnp.ndarray = 0,
    segment_ids: Optional[jnp.ndarray] = None,
    kv_segment_ids: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    logits_soft_cap: Optional[float] = None,
    attn_mask: Optional[jnp.ndarray] = None,
    bias: Optional[jnp.ndarray] = None,
    window: int = 0,
) -> jnp.ndarray:
    """Reference scaled-dot-product attention.

    q: [b, sq, hq, d];  k/v: [b, skv, hkv, d]  (hkv divides hq — GQA).
    Softmax is computed in fp32 regardless of input dtype (the reference's
    inference softmax kernels do the same for stability).
    ``attn_mask`` [sq, skv] bool composes with causal/segment masking
    (block-sparse layouts route through here, ops/sparse_attention.py).
    ``bias`` [hq, sq, skv] or per-batch-row [b, hq, sq, skv] adds to the
    pre-softmax logits (ALiBi).  ``window`` (0: every key): a query sees
    its last ``window`` keys, its own included (``q - k < window``; the lower
    edge is ``causal``'s).
    """
    in_dtype = q.dtype
    hq, hkv = q.shape[2], k.shape[2]
    k = repeat_kv(k, hq // hkv)
    v = repeat_kv(v, hq // hkv)
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(jnp.float32)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    if bias is not None:
        bias = bias.astype(jnp.float32)
        logits = logits + (bias if bias.ndim == 4 else bias[None])
    if logits_soft_cap is not None:
        logits = logits_soft_cap * jnp.tanh(logits / logits_soft_cap)
    if causal:
        mask = make_causal_mask(q.shape[1], k.shape[1], q_offset=q_offset)
        logits = logits + mask[None, None, :, :]
    if segment_ids is not None:
        kv_seg = kv_segment_ids if kv_segment_ids is not None else segment_ids
        allowed = segment_ids[:, None, :, None] == kv_seg[:, None, None, :]
        logits = jnp.where(allowed, logits, jnp.finfo(jnp.float32).min)
    if attn_mask is not None:
        logits = jnp.where(attn_mask[None, None], logits, jnp.finfo(jnp.float32).min)
    if window:
        back = jnp.arange(q.shape[1])[:, None] + q_offset - jnp.arange(k.shape[1])[None, :]
        logits = jnp.where(back < window, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(in_dtype), v)
    return out


def get_attention_impl(name: str = "auto"):
    """Select an attention body by name — the analogue of the reference's
    op-builder ``is_compatible()`` dispatch (op_builder/builder.py).

    names: 'reference' | 'flash' | 'auto'.  'flash' and 'auto' both return
    the flash dispatcher, which itself takes the reference body off-TPU or
    where the kernel's ``supports()`` declines the shape.
    """
    if name in ("reference", "math"):
        return dot_product_attention
    if name not in ("flash", "auto"):
        raise ValueError(f"unknown attention impl '{name}' (reference|flash|auto)")
    from .pallas.flash_attention import flash_attention

    return flash_attention
