"""Blockwise flash attention (Pallas TPU).

TPU-native replacement for the reference's attention kernels
(``csrc/transformer/`` training softmax kernels, inference
``csrc/transformer/inference/csrc/softmax.cu``, and the blocked flash
attention in ``inference/v2/kernels/ragged_ops``).  Online-softmax blockwise
attention computed in VMEM tiles feeding the MXU.

Entry point ``flash_attention`` has the same signature as
``ops.attention.dot_product_attention`` and falls back to it off-TPU, so the
model code is kernel-agnostic.

Partitioning rule.  GSPMD cannot partition a Mosaic custom call ("Mosaic
kernels cannot be automatically partitioned"), so under a mesh of more than
one device the kernel runs inside a fully-manual ``shard_map`` region: batch
over ``data``/``fsdp``/``sub``, heads over ``model`` (and ``seq`` — the
Ulysses head layout), every other axis replicated.  An axis entry that does
not divide its dimension is dropped (that dimension is then replicated in the
region), and ``supports()`` judges the PER-SHARD shape, so the kernel sees
exactly one chip's slice.  This is the one place training
(``models/transformer.py``), serving prefill and packed prefill
(``inference/model_runner.py``) get the rule from.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ...parallel.sharding import filter_spec, get_current_mesh, shard_map_compat
from ...parallel.topology import BATCH_AXES, MODEL_AXIS, SEQ_AXIS
from ..attention import dot_product_attention
from . import note_dispatch, on_tpu


def is_compatible() -> bool:
    return on_tpu()


def _partition_specs(mesh, q_shape, kv_shape):
    """(q/out spec, kv spec, segment-id spec) for [b, s, h, d] operands.
    q and kv heads shard on the SAME axes or not at all: a head entry that
    divides hq but not hkv would misalign the GQA groups per shard."""
    for heads in ((MODEL_AXIS, SEQ_AXIS), MODEL_AXIS, None):
        spec = P(BATCH_AXES, None, heads, None)
        q_spec = filter_spec(q_shape, spec, mesh)
        kv_spec = filter_spec(kv_shape, spec, mesh)
        if q_spec[2] == kv_spec[2]:
            break
    return q_spec, kv_spec, P(q_spec[0], None)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    q_offset=0,
    segment_ids: Optional[jnp.ndarray] = None,
    kv_segment_ids: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    logits_soft_cap: Optional[float] = None,
    mesh=None,
    window: int = 0,
):
    """[b, s, h, d] flash attention: dispatches to the hand-tiled Pallas
    kernel (flash_kernel.py — causal, GQA, packed segments, soft cap, a
    window) when ``supports()`` holds for the per-shard shape, else the
    fused-by-XLA reference body.  ``mesh`` defaults to the ambient mesh
    (``parallel.sharding.get_current_mesh``); serving passes its own.
    ``window`` (0: every key): a query sees its last ``window`` keys, its own
    included; the reference body masks the band."""
    def reference():
        return dot_product_attention(
            q, k, v, causal=causal, q_offset=q_offset, segment_ids=segment_ids,
            kv_segment_ids=kv_segment_ids, scale=scale, logits_soft_cap=logits_soft_cap,
            window=window,
        )

    from . import flash_kernel as fk

    if not (is_compatible() or fk._INTERPRET):
        return reference()
    mesh = mesh if mesh is not None else get_current_mesh()
    # one device needs no region; inside a caller's manual region (Ulysses,
    # whole-step shard_map optimizers) the operands already ARE the shard
    if mesh is not None and (
        mesh.size == 1 or jax.sharding.get_abstract_mesh().manual_axes
    ):
        mesh = None
    q_l, k_l = q.shape, k.shape
    if mesh is not None:
        q_spec, kv_spec, seg_spec = _partition_specs(mesh, q.shape, k.shape)
        q_l = NamedSharding(mesh, q_spec).shard_shape(q.shape)
        k_l = NamedSharding(mesh, kv_spec).shard_shape(k.shape)
    seg_l = None
    if segment_ids is not None:
        seg_l = jax.ShapeDtypeStruct(
            (q_l[0],) + tuple(segment_ids.shape[1:]), segment_ids.dtype)
    ok = fk.supports(
        jax.ShapeDtypeStruct(q_l, q.dtype), jax.ShapeDtypeStruct(k_l, k.dtype),
        jax.ShapeDtypeStruct(k_l, v.dtype), causal, q_offset, seg_l,
        logits_soft_cap, window,
    )
    note_dispatch("flash_fwd", ok, q_l, interpret=fk._INTERPRET,
                  reason="" if ok else "flash_kernel.supports() declined")
    if not ok:
        return reference()

    def kernel(q, k, v, seg, kv_seg):
        return fk.pallas_flash_attention(
            q, k, v, causal=causal, scale=scale, segment_ids=seg,
            kv_segment_ids=kv_seg, logits_soft_cap=logits_soft_cap, window=window,
        )

    if mesh is None:
        return kernel(q, k, v, segment_ids, kv_segment_ids)
    # None operands are empty pytrees: their spec entry is never consulted
    return shard_map_compat(
        kernel, mesh,
        in_specs=(q_spec, kv_spec, kv_spec, seg_spec, seg_spec),
        out_specs=q_spec,
    )(q, k, v, segment_ids, kv_segment_ids)
