"""Flagship decoder-only transformer (Llama family), TPU-first.

The reference has no model zoo for *training* (users bring nn.Modules; the
kernel-injection containers in ``module_inject/containers/`` and the
inference-v2 model implementations ``inference/v2/model_implementations/``
enumerate the supported families).  Our framework ships a first-class model
family instead, because on TPU the model and its sharding are designed
together.  Architecture knobs cover the reference's supported families:
Llama/Llama-2/Llama-3 (RMSNorm+RoPE+SwiGLU+GQA), Mistral, GPT-2/NeoX-style
(LayerNorm+learned-pos+GELU), Qwen (qkv bias), and — with
``moe_num_experts>0`` — Mixtral-style MoE blocks (deepspeed_tpu/moe/).

TPU-native design decisions:
- **Stacked layer parameters + ``lax.scan``**: all L layers' weights are one
  pytree with a leading layer dimension, so the decoder is a single scanned
  block — one trace, O(1) compile time in depth, and pipeline stages are
  contiguous slices of the stacked arrays (runtime/pipeline/).
- **Remat policies** (``remat='none'|'full'|'dots'``) replace the reference's
  activation-checkpointing module (runtime/activation_checkpointing/
  checkpointing.py:488): ``jax.checkpoint`` over the scanned block.
- **Sharding by rule, not surgery**: ``tp_rules()`` returns regex→PartitionSpec
  megatron-style rules consumed by the ZeRO planner (runtime/zero.py),
  replacing AutoTP module replacement (module_inject/auto_tp.py:193).
- Everything static-shaped; attention body is pluggable (ops/attention.py)
  so Ulysses / ring / flash compose without touching the model.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops.attention import dot_product_attention
from ..parallel.topology import DATA_AXIS, FSDP_AXIS, MODEL_AXIS, SEQ_AXIS, SUB_AXIS

Params = Dict[str, Any]


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    hidden_size: int = 512
    intermediate_size: int = 1408
    num_layers: int = 4
    num_heads: int = 8
    num_kv_heads: int = 8  # < num_heads => GQA (Llama-3 / Mistral style)
    head_dim: Optional[int] = None  # default hidden_size // num_heads
    max_seq_len: int = 2048
    # architecture switches
    norm: str = "rmsnorm"  # 'rmsnorm' (llama) | 'layernorm' (gpt2/bert)
    activation: str = "silu"  # 'silu' (swiglu) | 'gelu' (gpt2: plain mlp)
    gated_mlp: bool = True
    position: str = "rope"  # 'rope' | 'learned' | 'alibi' (bloom) | 'none'
    rope_theta: float = 500_000.0  # llama-3 default; llama-2 used 1e4
    # partial rotary (gptj rotary_dim=64, phi-2=32, neox rotary_pct):
    # rope applies to the FIRST rotary_dim of each head; None = full head
    rotary_dim: Optional[int] = None
    qkv_bias: bool = False  # qwen-style
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    logits_soft_cap: Optional[float] = None  # gemma-2 style
    # family switches (reference module_inject/containers: falcon/gptj/phi
    # parallel attn+MLP, bloom alibi + embedding LN, gpt2/opt biases)
    parallel_block: bool = False  # x + attn(ln(x)) + mlp(ln(x)), one shared LN
    attn_out_bias: bool = False  # bias on the o-projection
    mlp_bias: bool = False  # biases on the MLP projections
    embedding_norm: bool = False  # bloom word_embeddings_layernorm
    head_bias: bool = False  # lm_head bias (gptj/phi)
    # (LayerNorm beta comes automatically with norm='layernorm')
    # MoE (Mixtral): >0 turns the MLP into a top-k routed expert layer
    moe_num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_loss_coef: float = 0.01
    # explicit expert-parallel dispatch/combine transport (moe/layer.py
    # routed_ffn_ep over comm/qcomm.py): None = GSPMD layout-change
    # all-to-all (full-width, the default); 'none' = explicit shard_map
    # all-to-all, exact; 'int8'/'fp8' = quantized wire payload.  Takes
    # effect only when the ambient mesh has an expert axis > 1.
    moe_qcomm: Optional[str] = None
    # training
    dtype: Any = jnp.bfloat16
    remat: str = "none"  # 'none' | 'full' | 'dots'
    attn_impl: str = "reference"  # 'reference' | 'flash' | 'auto'
    # sequence parallelism: 'none' | 'ulysses' | 'ring'
    sequence_parallel: str = "none"
    # chunked logits+loss (FPDT_LogitsLoss analogue): 0 = full logits
    loss_chunk_size: int = 0
    # activation fake-quant bits (compression subsystem wires this via
    # initialize(); applied to sublayer inputs with STE).  Unlike the
    # reference's schedule_offset-gated module hooks, quantization is active
    # from step 0 — the loss_fn contract carries no step.
    act_quant_bits: Optional[int] = None
    # block-sparse attention layout (ops/sparse_attention.SparsityConfig);
    # wired from the config's sparse_attention section by initialize()
    sparse_attention: Optional[Any] = None
    # Domino-style TP overlap (reference runtime/domino): split the batch
    # into this many independent chunks inside the layer-scan body so XLA
    # can overlap one chunk's TP all-reduce with another's compute; 1 = off.
    # Wired from config tensor_parallel.domino_chunks by initialize().
    domino_chunks: int = 1
    # layers of several kinds (models/latent.py LatentSpec): latent attention
    # with a key selector or a window per layer, a dense or an expert
    # feed-forward per layer, of which a share may be held here.  Set, it
    # replaces the one-kind layer the fields above describe (the widths they
    # share stay: hidden, dense intermediate, vocabulary, depth, norm_eps).
    # Served through InferenceEngineV2; trained where models/latent.py's note
    # on training says (attention over K / V, SwiGLU or held experts).
    latent: Optional[Any] = None

    @property
    def hd(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    def replace(self, **kw) -> "TransformerConfig":
        return dataclasses.replace(self, **kw)

    @property
    def param_count(self) -> int:
        if self.latent is not None:
            from .latent import param_count

            return param_count(self)
        d, f, L, v = self.hidden_size, self.intermediate_size, self.num_layers, self.vocab_size
        hq, hkv, hd = self.num_heads, self.num_kv_heads, self.hd
        attn = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
        mlp = (3 if self.gated_mlp else 2) * d * f
        if self.moe_num_experts > 0:
            mlp = mlp * self.moe_num_experts + d * self.moe_num_experts
        per_layer = attn + mlp + 2 * d
        emb = v * d * (1 if self.tie_embeddings else 2)
        return L * per_layer + emb + d


# activation-sharding hints (GSPMD) — ambient mesh context lives in
# parallel/sharding.py; re-exported here for the public API.
from ..parallel.sharding import set_current_mesh, shard_activation  # noqa: E402


ACT_SPEC = P((DATA_AXIS, FSDP_AXIS, SUB_AXIS), SEQ_AXIS, None)  # [batch, seq, hidden]


# ---------------------------------------------------------------------------
# initialization
# ---------------------------------------------------------------------------
def _dense_init(key, shape, in_axis: int, dtype):
    fan_in = shape[in_axis]
    std = 1.0 / np.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init_params(rng: jax.Array, cfg: TransformerConfig, dtype=jnp.float32) -> Params:
    """Build the parameter pytree.  Layer weights carry a leading ``L`` dim.

    fp32 by default — the engine keeps fp32 masters and casts to
    ``cfg.dtype`` inside the train step (runtime/precision.py).
    """
    if cfg.latent is not None:
        from . import latent

        return latent.init_params(rng, cfg, dtype)
    d, f, L, v = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers, cfg.vocab_size
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    ks = jax.random.split(rng, 12)

    def dinit(key, shape, in_axis=-2):
        return _dense_init(key, shape, in_axis, dtype)

    layers: Params = {
        "attn": {
            "wq": dinit(ks[0], (L, d, hq * hd)),
            "wk": dinit(ks[1], (L, d, hkv * hd)),
            "wv": dinit(ks[2], (L, d, hkv * hd)),
            "wo": dinit(ks[3], (L, hq * hd, d)),
        },
        "attn_norm": {"scale": jnp.ones((L, d), dtype)},
    }
    if not cfg.parallel_block:
        # parallel blocks (falcon/gptj/phi) share attn_norm for both branches
        layers["mlp_norm"] = {"scale": jnp.ones((L, d), dtype)}
    if cfg.qkv_bias:
        layers["attn"]["bq"] = jnp.zeros((L, hq * hd), dtype)
        layers["attn"]["bk"] = jnp.zeros((L, hkv * hd), dtype)
        layers["attn"]["bv"] = jnp.zeros((L, hkv * hd), dtype)
    if cfg.attn_out_bias:
        layers["attn"]["bo"] = jnp.zeros((L, d), dtype)
    if cfg.moe_num_experts > 0:
        E = cfg.moe_num_experts
        layers["moe"] = {
            "router": dinit(ks[4], (L, d, E)),
            "w_gate": dinit(ks[5], (L, E, d, f)),
            "w_up": dinit(ks[6], (L, E, d, f)),
            "w_down": dinit(ks[7], (L, E, f, d)),
        }
    else:
        mlp = {
            "w_up": dinit(ks[5], (L, d, f)),
            "w_down": dinit(ks[6], (L, f, d)),
        }
        if cfg.gated_mlp:
            mlp["w_gate"] = dinit(ks[4], (L, d, f))
        if cfg.mlp_bias:
            mlp["b_up"] = jnp.zeros((L, f), dtype)
            mlp["b_down"] = jnp.zeros((L, d), dtype)
            if cfg.gated_mlp:
                mlp["b_gate"] = jnp.zeros((L, f), dtype)
        layers["mlp"] = mlp

    params: Params = {
        "embed": {"embedding": _dense_init(ks[8], (v, d), 1, dtype)},
        "layers": layers,
        "final_norm": {"scale": jnp.ones((d,), dtype)},
    }
    if cfg.position == "learned":
        params["pos_embed"] = {"embedding": _dense_init(ks[9], (cfg.max_seq_len, d), 1, dtype)}
    if cfg.embedding_norm:
        params["embed_norm"] = {"scale": jnp.ones((d,), dtype)}
    if cfg.norm == "layernorm":
        layers["attn_norm"]["bias"] = jnp.zeros((L, d), dtype)
        if "mlp_norm" in layers:
            layers["mlp_norm"]["bias"] = jnp.zeros((L, d), dtype)
        params["final_norm"]["bias"] = jnp.zeros((d,), dtype)
        if cfg.embedding_norm:
            params["embed_norm"]["bias"] = jnp.zeros((d,), dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": _dense_init(ks[10], (d, v), 0, dtype)}
        if cfg.head_bias:
            params["lm_head"]["bias"] = jnp.zeros((v,), dtype)
    return params


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------
# ``jax.named_scope`` at the layer boundaries (embed / attn / mlp / norm / head
# / loss): metadata only, it names every HLO instruction's ``op_name`` so a
# device trace can be attributed by layer (telemetry/programs.py).
# The scopes are ``with`` blocks, never wrappers: one more Python frame under
# every traced op slows JAX's tracing of a deep program by seconds (PERF.md).
def norm(x: jnp.ndarray, w: Params, kind: str, eps: float) -> jnp.ndarray:
    with jax.named_scope("norm"):
        xf = x.astype(jnp.float32)
        if kind == "rmsnorm":
            xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        else:
            mu = jnp.mean(xf, axis=-1, keepdims=True)
            var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
            xf = (xf - mu) * jax.lax.rsqrt(var + eps)
        out = xf.astype(x.dtype) * w["scale"]
        if "bias" in w:
            out = out + w["bias"]
        return out


def alibi_slopes(num_heads: int) -> jnp.ndarray:
    """Per-head ALiBi slopes (bloom; 'Train Short, Test Long').  Geometric
    sequence 2^(-8/n), with the standard non-power-of-2 extension."""
    import math

    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]

    n = 2 ** int(math.floor(math.log2(num_heads)))
    slopes = pow2_slopes(n)
    if n < num_heads:
        extra = pow2_slopes(2 * n)[0::2][: num_heads - n]
        slopes += extra
    return jnp.asarray(slopes, jnp.float32)


def alibi_bias(
    num_heads: int, q_positions: jnp.ndarray, kv_positions: jnp.ndarray
) -> jnp.ndarray:
    """Additive attention bias ``-slope_h * (q_pos - k_pos)`` for keys at
    or before the query (the causal mask handles the rest).

    ``q_positions``/``kv_positions`` may be [s] (shared row ->
    [h, sq, skv]) or PER BATCH ROW [b, s] (-> [b, h, sq, skv]): distances
    come from each row's ACTUAL positions — computing them from row 0's
    positions and the raw key index silently skewed every other row
    whenever rows disagree (left-padded batches, ragged decode offsets;
    ADVICE r5 low #3)."""
    batched = q_positions.ndim == 2 or kv_positions.ndim == 2
    q2 = q_positions if q_positions.ndim == 2 else q_positions[None]
    k2 = kv_positions if kv_positions.ndim == 2 else kv_positions[None]
    dist = q2[:, :, None].astype(jnp.float32) - k2[:, None, :]  # [b, sq, skv]
    bias = (
        -alibi_slopes(num_heads)[None, :, None, None]
        * jnp.maximum(dist, 0.0)[:, None]
    )
    return bias if batched else bias[0]


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary embedding, [b, s, h, d] with per-token ``positions`` [b, s] or [s]."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]
    cos = jnp.cos(angles)[:, :, None, :]  # [b, s, 1, d/2]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _activation(name: str):
    return {"silu": jax.nn.silu, "gelu": partial(jax.nn.gelu, approximate=True), "relu": jax.nn.relu}[name]


def _ckpt_name(x: jnp.ndarray, name: str) -> jnp.ndarray:
    """Tag a tensor as a named rematerialization save point (consumed by the
    ``remat='selective'`` policy; identity under any other policy)."""
    from jax.ad_checkpoint import checkpoint_name

    return checkpoint_name(x, name)


# remat='selective': save the flash-attention inputs/outputs (small, expensive
# to recompute: the whole attention chain) but RECOMPUTE the gated-MLP
# intermediates (b*s*intermediate_size — the largest activations in the model,
# cheap to rebuild as two matmuls).  This is the memory/recompute sweet spot
# for SwiGLU blocks: live activations/layer ≈ 5 × [b,s,d] instead of
# 2 × [b,s,f] + 5 × [b,s,d] (f = 4d), at ~18% extra matmul FLOPs vs
# remat='none' (vs +33% for remat='full').
_SELECTIVE_SAVE_NAMES = ("save_q", "save_k", "save_v", "save_attn")


def checkpointed(body: Callable, remat: str, prevent_cse: bool = False) -> Callable:
    """``body`` under ``jax.checkpoint`` as ``cfg.remat`` names it (``'none'``:
    as it is).  ``prevent_cse=False`` is for a body that ``lax.scan`` runs."""
    if remat == "full":
        return jax.checkpoint(body, prevent_cse=prevent_cse)
    if remat == "dots":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
            prevent_cse=prevent_cse,
        )
    if remat == "selective":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_only_these_names(
                *_SELECTIVE_SAVE_NAMES
            ),
            prevent_cse=prevent_cse,
        )
    if remat == "offload":
        # FPDT-style host offload (reference sequence/fpdt_layer.py:510
        # _FPDTGPUOffloadingAttentionImpl_ / SequenceChunk:462): the
        # per-layer save points move to pinned host memory, bounding
        # device activation memory for multi-million-token sequences;
        # XLA streams them back during backward
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_and_offload_only_these_names(
                names_which_can_be_saved=[],
                names_which_can_be_offloaded=list(_SELECTIVE_SAVE_NAMES),
                offload_src="device",
                offload_dst="pinned_host",
            ),
            prevent_cse=prevent_cse,
        )
    return body


def attention_block(
    lw: Params,
    x: jnp.ndarray,
    cfg: TransformerConfig,
    positions: jnp.ndarray,
    attn_fn: Callable,
    segment_ids: Optional[jnp.ndarray],
    cache: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    cache_index: Optional[jnp.ndarray] = None,
):
    """One attention sublayer (no residual). Returns (out, new_cache)."""
    b, s, d = x.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    q = x @ lw["wq"]
    k = x @ lw["wk"]
    v = x @ lw["wv"]
    if cfg.qkv_bias:
        q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
    q = q.reshape(b, s, hq, hd)
    k = k.reshape(b, s, hkv, hd)
    v = v.reshape(b, s, hkv, hd)
    if cfg.position == "rope":
        rot = cfg.rotary_dim or hd
        if rot < hd:
            # partial rotary (gptj/phi/neox): first `rot` dims rotate, the
            # rest pass through
            q = jnp.concatenate(
                [rope(q[..., :rot], positions, cfg.rope_theta), q[..., rot:]], -1
            )
            k = jnp.concatenate(
                [rope(k[..., :rot], positions, cfg.rope_theta), k[..., rot:]], -1
            )
        else:
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
    # named save points for remat='selective' (no-ops otherwise)
    q = _ckpt_name(q, "save_q")
    k = _ckpt_name(k, "save_k")
    v = _ckpt_name(v, "save_v")
    new_cache = None
    q_offset = 0
    if cache is not None:
        ck, cv = cache
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), cache_index, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), cache_index, axis=1)
        k, v = ck, cv
        new_cache = (ck, cv)
        q_offset = cache_index
    kw = {}
    if cfg.position == "alibi":
        # additive bias from the ACTUAL positions tensor, per batch row
        # (bloom; ADVICE r5 low #3 — this used positions[0] + the raw key
        # index for the whole batch).  Self-attention keys are the row's
        # own tokens, so their positions ARE the row's positions.  Cached
        # decode keys use the cache index as their position: the cache
        # stores no per-slot positions, so this is exact only when cache
        # writes are position-aligned — true for every engine flow (the v1
        # cache writes row i's token at index cache_index + i with
        # positions derived from the same arange); callers feeding a cache
        # together with CUSTOM non-arange positions (e.g. left-padded rows)
        # are outside this contract.  Packed segments raise: their
        # positions restart mid-row while the cache index keeps counting,
        # so no consistent key-position vector exists.  The reference
        # attention impl is the alibi-capable body (_get_attn_fn enforces
        # this).
        if segment_ids is not None:
            raise NotImplementedError(
                "position='alibi' does not support packed sequences "
                "(segment_ids): per-segment restarting positions have no "
                "consistent key-position vector against the cache index"
            )
        kvpos = jnp.arange(k.shape[1]) if cache is not None else positions
        kw["bias"] = alibi_bias(hq, positions, kvpos)
    out = attn_fn(
        q, k, v, causal=True, q_offset=q_offset,
        segment_ids=segment_ids,
        logits_soft_cap=cfg.logits_soft_cap,
        **kw,
    )
    out = _ckpt_name(out, "save_attn")
    out = out.reshape(b, s, hq * hd) @ lw["wo"]
    if "bo" in lw:
        out = out + lw["bo"]
    return out, new_cache


def mlp_block(lw: Params, x: jnp.ndarray, cfg: TransformerConfig) -> jnp.ndarray:
    act = _activation(cfg.activation)
    up = x @ lw["w_up"]
    if "b_up" in lw:
        up = up + lw["b_up"]
    if cfg.gated_mlp:
        gate = x @ lw["w_gate"]
        if "b_gate" in lw:
            gate = gate + lw["b_gate"]
        h = act(gate) * up
    else:
        h = act(up)
    out = h @ lw["w_down"]
    if "b_down" in lw:
        out = out + lw["b_down"]
    return out


@functools.lru_cache(maxsize=None)
def _tp_copy_fn(axis: str):
    """Megatron's 'f' operator for MANUAL tensor parallelism: identity in
    forward, ``psum`` over the TP axis in backward.  Needed wherever a
    replicated activation fans out into column-parallel shards inside a
    fully-manual ``shard_map`` region (the pipelined executor) — each
    rank's branch cotangent is partial and must be summed.  Under GSPMD
    (the dense path) this is implicit; reference analogue:
    module_inject/layers.py:66 row/col autograd fns."""

    @jax.custom_vjp
    def f(x):
        return x

    f.defvjp(lambda x: (x, None), lambda _, g: (jax.lax.psum(g, axis),))
    return f


@functools.lru_cache(maxsize=None)
def _tp_psum_fn(axis: str):
    """Megatron's 'g' operator: ``psum`` in forward (row-parallel partial
    sums), IDENTITY in backward — the cotangent of the summed output is
    already replicated across TP ranks.  A raw ``lax.psum`` must not be
    used here: under ``shard_map`` with unreplicated-value semantics
    (check_vma=False) its autodiff transpose is another psum, which
    multiplies every upstream cotangent by the TP degree per layer."""

    @jax.custom_vjp
    def g(x):
        return jax.lax.psum(x, axis)

    g.defvjp(lambda x: (jax.lax.psum(x, axis), None), lambda _, ct: (ct,))
    return g


def decoder_layer(
    lw: Params,
    x: jnp.ndarray,
    cfg: TransformerConfig,
    positions: jnp.ndarray,
    attn_fn: Callable,
    segment_ids: Optional[jnp.ndarray] = None,
    cache: Optional[Tuple] = None,
    cache_index: Optional[jnp.ndarray] = None,
    tp_axis: Optional[str] = None,
):
    """Pre-norm residual block. Returns (x, new_cache, aux_loss).

    ``tp_axis`` activates MANUAL Megatron TP for use inside fully-manual
    shard_map regions: the caller passes cfg with LOCAL head counts and
    model-sharded weights (wq/wk/wv/w_up/w_gate column-parallel, wo/w_down
    row-parallel); this function inserts the f/g collective pair — identity-
    fwd/psum-bwd at each branch input, psum-fwd at each branch output.
    Under GSPMD (tp_axis=None) the same layout comes from tp_rules specs.
    """
    if tp_axis is not None and cfg.moe_num_experts > 0:
        raise NotImplementedError("manual TP inside MoE layers is unsupported")
    dtype = x.dtype
    tp_in = _tp_copy_fn(tp_axis) if tp_axis is not None else (lambda v: v)
    attn_in = norm(x, lw["attn_norm"], cfg.norm, cfg.norm_eps)
    if cfg.act_quant_bits:
        from ..compression.compress import quantize_activation

        attn_in = quantize_activation(attn_in, cfg.act_quant_bits)
    with jax.named_scope("attn"):
        h, new_cache = attention_block(
            lw["attn"], tp_in(attn_in), cfg,
            positions, attn_fn, segment_ids, cache, cache_index,
        )
        if tp_axis is not None:
            h = _tp_psum_fn(tp_axis)(h)  # row-parallel wo partial sums
    aux = jnp.asarray(0.0, jnp.float32)
    if cfg.parallel_block:
        # falcon/gptj/phi: both branches read the SAME normed input; one
        # residual add (reference containers' parallel attn+mlp layout)
        with jax.named_scope("mlp"):
            m = mlp_block(lw["mlp"], tp_in(attn_in), cfg)
            if tp_axis is not None:
                m = _tp_psum_fn(tp_axis)(m)
        x = shard_activation(x + h.astype(dtype) + m.astype(dtype), ACT_SPEC)
        return x, new_cache, aux
    x = shard_activation(x + h.astype(dtype), ACT_SPEC)
    y = norm(x, lw["mlp_norm"], cfg.norm, cfg.norm_eps)
    if cfg.act_quant_bits:
        from ..compression.compress import quantize_activation

        y = quantize_activation(y, cfg.act_quant_bits)
    with jax.named_scope("mlp"):
        if cfg.moe_num_experts > 0:
            from ..parallel.sharding import axis_size, get_current_mesh
            from ..parallel.topology import EXPERT_AXIS

            mesh = get_current_mesh()
            if cache is not None:
                # inference (KV-cache) path: dropless routing — capacity
                # dropping is a training regularizer and would couple routing
                # to batch/padding shape (moe/layer.py moe_block_dropless)
                from ..moe.layer import moe_block_dropless as _moe

                h, aux = _moe(lw["moe"], y, cfg)
            elif (cfg.moe_qcomm is not None and mesh is not None
                    and axis_size(EXPERT_AXIS) > 1):
                # explicit expert-parallel region: the dispatch/combine slabs
                # travel through qcomm (quantized when asked) instead of
                # GSPMD's full-width layout-change all-to-all
                from ..moe.layer import routed_ffn_ep

                h, aux = routed_ffn_ep(lw["moe"], y, cfg, mesh,
                                       fmt=cfg.moe_qcomm)
            else:
                from ..moe.layer import moe_block as _moe

                h, aux = _moe(lw["moe"], y, cfg)
        else:
            h = mlp_block(lw["mlp"], tp_in(y), cfg)
        if tp_axis is not None:
            h = _tp_psum_fn(tp_axis)(h)  # row-parallel w_down partial sums
    x = shard_activation(x + h.astype(dtype), ACT_SPEC)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------
def _get_attn_fn(cfg: TransformerConfig) -> Callable:
    from ..ops.attention import get_attention_impl

    if cfg.position == "alibi":
        # additive [h, sq, skv] bias exists only in the reference attention
        # body; the flash/sparse/SP paths have no bias operand yet
        if cfg.attn_impl not in ("reference", "math") or (
            cfg.sparse_attention is not None or cfg.sequence_parallel != "none"
        ):
            raise NotImplementedError(
                "position='alibi' requires attn_impl='reference' without "
                "sparse attention or sequence parallelism"
            )
    if cfg.sparse_attention is not None:
        import functools as _ft

        from ..ops.sparse_attention import block_sparse_attention

        base = _ft.partial(block_sparse_attention, config=cfg.sparse_attention)
    else:
        base = get_attention_impl(cfg.attn_impl)
    if cfg.sequence_parallel == "ulysses":
        from ..sequence.layer import DistributedAttention

        return DistributedAttention(base)
    if cfg.sequence_parallel == "ring":
        from ..sequence.ring import ring_attention

        return ring_attention
    return base


def forward(
    params: Params,
    tokens: jnp.ndarray,
    cfg: TransformerConfig,
    positions: Optional[jnp.ndarray] = None,
    segment_ids: Optional[jnp.ndarray] = None,
    cache: Optional[Params] = None,
    cache_index: Optional[jnp.ndarray] = None,
    return_hidden: bool = False,
    stack_apply: Optional[Callable] = None,
    layer_keep: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Optional[Params], jnp.ndarray]:
    """tokens [b, s] -> (logits [b, s, v] | hidden, new_cache, moe_aux_loss).

    The L layers run as one ``lax.scan`` over the stacked layer params; the
    scanned body is optionally wrapped in ``jax.checkpoint`` per ``cfg.remat``.
    ``stack_apply(layer_params, x, positions, segment_ids) -> x`` overrides
    the decoder stack execution (the pipeline-parallel executor hooks in
    here); caches are unsupported on that path.
    """
    if cfg.latent is not None:
        from . import latent

        for option, given in (("cache", cache), ("stack_apply", stack_apply),
                              ("layer_keep", layer_keep), ("segment_ids", segment_ids),
                              ("positions", positions)):
            if given is not None:
                latent.refuse(f"forward({option}=...)", "the uncached forward takes "
                              "whole sequences from position 0; serving goes through "
                              "InferenceEngineV2")
        return latent.forward(params, tokens, cfg, return_hidden=return_hidden)
    attn_fn = _get_attn_fn(cfg)
    b, s = tokens.shape
    if positions is None:
        base = cache_index if cache_index is not None else 0
        positions = jnp.arange(s)[None, :] + base
        positions = jnp.broadcast_to(positions, (b, s))
    with jax.named_scope("embed"):
        x = params["embed"]["embedding"][tokens].astype(cfg.dtype)
        if cfg.position == "learned":
            x = x + params["pos_embed"]["embedding"][positions].astype(cfg.dtype)
        if cfg.embedding_norm:
            # bloom word_embeddings_layernorm (module_inject containers/bloom)
            x = norm(x, params["embed_norm"], cfg.norm, cfg.norm_eps)
        x = shard_activation(x, ACT_SPEC)

    if stack_apply is not None:
        if layer_keep is not None:
            raise NotImplementedError(
                "layer_keep (progressive layer drop) is not supported on the "
                "stack_apply/pipelined path"
            )
        out = stack_apply(params["layers"], x, positions, segment_ids)
        # pipelined stacks return (x, moe_aux_loss); plain ones just x
        x, aux_loss = out if isinstance(out, tuple) else (
            out, jnp.asarray(0.0, jnp.float32)
        )
        new_caches = None
    else:
        # Domino-style TP overlap (reference runtime/domino/transformer.py:18):
        # split the batch into C independent chunks INSIDE the layer-scan
        # body.  Each chunk's ops form an independent dataflow, so XLA's
        # latency-hiding scheduler can run chunk B's matmuls while chunk A's
        # row-parallel activation all-reduce rides the ICI — the overlap a
        # single-chunk body cannot offer (the allreduce sits on the one
        # critical path; measured sync in the TP=8 HLO, README).  Chunking
        # at the top of the scan (not two scans) matters: while loops are
        # scheduling barriers, one loop body is not.
        C = cfg.domino_chunks if cache is None else 1
        if C > 1 and cfg.moe_num_experts > 0:
            raise ValueError(
                "domino_chunks does not compose with MoE (per-chunk routing "
                "capacity changes token dropping)"
            )
        if C > 1 and b % C:
            C = 1  # indivisible batch: fall back to the single-chunk body

        def body(carry, scanned):
            h = carry
            lw, layer_cache, keep = scanned

            def run_layer(h):
                if C > 1:
                    outs = []
                    auxs = []
                    bc = b // C
                    for c in range(C):
                        sl = slice(c * bc, (c + 1) * bc)
                        h_c, _, aux_c = decoder_layer(
                            lw, h[sl], cfg, positions[sl], attn_fn,
                            segment_ids[sl] if segment_ids is not None else None,
                            None, None,
                        )
                        outs.append(h_c)
                        auxs.append(aux_c)
                    # per-chunk aux are means over their rows; equal-size
                    # chunks -> plain mean preserves the dense semantics
                    return (
                        jnp.concatenate(outs, axis=0), None,
                        jnp.mean(jnp.stack(auxs)),
                    )
                return decoder_layer(
                    lw, h, cfg, positions, attn_fn, segment_ids, layer_cache,
                    cache_index,
                )

            if keep is None:
                h_new, new_cache, aux = run_layer(h)
            else:
                # progressive layer drop (runtime/progressive_layer_drop.py):
                # a dropped layer is the identity.  lax.cond executes ONE
                # branch at runtime, so dropped layers skip their compute —
                # the training-speed tradeoff PLD exists for ('the lower the
                # theta, the faster the training', reference PLD post)
                def skipped(h):
                    return h, layer_cache, jnp.asarray(0.0, jnp.float32)

                h_new, new_cache, aux = jax.lax.cond(
                    keep > 0, run_layer, skipped, h
                )
            return h_new, (new_cache, aux)

        body = checkpointed(body, cfg.remat)

        layer_params = params["layers"]
        x, (new_caches, aux_losses) = jax.lax.scan(
            body, x, (layer_params, cache, layer_keep)
        )
        aux_loss = jnp.sum(aux_losses)

    x = norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    if return_hidden:
        return x, new_caches, aux_loss
    with jax.named_scope("head"):
        logits = x @ head_kernel(params, cfg)
        hb = head_bias_vec(params)
        if hb is not None:
            logits = logits + hb
    return logits, new_caches, aux_loss


def head_kernel(params: Params, cfg: TransformerConfig) -> jnp.ndarray:
    """[d, v] output projection (transposed embedding when tied)."""
    if cfg.tie_embeddings:
        return params["embed"]["embedding"].T.astype(cfg.dtype)
    return params["lm_head"]["kernel"]


def head_bias_vec(params: Params):
    """[v] lm_head bias (gptj/phi) or None."""
    lm = params.get("lm_head") if isinstance(params, dict) else None
    return lm.get("bias") if lm else None


def init_kv_cache(cfg: TransformerConfig, batch: int, max_len: int, dtype=None) -> Tuple:
    """Stacked KV cache for autoregressive decode: ([L,b,S,hkv,hd], same)."""
    dtype = dtype or cfg.dtype
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, cfg.hd)
    return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def cross_entropy_loss(
    logits: jnp.ndarray, labels: jnp.ndarray, ignore_index: int = -100
) -> jnp.ndarray:
    """Token-mean causal-LM loss in fp32; positions == ignore_index masked."""
    mask = (labels != ignore_index).astype(jnp.float32)
    safe = jnp.where(labels == ignore_index, 0, labels)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)


class CausalLM:
    """Model adapter consumed by ``deepspeed_tpu.initialize(model=...)``.

    Exposes ``loss_fn(params, batch, rng)``, ``init_params(rng)``,
    ``tp_rules`` — the contract in deepspeed_tpu/__init__.py.
    Batch: {'input_ids': [b, s]} (labels = shifted inputs) or
    {'input_ids', 'labels'} for pre-shifted data.
    """

    def __init__(self, cfg: TransformerConfig, stack_apply: Optional[Callable] = None):
        self.cfg = cfg
        self.stack_apply = stack_apply

    def init_params(self, rng) -> Params:
        return init_params(rng, self.cfg)

    def apply(self, params, tokens, **kw):
        return forward(params, tokens, self.cfg, **kw)

    def prepare_batch(self, batch, rng=None):
        """Batch preprocessing shared by ``loss_fn`` and the KD loss
        (compression/compress.py make_kd_loss_fn): label shift / segment
        trim, and the progressive-layer-drop keep mask when the engine
        injected a traced theta.  Returns (inputs, labels, segment_ids,
        layer_keep)."""
        tokens = batch["input_ids"]
        segment_ids = batch.get("segment_ids")
        # progressive layer drop: the engine injects a traced per-step theta
        # under this key (runtime/engine.py PLD wiring; reference
        # engine.py:1959 progressive_layer_drop.update_state)
        pld_theta = batch.get("pld_theta") if hasattr(batch, "get") else None
        layer_keep = None
        if pld_theta is not None:
            from ..runtime.progressive_layer_drop import layer_keep_mask

            krng = rng if rng is not None else jax.random.PRNGKey(0)
            layer_keep = layer_keep_mask(
                jax.random.fold_in(krng, 0x91D), self.cfg.num_layers, pld_theta
            )
        if "labels" in batch:
            inputs, labels = tokens, batch["labels"]
        else:
            inputs, labels = tokens[:, :-1], tokens[:, 1:]
            if segment_ids is not None:
                segment_ids = segment_ids[:, :-1]
        return inputs, labels, segment_ids, layer_keep

    def loss_fn(self, params, batch, rng=None):
        return self.loss_and_picks(params, batch, rng)[0]

    def loss_and_picks(self, params, batch, rng=None):
        """(the loss ``loss_fn`` returns, the experts each expert layer's router
        picked on the way: ``models/latent.py: forward``'s second result, None
        for a model that has none).  Selection is discontinuous: who compares a
        gradient with another implementation's holds the other to these picks."""
        inputs, labels, segment_ids, layer_keep = self.prepare_batch(batch, rng)
        if self.cfg.loss_chunk_size:
            from ..sequence.cross_entropy import chunked_cross_entropy

            hidden, picks, aux = forward(
                params, inputs, self.cfg, segment_ids=segment_ids,
                return_hidden=True, stack_apply=self.stack_apply,
                layer_keep=layer_keep,
            )
            with jax.named_scope("loss"):  # the head matmul rides the chunks
                loss = chunked_cross_entropy(
                    hidden, head_kernel(params, self.cfg), labels,
                    chunk_size=self.cfg.loss_chunk_size,
                    head_bias=head_bias_vec(params),
                )
        else:
            logits, picks, aux = forward(
                params, inputs, self.cfg, segment_ids=segment_ids,
                stack_apply=self.stack_apply, layer_keep=layer_keep,
            )
            with jax.named_scope("loss"):
                loss = cross_entropy_loss(logits, labels)
        if self.cfg.moe_num_experts > 0:
            loss = loss + self.cfg.moe_aux_loss_coef * aux / max(self.cfg.num_layers, 1)
        spec = self.cfg.latent
        if spec is not None and spec.router_aux_loss_coef:
            # the routers' balance term, a mean over the layers that hold experts
            loss = loss + spec.router_aux_loss_coef * aux / len(spec.expert_layers)
        return loss, picks if spec is not None else None

    @property
    def tp_rules(self):
        return tp_rules(self.cfg)

    @property
    def param_count(self) -> int:
        return self.cfg.param_count

    def flops_per_token(self, seq_len: int) -> float:
        """Approximate training FLOPs/token (6N + attention quadratic term)."""
        c = self.cfg
        if c.latent is not None:
            from .latent import flops_per_token

            return flops_per_token(c, seq_len)
        n = c.param_count
        attn = 12 * c.num_layers * c.hidden_size * seq_len
        return 6.0 * n + attn


def tp_rules(cfg: TransformerConfig):
    """Megatron-style tensor-parallel rules over the stacked param tree.

    Column-parallel (output dim on ``model``): wq/wk/wv, w_gate/w_up.
    Row-parallel (input dim on ``model``): wo, w_down.  Embedding and head
    shard the vocab dim.  The leading dim of layer weights is the layer dim
    (scanned), never sharded.  Replaces AutoTP (module_inject/auto_tp.py:193).
    """
    if cfg.latent is not None:
        return []  # no rule is written for layers of several kinds (initialize refuses model > 1)
    moe = cfg.moe_num_experts > 0
    rules = [
        (r"layers/attn/w[qkv]$", P(None, None, MODEL_AXIS)),
        (r"layers/attn/b[qkv]$", P(None, MODEL_AXIS)),
        (r"layers/attn/wo$", P(None, MODEL_AXIS, None)),
        (r"embed/embedding$", P(MODEL_AXIS, None)),
        (r"lm_head/kernel$", P(None, MODEL_AXIS)),
    ]
    if moe:
        rules += [
            (r"layers/moe/w_(gate|up)$", P(None, "expert", None, MODEL_AXIS)),
            (r"layers/moe/w_down$", P(None, "expert", MODEL_AXIS, None)),
        ]
    else:
        rules += [
            (r"layers/mlp/w_(gate|up)$", P(None, None, MODEL_AXIS)),
            (r"layers/mlp/w_down$", P(None, MODEL_AXIS, None)),
            # col-parallel biases shard with their output dim; bo/b_down
            # (row-parallel outputs) stay replicated by the default rule
            (r"layers/mlp/b_(gate|up)$", P(None, MODEL_AXIS)),
        ]
    return rules
