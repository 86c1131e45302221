"""Named architecture presets covering the reference's supported families.

The reference enumerates supported model families in its kernel-injection
policies (module_inject/containers/: bert, bloom, gpt2, gptj, gptneox, llama,
llama2, opt, megatron, ...) and inference-v2 implementations
(inference/v2/model_implementations/{llama_v2,mistral,mixtral,falcon,opt,phi,
qwen,...}).  Here each family is a ``TransformerConfig`` preset; smaller
"*_proxy" configs keep the exact architecture shape but scale width/depth for
single-chip benchmarking and tests.
"""
from __future__ import annotations

from .transformer import TransformerConfig

_REGISTRY = {}


def register(name: str, cfg: TransformerConfig) -> TransformerConfig:
    _REGISTRY[name] = cfg
    return cfg


def get_preset(name: str, **overrides) -> TransformerConfig:
    cfg = _REGISTRY[name]
    return cfg.replace(**overrides) if overrides else cfg


def list_presets():
    return sorted(_REGISTRY)


# --- Llama family (RMSNorm + RoPE + SwiGLU (+GQA for v3)) -------------------
register("llama2_7b", TransformerConfig(
    vocab_size=32000, hidden_size=4096, intermediate_size=11008, num_layers=32,
    num_heads=32, num_kv_heads=32, max_seq_len=4096, rope_theta=10_000.0,
    remat="dots", attn_impl="auto"))
register("llama3_8b", TransformerConfig(
    vocab_size=128256, hidden_size=4096, intermediate_size=14336, num_layers=32,
    num_heads=32, num_kv_heads=8, max_seq_len=8192, rope_theta=500_000.0,
    remat="dots", attn_impl="auto"))
register("llama3_70b", TransformerConfig(
    vocab_size=128256, hidden_size=8192, intermediate_size=28672, num_layers=80,
    num_heads=64, num_kv_heads=8, max_seq_len=8192, rope_theta=500_000.0,
    remat="full", attn_impl="auto"))

# ~410M-param Llama-3-shaped proxy: same GQA ratio (4:1) and the real
# Llama-3 head_dim of 128 (MXU-native: fills the 128-deep systolic array;
# hd=64 halves attention-matmul efficiency), RMSNorm/SwiGLU/RoPE, fits one
# v5e chip with fp32 masters + Adam state.
register("llama3_proxy_410m", TransformerConfig(
    vocab_size=32128, hidden_size=1024, intermediate_size=4096, num_layers=24,
    num_heads=8, num_kv_heads=2, max_seq_len=4096, rope_theta=500_000.0,
    remat="selective", attn_impl="auto"))

# --- Mistral / Mixtral ------------------------------------------------------
register("mistral_7b", TransformerConfig(
    vocab_size=32000, hidden_size=4096, intermediate_size=14336, num_layers=32,
    num_heads=32, num_kv_heads=8, max_seq_len=8192, rope_theta=10_000.0,
    remat="dots", attn_impl="auto"))
register("mixtral_8x7b", TransformerConfig(
    vocab_size=32000, hidden_size=4096, intermediate_size=14336, num_layers=32,
    num_heads=32, num_kv_heads=8, max_seq_len=8192, rope_theta=1_000_000.0,
    moe_num_experts=8, moe_top_k=2, remat="full", attn_impl="auto"))

# --- GPT-2 (LayerNorm + learned positions + GELU, tied embeddings) ----------
register("gpt2_small", TransformerConfig(
    vocab_size=50257, hidden_size=768, intermediate_size=3072, num_layers=12,
    num_heads=12, num_kv_heads=12, max_seq_len=1024, norm="layernorm",
    activation="gelu", gated_mlp=False, position="learned", tie_embeddings=True))

# --- Qwen-2 style (qkv bias) ------------------------------------------------
register("qwen2_7b", TransformerConfig(
    vocab_size=152064, hidden_size=3584, intermediate_size=18944, num_layers=28,
    num_heads=28, num_kv_heads=4, max_seq_len=8192, rope_theta=1_000_000.0,
    qkv_bias=True, remat="dots", attn_impl="auto"))

# --- Falcon (parallel attn+MLP, MQA, no biases) -----------------------------
register("falcon_7b", TransformerConfig(
    vocab_size=65024, hidden_size=4544, intermediate_size=18176, num_layers=32,
    num_heads=71, num_kv_heads=1, head_dim=64, max_seq_len=2048,
    norm="layernorm", activation="gelu", gated_mlp=False,
    parallel_block=True, rope_theta=10_000.0,
    remat="dots", attn_impl="auto"))

# --- GPT-J (parallel block, partial rotary, mlp biases) ---------------------
register("gptj_6b", TransformerConfig(
    vocab_size=50400, hidden_size=4096, intermediate_size=16384, num_layers=28,
    num_heads=16, num_kv_heads=16, max_seq_len=2048, rotary_dim=64,
    norm="layernorm", activation="gelu", gated_mlp=False,
    parallel_block=True, mlp_bias=True, rope_theta=10_000.0,
    remat="dots", attn_impl="auto"))

# --- Phi-2 (parallel block, partial rotary, biases everywhere) --------------
register("phi_2", TransformerConfig(
    vocab_size=51200, hidden_size=2560, intermediate_size=10240, num_layers=32,
    num_heads=32, num_kv_heads=32, max_seq_len=2048, rotary_dim=32,
    norm="layernorm", activation="gelu", gated_mlp=False,
    parallel_block=True, qkv_bias=True, attn_out_bias=True, mlp_bias=True,
    rope_theta=10_000.0, remat="dots", attn_impl="auto"))

# --- GPT-NeoX-20B (parallel residual, rotary_pct=0.25, biases) --------------
register("gpt_neox_20b", TransformerConfig(
    vocab_size=50432, hidden_size=6144, intermediate_size=24576, num_layers=44,
    num_heads=64, num_kv_heads=64, max_seq_len=2048, rotary_dim=24,
    norm="layernorm", activation="gelu", gated_mlp=False,
    parallel_block=True, qkv_bias=True, attn_out_bias=True, mlp_bias=True,
    rope_theta=10_000.0, remat="full", attn_impl="auto"))

# --- Bloom (ALiBi, embedding LN, all biases, tied) --------------------------
register("bloom_7b1", TransformerConfig(
    vocab_size=250880, hidden_size=4096, intermediate_size=16384, num_layers=30,
    num_heads=32, num_kv_heads=32, max_seq_len=2048, position="alibi",
    norm="layernorm", activation="gelu", gated_mlp=False,
    qkv_bias=True, attn_out_bias=True, mlp_bias=True, embedding_norm=True,
    tie_embeddings=True, remat="dots", attn_impl="reference"))

# --- OPT (learned positions, ReLU, all biases, tied) ------------------------
register("opt_6_7b", TransformerConfig(
    vocab_size=50272, hidden_size=4096, intermediate_size=16384, num_layers=32,
    num_heads=32, num_kv_heads=32, max_seq_len=2048, position="learned",
    norm="layernorm", activation="relu", gated_mlp=False,
    qkv_bias=True, attn_out_bias=True, mlp_bias=True, tie_embeddings=True,
    remat="dots", attn_impl="auto"))

# --- tiny configs for tests -------------------------------------------------
register("tiny", TransformerConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
    num_heads=4, num_kv_heads=2, max_seq_len=128))
register("tiny_moe", TransformerConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
    num_heads=4, num_kv_heads=2, max_seq_len=128,
    moe_num_experts=4, moe_top_k=2))
register("tiny_gpt2", TransformerConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
    num_heads=4, num_kv_heads=4, max_seq_len=128, norm="layernorm",
    activation="gelu", gated_mlp=False, position="learned", tie_embeddings=True))
register("tiny_parallel", TransformerConfig(
    # falcon/phi-shaped: parallel block, partial rotary, biases
    vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
    num_heads=4, num_kv_heads=4, max_seq_len=128, rotary_dim=8,
    norm="layernorm", activation="gelu", gated_mlp=False,
    parallel_block=True, qkv_bias=True, attn_out_bias=True, mlp_bias=True))
register("tiny_alibi", TransformerConfig(
    # bloom-shaped: alibi + embedding LN + biases, tied
    vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
    num_heads=4, num_kv_heads=4, max_seq_len=128, position="alibi",
    norm="layernorm", activation="gelu", gated_mlp=False,
    qkv_bias=True, attn_out_bias=True, mlp_bias=True, embedding_norm=True,
    tie_embeddings=True, attn_impl="reference"))


def _tiny_parallel_mixers() -> TransformerConfig:
    """Blocks of TWO PARALLEL MIXERS on one normed input (``models/latent.py``:
    ``par``; Falcon-H1's block, https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct):
    a Mamba-2 recurrence (2 groups, a state wider than its head) beside GQA at a
    group of 5 with rotary over the whole head, a dense SwiGLU, a constant
    multiplier on every projection.  For the CPU tests only: served through
    ``InferenceEngineV2`` (a recurrence's state AND K / V pages for every layer),
    forward through ``CausalLM``; no backward."""
    from .latent import Gqa, LatentSpec, Mamba

    n = 2
    spec = LatentSpec(
        layer_kinds=("par",) * n, full=None, sliding=None, index_heads=0, index_dim=0,
        index_topk=0, first_dense=n, n_routed=0, n_held=0, held_offset=0, experts_per_tok=0,
        moe_width=0, n_shared=0,
        mamba=Mamba(num_heads=8, head_dim=8, n_groups=2, state=16, conv=4, chunk=8,
                    in_multiplier=0.8, multipliers=(0.9, 0.8, 0.7, 0.6, 0.5), out_multiplier=0.5),
        gqa=Gqa(num_heads=10, num_kv_heads=2, head_dim=16, rope_theta=10_000.0,
                in_multiplier=0.9, key_multiplier=0.7, out_multiplier=0.5),
        embedding_multiplier=4.0, mlp_gate_multiplier=0.9, mlp_down_multiplier=0.4, fp32_logits=True)
    return TransformerConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=n, num_heads=10,
        num_kv_heads=2, head_dim=16, max_seq_len=128, norm_eps=1e-5, latent=spec)


register("tiny_parallel_mixers", _tiny_parallel_mixers())


def _tiny_block_mixers() -> TransformerConfig:
    """Two-norm blocks whose mixer is chosen BY BLOCK (``models/latent.py``:
    ``LatentSpec.two_norms``; Granite 4.0-H's block,
    https://huggingface.co/ibm-granite/granite-4.0-h-small): a Mamba-2 recurrence
    (one group) in three blocks of four and position-free GQA in the fourth, held
    SwiGLU experts (4 of 8, softmax top-3) and a shared one behind every mixer, one
    constant on both residual branches, a softmax scale that is no ``head_dim **
    -0.5``, a tied head.  For the CPU tests only: served through
    ``InferenceEngineV2`` (three states and one layer of pages a slot), forward
    through ``CausalLM``; no backward."""
    from .latent import Gqa, LatentSpec, Mamba

    kinds = ("mamba", "mamba", "gqa", "mamba")
    spec = LatentSpec(
        layer_kinds=kinds, full=None, sliding=None, index_heads=0, index_dim=0, index_topk=0,
        first_dense=0, n_routed=8, n_held=4, held_offset=0, experts_per_tok=3, moe_width=32,
        n_shared=1, shared_width=48, routing="softmax", two_norms=True,
        mamba=Mamba(num_heads=8, head_dim=8, n_groups=1, state=16, conv=4, chunk=8),
        gqa=Gqa(num_heads=4, num_kv_heads=2, head_dim=16, scale=0.11),
        embedding_multiplier=3.0, logits_multiplier=0.25, residual_multiplier=0.6,
        fp32_logits=True)
    return TransformerConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=len(kinds),
        num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=128, norm_eps=1e-5,
        tie_embeddings=True, latent=spec)


register("tiny_block_mixers", _tiny_block_mixers())
