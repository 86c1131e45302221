"""From a configuration file's published keys (the model's own ``config.json``
names) to the program's ``TransformerConfig``: the configuration AS IT IS RUN
is the file, not a preset of the program."""
from __future__ import annotations


def transformer_config(model: dict, **overrides):
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer import TransformerConfig

    if model.get("hidden_act", "silu") != "silu":
        raise ValueError("only the SwiGLU block is mapped here")
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    kw = dict(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        num_layers=model["num_hidden_layers"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        head_dim=model.get("head_dim"),
        max_seq_len=model["max_position_embeddings"],
        rope_theta=float(model["rope_theta"]), norm_eps=float(model["rms_norm_eps"]),
        tie_embeddings=bool(model.get("tie_word_embeddings", False)),
        norm="rmsnorm", activation="silu", gated_mlp=True, position="rope",
        dtype=dtypes[model["torch_dtype"]], attn_impl="auto",
    )
    kw.update(overrides)
    return TransformerConfig(**kw)
