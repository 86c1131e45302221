"""Hybrid engine, ZeRO-Inference, and AutoTP inference tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.inference.sampling import SamplingParams
from deepspeed_tpu.models import CausalLM, get_preset
from deepspeed_tpu.runtime.hybrid_engine import DeepSpeedHybridEngine


def _train_engine(model=None):
    cfg = get_preset("tiny", max_seq_len=64).replace(dtype=jnp.float32)
    model = model or CausalLM(cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 5e-3}},
        },
        mesh=deepspeed_tpu.initialize_mesh(data=8),
    )
    return engine, model, cfg


def test_hybrid_train_generate_loop():
    """The RLHF loop: generate -> train -> generate; generations reflect the
    updated weights without rebuilding the serving engine."""
    engine, model, cfg = _train_engine()
    hybrid = DeepSpeedHybridEngine(engine, max_seqs=4, num_blocks=64, block_size=8)
    rng = np.random.default_rng(0)
    prompt = list(map(int, rng.integers(1, 250, 9)))
    greedy = SamplingParams(max_new_tokens=6, temperature=0.0)

    out0 = hybrid.generate(prompt, greedy)
    assert len(out0) == 6
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (16, 65)).astype(np.int32)}
    for _ in range(5):
        hybrid.train_batch(batch)  # delegation
    out1 = hybrid.generate(prompt, greedy)
    assert len(out1) == 6
    assert out0 != out1  # weights moved, generations follow
    # deterministic for fixed weights
    assert hybrid.generate(prompt, greedy) == out1


def test_hybrid_generate_batch_matches_single():
    engine, model, cfg = _train_engine()
    hybrid = DeepSpeedHybridEngine(engine, max_seqs=4, num_blocks=64, block_size=8)
    rng = np.random.default_rng(1)
    prompts = [list(map(int, rng.integers(1, 250, n))) for n in (5, 9, 13)]
    greedy = SamplingParams(max_new_tokens=5, temperature=0.0)
    batched = hybrid.generate_batch(prompts, greedy)
    singles = [hybrid.generate(p, greedy) for p in prompts]
    assert batched == singles


def test_hybrid_with_lora_merges_before_generate():
    from deepspeed_tpu.linear import LoRACausalLM, LoRAConfig

    cfg = get_preset("tiny", max_seq_len=64).replace(dtype=jnp.float32)
    model = LoRACausalLM(CausalLM(cfg), LoRAConfig(lora_r=4))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 5e-3}},
        },
        mesh=deepspeed_tpu.initialize_mesh(data=8),
    )
    hybrid = DeepSpeedHybridEngine(engine, max_seqs=2, num_blocks=64, block_size=8)
    rng = np.random.default_rng(2)
    out = hybrid.generate(list(map(int, rng.integers(1, 250, 7))),
                          SamplingParams(max_new_tokens=4, temperature=0.0))
    assert len(out) == 4


def test_zero_inference_weight_offload():
    """offload_weights: host-resident params, identical generations."""
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2

    cfg = get_preset("tiny", max_seq_len=64).replace(dtype=jnp.float32)
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32),
        CausalLM(cfg).init_params(jax.random.PRNGKey(0)),
    )
    rng = np.random.default_rng(3)
    prompt = list(map(int, rng.integers(1, 250, 9)))
    greedy = SamplingParams(max_new_tokens=6, temperature=0.0)

    plain = InferenceEngineV2(params, cfg, max_seqs=2, num_blocks=64, block_size=8)
    off = InferenceEngineV2(params, cfg, max_seqs=2, num_blocks=64, block_size=8,
                            offload_weights=True)
    assert plain.generate(prompt, greedy) == off.generate(prompt, greedy)


def test_auto_tp_rule_inference_on_model_tree():
    from deepspeed_tpu.parallel.auto_tp import infer_tp_rules
    from deepspeed_tpu.runtime.zero import match_rules

    cfg = get_preset("tiny")
    shapes = jax.eval_shape(
        lambda k: CausalLM(cfg).init_params(k), jax.random.PRNGKey(0)
    )
    rules = infer_tp_rules(shapes, model_axis_size=2, vocab_size=cfg.vocab_size)
    by = {}
    for kp, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)
        by[path] = match_rules(path, tuple(leaf.shape), rules)
    # column-parallel: qkv + gate/up shard output dim
    assert by["layers/attn/wq"] == P(None, None, "model")
    assert by["layers/mlp/w_gate"] == P(None, None, "model")
    # row-parallel: wo + w_down shard input dim
    assert by["layers/attn/wo"] == P(None, "model", None)
    assert by["layers/mlp/w_down"] == P(None, "model", None)
    # embedding: vocab dim
    assert by["embed/embedding"] == P("model", None)
    # norms replicate
    assert by["final_norm/scale"] == P(None)


def test_auto_tp_rules_on_foreign_tree():
    """Arbitrary (HF-style-named) pytree — the reference AutoTP use case."""
    from deepspeed_tpu.parallel.auto_tp import infer_tp_rules
    from deepspeed_tpu.runtime.zero import match_rules

    tree = {
        "h": {
            "attn": {"q_proj": jnp.zeros((64, 64)), "o_proj": jnp.zeros((64, 64))},
            "mlp": {"fc1": jnp.zeros((64, 128)), "fc2": jnp.zeros((128, 64)),
                    "fc1_bias": jnp.zeros((128,))},
            "ln": {"weight": jnp.zeros((64,))},
        }
    }
    rules = infer_tp_rules(tree, model_axis_size=4)
    get = lambda p, s: match_rules(p, s, rules)
    assert get("h/attn/q_proj", (64, 64)) == P(None, "model")
    assert get("h/attn/o_proj", (64, 64)) == P("model", None)
    assert get("h/mlp/fc2", (128, 64)) == P("model", None)
    assert get("h/mlp/fc1", (64, 128)) == P(None, "model")
    assert get("h/mlp/fc1_bias", (128,)) == P("model")
    assert get("h/ln/weight", (64,)) == P(None)


def test_auto_tp_indivisible_dims_replicate():
    from deepspeed_tpu.parallel.auto_tp import infer_tp_rules

    tree = {"w": jnp.zeros((7, 13))}  # nothing divides 4
    assert infer_tp_rules(tree, model_axis_size=4) == []


def test_auto_tp_head_divisibility_gates_attention_shards():
    """Attention projections shard at HEAD granularity only: with
    num_kv_heads=2 on a 4-way model axis, wk/wv (and their biases) must
    replicate even though their fan_out (hkv*hd=32) divides 4 — sub-head
    sharding slices head_dim across shards, which rope/paged-attention
    consumers cannot survive (the root cause of the historical tp=4 token-
    parity failure).  wq keeps sharding (4 heads / 4 shards = whole heads),
    and without hints the shape-only heuristic is unchanged."""
    from deepspeed_tpu.parallel.auto_tp import infer_tp_rules
    from deepspeed_tpu.runtime.zero import match_rules

    tree = {
        "layers": {"attn": {
            "wq": jnp.zeros((3, 64, 64)), "wk": jnp.zeros((3, 64, 32)),
            "wv": jnp.zeros((3, 64, 32)), "wo": jnp.zeros((3, 64, 64)),
            "bk": jnp.zeros((3, 32)),
        }},
    }
    rules = infer_tp_rules(tree, model_axis_size=4, num_heads=4,
                           num_kv_heads=2)
    get = lambda p, s: match_rules(p, s, rules)
    assert get("layers/attn/wq", (3, 64, 64)) == P(None, None, "model")
    assert get("layers/attn/wk", (3, 64, 32)) == P(None, None, None)
    assert get("layers/attn/wv", (3, 64, 32)) == P(None, None, None)
    assert get("layers/attn/bk", (3, 32)) == P(None, None)
    assert get("layers/attn/wo", (3, 64, 64)) == P(None, "model", None)
    # no hints: the pure shape heuristic still shards (back-compat)
    loose = infer_tp_rules(tree, model_axis_size=4)
    assert match_rules("layers/attn/wk", (3, 64, 32), loose) \
        == P(None, None, "model")
    # num_heads gates q too (hq=2 on a 4-way axis -> replicate)
    qgate = infer_tp_rules(tree, model_axis_size=4, num_heads=2,
                           num_kv_heads=2)
    assert match_rules("layers/attn/wq", (3, 64, 64), qgate) \
        == P(None, None, None)


def test_auto_tp_quantized_scales_shard_with_col_kernels():
    """ServingQuant trees: the per-output-channel scale rides its kernel —
    sharded for column-parallel layers (the fused epilogue reads only local
    channels), replicated for row-parallel ones (out dim unsharded)."""
    from deepspeed_tpu.ops.quantizer import quantize_serving_params
    from deepspeed_tpu.parallel.auto_tp import infer_tp_rules
    from deepspeed_tpu.runtime.zero import match_rules

    cfg = get_preset("tiny")
    params = CausalLM(cfg).init_params(jax.random.PRNGKey(0))
    qparams = quantize_serving_params(params, "int8")
    rules = infer_tp_rules(qparams, model_axis_size=2,
                           vocab_size=cfg.vocab_size,
                           num_heads=cfg.num_heads,
                           num_kv_heads=cfg.num_kv_heads)
    by = {}
    for kp, leaf in jax.tree_util.tree_flatten_with_path(qparams)[0]:
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k))))
                        for k in kp)
        by[path] = match_rules(path, tuple(leaf.shape), rules)
    assert by["layers/attn/wq/q"] == P(None, None, "model")
    assert by["layers/attn/wq/s"] == P(None, "model")
    assert by["layers/mlp/w_up/s"] == P(None, "model")
    # row-parallel kernels shard in-features; their scales replicate
    assert by["layers/attn/wo/q"] == P(None, "model", None)
    assert by["layers/attn/wo/s"] == P(None, None)
    assert by["layers/mlp/w_down/s"] == P(None, None)
    # vocab-sharded head: scale follows the sharded out (vocab) dim
    assert by["lm_head/kernel/q"] == P(None, "model")
    assert by["lm_head/kernel/s"] == P("model")
