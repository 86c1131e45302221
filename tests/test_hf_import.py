"""HF safetensors import: logits parity with transformers + round-trip.

Mirrors the reference's inference checkpoint-loading coverage
(``tests/unit/inference/test_checkpoint_sharding.py`` /
``test_inference.py`` HF-model sweep): weights imported from an HF
checkpoint must reproduce the HF model's logits.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from deepspeed_tpu.checkpoint.hf_import import (
    config_from_hf,
    export_hf_checkpoint,
    load_hf_checkpoint,
)
from deepspeed_tpu.models.transformer import CausalLM, forward


def _tiny_llama_dir(tmp_path, tie=False):
    cfg = transformers.LlamaConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=128,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
        tie_word_embeddings=tie,
        torch_dtype="float32",
    )
    torch.manual_seed(0)
    model = transformers.LlamaForCausalLM(cfg)
    d = str(tmp_path / "hf_model")
    model.save_pretrained(d, safe_serialization=True)
    return d, model


# slow: 11-17 s (50 s on a loaded machine): imports torch and transformers and runs HF's own Llama as the reference
@pytest.mark.slow
def test_llama_logits_parity(tmp_path):
    d, hf_model = _tiny_llama_dir(tmp_path)
    params, cfg = load_hf_checkpoint(d)
    assert cfg.num_kv_heads == 2 and cfg.num_layers == 2

    x = np.array([[1, 5, 9, 42, 99, 3]], dtype=np.int32)
    with torch.no_grad():
        ref = hf_model(torch.tensor(x, dtype=torch.long)).logits.numpy()
    got, _, _ = forward(params, jnp.asarray(x), cfg.replace(dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(got, np.float32), ref, rtol=2e-4, atol=2e-4)


def test_tied_embeddings_import(tmp_path):
    d, hf_model = _tiny_llama_dir(tmp_path, tie=True)
    params, cfg = load_hf_checkpoint(d)
    assert cfg.tie_embeddings and "lm_head" not in params
    x = np.array([[7, 2, 64]], dtype=np.int32)
    with torch.no_grad():
        ref = hf_model(torch.tensor(x, dtype=torch.long)).logits.numpy()
    got, _, _ = forward(params, jnp.asarray(x), cfg.replace(dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(got, np.float32), ref, rtol=2e-4, atol=2e-4)


def test_export_round_trip(tmp_path):
    d, _ = _tiny_llama_dir(tmp_path)
    params, cfg = load_hf_checkpoint(d)
    out = str(tmp_path / "exported")
    export_hf_checkpoint(params, cfg, out)
    params2, cfg2 = load_hf_checkpoint(out)
    assert cfg2.hidden_size == cfg.hidden_size
    for a, b in zip(
        jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(params2)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_hf_serves_through_engine_v2(tmp_path):
    """VERDICT item 3: tiny-llama loads and serves through InferenceEngineV2;
    greedy decode must match HF's greedy continuation."""
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.inference.sampling import SamplingParams

    d, hf_model = _tiny_llama_dir(tmp_path)
    eng = InferenceEngineV2.from_hf(d, dtype=jnp.float32, max_seqs=2, block_size=8)
    prompt = [3, 17, 31, 8]
    ours = eng.generate(prompt, SamplingParams(max_new_tokens=6))
    with torch.no_grad():
        ref = hf_model.generate(
            torch.tensor([prompt], dtype=torch.long),
            max_new_tokens=6,
            do_sample=False,
            eos_token_id=None,  # compare full continuations, no early stop
        )[0, len(prompt):].tolist()
    assert ours == ref, f"{ours} vs {ref}"


def test_hf_initializes_training(tmp_path):
    import deepspeed_tpu

    d, _ = _tiny_llama_dir(tmp_path)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=d,
        config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "bf16": {"enabled": False},
            "zero_optimization": {"stage": 1},
        },
        mesh=deepspeed_tpu.initialize_mesh(fsdp=8),
    )
    x = np.random.RandomState(0).randint(0, 128, (8, 16)).astype(np.int32)
    losses = [float(engine.train_batch({"input_ids": x})) for _ in range(3)]
    assert losses[-1] < losses[0]


def test_config_from_hf_qwen_bias():
    cfg = config_from_hf(
        {
            "model_type": "qwen2",
            "vocab_size": 64,
            "hidden_size": 32,
            "intermediate_size": 64,
            "num_hidden_layers": 2,
            "num_attention_heads": 4,
        }
    )
    assert cfg.qkv_bias


def test_hf_tp_sharded_serving(tmp_path):
    """from_hf(grid=) streams the checkpoint into TP shardings and serves it;
    greedy continuation must match the unsharded engine, and the loaded
    params must actually be split on 'model' (never materialized whole)."""
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.inference.sampling import SamplingParams
    from deepspeed_tpu.parallel.topology import MODEL_AXIS, initialize_mesh

    d, hf_model = _tiny_llama_dir(tmp_path)
    prompt = [3, 17, 31, 8]
    base = InferenceEngineV2.from_hf(d, dtype=jnp.float32, max_seqs=2, block_size=8)
    want = base.generate(prompt, SamplingParams(max_new_tokens=6))

    grid = initialize_mesh(devices=jax.devices()[:2], model=2)
    eng = InferenceEngineV2.from_hf(
        d, dtype=jnp.float32, max_seqs=2, block_size=8, grid=grid
    )
    leaves = jax.tree_util.tree_leaves(eng.params)
    assert any(MODEL_AXIS in tuple(a.sharding.spec) for a in leaves)
    got = eng.generate(prompt, SamplingParams(max_new_tokens=6))
    assert got == want, (got, want)
