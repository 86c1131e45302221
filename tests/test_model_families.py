"""Model-family breadth (r4 VERDICT missing #6): parallel-block
(falcon/gptj/phi), learned-position (gpt2/opt), and ALiBi (bloom) families —
HF import logits parity against transformers + training smoke.

Reference: module_inject/containers/ (20 policy files) +
inference/v2/model_implementations/ (10 families)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import deepspeed_tpu
from deepspeed_tpu.checkpoint.hf_import import load_hf_checkpoint
from deepspeed_tpu.models import CausalLM, get_preset
from deepspeed_tpu.models.transformer import forward


def _save(model, tmp_path):
    model.eval()  # gpt2/opt/bloom carry active dropout modules
    d = str(tmp_path / "hf_model")
    model.save_pretrained(d, safe_serialization=True)
    return d


def _parity(d, hf_model, rtol=2e-4, atol=2e-4):
    params, cfg = load_hf_checkpoint(d)
    x = np.array([[1, 5, 9, 42, 99, 3, 17, 8]], dtype=np.int32)
    with torch.no_grad():
        ref = hf_model(torch.tensor(x, dtype=torch.long)).logits.numpy()
    got, _, _ = forward(params, jnp.asarray(x), cfg.replace(dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(got, np.float32), ref, rtol=rtol, atol=atol)
    return cfg


def test_gpt2_parity(tmp_path):
    torch.manual_seed(0)
    m = transformers.GPT2LMHeadModel(transformers.GPT2Config(
        vocab_size=128, n_embd=64, n_layer=2, n_head=4, n_positions=128,
        torch_dtype="float32"))
    cfg = _parity(_save(m, tmp_path), m)
    assert cfg.position == "learned" and cfg.tie_embeddings


def test_opt_parity(tmp_path):
    torch.manual_seed(0)
    m = transformers.OPTForCausalLM(transformers.OPTConfig(
        vocab_size=128, hidden_size=64, ffn_dim=128, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=128,
        activation_function="relu", do_layer_norm_before=True,
        torch_dtype="float32"))
    cfg = _parity(_save(m, tmp_path), m)
    assert cfg.activation == "relu" and cfg.position == "learned"


def test_bloom_parity(tmp_path):
    torch.manual_seed(0)
    m = transformers.BloomForCausalLM(transformers.BloomConfig(
        vocab_size=128, hidden_size=64, n_layer=2, n_head=4,
        torch_dtype="float32"))
    cfg = _parity(_save(m, tmp_path), m)
    assert cfg.position == "alibi" and cfg.embedding_norm


def test_falcon_parity(tmp_path):
    torch.manual_seed(0)
    m = transformers.FalconForCausalLM(transformers.FalconConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, multi_query=True, parallel_attn=True,
        bias=False, new_decoder_architecture=False, alibi=False,
        torch_dtype="float32"))
    cfg = _parity(_save(m, tmp_path), m)
    assert cfg.parallel_block and cfg.num_kv_heads == 1  # MQA


def test_gptj_parity(tmp_path):
    torch.manual_seed(0)
    m = transformers.GPTJForCausalLM(transformers.GPTJConfig(
        vocab_size=128, n_embd=64, n_layer=2, n_head=4, n_positions=128,
        rotary_dim=8, torch_dtype="float32"))
    cfg = _parity(_save(m, tmp_path), m)
    assert cfg.parallel_block and cfg.rotary_dim == 8 and cfg.head_bias


def test_phi_parity(tmp_path):
    torch.manual_seed(0)
    m = transformers.PhiForCausalLM(transformers.PhiConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        partial_rotary_factor=0.5, torch_dtype="float32"))
    cfg = _parity(_save(m, tmp_path), m)
    assert cfg.parallel_block and cfg.rotary_dim == 8


@pytest.mark.parametrize("preset", ["tiny_parallel", "tiny_alibi"])
def test_new_family_presets_train(preset):
    cfg = get_preset(preset)
    model = CausalLM(cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 5e-3}},
            "zero_optimization": {"stage": 1},
            "bf16": {"enabled": True},
        },
        mesh=deepspeed_tpu.initialize_mesh(fsdp=8),
    )
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (16, 33)).astype(np.int32)}
    losses = [float(engine.train_batch(batch)) for _ in range(10)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_family_presets_registered():
    for name in ("falcon_7b", "gptj_6b", "phi_2", "gpt_neox_20b",
                 "bloom_7b1", "opt_6_7b"):
        cfg = get_preset(name)
        assert cfg.param_count > 1e9, name


@pytest.mark.parametrize("preset", ["tiny_parallel", "tiny_alibi"])
def test_new_families_generate_v1(preset):
    """v1 inference (dense KV cache) drives the new architectures: cached
    decode must match the no-cache forward argmax path."""
    import jax.numpy as jnp

    from deepspeed_tpu.inference import SamplingParams, init_inference

    cfg = get_preset(preset, dtype=jnp.float32)
    model = CausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = init_inference(model, params)
    prompt = np.array([[3, 1, 4, 1, 5, 9, 2, 6]], np.int32)
    out = eng.generate(prompt, SamplingParams(max_new_tokens=4))
    assert out.shape == (1, 4)
    # teacher-forced check: feeding prompt+generated through the plain
    # forward must reproduce the same greedy choices
    full = np.concatenate([prompt, out], axis=1)
    logits, _, _ = forward(params, jnp.asarray(full), cfg)
    greedy = np.asarray(jnp.argmax(logits[:, prompt.shape[1] - 1 : -1], -1))
    np.testing.assert_array_equal(out, greedy)


def test_alibi_bias_uses_per_row_positions():
    """ALiBi distances come from each row's ACTUAL positions (ADVICE r5
    low #3: the bias was computed from positions[0] + the raw key index
    for the whole batch).  Ragged rows — row 1 carries left-pad-style
    positions that disagree with row 0 AND with its own buffer indices —
    must (a) match running that row alone, and (b) genuinely differ from
    the row-0-positions bias the old code applied (ALiBi is per-query
    shift-invariant, so only non-separable disagreement like this is
    observable at all)."""
    cfg = get_preset("tiny_alibi", dtype=jnp.float32)
    params = CausalLM(cfg).init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    s = 16
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, (2, s)), jnp.int32)
    # row 0: plain arange; row 1: three left pads at position 0, then the
    # real tokens at positions 0..s-4 (HF left-padded batch shape)
    row1 = jnp.concatenate([jnp.zeros(3, jnp.int32), jnp.arange(s - 3)])
    positions = jnp.stack([jnp.arange(s), row1])
    batched, _, _ = forward(params, tokens, cfg, positions=positions)
    for i in range(2):
        solo, _, _ = forward(
            params, tokens[i : i + 1], cfg, positions=positions[i : i + 1]
        )
        np.testing.assert_allclose(
            np.asarray(batched[i]), np.asarray(solo[0]), rtol=2e-5, atol=2e-5
        )
    # (b): applying row 0's positions to row 1 (what the old code did)
    # changes row 1's logits materially
    wrong, _, _ = forward(
        params, tokens, cfg,
        positions=jnp.broadcast_to(jnp.arange(s)[None], (2, s)),
    )
    assert np.abs(np.asarray(batched[1]) - np.asarray(wrong[1])).max() > 1e-3


def test_alibi_rejects_packed_segments():
    """Packed rows restart positions mid-row while the key cache index
    keeps counting — ALiBi distances would be silently wrong, so the model
    refuses."""
    cfg = get_preset("tiny_alibi", dtype=jnp.float32)
    params = CausalLM(cfg).init_params(jax.random.PRNGKey(0))
    tokens = jnp.ones((1, 8), jnp.int32)
    seg = jnp.asarray([[1, 1, 1, 1, 2, 2, 2, 2]], jnp.int32)
    with pytest.raises(NotImplementedError, match="alibi"):
        forward(params, tokens, cfg, segment_ids=seg)
