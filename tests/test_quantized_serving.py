"""Quantized-weight serving (r4 VERDICT next #3): int8/fp8 kernels with
per-output-channel scales applied post-matmul.

Reference: ``csrc/fp_quantizer/*`` + FP6 serving
(blogs/deepspeed-fp6/03-05-2024/README.md — the quantized-GEMM capacity/
throughput axis of the serving engine)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceEngineV2, SamplingParams
from deepspeed_tpu.models import CausalLM, get_preset
from deepspeed_tpu.ops.quantizer import (
    ServingQuant,
    quantize_serving_params,
    quantize_serving_weight,
    serving_mm,
    tree_nbytes,
)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_preset("tiny", max_seq_len=128, dtype=jnp.float32)
    model = CausalLM(cfg)
    return model, model.init_params(jax.random.PRNGKey(0))


def test_serving_mm_int8_accuracy():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    qw = quantize_serving_weight(w, "int8")
    assert qw.q.dtype == jnp.int8 and qw.s.shape == (32,)
    ref = np.asarray(x @ w)
    got = np.asarray(serving_mm(x, qw))
    # int8 per-output-channel: well under 1% relative error on gaussians
    rel = np.abs(got - ref).max() / np.abs(ref).max()
    assert rel < 0.02, rel
    # dense passthrough unchanged
    np.testing.assert_allclose(np.asarray(serving_mm(x, w)), ref, rtol=1e-6)


def test_serving_mm_stacked_per_layer_scales():
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(size=(3, 16, 8)) * np.array([1, 10, 100])[:, None, None],
                    jnp.float32)
    qw = quantize_serving_weight(w, "int8")
    assert qw.s.shape == (3, 8)  # per layer AND per channel
    # per-layer slice (the model_runner tree_map) keeps its own scales
    sl = jax.tree_util.tree_map(lambda a: a[2], qw)
    x = jnp.asarray(rng.normal(size=(2, 16)), jnp.float32)
    ref = np.asarray(x @ w[2])
    got = np.asarray(serving_mm(x, sl))
    assert np.abs(got - ref).max() / np.abs(ref).max() < 0.02


def test_quantize_serving_params_halves_layer_bytes(tiny_model):
    model, params = tiny_model
    qp = quantize_serving_params(params, "int8")
    dense_layers = tree_nbytes(params["layers"])
    q_layers = tree_nbytes(qp["layers"])
    # fp32 kernels -> int8 + fp32 per-channel scales: ~4x smaller here
    # (bf16 production weights: ~2x)
    assert q_layers < dense_layers * 0.3, (dense_layers, q_layers)
    # norms untouched
    assert qp["layers"]["attn_norm"]["scale"].dtype == params["layers"]["attn_norm"]["scale"].dtype
    assert isinstance(qp["layers"]["attn"]["wq"], ServingQuant)


@pytest.mark.parametrize("fmt", ["int8", "fp8"])
def test_quantized_prefill_logits_track_dense(tiny_model, fmt):
    """Teacher-forced parity (no trajectory compounding — an untrained
    random model's near-flat logits flip argmax on any perturbation): the
    quantized serving forward's logits must track the dense serving forward
    closely at every position."""
    from deepspeed_tpu.inference import model_runner
    from deepspeed_tpu.inference.paged import init_paged_cache

    model, params = tiny_model
    cfg = model.cfg
    qp = quantize_serving_params(params, fmt)
    tokens = jnp.asarray([3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3], jnp.int32)
    blocks = jnp.arange(2, dtype=jnp.int32)  # 16 tokens / block_size 8
    mk_kv = lambda: init_paged_cache(
        cfg.num_layers, 16, 8, cfg.num_kv_heads, cfg.hd, dtype=cfg.dtype
    )
    # one prompt, one segment of a pack; its last token's row is scored
    pack = jax.jit(lambda p, kv: model_runner.prefill_packed(
        p, cfg, tokens, jnp.ones(16, jnp.int32), jnp.arange(16, dtype=jnp.int32),
        blocks, jnp.asarray([15], jnp.int32), kv))
    dense_logits, _ = pack(params, mk_kv())
    quant_logits, _ = pack(qp, mk_kv())
    d, q = np.asarray(dense_logits[0]), np.asarray(quant_logits[0])
    rel = np.abs(d - q).max() / (np.abs(d).max() + 1e-9)
    # e4m3's 3-bit mantissa is coarser than int8's 7 significant bits
    assert rel < (0.12 if fmt == "fp8" else 0.05), rel
    # and the softmax distributions agree (cosine > 0.99)
    cos = float(np.sum(d * q) / (np.linalg.norm(d) * np.linalg.norm(q) + 1e-9))
    assert cos > 0.99, cos


@pytest.mark.parametrize("fmt", ["int8", "fp8"])
def test_quantized_generation_runs(tiny_model, fmt):
    model, params = tiny_model
    eng = InferenceEngineV2(
        params, model.cfg, max_seqs=2, num_blocks=64, block_size=8,
        prefill_buckets=(16,), quantize_weights=fmt,
    )
    out = eng.generate([3, 1, 4, 1, 5, 9, 2, 6], SamplingParams(max_new_tokens=6))
    assert len(out) == 6 and all(0 <= int(t) < model.cfg.vocab_size for t in out)


def test_quantized_continuous_batching(tiny_model):
    model, params = tiny_model
    eng = InferenceEngineV2(
        params, model.cfg, max_seqs=2, num_blocks=64, block_size=8,
        prefill_buckets=(16,), quantize_weights="int8",
    )
    first = eng.put([1, 2], [[3, 1, 4, 1, 5], [2, 7, 1, 8]],
                    SamplingParams(max_new_tokens=4))
    assert set(first) == {1, 2}
    ticks = [eng.step() for _ in range(3)]
    assert all(set(t) == {1, 2} for t in ticks)


def test_quantize_composes_with_tp(tiny_model):
    """TP x quantized weights construct together (full parity is asserted in
    test_inference_tp.py::test_tp_serving_with_quantized_weights)."""
    import deepspeed_tpu

    model, params = tiny_model
    grid = deepspeed_tpu.initialize_mesh(model=2)
    eng = InferenceEngineV2(
        params, model.cfg, grid=grid, quantize_weights="int8",
        max_seqs=2, num_blocks=32, block_size=8, prefill_buckets=(16,),
    )
    out = eng.generate([3, 1, 4, 1], SamplingParams(max_new_tokens=3))
    assert len(out) == 3


# ---------------------------------------------------------------------------
# FP6 (e2m3, bit-packed) — the reference TC-FPx format class
# (csrc/fp_quantizer, blogs/deepspeed-fp6)
# ---------------------------------------------------------------------------
def test_fp6_roundtrip_and_pack():
    from deepspeed_tpu.ops.quantizer import (
        _fp6_decode,
        _fp6_encode,
        _fp6_pack,
        _fp6_unpack,
    )

    # every representable magnitude round-trips exactly
    vals = []
    for s in (1, -1):
        for e in range(4):
            for m in range(8):
                mag = m / 8.0 if e == 0 else (1 + m / 8.0) * 2.0 ** (e - 1)
                vals.append(s * mag)
    x = jnp.asarray(vals, jnp.float32)
    codes = _fp6_encode(x)
    np.testing.assert_allclose(np.asarray(_fp6_decode(codes, jnp.float32)),
                               np.abs(np.asarray(x)) * np.sign(np.asarray(x)),
                               rtol=0, atol=0)
    # pack/unpack is the identity on codes
    c2 = codes.reshape(16, 4).T.reshape(4, 16)  # any [in, out] view, in%4==0
    np.testing.assert_array_equal(
        np.asarray(_fp6_unpack(_fp6_pack(c2), 4)), np.asarray(c2)
    )


def test_fp6_serving_mm_accuracy_and_size():
    from deepspeed_tpu.ops.quantizer import (
        ServingQuantFP6,
        quantize_serving_weight_fp6,
        serving_mm,
        tree_nbytes,
    )

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    qw = quantize_serving_weight_fp6(w)
    assert isinstance(qw, ServingQuantFP6)
    # 0.75 bytes/weight (three [in/4, out] byte planes) + fp32 scales
    assert qw.packed.shape == (3, 16, 32) and qw.packed.dtype == jnp.uint8
    ref = np.asarray(x @ w)
    got = np.asarray(serving_mm(x, qw))
    rel = np.abs(got - ref).max() / np.abs(ref).max()
    # e2m3: 3 mantissa bits -> coarser than int8, finer than nothing
    assert rel < 0.06, rel


def test_fp6_generation_runs(tiny_model):
    model, params = tiny_model
    eng = InferenceEngineV2(
        params, model.cfg, max_seqs=2, num_blocks=64, block_size=8,
        prefill_buckets=(16,), quantize_weights="fp6",
    )
    out = eng.generate([3, 1, 4, 1, 5, 9, 2, 6], SamplingParams(max_new_tokens=4))
    assert len(out) == 4 and all(0 <= int(t) < model.cfg.vocab_size for t in out)
