"""Fused Pallas dequant-matmul (ops/pallas/quant_matmul.py).

Parity bar (ISSUE 3): the fused int8/FP6 kernels must match the
dequantize-then-matmul jnp path on CPU (interpreter mode) at the 410M and
8B layer shapes, GQA head counts, and bias/no-bias — and ``serving_mm``
must route through them transparently with greedy decode token-identical
to the jnp path.  Reference analogue: inference/v2 cuda_linear TC-FPx GEMM
+ csrc/fp_quantizer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import quantizer as Q
from deepspeed_tpu.ops.pallas import quant_matmul as qm


@pytest.fixture(autouse=True)
def _interpret():
    qm.set_interpret(True)
    yield
    qm.set_interpret(False)


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return np.abs(a - b).max() / (np.abs(b).max() + 1e-9)


# 410M proxy layer shapes (d=1024, f=4096, GQA 8:2 with hd=128 -> kv proj
# [1024, 256]) — every serving matmul class: q/o square, GQA-narrow kv,
# MLP up and down, and the vocab head.
SHAPES_410M = [
    (1024, 1024),  # wq / wo
    (1024, 256),   # wk / wv (GQA 4:1)
    (1024, 4096),  # w_up / w_gate
    (4096, 1024),  # w_down
    (1024, 32128), # lm_head
]
# 8B layer shapes (d=4096, f=14336, GQA 32:8): the decode-roofline shapes.
# (the vocab head [4096, 128256] interpreted block-by-block alone takes
# minutes, so the lane stops at the MLP.)
SHAPES_8B = [
    (4096, 4096),   # wq / wo
    (4096, 1024),   # wk / wv (GQA 4:1)
    # slow: 23 s: 14336 columns of an 8B layer interpreted block by block; the width is what is tested
    pytest.param(4096, 14336, marks=pytest.mark.slow),  # w_up / w_gate
]


@pytest.mark.parametrize("k,n", SHAPES_410M[:3])
@pytest.mark.parametrize("with_bias", [False, True])
def test_int8_fused_matches_jnp(k, n, with_bias):
    rng = np.random.default_rng(k + n)
    x = jnp.asarray(rng.normal(size=(4, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(n,)), jnp.float32) if with_bias else None
    qw = Q.quantize_serving_weight(w, "int8")
    assert qm.supports_int8(x, qw.q)
    ref = qm.ref_quant_matmul(x, qw.q, qw.s, bias)
    got = qm.quant_matmul(x, qw.q, qw.s, bias=bias)
    assert _rel(got, ref) < 1e-5


@pytest.mark.parametrize("k,n", SHAPES_410M[:3])
@pytest.mark.parametrize("with_bias", [False, True])
def test_fp6_fused_matches_jnp(k, n, with_bias):
    rng = np.random.default_rng(k * 7 + n)
    x = jnp.asarray(rng.normal(size=(4, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(n,)), jnp.float32) if with_bias else None
    qw = Q.quantize_serving_weight_fp6(w)
    assert qm.supports_fp6(x, qw.packed, qw.in_dim)
    deq = Q._fp6_decode(Q._fp6_unpack(qw.packed, qw.in_dim), x.dtype)
    ref = ((x @ deq) * qw.s).astype(x.dtype)
    if bias is not None:
        ref = ref + bias
    got = qm.quant_matmul_fp6(x, qw.packed, qw.s, qw.in_dim, bias=bias)
    assert _rel(got, ref) < 1e-5


def test_fp8_fused_matches_jnp():
    rng = np.random.default_rng(8)
    k, n = 1024, 256
    x = jnp.asarray(rng.normal(size=(4, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    qw = Q.quantize_serving_weight(w, "fp8")
    assert qm.supports_int8(x, qw.q)  # fp8 is a real dtype: same kernel
    ref = qm.ref_quant_matmul(x, qw.q, qw.s)
    got = qm.quant_matmul(x, qw.q, qw.s)
    assert _rel(got, ref) < 1e-5


def test_bf16_activations_and_odd_rows():
    """bf16 compute dtype + an M that needs sublane padding (decode batch
    5) + 3D activations (prefill packs)."""
    rng = np.random.default_rng(3)
    k, n = 1024, 512
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    qw = Q.quantize_serving_weight(w, "int8")
    for shape in [(5, k), (2, 3, k), (k,)]:
        x = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
        ref = qm.ref_quant_matmul(x, qw.q, qw.s)
        got = qm.quant_matmul(x, qw.q, qw.s)
        assert got.shape == ref.shape and got.dtype == jnp.bfloat16
        assert _rel(got, ref) < 2e-2, shape


@pytest.mark.parametrize("k,n", SHAPES_8B)
def test_8b_shapes_int8_and_fp6(k, n):
    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.normal(size=(4, k)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(k, n)) * 0.02, jnp.float32)
    qi = Q.quantize_serving_weight(w, "int8")
    assert _rel(
        qm.quant_matmul(x, qi.q, qi.s), qm.ref_quant_matmul(x, qi.q, qi.s)
    ) < 2e-2
    q6 = Q.quantize_serving_weight_fp6(w)
    deq = Q._fp6_decode(Q._fp6_unpack(q6.packed, k), x.dtype)
    ref = ((x @ deq) * q6.s).astype(x.dtype)
    assert _rel(qm.quant_matmul_fp6(x, q6.packed, q6.s, k), ref) < 2e-2


def test_lm_head_shape_int8():
    """Vocab-head shape at 410M."""
    rng = np.random.default_rng(11)
    k, n = SHAPES_410M[-1]
    x = jnp.asarray(rng.normal(size=(2, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)) * 0.02, jnp.float32)
    qw = Q.quantize_serving_weight(w, "int8")
    assert _rel(
        qm.quant_matmul(x, qw.q, qw.s), qm.ref_quant_matmul(x, qw.q, qw.s)
    ) < 1e-5


def test_serving_mm_routes_fused_and_falls_back():
    """serving_mm dispatch: lane-aligned shapes route the kernel (interpret
    on), tiny/unaligned shapes keep the jnp body, stacked [L, ...] trees
    keep the jnp body; numerics agree either way."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(4, 256)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(256, 128)), jnp.float32)
    qw = Q.quantize_serving_weight(w, "int8")
    assert qm.supports_int8(x, qw.q)
    fused = Q.serving_mm(x, qw)
    qm.set_interpret(False)  # -> jnp body on CPU
    ref = Q.serving_mm(x, qw)
    qm.set_interpret(True)
    assert _rel(fused, ref) < 1e-5
    # unaligned: no fused support, still correct
    xs = jnp.asarray(rng.normal(size=(4, 60)), jnp.float32)
    ws = jnp.asarray(rng.normal(size=(60, 40)), jnp.float32)
    qs = Q.quantize_serving_weight(ws, "int8")
    assert not qm.supports_int8(xs, qs.q)
    assert _rel(Q.serving_mm(xs, qs), xs @ ws) < 0.03
    # stacked layer weights never hit the kernel directly
    wl = jnp.asarray(rng.normal(size=(2, 256, 128)), jnp.float32)
    ql = Q.quantize_serving_weight(wl, "int8")
    assert not qm.supports_int8(x, ql.q)


def test_fused_serving_gate_is_per_call():
    """The fused-kernel gate is per-call ServingContext state, not process
    state: a fused=False call runs the jnp body and leaves every other call
    (and every other engine in the process) on the kernel path."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(4, 256)), jnp.float32)
    qw = Q.quantize_serving_weight(
        jnp.asarray(rng.normal(size=(256, 128)), jnp.float32), "int8"
    )
    off_ctx = Q.ServingContext(fused=False)
    off = Q.serving_mm(x, qw, ctx=off_ctx)  # jnp body though interpret is on
    on = Q.serving_mm(x, qw)  # default: fused (interpreter kernel)
    assert _rel(on, off) < 1e-5
    # the process-global switch is gone — nothing for one engine to pin
    assert not hasattr(Q, "set_fused_serving")
    assert not hasattr(Q, "_FUSED_SERVING")


def test_greedy_decode_token_identical_fused_vs_jnp():
    """End-to-end: a lane-aligned fp32 model served through the v2 engine
    produces the SAME greedy continuation with the fused kernels
    (interpreter) as with the jnp serving_mm body."""
    from deepspeed_tpu.inference import InferenceEngineV2, SamplingParams
    from deepspeed_tpu.models import CausalLM, get_preset

    cfg = get_preset("tiny", max_seq_len=128, dtype=jnp.float32).replace(
        hidden_size=128, intermediate_size=256, num_heads=2, num_kv_heads=2,
    )
    params = CausalLM(cfg).init_params(jax.random.PRNGKey(0))
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    samp = SamplingParams(temperature=0.0, max_new_tokens=6)

    def run():
        eng = InferenceEngineV2(
            params, cfg, max_seqs=2, num_blocks=64, block_size=8,
            prefill_buckets=(16,), quantize_weights="int8",
        )
        return eng.generate(prompt, samp)

    fused = run()
    qm.set_interpret(False)
    jnp_path = run()
    qm.set_interpret(True)
    assert fused == jnp_path
