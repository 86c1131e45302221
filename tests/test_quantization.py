"""Quantization stack: kernels, ZeRO++ qwZ/qgZ, 1-bit Adam.

Mirrors the reference's coverage: ``tests/unit/ops/quantizer/`` (kernel vs
reference parity), ``tests/unit/runtime/zero/test_zeropp.py`` (training
with quantized collectives), ``tests/onebit/`` (compressed optimizer
correctness).  The comm-payload A/B check inspects the lowered HLO for int8
collectives — the CPU-mesh analogue of counting bytes on the wire.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.ops import quantizer
from deepspeed_tpu.ops.pallas import quant_kernel
from simple_model import init_mlp, mlp_loss, random_batches


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
def test_int8_round_trip_jnp():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 256), jnp.float32)
    qt = quantizer.quantize_int8(x)
    assert qt.data.dtype == jnp.int8
    back = quantizer.dequantize(qt, dtype=jnp.float32)
    # per-row amax/127 quantization: error bounded by half a step
    step = np.asarray(qt.scales)[:, None]
    assert np.max(np.abs(np.asarray(back) - np.asarray(x))) <= step.max() * 0.51


def test_int8_pallas_matches_jnp():
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 128), jnp.float32)
    ref = quantizer.quantize_int8(x)
    quant_kernel.set_interpret(True)
    try:
        q, s = quant_kernel.quantize_int8(x)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(ref.data))
        np.testing.assert_allclose(np.asarray(s), np.asarray(ref.scales), rtol=1e-6)
        deq = quant_kernel.dequantize_int8(q, s, out_dtype=jnp.float32)
        np.testing.assert_allclose(
            np.asarray(deq),
            np.asarray(quantizer.dequantize(ref, dtype=jnp.float32)),
            rtol=1e-6,
        )
    finally:
        quant_kernel.set_interpret(False)


def test_fp8_round_trip():
    x = jax.random.normal(jax.random.PRNGKey(2), (16, 64), jnp.float32)
    qt = quantizer.quantize_fp8(x)
    assert qt.data.dtype == jnp.float8_e4m3fn
    back = quantizer.dequantize(qt, dtype=jnp.float32)
    # e4m3 has ~2 decimal digits; scaled to amax this is ~6% worst-case
    np.testing.assert_allclose(np.asarray(back), np.asarray(x), rtol=0.08, atol=1e-3)


def test_fp8_pallas_matches_jnp():
    x = jax.random.normal(jax.random.PRNGKey(3), (16, 128), jnp.float32)
    ref = quantizer.quantize_fp8(x)
    quant_kernel.set_interpret(True)
    try:
        q, s = quant_kernel.quantize_fp8(x)
        np.testing.assert_allclose(np.asarray(s), np.asarray(ref.scales), rtol=1e-6)
        np.testing.assert_array_equal(
            np.asarray(q, np.float32), np.asarray(ref.data, np.float32)
        )
    finally:
        quant_kernel.set_interpret(False)


# ---------------------------------------------------------------------------
# ZeRO++ training
# ---------------------------------------------------------------------------
CFG = {
    "train_micro_batch_size_per_gpu": 2,
    "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
    "bf16": {"enabled": False},
    "steps_per_print": 100,
}


def _engine(zero):
    params = init_mlp(jax.random.PRNGKey(0), in_dim=8, hidden=64, out_dim=8)
    return deepspeed_tpu.initialize(
        loss_fn=mlp_loss,
        params=params,
        config={**CFG, "zero_optimization": zero},
        mesh=deepspeed_tpu.initialize_mesh(fsdp=8),
    )[0]


def _train(engine, steps=6):
    return [
        float(engine.train_batch(b)) for b in random_batches(steps, 1, 16)
    ]


@pytest.mark.parametrize("qw,qg", [(True, False), (False, True), (True, True)])
def test_zeropp_trains_and_tracks_dense(qw, qg):
    zero = {
        "stage": 3,
        "param_persistence_threshold": 0,
        "zero_quantized_weights": qw,
        "zero_quantized_gradients": qg,
    }
    ref = _train(_engine({"stage": 3, "param_persistence_threshold": 0}))
    got = _train(_engine(zero))
    assert got[-1] < got[0]
    # lossy by design: trajectories track within a few percent
    np.testing.assert_allclose(got, ref, rtol=0.1, atol=0.05)


def test_zeropp_int8_on_the_wire():
    """A/B payload check: qwZ/qgZ graphs carry s8 collectives, dense doesn't."""
    eng_q = _engine(
        {
            "stage": 3,
            "param_persistence_threshold": 0,
            "zero_quantized_weights": True,
            "zero_quantized_gradients": True,
        }
    )
    eng_d = _engine({"stage": 3, "param_persistence_threshold": 0})
    b = random_batches(1, 1, 16)[0]
    batch = {k: v.reshape((1,) + v.shape[1:]) if v.ndim == 2 else v for k, v in b.items()}

    def colls_of(eng):
        from deepspeed_tpu.analysis import stablehlo_collectives

        step = eng._get_train_step(b)
        import jax as _j

        return stablehlo_collectives(
            step.lower(eng.state, b, _j.random.PRNGKey(0)).as_text()
        )

    def n_int8(colls):
        return sum(1 for c in colls
                   if c.kind in ("all_gather", "all_to_all")
                   and c.dtype == "i8")

    n_q = n_int8(colls_of(eng_q))
    n_d = n_int8(colls_of(eng_d))
    assert n_q > 0, "expected int8 collectives in the ZeRO++ graph"
    assert n_d == 0, "dense graph must not carry int8 collectives"


# ---------------------------------------------------------------------------
# 1-bit Adam
# ---------------------------------------------------------------------------
def _onebit_engine(freeze_step=3, opt_type="onebitadam"):
    params = init_mlp(jax.random.PRNGKey(0))
    return deepspeed_tpu.initialize(
        loss_fn=mlp_loss,
        params=params,
        config={
            **CFG,
            "optimizer": {
                "type": opt_type,
                "params": {"lr": 1e-2, "freeze_step": freeze_step},
            },
            "zero_optimization": {"stage": 0},
        },
        mesh=deepspeed_tpu.initialize_mesh(data=8),
    )[0]


def test_onebit_adam_warmup_matches_dense():
    """During freeze (warmup) steps the math is exact dense Adam."""
    params = init_mlp(jax.random.PRNGKey(0))
    dense = deepspeed_tpu.initialize(
        loss_fn=mlp_loss,
        params=params,
        config={
            **CFG,
            "optimizer": {
                "type": "adam",
                "params": {"lr": 1e-2, "adam_w_mode": False},
            },
            "zero_optimization": {"stage": 0},
        },
        mesh=deepspeed_tpu.initialize_mesh(data=8),
    )[0]
    ob = _onebit_engine(freeze_step=100)  # never leaves warmup
    ref = _train(dense, steps=4)
    got = _train(ob, steps=4)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("opt_type", ["onebitadam", "zerooneadam", "onebitlamb"])
def test_onebit_compressed_phase_trains(opt_type):
    eng = _onebit_engine(freeze_step=2, opt_type=opt_type)
    losses = _train(eng, steps=10)
    assert losses[-1] < losses[0]
    assert np.isfinite(losses).all()
    # error-feedback buffers are live after the compressed phase
    assert float(jnp.abs(eng.state.opt_state.worker_error).sum()) > 0


def test_onebit_int8_on_the_wire():
    from deepspeed_tpu.analysis import stablehlo_collectives

    eng = _onebit_engine(freeze_step=0)
    b = random_batches(1, 1, 16)[0]
    step = eng._get_train_step(b)
    colls = stablehlo_collectives(
        step.lower(eng.state, b, jax.random.PRNGKey(0)).as_text()
    )
    assert any(c.kind in ("all_gather", "all_to_all") and c.dtype == "i8"
               for c in colls)


def test_onebit_direct_build_raises():
    from deepspeed_tpu.ops.optimizers import build_optimizer

    with pytest.raises(ValueError, match="engine-managed"):
        build_optimizer("OnebitAdam", {"lr": 1e-3})
