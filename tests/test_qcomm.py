"""Quantized collectives (comm/qcomm.py): transport parity, error-feedback
convergence, the overflow guard rail, and the three wired hot paths —
ZeRO-3/ZeRO++ gathers and reduces, TP serving's row-parallel partial-sum
transport (passthrough token identity + int8 tolerance), and the explicit
expert-parallel MoE dispatch/combine.

Everything runs on the virtual 8-device CPU mesh; the scheduled-HLO
payload/overlap proofs live in tests/test_overlap_hlo.py (AOT TPU
topology, slow lane).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.comm import qcomm
from deepspeed_tpu.parallel.sharding import (
    set_current_mesh,
    shard_map_compat,
)
from deepspeed_tpu.parallel.topology import EXPERT_AXIS, MODEL_AXIS

from conftest import make_grid
from simple_model import init_mlp, mlp_loss, random_batches

W = 8


@pytest.fixture
def mesh():
    grid = make_grid(model=W)
    set_current_mesh(grid.mesh)  # ambient fallback for collective_axis_size
    yield grid.mesh
    set_current_mesh(None)


def _run(mesh, body, x, in_spec=P(MODEL_AXIS), out_spec=P(MODEL_AXIS)):
    return shard_map_compat(
        body, mesh, in_specs=in_spec, out_specs=out_spec, check_vma=False
    )(x)


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-9))


# ---------------------------------------------------------------------------
# collective parity
# ---------------------------------------------------------------------------
def test_q_all_reduce_passthrough_exact_and_quant_close(mesh):
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((W, 32, 48)), jnp.float32
    )
    ref = jnp.sum(x, 0)

    def ar(fmt):
        return _run(
            mesh, lambda xl: qcomm.q_all_reduce(xl[0], MODEL_AXIS, fmt)[None], x
        )[0]

    assert jnp.allclose(ar("none"), ref, atol=1e-5)
    assert _rel(ar("int8"), ref) < 0.02
    # fp8 e4m3 has a 3-bit mantissa and the payload crosses TWO hops
    assert _rel(ar("fp8"), ref) < 0.10


def test_q_all_gather_parity(mesh):
    shards = jnp.asarray(
        np.random.default_rng(1).standard_normal((W, 16, 8)), jnp.float32
    )
    full = jnp.concatenate([shards[i] for i in range(W)], 0)

    def ag(fmt):
        return _run(
            mesh,
            lambda xl: qcomm.q_all_gather(
                xl[0], MODEL_AXIS, fmt, tiled=True, axis=0
            )[None],
            shards,
            out_spec=P(MODEL_AXIS, None),
        )[0]

    assert jnp.allclose(ag("none"), full)
    assert _rel(ag("int8"), full) < 0.02


def test_q_reduce_scatter_parity_and_error_shape(mesh):
    g = jnp.asarray(
        np.random.default_rng(2).standard_normal((W, 64, 24)), jnp.float32
    )
    ref = jnp.mean(g, 0)

    def rs(fmt):
        def body(xl):
            out, err = qcomm.q_reduce_scatter(
                xl[0], MODEL_AXIS, fmt, scatter_axis=0, mean=True,
                error=jnp.zeros_like(xl[0]),
            )
            return out[None], err[None]

        return shard_map_compat(
            body, mesh, in_specs=P(MODEL_AXIS),
            out_specs=(P(MODEL_AXIS), P(MODEL_AXIS)), check_vma=False,
        )(g)

    exact, err0 = rs("none")
    got = jnp.concatenate([exact[i] for i in range(W)], 0)
    assert jnp.allclose(got, ref, atol=1e-5)
    assert float(jnp.max(jnp.abs(err0))) == 0.0  # exact transport: no residual
    q, err = rs("int8")
    got = jnp.concatenate([q[i] for i in range(W)], 0)
    assert _rel(got, ref) < 0.05
    assert err.shape == g.shape
    assert float(jnp.max(jnp.abs(err))) > 0.0  # quantized: residual persists


def test_q_all_to_all_parity(mesh):
    a = jnp.asarray(
        np.random.default_rng(3).standard_normal((W, 16, 24)), jnp.float32
    )

    def a2a(fmt):
        return _run(
            mesh,
            lambda xl: qcomm.q_all_to_all(
                xl[0], MODEL_AXIS, fmt, split_axis=0, concat_axis=0
            )[None],
            a,
        )

    plain = a2a("none")
    assert _rel(a2a("int8"), plain) < 0.02
    assert _rel(a2a("fp8"), plain) < 0.06


def test_q_psum_tiled_passthrough_bit_identical_and_tiled_exact(mesh):
    y = jnp.asarray(
        np.random.default_rng(4).standard_normal((W, 8, 100)), jnp.float32
    )
    ref = jnp.sum(y, 0)

    def pt(fmt, tiles):
        return _run(
            mesh,
            lambda xl: qcomm.q_psum_tiled(
                xl[0], MODEL_AXIS, fmt, tiles=tiles
            )[None],
            y,
        )[0]

    plain = _run(mesh, lambda xl: jax.lax.psum(xl[0], MODEL_AXIS)[None], y)[0]
    # passthrough/1 must be the SAME op as lax.psum — bit identity
    assert jnp.array_equal(pt("none", 1), plain)
    # free-dim tiling changes scheduling, not math (100 does not divide 4:
    # the ragged tail tile is exercised too)
    assert jnp.allclose(pt("none", 4), ref, atol=1e-5)
    assert _rel(pt("int8", 4), ref) < 0.02


# ---------------------------------------------------------------------------
# error feedback
# ---------------------------------------------------------------------------
def test_error_feedback_beats_plain_quantization(mesh):
    """Accumulating the SAME gradient over steps: with error feedback the
    running mean of dequantized reduces converges to the true value (the
    residual re-enters each step); without it the per-step bias persists.
    This is the property that lets int8 gradient transport track fp32 loss
    trajectories (1-bit Adam's compensation argument, multi-bit)."""
    rng = np.random.default_rng(5)
    g = jnp.asarray(rng.standard_normal((W, 64, 16)), jnp.float32)
    ref = jnp.mean(g, 0)
    steps = 8

    def accum(with_ef):
        def body(xl):
            x0 = xl[0]

            def step(carry, _):
                err, acc = carry
                out, err2 = qcomm.q_reduce_scatter(
                    x0, MODEL_AXIS, "int8", scatter_axis=0, mean=True,
                    error=err,
                )
                err = err2 if with_ef else jnp.zeros_like(x0)
                return (err, acc + out), None

            (_, acc), _ = jax.lax.scan(
                step,
                (jnp.zeros_like(x0), jnp.zeros((64 // W, 16), jnp.float32)),
                None, length=steps,
            )
            return (acc / steps)[None]

        shards = shard_map_compat(
            body, mesh, in_specs=P(MODEL_AXIS), out_specs=P(MODEL_AXIS),
            check_vma=False,
        )(g)
        return jnp.concatenate([shards[i] for i in range(W)], 0)

    err_ef = float(jnp.mean(jnp.abs(accum(True) - ref)))
    err_plain = float(jnp.mean(jnp.abs(accum(False) - ref)))
    assert err_ef < 0.5 * err_plain, (err_ef, err_plain)


CFG = {
    "train_micro_batch_size_per_gpu": 2,
    "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
    "bf16": {"enabled": False},
    "steps_per_print": 100,
}


def _zero3_engine(extra):
    params = init_mlp(jax.random.PRNGKey(0), in_dim=8, hidden=64, out_dim=8)
    return deepspeed_tpu.initialize(
        loss_fn=mlp_loss,
        params=params,
        config={**CFG, "zero_optimization": {
            "stage": 3, "param_persistence_threshold": 0, **extra}},
        mesh=deepspeed_tpu.initialize_mesh(fsdp=8),
    )[0]


def test_zero3_int8_grad_reduce_with_error_feedback_tracks_fp32():
    """The ISSUE's convergence criterion: a small ZeRO-3 run whose gradient
    reduce-scatter ships int8 WITH error feedback (ZeRO++ LoCo through
    qcomm.q_reduce_scatter) tracks the fp32 loss trajectory within
    tolerance — the error buffer carries each step's quantization residual
    into the next step's compensation."""
    steps = 6
    ref_eng = _zero3_engine({})
    got_eng = _zero3_engine({
        "zero_quantized_gradients": True,
        "zeropp_loco_param": {"err_beta": 0.9, "reset_T": 64},
    })
    ref = [float(ref_eng.train_batch(b))
           for b in random_batches(steps, 1, 16)]
    got = [float(got_eng.train_batch(b))
           for b in random_batches(steps, 1, 16)]
    assert got[-1] < got[0]  # it trains
    np.testing.assert_allclose(got, ref, rtol=0.1, atol=0.05)


# ---------------------------------------------------------------------------
# guard rail + config validation
# ---------------------------------------------------------------------------
def test_overflow_guard_rail_typed_error(mesh):
    y = jnp.zeros((W, 4, 8), jnp.float32)
    for op, kw in (
        (qcomm.q_all_reduce, {}),
        (qcomm.q_reduce_scatter, {"scatter_axis": 0}),
    ):
        with pytest.raises(qcomm.QCommOverflowError, match="fp32"):
            _run(
                mesh,
                lambda xl: op(xl[0], MODEL_AXIS, "int8", accum="int8", **kw)[
                    None
                ],
                y,
            )
    # 'none' payload + fp32 accum never trips; bogus formats are typed too
    with pytest.raises(qcomm.QCommError, match="format"):
        qcomm.q_all_gather(jnp.zeros(4), MODEL_AXIS, "int4")
    with pytest.raises(qcomm.QCommError):
        qcomm.wire_bytes("all_gather", 64, "bf16", 8)


def test_serve_config_rejects_bad_quant_comm():
    from deepspeed_tpu.config.config import ConfigError, ServeConfig

    with pytest.raises(ConfigError, match="quant_comm"):
        ServeConfig(quant_comm="int4")
    with pytest.raises(ConfigError, match="comm_tiles"):
        ServeConfig(comm_tiles=0)
    assert ServeConfig(quant_comm="int8", comm_tiles=4).quant_comm == "int8"


def test_wire_bytes_accounting():
    n = 4096
    fp32 = qcomm.wire_bytes("all_reduce", n, "none", 8)
    q8 = qcomm.wire_bytes("all_reduce", n, "int8", 8)
    # int8 + 1 fp32 scale per 256 elements ~ 4x fewer bytes than fp32
    assert q8 < 0.3 * fp32
    assert qcomm.wire_bytes("all_gather", n, "int8", 8) == q8 // 2
    bf16 = qcomm.wire_bytes("all_reduce", n, "none", 8, none_bytes_per_el=2)
    assert bf16 == fp32 // 2


# ---------------------------------------------------------------------------
# TP serving transport (engine level)
# ---------------------------------------------------------------------------
def _tiny_cfg():
    from deepspeed_tpu.models import get_preset

    return get_preset(
        "tiny", num_layers=2, num_heads=4, num_kv_heads=4, hidden_size=64,
        intermediate_size=128, vocab_size=256, max_seq_len=128,
        dtype=jnp.float32,
    )


def _greedy_tokens(eng, prompts, steps=12):
    from deepspeed_tpu.inference.engine_v2 import SamplingParams

    samp = SamplingParams(temperature=0.0)
    eng.put(list(range(1, len(prompts) + 1)), prompts, samp)
    out = {u: [] for u in range(1, len(prompts) + 1)}
    for _ in range(steps):
        for u, t in eng.step(samp).items():
            if t >= 0:
                out[u].append(t)
    return out


def _tp_engine(quant_comm, tiles=1, tp=2, cfg=None):
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import CausalLM

    cfg = cfg or _tiny_cfg()
    params = CausalLM(cfg).init_params(jax.random.PRNGKey(0))
    grid = make_grid(model=tp) if tp > 1 else None
    return InferenceEngineV2(
        params, cfg, grid=grid, max_seqs=2, num_blocks=64, block_size=8,
        prefill_buckets=(32,), quant_comm=quant_comm, comm_tiles=tiles,
    )


def test_tp_greedy_decode_token_identity_passthrough():
    """quant_comm='none' keeps the exact lax.psum — TP decode must stay
    token-identical to the single-chip engine (the acceptance criterion's
    exactness half)."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 255, 12).tolist() for _ in range(2)]
    ref = _greedy_tokens(_tp_engine(None, tp=1), prompts)
    tp_none = _greedy_tokens(_tp_engine("none"), prompts)
    assert ref == tp_none


def test_tp_greedy_decode_int8_within_documented_tolerance():
    """int8 partial-sum transport is LOSSY: the documented tolerance is
    that greedy decode agrees with passthrough on the large majority of
    positions of a short decode (logit argmax is robust to ~1% relative
    psum error except at near-ties).  Exactness is NOT promised — that is
    what passthrough mode is for (README Quantized collectives)."""
    rng = np.random.default_rng(8)
    prompts = [rng.integers(1, 255, 12).tolist() for _ in range(2)]
    ref = _greedy_tokens(_tp_engine("none"), prompts)
    got = _greedy_tokens(_tp_engine("int8", tiles=2), prompts)
    total = agree = 0
    for u in ref:
        for a, b in zip(ref[u], got[u]):
            total += 1
            agree += int(a == b)
    assert total > 0
    assert agree / total >= 0.75, (agree, total, ref, got)


def test_tp_engine_comm_byte_accounting():
    """comm/bytes_on_wire diffs across the passthrough/int8 twin:
    int8 transport must report ~4x fewer wire bytes
    per tick (fp32 compute dtype here), and the counter stays 0 without a
    TP mesh.  The accounting now models qcomm's tp*chunk payload padding
    (the Graft Auditor reconciliation — the counter matches the compiled
    program byte-for-byte), so the ratio is asserted at a pad-neutral
    hidden size; at the toy hidden=64 shape the chunk floor dominates and
    the counter truthfully reports it."""
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, 255, 12).tolist() for _ in range(2)]
    cfg = _tiny_cfg().replace(hidden_size=256, intermediate_size=256)

    def bytes_of(eng):
        _greedy_tokens(eng, prompts, steps=4)
        return eng.telemetry.registry.get(
            f"{eng._comm_ns}/bytes_on_wire"
        ).value

    solo = _tp_engine(None, tp=1, cfg=cfg)
    assert bytes_of(solo) == 0
    b_none = bytes_of(_tp_engine("none", cfg=cfg))
    b_q = bytes_of(_tp_engine("int8", cfg=cfg))
    assert b_none > 0 and b_q > 0
    assert b_q < 0.35 * b_none, (b_q, b_none)
    # the overhead counter (GSPMD embed/gather wire) is format-independent
    e_none = _tp_engine("none", cfg=cfg)
    e_q = _tp_engine("int8", cfg=cfg)
    _greedy_tokens(e_none, prompts, steps=4)
    _greedy_tokens(e_q, prompts, steps=4)
    oh = lambda e: e.telemetry.registry.get(
        f"{e._comm_ns}/bytes_on_wire_overhead").value
    assert oh(e_none) == oh(e_q) > 0


@pytest.mark.parametrize("fmt_w", ["int8", "fp6"])
def test_tiled_row_region_parity(mesh, fmt_w):
    """The T3 tile decomposition (per-tile GEMM + independent transport)
    must reproduce the untiled row-parallel region exactly in passthrough
    — including a tile count that does not divide the out dim — and within
    quantization tolerance in int8 transport."""
    from deepspeed_tpu.ops import quantizer as Q

    rng = np.random.default_rng(21)
    kd, nd = 64, 80  # 80 % 3 != 0: ragged tail tile
    x = jnp.asarray(rng.standard_normal((5, kd)), jnp.float32)
    wf = jnp.asarray(rng.standard_normal((kd, nd)) * 0.05, jnp.float32)
    w = (Q.quantize_serving_weight_fp6(wf, row_shards=W) if fmt_w == "fp6"
         else Q.quantize_serving_weight(wf, fmt_w))

    def run(comm_fmt, tiles):
        ctx = Q.ServingContext(mesh=mesh, axis=MODEL_AXIS, size=W,
                               fused=False, comm_fmt=comm_fmt,
                               comm_tiles=tiles)
        return jax.jit(
            lambda a: Q.serving_mm(a, w, kind="row", ctx=ctx)
        )(x)

    base = run("none", 1)
    assert jnp.allclose(run("none", 3), base, atol=1e-5)
    assert _rel(run("int8", 3), base) < 0.03


# ---------------------------------------------------------------------------
# MoE expert-parallel dispatch/combine
# ---------------------------------------------------------------------------
def _moe_fixtures():
    from deepspeed_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=64, hidden_size=32, num_layers=1, num_heads=4,
        intermediate_size=64, moe_num_experts=4, moe_top_k=2,
        moe_capacity_factor=8.0, dtype=jnp.float32,
    )
    rng = np.random.default_rng(11)
    e, d, f = 4, 32, 64
    lw = {
        "router": jnp.asarray(rng.standard_normal((d, e)) * 0.1, jnp.float32),
        "w_gate": jnp.asarray(rng.standard_normal((e, d, f)) * 0.1, jnp.float32),
        "w_up": jnp.asarray(rng.standard_normal((e, d, f)) * 0.1, jnp.float32),
        "w_down": jnp.asarray(rng.standard_normal((e, f, d)) * 0.1, jnp.float32),
    }
    x = jnp.asarray(rng.standard_normal((8, 16, d)), jnp.float32)
    return cfg, lw, x


def test_moe_ep_explicit_a2a_matches_gspmd_and_int8_close():
    from deepspeed_tpu.moe.layer import moe_block, routed_ffn_ep

    cfg, lw, x = _moe_fixtures()
    grid = make_grid(expert=4, data=2)
    set_current_mesh(grid.mesh)
    try:
        ref, _ = jax.jit(functools.partial(moe_block, cfg=cfg))(lw, x)
        ep, _ = jax.jit(
            lambda lw, x: routed_ffn_ep(lw, x, cfg, grid.mesh, fmt="none")
        )(lw, x)
        q, _ = jax.jit(
            lambda lw, x: routed_ffn_ep(lw, x, cfg, grid.mesh, fmt="int8")
        )(lw, x)
    finally:
        set_current_mesh(None)
    # generous capacity -> nothing drops -> explicit EP == GSPMD exactly
    assert jnp.allclose(ep, ref, atol=2e-5)
    assert _rel(q, ep) < 0.05


def test_moe_ep_int8_gradients_flow_ste():
    """The quantized dispatch/combine must not kill training gradients:
    q_all_to_all's straight-through VJP routes cotangents through the
    transposed all-to-all, so expert-weight grads under fmt='int8' stay
    close to the exact-transport grads (and are nowhere near zero)."""
    from deepspeed_tpu.moe.layer import routed_ffn_ep

    cfg, lw, x = _moe_fixtures()
    grid = make_grid(expert=4, data=2)
    set_current_mesh(grid.mesh)
    try:
        def loss(fmt):
            def f(lw_):
                out, _ = routed_ffn_ep(lw_, x, cfg, grid.mesh, fmt=fmt)
                return jnp.sum(out ** 2)
            return jax.jit(jax.grad(f))(lw)

        g_none = loss("none")
        g_q = loss("int8")
    finally:
        set_current_mesh(None)
    for k in ("w_gate", "w_up", "w_down", "router"):
        ref, got = g_none[k], g_q[k]
        mag = float(jnp.max(jnp.abs(ref)))
        assert mag > 0
        assert float(jnp.max(jnp.abs(got))) > 0.1 * mag, f"{k} grad ~zero"
        assert _rel(got, ref) < 0.2, (k, _rel(got, ref))


def test_moe_ep_divisibility_typed_error():
    from deepspeed_tpu.moe.layer import routed_ffn_ep

    cfg, lw, x = _moe_fixtures()
    grid = make_grid(expert=4, data=2)
    with pytest.raises(qcomm.QCommError, match="divide"):
        routed_ffn_ep(lw, x[:5], cfg, grid.mesh, fmt="none")


def test_moe_qcomm_config_routes_through_ep(monkeypatch):
    """cfg.moe_qcomm routes the transformer's MoE layer through the
    explicit EP region (spied) when an expert axis is present, and the
    loss matches the GSPMD path on the no-drop regime."""
    import deepspeed_tpu.models.transformer as T
    from deepspeed_tpu.models import CausalLM, get_preset

    cfg = get_preset(
        "tiny", num_layers=1, num_heads=4, hidden_size=32,
        intermediate_size=64, vocab_size=64, max_seq_len=64,
        moe_num_experts=4, moe_top_k=2, moe_capacity_factor=8.0,
        dtype=jnp.float32,
    )
    model = CausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    tokens = jnp.asarray(
        np.random.default_rng(12).integers(0, 64, (8, 16)), jnp.int32
    )

    calls = []
    import deepspeed_tpu.moe.layer as moe_layer

    orig = moe_layer.routed_ffn_ep

    def spy(*a, **k):
        calls.append(k.get("fmt", a[4] if len(a) > 4 else None))
        return orig(*a, **k)

    monkeypatch.setattr(moe_layer, "routed_ffn_ep", spy)
    grid = make_grid(expert=4, data=2)
    set_current_mesh(grid.mesh)
    try:
        ref = jax.jit(
            lambda p, t: CausalLM(cfg).loss_fn(p, {"input_ids": t})
        )(params, tokens)
        assert not calls  # moe_qcomm unset -> GSPMD path
        cfg_q = cfg.replace(moe_qcomm="none")
        got = jax.jit(
            lambda p, t: CausalLM(cfg_q).loss_fn(p, {"input_ids": t})
        )(params, tokens)
        assert calls and calls[0] == "none"
    finally:
        set_current_mesh(None)
    # the EP region's aux loss is the pmean of per-rank estimates (each
    # over its local tokens) — a slightly different estimator than the
    # global GSPMD aux (mean of products != product of means), so the
    # total loss agrees to ~1e-3, not bitwise
    np.testing.assert_allclose(float(got), float(ref), rtol=5e-3)


# ---------------------------------------------------------------------------
# host-side payload codec (the paged-KV handoff wire format)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt", ["none", "int8", "fp8"])
def test_payload_codec_round_trip(fmt):
    rng = np.random.default_rng(0)
    arr = rng.standard_normal((3, 8, 2, 4)).astype(np.float32)
    q, s = qcomm.quantize_payload(arr, fmt)
    out = qcomm.dequantize_payload(q, s, arr.shape, np.float32, fmt)
    assert out.shape == arr.shape and out.dtype == np.float32
    if fmt == "none":
        assert s is None
        np.testing.assert_array_equal(out, arr)  # exact passthrough
    else:
        # per-chunk amax scaling bounds the relative error like the
        # collectives' wire format (int8: ~amax/127 per element)
        err = np.abs(out - arr).max()
        amax = np.abs(arr).max()
        bound = amax / 127 if fmt == "int8" else amax / 8
        assert err <= bound * 1.01, (err, bound)


def test_payload_codec_rejects_bad_fmt():
    with pytest.raises(qcomm.QCommError):
        qcomm.quantize_payload(np.zeros(4, np.float32), "int4")
    with pytest.raises(qcomm.QCommError):
        qcomm.payload_wire_bytes(16, "bf16")


def test_payload_wire_bytes_accounting():
    # 1000 elements, chunk 256 -> 4 scale groups
    assert qcomm.payload_wire_bytes(1000, "none") == 2000  # bf16 default
    assert qcomm.payload_wire_bytes(1000, "none", none_bytes_per_el=4) == 4000
    assert qcomm.payload_wire_bytes(1000, "int8") == 1000 + 4 * 4
    assert qcomm.payload_wire_bytes(1000, "fp8") == 1000 + 4 * 4
