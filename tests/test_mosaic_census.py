"""No-chip Mosaic compile census: every Pallas entry point in ``ops/pallas/``
either compiles for ``TPU v5 lite`` at Mistral-7B widths (hq 32 / hkv 8 /
hd 128, d 4096, ffn 14336) or its ``supports()`` gate declines the shape.

``libtpu`` is installed in the CPU sandbox, so ``jax.experimental.topologies``
hands out abstract v5e devices and ``.lower(lowering_platforms=("tpu",))
.compile()`` runs the real Mosaic and XLA:TPU compilers with no chip attached.
This is a PRE-FLIGHT for a chip run (it catches API drift, layout refusals and
VMEM overflows in seconds instead of chip-minutes) — it is never evidence that
a kernel runs or computes the right numbers; ``chip_smoke.py`` on the chip is.
"""
import os

import jax
import jax.numpy as jnp
import pytest

HQ, HKV, HD, D, FFN = 32, 8, 128, 4096, 14336
BS = 32  # KV page size the smoke serves with


@pytest.fixture(scope="module")
def v5e():
    # the tier-1 process may already hold libtpu's lockfile (first loader
    # wins); the census only compiles, it never opens a device
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    topologies = pytest.importorskip("jax.experimental.topologies")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu in this environment
        pytest.skip(f"no TPU compiler available off-chip: {e}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo.devices


def _compile(fn, devices, *specs):
    """Trace + lower for TPU + run the Mosaic/XLA:TPU compilers on one
    abstract v5e device.  Returns the compiled executable's text."""
    sh = jax.sharding.SingleDeviceSharding(devices[0])
    specs = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)
             for s in specs]
    compiled = jax.jit(fn).trace(*specs).lower(
        lowering_platforms=("tpu",)).compile()
    return compiled.as_text()


def _spec(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _assert_mosaic(text):
    assert "tpu_custom_call" in text, "no Mosaic kernel in the executable"


@pytest.mark.parametrize("hd,hq,hkv", [(128, HQ, HKV), (64, 16, 8)])
def test_flash_fwd_and_bwd_compile(v5e, hd, hq, hkv):
    from deepspeed_tpu.ops.pallas import flash_kernel as fk

    q = _spec((1, 4096, hq, hd))
    kv = _spec((1, 4096, hkv, hd))
    seg = _spec((1, 4096), jnp.int32)
    assert fk.supports(q, kv, kv, True, 0, seg, None)

    def loss(q, k, v, seg):
        out = fk.pallas_flash_attention(q, k, v, segment_ids=seg)
        return jnp.sum(out.astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), v5e, q, kv, kv, seg)
    # fwd + dq + dkv
    assert text.count("tpu_custom_call") >= 3, text.count("tpu_custom_call")


@pytest.mark.parametrize("slots,hq,hkv,hd,bs,blocks,pages", [
    (16, HQ, HKV, 128, BS, 256, 64),
    (16, 16, 8, 64, BS, 256, 64),       # the packed-lane kernel (hd < 128)
    (64, HQ, HKV, 128, 32, 2304, 128),  # mistral7b_l16_serve_1chip, exactly
    (128, 32, 2, 128, 128, 6272, 48),   # nemotron3_super_l11_e128_serve_1chip
])
def test_paged_decode_compiles(v5e, slots, hq, hkv, hd, bs, blocks, pages):
    """A key tile that Mosaic refuses (a DMA slice off the tiling, a VMEM
    overflow) fails here, on the CPU, not on the chip."""
    from deepspeed_tpu.ops.pallas import paged_attention as pk

    q = _spec((slots, hq, hd))
    pool = _spec((blocks, bs, hkv, hd))
    tables = _spec((slots, pages), jnp.int32)
    lens = _spec((slots,), jnp.int32)
    assert pk.supports(q, pool, None)
    text = _compile(pk.paged_attention_decode_kernel, v5e,
                    q, pool, pool, tables, lens)
    _assert_mosaic(text)
    if hd % 128 == 0:
        # the kernel reads a page's (key, kv head) rows in place: the view of
        # the pool it is handed must cost no copy of the pool
        assert not [l for l in text.splitlines()
                    if f"bf16[{blocks}," in l.split("=")[0] and " copy(" in l]


def test_paged_decode_gate_declines_unaligned_head_dim():
    from deepspeed_tpu.ops.pallas import paged_attention as pk

    # lone hd=64 with hkv*hd not a lane multiple: Mosaic refuses the DMA
    # slice, so the gate must decline it
    assert not pk.supports(_spec((4, 3, 64)), _spec((8, BS, 1, 64)), None)


@pytest.mark.parametrize("t,hq,hkv", [
    (64, HQ, HKV), (256, HQ, HKV), (512, HQ, HKV),
    (8, HQ, HKV),            # a one-slot verify pack
    (512, HQ // 4, HKV // 4),  # the TP=4 shard's pack
])
def test_ctx_kernel_compiles_inside_its_gate(v5e, t, hq, hkv):
    from deepspeed_tpu.ops.pallas import ctx_attention as ck

    q = _spec((t, hq, HD))
    kv = _spec((t, hkv, HD))
    seg = _spec((t,), jnp.int32)
    pool = _spec((2304, BS, hkv, HD))
    tables = _spec((64, 128), jnp.int32)
    lens = _spec((64,), jnp.int32)
    assert ck.supports(q, pool, tables)
    _assert_mosaic(_compile(ck.paged_attention_packed_ctx_kernel, v5e,
                            q, kv, kv, seg, pool, pool, tables, lens))


def test_ctx_gate_declines_oversized_pack():
    """The VMEM gate's verdict at Mistral-7B widths: packs of 256 and 512
    tokens stay on the kernel (the resident q / pack kv / fp32 outputs plus
    the tiles fit its budget), a 1024-token pack does not, so the
    dispatcher must route it to the dense body (and say so — see
    ops.pallas.note_dispatch)."""
    from deepspeed_tpu.ops.pallas import ctx_attention as ck

    pool = _spec((2304, BS, HKV, HD))
    tables = _spec((64, 128), jnp.int32)
    assert ck.supports(_spec((256, HQ, HD)), pool, tables)
    assert ck.supports(_spec((512, HQ, HD)), pool, tables)
    assert not ck.supports(_spec((1024, HQ, HD)), pool, tables)
    # a quarter of the heads (TP=4 local shard) fits the 1024 pack too
    assert ck.supports(_spec((1024, HQ // 4, HD)),
                       _spec((2304, BS, HKV // 4, HD)), tables)


@pytest.mark.parametrize("m", [16, 256])
def test_quant_matmul_int8_and_fp6_compile(v5e, m):
    from deepspeed_tpu.ops.pallas import quant_matmul as qm

    x = _spec((m, D))
    q8 = _spec((D, FFN), jnp.int8)
    planes = _spec((3, D // 4, FFN), jnp.uint8)
    s = _spec((FFN,), jnp.float32)
    _assert_mosaic(_compile(qm.quant_matmul, v5e, x, q8, s))
    _assert_mosaic(_compile(
        lambda x, p, s: qm.quant_matmul_fp6(x, p, s, in_dim=D),
        v5e, x, planes, s))


@pytest.mark.parametrize("shape", [(4096, 4096), (4096, FFN), (32, 8192),
                                   (8, 128)])
def test_quantize_kernels_compile_or_decline(v5e, shape):
    from deepspeed_tpu.ops.pallas import quant_kernel as qk

    x = _spec(shape)
    if not qk.supports(x):
        return  # declined: the jnp body in ops/quantizer.py serves it
    _assert_mosaic(_compile(qk.quantize_int8, v5e, x))
    _assert_mosaic(_compile(qk.quantize_fp8, v5e, x))
    _assert_mosaic(_compile(
        qk.dequantize_int8, v5e, _spec(shape, jnp.int8),
        _spec((shape[0],), jnp.float32)))


def test_quantize_gate_accepts_the_common_weight_shape():
    from deepspeed_tpu.ops.pallas import quant_kernel as qk

    # the census above must not pass by declining everything
    assert qk.supports(_spec((4096, 4096)))
    assert not qk.supports(_spec((7, 100)))


def test_the_latent_kernels_compile_at_dots3_widths(v5e):
    """``index_scores`` and ``selected_attn`` at the served widths: 16 groups
    of 128 queries, 64 index heads x 128, 128 heads over rows of 640 lanes
    (``kv_rank`` 512), pages of 128, tables of 272 pages."""
    from deepspeed_tpu.ops.pallas import index_scores as ik
    from deepspeed_tpu.ops.pallas import selected_attention as sk

    g, c, nb, p = 16, 128, 2176, 272
    assert ik.supports(c, 64, 128, 128) and sk.supports(c, 128, 640, 512, 128)
    tables, live = _spec((g, p), jnp.int32), _spec((g,), jnp.int32)
    _assert_mosaic(_compile(
        lambda q, w, k, t, n: ik.paged_index_scores(q, w, k, t, n, 0.1), v5e,
        _spec((g, c, 64, 128)), _spec((g, c, 64), jnp.float32), _spec((nb, 128, 128)),
        tables, live))
    _assert_mosaic(_compile(
        lambda q, m, k, t, n: sk.selected_attention(q, m, k, t, n, 512, 0.07), v5e,
        _spec((g, c, 128, 640)), _spec((g, c, p * 128), jnp.int8), _spec((nb, 128, 640)),
        tables, live))


def test_the_every_row_kernels_compile_at_deepseek_v2_widths(v5e):
    """``latent_prefill`` and ``latent_decode`` at the served widths: a pack of
    2048 queries x 128 heads of 128 + 64 (padded to the 256 lanes of ``[k_nope ;
    row[512:]]``), ``[W_uk | W_uv]`` 512 x 256 a head, rows of 640 lanes, pages of
    128, tables of 392 pages, up to 8 runs a pack; 24 slots a tick."""
    from deepspeed_tpu.ops.pallas import latent_decode as dk
    from deepspeed_tpu.ops.pallas import latent_prefill as pk

    t, h, nb, p = 2048, 128, 3840, 392
    assert pk.supports(t, h, 640, 512, 128, 128, 128) and dk.supports(h, 640, 512, 128)
    _assert_mosaic(_compile(
        lambda q, w, k, tab, runs: pk.latent_prefill(q, w, k, tab, runs, 512, 0.11), v5e,
        _spec((h, t, 256)), _spec((h, 512, 256)), _spec((nb, 128, 640)),
        _spec((8, p), jnp.int32), _spec((8, 3), jnp.int32)))
    _assert_mosaic(_compile(
        lambda q, k, tab, lens: dk.latent_decode(q, k, tab, lens, 512, 0.11), v5e,
        _spec((24, h, 640)), _spec((nb, 128, 640)), _spec((24, p), jnp.int32),
        _spec((24,), jnp.int32)))


def test_flash_partitions_on_four_chips(v5e, monkeypatch):
    """The flash dispatcher under a 4-device mesh lowers (shard_map region)
    where the bare kernel call raises 'Mosaic kernels cannot be
    automatically partitioned'."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.ops.pallas import flash_attention as fa
    from deepspeed_tpu.ops.pallas import flash_kernel as fk
    from deepspeed_tpu.parallel.topology import MeshSpec, build_mesh

    # the process's default backend is the CPU; the lowering target is not
    monkeypatch.setattr(fa, "is_compatible", lambda: True)
    q1 = jax.ShapeDtypeStruct(
        (4, 1024, HQ, HD), jnp.bfloat16,
        sharding=NamedSharding(build_mesh(MeshSpec(fsdp=4), v5e), P("fsdp")))
    with pytest.raises(NotImplementedError, match="automatically partitioned"):
        jax.jit(fk.pallas_flash_attention).trace(q1, q1, q1).lower(
            lowering_platforms=("tpu",))

    for axes, qspec in (({"fsdp": 4}, P("fsdp")),
                        ({"model": 4}, P(None, None, "model"))):
        mesh = build_mesh(MeshSpec(**axes), v5e)
        b = 4 if "fsdp" in axes else 1
        sh = NamedSharding(mesh, qspec)
        q = jax.ShapeDtypeStruct((b, 1024, HQ, HD), jnp.bfloat16, sharding=sh)
        kv = jax.ShapeDtypeStruct((b, 1024, HKV, HD), jnp.bfloat16,
                                  sharding=sh)

        def f(q, k, v):
            return fa.flash_attention(q, k, v, mesh=mesh)

        text = jax.jit(f).trace(q, kv, kv).lower(
            lowering_platforms=("tpu",)).compile().as_text()
        _assert_mosaic(text)
        # per-chip slice, not the gathered batch/heads
        local = "bf16[8,1024,128]" if "model" in axes else "bf16[32,1024,128]"
        assert local in text, f"flash did not run on the {axes} local slice"


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-q", "-m", "slow", "-x", "-s"]))
