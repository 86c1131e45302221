"""The serving programs read their attention weights in place (PR 30).

XLA:TPU folds ``_qkv``'s split into heads into the projection's dot and then
wants the weight as ``[heads, hd, d]``: under (8, 128) tiling that is no
bitcast of the stored ``[d, heads * hd]``, so every call of every serving
program re-laid wq / wk / wv of every layer.  ``model_runner._qkv`` now holds
the projections' results as ``[rows, features]`` behind one
``optimization_barrier``; nothing is placed, nothing is copied, and
``eng.params`` stays the stacked tree the benchmark's driver and plain
reference read.

Here, on the CPU: what the four bodies lower to; that the barrier is the
identity on every path that shares ``_qkv`` (bitwise); the benchmark's seam.
What the TPU's compiler makes of the bodies at tiled widths (no weight-sized
``copy`` is left) is in ``test_overlap_hlo.py``, beside the other compiles
for a described chip: one file loads the TPU's library, not two.
"""
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark import harness  # noqa: E402

from deepspeed_tpu.inference import model_runner  # noqa: E402
from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2  # noqa: E402
from deepspeed_tpu.inference.paged import init_paged_cache  # noqa: E402
from deepspeed_tpu.inference.sampling import SamplingParams  # noqa: E402
from deepspeed_tpu.models import get_preset  # noqa: E402
from deepspeed_tpu.models.transformer import init_params  # noqa: E402
from deepspeed_tpu.ops.quantizer import serving_mm  # noqa: E402

from conftest import make_grid  # noqa: E402

BODIES = ("prefill_packed", "prefill_packed_ctx", "verify_packed_ctx", "decode_step")


def _qkv_plain(lw, x, cfg, ctx=None):
    """``_qkv`` as the parent commit had it: the reference the barrier is
    held to, and what the compiler is shown to re-lay."""
    b, s, _ = x.shape
    kv_kind = "col" if (ctx is None or ctx.kv_cols) else "rep"
    bias = (lambda n: lw.get(n)) if cfg.qkv_bias else (lambda n: None)
    q = serving_mm(x, lw["wq"], bias("bq"), kind="col", ctx=ctx)
    k = serving_mm(x, lw["wk"], bias("bk"), kind=kv_kind, ctx=ctx)
    v = serving_mm(x, lw["wv"], bias("bv"), kind=kv_kind, ctx=ctx)
    return (q.reshape(b, s, cfg.num_heads, cfg.hd),
            k.reshape(b, s, cfg.num_kv_heads, cfg.hd),
            v.reshape(b, s, cfg.num_kv_heads, cfg.hd))


def _body(name, cfg, *, slots, pages, pack, bs, blocks, spec=lambda a: a):
    """One of the four dense entries as ``fn(params, *args)`` with the shapes
    of its arguments (``spec`` decorates each ``ShapeDtypeStruct``)."""
    i32 = jnp.int32
    S = lambda shape, dt=i32: spec(jax.ShapeDtypeStruct(shape, dt))
    kv = jax.tree_util.tree_map(
        spec, jax.eval_shape(lambda: init_paged_cache(
            cfg.num_layers, blocks, bs, cfg.num_kv_heads, cfg.hd, dtype=cfg.dtype)))
    params = jax.tree_util.tree_map(
        spec, jax.eval_shape(lambda k: init_params(k, cfg, dtype=cfg.dtype),
                             jax.random.PRNGKey(0)))
    fn = getattr(model_runner, name)
    args = {
        "prefill_packed": (S((pack,)), S((pack,)), S((pack,)), S((pack // bs,)),
                           S((slots,)), kv),
        "prefill_packed_ctx": (S((pack,)), S((pack,)), S((pack,)), S((pack // bs,)),
                               S((slots,)), S((slots, pages)), S((slots,)), kv),
        "verify_packed_ctx": (S((pack,)), S((pack,)), S((pack,)), S((pack,)),
                              S((pack,)), S((slots, pages)), S((slots,)), kv),
        "decode_step": (S((slots,)), S((slots,)), S((slots, pages)),
                        S((slots,), jnp.bool_), kv),
    }[name]
    return (lambda p, *a: fn(p, cfg, *a)), (params,) + args


# -- what the bodies lower to ------------------------------------------------
@pytest.mark.parametrize("name", BODIES)
def test_every_body_holds_its_projections_before_the_head_split(name):
    """One barrier a layer, from the one shared helper: between each
    projection's dot and its reshape into heads, in all four entries: the ONE layer body."""
    cfg = get_preset("tiny", max_seq_len=64, dtype=jnp.float32)
    fn, specs = _body(name, cfg, slots=4, pages=8, pack=16, bs=8, blocks=16)
    text = jax.jit(fn).lower(*specs).as_text()
    assert text.count("stablehlo.optimization_barrier") == cfg.num_layers
    # the barrier takes the three projections as [b, s, features]: still 3-D
    m = re.search(r"optimization_barrier .* : (.*)", text)
    assert m and re.fullmatch(r"(tensor<\d+x\d+x\d+xf32>(, )?){3}", m.group(1)), m


def test_plain_reference_has_no_barrier(monkeypatch):
    monkeypatch.setattr(model_runner, "_qkv", _qkv_plain)
    cfg = get_preset("tiny", max_seq_len=64, dtype=jnp.float32)
    fn, specs = _body("decode_step", cfg, slots=4, pages=8, pack=16, bs=8, blocks=16)
    assert "optimization_barrier" not in jax.jit(fn).lower(*specs).as_text()


# -- the barrier is the identity wherever _qkv runs ---------------------------
def _serve(eng, prompts, new=6):
    sched = eng.scheduler
    for uid, p in enumerate(prompts, 1):
        assert sched.try_submit(uid, p, SamplingParams(temperature=0.0,
                                                       max_new_tokens=new)).accepted
    uids = list(range(1, len(prompts) + 1))
    sched.run(wait_for=uids)
    return [sched.pop_result(u) for u in uids]


def _decode_logits(eng, cfg):
    """``decode_step`` through a fresh jit on ``eng.params``, the engine's
    mesh and serving context: one token a slot on pages of its own."""
    n, p = eng.mgr.max_seqs, eng.max_pages
    kv = init_paged_cache(cfg.num_layers, n + 1, eng.block_size, cfg.num_kv_heads,
                          cfg.hd, dtype=cfg.dtype)
    if eng._kv_shardings is not None:
        kv = jax.device_put(kv, eng._kv_shardings)
    table = np.full((n, p), -1, np.int32)
    table[:, 0] = np.arange(n)
    params = eng.params
    if eng._offload_weights:  # host-resident: staged as the engine stages them
        params = jax.tree_util.tree_map(lambda x: jnp.asarray(np.asarray(x)), params)
    lg, _ = jax.jit(lambda pr, *a: model_runner.decode_step(
        pr, cfg, *a, ctx=eng.serving_ctx, mesh=eng._mesh))(
        params, np.arange(3, 3 + n, dtype=np.int32), np.zeros(n, np.int32),
        table, np.ones(n, bool), kv)
    return np.asarray(lg)


MODES = {
    "float32": dict(),
    "bfloat16": dict(dtype=jnp.bfloat16),
    "qkv_bias": dict(cfg=dict(qkv_bias=True)),
    "int8": dict(eng=dict(quantize_weights="int8")),
    "fp6": dict(eng=dict(quantize_weights="fp6")),
    "offload_weights": dict(eng=dict(offload_weights=True)),
    "tp2": dict(grid=dict(model=2)),
    "tp4_replicated_kv": dict(grid=dict(model=4)),
    "prefix_cache_chunked": dict(eng=dict(enable_prefix_caching=True, prefill_chunk=16)),
    "speculation": dict(eng=dict(enable_speculation=True)),
}


@pytest.mark.parametrize("mode", sorted(MODES))
def test_outputs_are_bitwise_those_of_the_plain_projection(mode, monkeypatch):
    """Quantised leaves, host-resident weights, a TP mesh, biases, the ctx and
    verify packs: greedy tokens through the scheduler and ``decode_step``'s
    logits with the barrier are bit for bit those without it, and the engine
    hands back the tree it was given: nothing is placed, on any path."""
    m = MODES[mode]
    cfg = get_preset("tiny", max_seq_len=128, dtype=m.get("dtype", jnp.float32),
                     **m.get("cfg", {}))
    params = init_params(jax.random.PRNGKey(3), cfg, dtype=cfg.dtype)
    given = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    kw = dict(max_seqs=4, num_blocks=64, block_size=8, prefill_buckets=(16, 32),
              **m.get("eng", {}))
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6] * 3, [2, 7, 1, 8, 2, 8, 1], [3, 1, 4, 1, 5, 9, 2, 6] * 2 + [7]]
    got = {}
    for side in ("barrier", "plain"):
        if side == "plain":
            monkeypatch.setattr(model_runner, "_qkv", _qkv_plain)
        grid = make_grid(**m["grid"]) if "grid" in m else None
        eng = InferenceEngineV2(params, cfg, grid=grid, **kw)
        if not (kw.get("quantize_weights") or grid is not None or kw.get("offload_weights")):
            assert eng.params is params
        if not kw.get("quantize_weights"):
            assert jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), eng.params) == given
        got[side] = (_serve(eng, prompts), _decode_logits(eng, cfg))
        audit = eng.close()
        assert audit["blocks_in_use"] == 0, audit
    assert got["barrier"][0] == got["plain"][0]
    assert all(len(t) == 6 for t in got["barrier"][0])
    np.testing.assert_array_equal(got["barrier"][1], got["plain"][1])


def test_latent_layers_do_not_pass_through_qkv(monkeypatch):
    """``cfg.latent`` goes through ``latent_runner`` and its per-layer trees:
    with ``_qkv`` made to raise it serves as before."""
    def boom(*a, **k):
        raise AssertionError("a latent model reached the dense _qkv")

    monkeypatch.setattr(model_runner, "_qkv", boom)
    m = harness.rehearsed(harness.load_json(
        ROOT / "benchmark/configs/dots3_note_l5_e32_serve_1chip.json"), True)
    arch = harness.module("models", m["model_type"])
    cfg = arch.transformer_config(m, max_seq_len=m["engine"]["max_seq_len"])
    params = init_params(jax.random.PRNGKey(7), cfg)
    eng = InferenceEngineV2(params, cfg, max_seqs=4, num_blocks=64, block_size=8,
                            prefill_buckets=(32,), prefill_chunk=32, max_seq_len=256)
    (out,) = _serve(eng, [list(range(5, 45))], new=4)
    assert len(out) == 4
    eng.close()


# -- the benchmark's seam: eng.params is the stacked tree ---------------------
def test_harness_seam_reads_the_engines_params():
    """``benchmark/drivers/serve.py`` hands ``eng.params`` to its own
    ``jax.jit`` of ``prefill_packed`` / ``decode_step`` and to the plain
    reference, which scans ``params["layers"]``: both run on the engine's
    tree and agree with the engine's own programs."""
    from benchmark.drivers import serve

    model = harness.rehearsed(harness.load_json(
        ROOT / "benchmark/configs/mistral7b_l16_serve_1chip.json"), True)
    arch = harness.module("models", model["model_type"])
    e = model["engine"]
    cfg = arch.transformer_config(model, max_seq_len=e["max_seq_len"])
    params = init_params(jax.random.PRNGKey(11), cfg, dtype=cfg.dtype)
    eng = InferenceEngineV2(
        params, cfg, max_seqs=e["max_seqs"], num_blocks=e["num_blocks"],
        block_size=e["block_size"], max_seq_len=e["max_seq_len"],
        prefill_buckets=(e["prefill_chunk"],), prefill_chunk=e["prefill_chunk"],
        enable_prefix_caching=e["prefix_caching"])
    # the stacked [L, ...] tree, leaf for leaf the one that was handed in
    assert jax.tree_util.tree_structure(eng.params) == jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(eng.params), jax.tree_util.tree_leaves(params)):
        assert a.shape == b.shape and a.dtype == b.dtype
    assert eng.params["layers"]["attn"]["wq"].shape[0] == cfg.num_layers

    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, 20).tolist()
    steps = 6
    seq, got = serve._runner_logits(jax, np, eng, cfg, prompt, steps)
    buf = np.zeros((1, 64), np.int32)
    buf[0, :len(seq)] = seq
    ref = np.asarray(jax.jit(lambda p, t: arch.logits(p, t, model))(eng.params, buf))[0]
    ref = ref[len(prompt) - 1: len(prompt) + steps]
    np.testing.assert_allclose(got, ref, atol=2e-4, rtol=0)
    # a scan over the stacked layers, as the reference does it
    n = jax.lax.scan(lambda c, lw: (c + lw["attn"]["wq"].shape[0], None), 0,
                     eng.params["layers"])[0]
    assert int(n) == cfg.num_layers * cfg.hidden_size
    # the engine's own programs (flash pack, decode ticks, fused sampling)
    (out,) = _serve(eng, [prompt], new=steps)
    assert out == seq[len(prompt):]
    assert eng.close()["blocks_in_use"] == 0
