"""Packed-suffix context-attention Pallas kernel (ISSUE 19).

The prefill/verify analogue of test_paged_kernel.py: interpreter-mode
parity of ``ops/pallas/ctx_attention.py`` against the jnp dense body it
replaces (``inference/paged.py``), across the shapes the engine actually
serves — GQA-narrow kv heads, fused ``logits_soft_cap``, padded pack
rows, mid-page verify starts, prefix-cache hits vs the cold prefill they
must be numerically identical to — plus the seq-shard flash-partial
contract (``include_pack`` charge-to-shard-0, log-sum-exp ring merge),
the ``ServingContext.fused`` dispatch gate, greedy token identity through
the full engine on tp/dp/seq-shard meshes, and the compiled
memory-analysis proof that pack temporaries no longer scale with the
block-table width (the dense body's O(T * P * bs) gather); since PR 25 the
cases the kernel's own loops can get wrong (key-tile and row-tile edges,
empty slots, off-shard table rows) and its step count.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import dense_serving_context
from deepspeed_tpu.inference import InferenceEngineV2, SamplingParams
from deepspeed_tpu.inference import paged
from deepspeed_tpu.inference.paged import (
    _lse_merge_packed,
    _packed_ctx_partial,
    _paged_attention_packed_ctx_dense,
    paged_attention_packed_ctx,
)
from deepspeed_tpu.ops.pallas import ctx_attention as ck


@pytest.fixture(autouse=True)
def _interpret():
    ck.set_interpret(True)
    yield
    ck.set_interpret(False)


def _setup(segs, hq=8, hkv=2, hd=16, nb=32, bs=8, pad=0, seed=0,
           dtype=jnp.float32):
    """Build a pack from ``segs`` = [(pack_len, ctx_len), ...]: contiguous
    1-based segment ids (+ ``pad`` trailing zero rows), pools with random
    contents, and per-slot tables holding distinct live pages."""
    rng = np.random.default_rng(seed)
    t = sum(l for l, _ in segs) + pad
    n = len(segs)
    p = max(max((-(-c // bs) for _, c in segs), default=1), 1)
    q = jnp.asarray(rng.normal(size=(t, hq, hd)), dtype)
    kpk = jnp.asarray(rng.normal(size=(t, hkv, hd)), dtype)
    vpk = jnp.asarray(rng.normal(size=(t, hkv, hd)), dtype)
    ckl = jnp.asarray(rng.normal(size=(nb, bs, hkv, hd)), dtype)
    cvl = jnp.asarray(rng.normal(size=(nb, bs, hkv, hd)), dtype)
    seg_ids = sum(([i + 1] * l for i, (l, _) in enumerate(segs)), [])
    seg_ids += [0] * pad
    perm = rng.permutation(nb)
    tables = np.full((n, p), -1, np.int32)
    nxt = 0
    for i, (_, c) in enumerate(segs):
        for j in range(-(-c // bs)):
            tables[i, j] = perm[nxt]
            nxt += 1
    lens = jnp.asarray([c for _, c in segs], jnp.int32)
    return (q, kpk, vpk, jnp.asarray(seg_ids, jnp.int32), ckl, cvl,
            jnp.asarray(tables), lens)


SEGS = [(10, 13), (6, 0), (6, 37)]  # mid-page, cold, multi-page


@pytest.mark.parametrize("hq,hkv,hd,cap", [
    (8, 8, 64, None),     # 410M-proxy: MHA, hd 64
    (8, 2, 128, None),    # 8B-proxy: GQA-narrow (hkv < tp at tp=4), hd 128
    (4, 1, 16, None),     # MQA corner
    (8, 2, 128, 20.0),
    (8, 8, 64, 20.0),
    (4, 1, 16, 20.0),
])
def test_kernel_parity_vs_dense(hq, hkv, hd, cap):
    q, k, v, seg, ckl, cvl, tb, ln = _setup(SEGS, hq=hq, hkv=hkv, hd=hd,
                                            pad=2)
    out = ck.paged_attention_packed_ctx_kernel(
        q, k, v, seg, ckl, cvl, tb, ln, logits_soft_cap=cap)
    ref = _paged_attention_packed_ctx_dense(
        q, k, v, seg, ckl, cvl, tb, ln, logits_soft_cap=cap)
    valid = np.asarray(seg) > 0
    np.testing.assert_allclose(np.asarray(out)[valid], np.asarray(ref)[valid],
                               atol=2e-5, err_msg=f"{hq}/{hkv}/{hd} cap={cap}")


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles the toy shapes overflow: key tile K*bs = 16 keys (K = 2 pages
    of 8), row tile 16 rows — so the dynamic loops take several trips."""
    monkeypatch.setattr(ck, "_KEY_TILE", 16)
    monkeypatch.setattr(ck, "_ROW_TILE_M", 64)  # g = 4 -> tq = 16


# what the loop structure can get wrong, each against the dense body:
# (pack_len, ctx_len) per slot row; (0, 0) is an empty slot
_LOOP_CASES = {
    # context lengths around the key tile's edge (K*bs = 16): 1, bs-1,
    # K*bs-1, K*bs, K*bs+1 keys, and two tiles and a bit
    "ctx_tile_edges": [(4, 1), (4, 7), (4, 15), (4, 16), (4, 17), (4, 37)],
    # 8 page-aligned segments, contexts all different, between empty slots
    # at both ends of the table (the engine's chat pack)
    "eight_aligned_between_empty_slots":
        [(0, 0), (0, 0)] + [(8 * (1 + i % 2), 5 + 11 * i) for i in range(8)]
        + [(0, 0), (0, 0), (0, 0)],
    # a verify pack: 1 + n_draft rows a slot, every start mid row-tile, the
    # last row tile clamped at the pack's end
    "verify_starts_mid_row_tile": [(5, 13 + 9 * i) for i in range(7)],
    # one segment over several row tiles: the causal stage crosses tiles
    "one_segment_three_row_tiles": [(40, 37)],
    # ctx_len 0 beside a full table row (ctx_len = P * bs) in one pack
    "cold_beside_full_table": [(6, 0), (10, 64), (3, 0)],
}


@pytest.mark.parametrize("case", sorted(_LOOP_CASES))
def test_loop_structure_parity(case, small_tiles):
    segs = _LOOP_CASES[case]
    args = _setup(segs, hq=8, hkv=2, hd=16, nb=64, pad=3)
    if case == "cold_beside_full_table":
        assert args[6].shape[1] * 8 == 64  # the row is full: ctx_len = P * bs
    out = ck.paged_attention_packed_ctx_kernel(*args)
    ref = _paged_attention_packed_ctx_dense(*args)
    valid = np.asarray(args[3]) > 0
    np.testing.assert_allclose(np.asarray(out)[valid], np.asarray(ref)[valid],
                               atol=2e-5, err_msg=case)
    assert (np.asarray(out)[~valid] == 0.0).all()


def test_partial_row_of_out_of_range_ids(small_tiles):
    """Under striping a slot's table row may hold only another shard's pages
    (ids >= nb, or < 0 after the local translation): nothing is fetched for
    it, its rows keep the pack's keys alone (or nothing, off shard 0), and
    every slot's partial is what the jnp partial gives."""
    q, k, v, seg, ckl, cvl, tb, ln = _setup(
        [(6, 21), (5, 30), (4, 9)], hq=8, hkv=2, hd=16, nb=32, pad=1)
    tb = np.asarray(tb).copy()
    tb[1] = np.where(tb[1] >= 0, tb[1] + 32, -1)   # all of slot 1: off-shard
    tb[2, 0] = -5                                  # slot 2: page 0 off-shard
    tb = jnp.asarray(tb)
    for inc in (True, False):
        got = ck.paged_attention_packed_ctx_kernel(
            q, k, v, seg, ckl, cvl, tb, ln, include_pack=jnp.asarray(inc),
            partial=True)
        want = _packed_ctx_partial(q, k, v, seg, ckl, cvl, tb, ln,
                                   jnp.asarray(inc))
        vrows = np.asarray(seg) > 0
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g)[vrows],
                                       np.asarray(w)[vrows], atol=2e-4,
                                       err_msg=f"include_pack={inc}")


def _pallas_grids(jaxpr):
    """Grids of every pallas_call under ``jaxpr``, nested jits included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(tuple(eqn.params["grid_mapping"].grid))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_grids(sub)
    return found


def test_step_count_follows_the_pack_not_the_table():
    """At the benchmark's shape (64 slots x 128 table pages, a 256-row pack)
    the call has at most N grid steps: the table's page dimension is walked
    by the loop inside the kernel, for live pages only."""
    n, p, t = 64, 128, 256
    sds = jax.ShapeDtypeStruct
    bf = jnp.bfloat16
    args = (sds((t, 32, 128), bf), sds((t, 8, 128), bf), sds((t, 8, 128), bf),
            sds((t,), jnp.int32), sds((2304, 32, 8, 128), bf),
            sds((2304, 32, 8, 128), bf), sds((n, p), jnp.int32),
            sds((n,), jnp.int32))
    grids = _pallas_grids(
        jax.make_jaxpr(ck.paged_attention_packed_ctx_kernel)(*args).jaxpr)
    assert len(grids) == 1, grids
    steps = int(np.prod(grids[0], dtype=np.int64))
    assert steps <= n, grids


def test_supports_takes_the_benchmark_shape_on_hardware():
    """Pure shape arithmetic with the interpreter off: the one-chip serving
    shape (T = 256 at Mistral-7B widths) and the TP = 4 shard's (T = 512 at a
    quarter of the heads) stay on the kernel; only hd % 128 and VMEM decline."""
    ck.set_interpret(False)
    sds = jax.ShapeDtypeStruct
    bf = jnp.bfloat16
    tables = sds((64, 128), jnp.int32)
    assert ck.supports(sds((256, 32, 128), bf), sds((2304, 32, 8, 128), bf),
                       tables)
    assert ck.supports(sds((512, 8, 128), bf), sds((2304, 32, 2, 128), bf),
                       tables)
    assert not ck.supports(sds((256, 32, 64), bf), sds((2304, 32, 8, 64), bf),
                           tables)
    assert not ck.supports(sds((4096, 32, 128), bf),
                           sds((2304, 32, 8, 128), bf), tables)


def test_mid_page_verify_starts():
    """Verify packs are k+1 rows per slot starting at the decode head —
    ctx_lens deliberately NOT page-aligned, pack segments tiny."""
    q, k, v, seg, ckl, cvl, tb, ln = _setup(
        [(3, 13), (3, 21), (3, 5), (3, 0)], hq=4, hkv=2, hd=32, pad=4)
    out = ck.paged_attention_packed_ctx_kernel(q, k, v, seg, ckl, cvl, tb, ln)
    ref = _paged_attention_packed_ctx_dense(q, k, v, seg, ckl, cvl, tb, ln)
    valid = np.asarray(seg) > 0
    np.testing.assert_allclose(np.asarray(out)[valid], np.asarray(ref)[valid],
                               atol=2e-5)


def test_pad_rows_come_back_exactly_zero():
    """The kernel leaves padding rows (segment_ids == 0) at the (0, -inf, 0)
    init state, so normalization returns exactly 0 — unlike the dense body,
    whose pad rows hold garbage the engine never reads.  This pins the
    stronger kernel contract so nothing starts depending on dense garbage."""
    q, k, v, seg, ckl, cvl, tb, ln = _setup(SEGS, pad=6)
    out = np.asarray(
        ck.paged_attention_packed_ctx_kernel(q, k, v, seg, ckl, cvl, tb, ln))
    assert (out[np.asarray(seg) == 0] == 0.0).all()


def test_kernel_ignores_garbage_in_dead_pages():
    """Pool blocks no segment owns may hold other sequences' live KV — the
    kernel routes only the table's live entries, so poisoning every dead
    block cannot move the output."""
    q, k, v, seg, ckl, cvl, tb, ln = _setup(SEGS, pad=2)
    out1 = ck.paged_attention_packed_ctx_kernel(q, k, v, seg, ckl, cvl, tb, ln)
    live = {int(b) for b in np.asarray(tb).ravel() if b >= 0}
    dead = jnp.asarray([b for b in range(ckl.shape[0]) if b not in live])
    out2 = ck.paged_attention_packed_ctx_kernel(
        q, k, v, seg, ckl.at[dead].set(1e4), cvl.at[dead].set(1e4), tb, ln)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=2e-5)


def test_prefix_hit_identical_to_cold_prefill():
    """A suffix prefill over cached context must be numerically the SAME
    reduction as the cold full-prompt prefill — the invariant prefix
    caching rides on.  Build one 21-token prompt; serve it cold (one pack
    segment, no ctx) and as a 5-token suffix over a 16-token (2-page)
    cached prefix; the suffix rows must agree."""
    rng = np.random.default_rng(7)
    L, pre, bs, hq, hkv, hd, nb = 21, 16, 8, 4, 2, 16, 16
    q = jnp.asarray(rng.normal(size=(L, hq, hd)), jnp.float32)
    kk = jnp.asarray(rng.normal(size=(L, hkv, hd)), jnp.float32)
    vv = jnp.asarray(rng.normal(size=(L, hkv, hd)), jnp.float32)
    ckl = jnp.asarray(rng.normal(size=(nb, bs, hkv, hd)), jnp.float32)
    cvl = jnp.asarray(rng.normal(size=(nb, bs, hkv, hd)), jnp.float32)
    cold = ck.paged_attention_packed_ctx_kernel(
        q, kk, vv, jnp.ones((L,), jnp.int32), ckl, cvl,
        jnp.full((1, 1), -1, jnp.int32), jnp.zeros((1,), jnp.int32))
    cold_ref = _paged_attention_packed_ctx_dense(
        q, kk, vv, jnp.ones((L,), jnp.int32), ckl, cvl,
        jnp.full((1, 1), -1, jnp.int32), jnp.zeros((1,), jnp.int32))
    # cache the prefix KV into pages 3 and 7, then prefill just the suffix
    ckl2 = ckl.at[3].set(kk[:bs]).at[7].set(kk[bs:pre])
    cvl2 = cvl.at[3].set(vv[:bs]).at[7].set(vv[bs:pre])
    hit = ck.paged_attention_packed_ctx_kernel(
        q[pre:], kk[pre:], vv[pre:], jnp.ones((L - pre,), jnp.int32),
        ckl2, cvl2, jnp.asarray([[3, 7]], jnp.int32),
        jnp.asarray([pre], jnp.int32))
    np.testing.assert_allclose(np.asarray(hit), np.asarray(cold)[pre:],
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(hit), np.asarray(cold_ref)[pre:],
                               atol=2e-5)


def test_partial_mode_striped_ring_merge():
    """Seq-shard contract: stripe the pool over 2 shards, run the kernel in
    ``partial=True`` on each shard's locally-translated tables (pack keys
    charged to shard 0 only via ``include_pack``), and the log-sum-exp ring
    merge of the two flash triples must equal the full dense softmax.  Each
    shard's triple also matches the jnp ``_packed_ctx_partial`` reference."""
    S = 2
    q, k, v, seg, ckl, cvl, tb, ln = _setup(SEGS, nb=32, pad=2)
    full = _paged_attention_packed_ctx_dense(q, k, v, seg, ckl, cvl, tb, ln)
    nb_l = ckl.shape[0] // S
    parts = []
    for s in range(S):
        ck_l, cv_l = ckl[s * nb_l:(s + 1) * nb_l], cvl[s * nb_l:(s + 1) * nb_l]
        tb_l = jnp.where(tb >= 0, tb - s * nb_l, -1)
        inc = jnp.asarray(s == 0)
        got = ck.paged_attention_packed_ctx_kernel(
            q, k, v, seg, ck_l, cv_l, tb_l, ln, include_pack=inc,
            partial=True)
        want = _packed_ctx_partial(q, k, v, seg, ck_l, cv_l, tb_l, ln, inc)
        vrows = np.asarray(seg) > 0  # pad rows: kernel stays at the
        for g, w in zip(got, want):  # (0, -inf, 0) init, dense self-attends
            np.testing.assert_allclose(np.asarray(g)[vrows],
                                       np.asarray(w)[vrows],
                                       atol=2e-4, err_msg=f"shard {s}")
        acc, m, l = got
        parts.append(jnp.concatenate(
            [acc, m[..., None], l[..., None]], axis=-1))
    merged = _lse_merge_packed(parts[0], parts[1])
    out = merged[..., :-2] / jnp.maximum(merged[..., -1:], 1e-30)
    valid = np.asarray(seg) > 0
    np.testing.assert_allclose(np.asarray(out)[valid], np.asarray(full)[valid],
                               atol=2e-5)


def test_dispatch_fused_gate(monkeypatch):
    """``paged_attention_packed_ctx`` routes to the kernel under the same
    convention as decode: on TPU or interpret AND ``supports()``, with
    ``ctx.fused is False`` (the ServingContext A/B lever) pinning dense."""
    calls = []
    real = ck.paged_attention_packed_ctx_kernel
    monkeypatch.setattr(ck, "paged_attention_packed_ctx_kernel",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    q, k, v, seg, ckl, cvl, tb, ln = _setup(SEGS, pad=2)
    ref = _paged_attention_packed_ctx_dense(q, k, v, seg, ckl, cvl, tb, ln)

    class Ctx:
        fused = None

    out = paged_attention_packed_ctx(q, k, v, seg, ckl, cvl, tb, ln, ctx=Ctx())
    assert calls, "auto dispatch skipped the kernel under interpret"
    valid = np.asarray(seg) > 0
    np.testing.assert_allclose(np.asarray(out)[valid], np.asarray(ref)[valid],
                               atol=2e-5)

    calls.clear()

    class CtxPin:
        fused = False

    out = paged_attention_packed_ctx(q, k, v, seg, ckl, cvl, tb, ln,
                                     ctx=CtxPin())
    assert not calls, "fused=False must pin the jnp dense body"
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=0)

    # unsupported lane width falls back even on the kernel-eligible path
    calls.clear()
    q2, k2, v2, seg2, ckl2, cvl2, tb2, ln2 = _setup(SEGS, hd=12, pad=2)
    assert not ck.supports(q2, ckl2, tb2)
    paged_attention_packed_ctx(q2, k2, v2, seg2, ckl2, cvl2, tb2, ln2,
                               ctx=Ctx())
    assert not calls


def test_dense_clamp_scales_with_true_context():
    """Satellite fix: with CONCRETE ctx_lens the dense/ground-truth body
    clamps its gather to ceil(max(ctx_lens)/bs) pages, so a wide table
    (engine tables size for max_seq_len) costs what the live context
    costs.  Identity across table widths, and traced lens still work."""
    q, k, v, seg, ckl, cvl, tb, ln = _setup(SEGS, pad=2)
    wide = jnp.concatenate(
        [tb, jnp.full((tb.shape[0], 64), -1, jnp.int32)], axis=1)
    narrow = _paged_attention_packed_ctx_dense(q, k, v, seg, ckl, cvl, tb, ln)
    out = _paged_attention_packed_ctx_dense(q, k, v, seg, ckl, cvl, wide, ln)
    np.testing.assert_allclose(np.asarray(out), np.asarray(narrow), atol=1e-6)
    # all-zero lens (pure cold pack) keeps at least one table column
    cold = _paged_attention_packed_ctx_dense(
        q, k, v, seg, ckl, cvl, wide, jnp.zeros_like(ln))
    assert np.isfinite(np.asarray(cold)[np.asarray(seg) > 0]).all()
    # under jit the lens are traced: the clamp is a no-op, not an error
    jit_out = jax.jit(_paged_attention_packed_ctx_dense)(
        q, k, v, seg, ckl, cvl, wide, ln)
    np.testing.assert_allclose(np.asarray(jit_out), np.asarray(narrow),
                               atol=2e-5)


# ---------------------------------------------------------------------------
# engine: greedy token identity, kernel vs pinned-dense
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_model():
    from deepspeed_tpu.models import CausalLM, get_preset

    # fp32 so greedy identity across reduction orders cannot flip argmax
    cfg = get_preset("tiny", max_seq_len=256, dtype=jnp.float32)
    model = CausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return model, params


ENGINE_KW = dict(max_seqs=4, num_blocks=64, block_size=8,
                 prefill_buckets=(16, 32), prefill_budget=32,
                 enable_prefix_caching=True, prefill_chunk=16,
                 enable_speculation=True, spec_max_draft=4,
                 quantize_weights="int8")


def _serve_all(eng, prompts, max_new=8):
    sched = eng.scheduler
    for uid, p in prompts.items():
        assert sched.try_submit(
            uid, p, SamplingParams(temperature=0.0,
                                   max_new_tokens=max_new)).accepted
    sched.run(wait_for=list(prompts))
    out = {u: sched.pop_result(u) for u in prompts}
    audit = eng.close()
    assert audit["blocks_in_use"] == 0, audit
    return out


def _workload():
    rng = np.random.default_rng(3)
    long_prompt = rng.integers(1, 200, 40).tolist()  # over budget: chunked
    shared = [7, 3, 9, 1, 4, 6, 2, 8] * 2
    return {1: long_prompt,
            2: [7, 8, 9] * 5,                # repetitive: spec accepts
            3: shared + [11, 21],            # shared prefix: cache hit
            4: shared + [12, 22, 32]}


# slow: 42 s a case (tp 1, 2): two engines with prefix caching, chunked prefill, speculation and int8 weights
# compile every hot jit on a dp2 x seq2 x tp mesh; the mesh and the feature set are what is tested
@pytest.mark.slow
@pytest.mark.parametrize("tp", [1, 2])
def test_engine_token_identity_kernel_vs_dense(tiny_model, tp, monkeypatch):
    """The acceptance bar: the ctx kernel is greedy token-identical to the
    dense body through the FULL engine — prefix caching + chunked prefill +
    spec verify + int8 weights — on the dp=2 x seq=2 x tp mesh, with the
    kernel provably tracing on the fused engine and never on the pinned
    one."""
    from deepspeed_tpu.parallel.topology import initialize_mesh

    model, params = tiny_model
    calls = []
    real = ck.paged_attention_packed_ctx_kernel
    monkeypatch.setattr(ck, "paged_attention_packed_ctx_kernel",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))

    grid = initialize_mesh(devices=jax.devices()[:4 * tp],
                           batch=2, seq=2, model=tp)
    with dense_serving_context():
        dense_eng = InferenceEngineV2(params, model.cfg, grid=grid,
                                      serve_replicas=2, seq_shards=2,
                                      **ENGINE_KW)
    assert dense_eng.serving_ctx.fused is False
    want = _serve_all(dense_eng, _workload())
    assert not calls, "a fused=False context must never trace the kernel"

    grid = initialize_mesh(devices=jax.devices()[:4 * tp],
                           batch=2, seq=2, model=tp)
    eng = InferenceEngineV2(params, model.cfg, grid=grid,
                            serve_replicas=2, seq_shards=2, **ENGINE_KW)
    got = _serve_all(eng, _workload())
    assert calls, "auto engine never dispatched the ctx kernel"
    assert got == want


# ---------------------------------------------------------------------------
# compiled memory proof: temporaries no longer scale O(T * P * bs)
# ---------------------------------------------------------------------------
# slow: 27 s: four compiles, two of them of the dense gather at a 384-page table; the width is the assertion
@pytest.mark.slow
def test_memory_analysis_pack_temps_bounded():
    """The compiler's own accounting: widen the block table 12x (P=32 ->
    P=384, the dense gather's O(T * P * bs) axis) and the dense program's
    temporaries must grow several-fold while the kernel program's stay
    flat — its working set is one key tile of at most ``_KEY_TILE`` keys
    (32 pages of 16 here, so both tables are past it) and one row tile.
    Traced ctx_lens keep the dense clamp out of the comparison."""
    t, hq, hkv, hd, nb, bs, n = 64, 8, 2, 64, 64, 16, 4
    sds = jax.ShapeDtypeStruct
    args = lambda p: (
        sds((t, hq, hd), jnp.float32), sds((t, hkv, hd), jnp.float32),
        sds((t, hkv, hd), jnp.float32), sds((t,), jnp.int32),
        sds((nb, bs, hkv, hd), jnp.float32),
        sds((nb, bs, hkv, hd), jnp.float32),
        sds((n, p), jnp.int32), sds((n,), jnp.int32),
    )
    kfn = jax.jit(ck.paged_attention_packed_ctx_kernel)
    dfn = jax.jit(_paged_attention_packed_ctx_dense)
    mem = {}
    for name, fn in (("kernel", kfn), ("dense", dfn)):
        for p in (32, 384):
            m = fn.lower(*args(p)).compile().memory_analysis()
            if m is None:
                pytest.skip("backend exposes no memory_analysis")
            mem[name, p] = m.temp_size_in_bytes
    assert mem["dense", 384] > 3 * mem["dense", 32], mem
    assert mem["kernel", 384] < 2 * mem["kernel", 32] + (1 << 20), mem
    assert mem["kernel", 384] < mem["dense", 384] / 2, mem
