"""A model of two-norm blocks (``models/latent.py``: window and full GQA with no
output gate, a held share of softmax-routed experts, no shared expert) TRAINED
through ``deepspeed_tpu.initialize`` -> ``train_batch``: loss and every gradient
against the benchmark's plain reference (``benchmark/models/mellum.py``), the
held shares adding up to the uncut layer, the grouped matmul's gradients, the
balance term, the step's counts, ZeRO over the per-kind tuples of trees on a
mesh of four, and what still refuses to train.  CPU, toy widths, float32."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import deepspeed_tpu as ds  # noqa: E402
from benchmark import harness  # noqa: E402
from deepspeed_tpu.models import CausalLM, latent  # noqa: E402
from deepspeed_tpu.models.transformer import init_params  # noqa: E402
from deepspeed_tpu.moe import layer  # noqa: E402
from deepspeed_tpu.moe.layer import grouped_matmul, held_routing, moe_block_held  # noqa: E402
from deepspeed_tpu.ops.pallas.selected_attention import interpreted  # noqa: E402
from deepspeed_tpu.parallel.topology import initialize_mesh  # noqa: E402

ARCH = harness.module("models", "mellum")
M = harness.rehearsed(harness.load_json(
    harness.HERE / "configs" / "mellum2_l4_e16_train_1chip.json"), True)
SEQ, ROWS = 48, 8
TOL = 2e-5  # float32 on both sides, O(1) losses and gradients


def _cfg(m=M, **kw):
    kw = {"max_seq_len": SEQ, "remat": "selective", "loss_chunk_size": 16, "attn_impl": "auto", **kw}
    return ARCH.transformer_config(m, **kw)


def _ids(rows=ROWS, seed=5):
    return np.random.default_rng([2**31 + seed, 1]).integers(
        0, M["vocab_size"], (rows, SEQ + 1)).astype(np.int32)


def _engine(cfg, micro, mesh, optimizer=None):
    config = {
        "train_micro_batch_size_per_gpu": micro, "gradient_accumulation_steps": 1,
        "optimizer": optimizer or {"type": "adamw", "params": {"lr": 1e-3, "weight_decay": 0.1}},
        "zero_optimization": {"stage": 3, "param_persistence_threshold": 0},
        "bf16": {"enabled": False}, "steps_per_print": 10**9, "seed": 7}
    return ds.initialize(model=CausalLM(cfg), config=config, mesh=mesh)[0]


def _a_quarter_held(monkeypatch):
    """The rehearsal size with 4 of SIXTEEN experts held and a row tile of 8:
    1152 pairs a layer, 36 x the groups' padding, so ``moe_block_held`` builds
    its bounded layout (576 pairs a pass in 608 rows for the worst case's 1184)."""
    monkeypatch.setattr(layer, "held_row_tile", lambda t, spec: 8)
    return dict(M, deployment=dict(M["deployment"], num_experts_total=16))


def _leaves(tree):
    return {jax.tree_util.keystr(k): np.asarray(v)
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


@pytest.mark.parametrize("bounded", [False, True], ids=["as_rehearsed", "a_quarter_held_bounded"])
def test_train_batch_gives_the_references_loss_and_every_gradient(monkeypatch, bounded):
    """SGD at lr 1 without momentum: the step's update IS its gradient."""
    m = _a_quarter_held(monkeypatch) if bounded else M
    cfg, ids = _cfg(m), _ids()
    engine = _engine(cfg, ROWS // 8, initialize_mesh(data=8),
                     {"type": "sgd", "params": {"lr": 1.0}})
    before = jax.device_get(engine.state.params)
    loss = float(engine.train_batch({"input_ids": ids}))
    after = jax.device_get(engine.state.params)
    ref_loss, ref = jax.value_and_grad(lambda p: ARCH.loss_on(p, jnp.asarray(ids), m))(before)
    assert abs(loss - float(ref_loss)) <= TOL
    got, want = _leaves(jax.tree_util.tree_map(lambda a, b: a - b, before, after)), _leaves(ref)
    assert set(got) == set(want) and len(got) > 40
    for name, g in want.items():
        assert np.isfinite(got[name]).all()
        assert np.abs(got[name] - g).max() <= TOL * max(1.0, np.abs(g).max()), name
        # every tensor trains (an expert no token met keeps a zero gradient)
        assert np.abs(g).max() > 0 or "moe" in name, name


@pytest.mark.parametrize("remat", ["none", "full", "selective"])
def test_a_block_under_any_recomputation_gives_the_same_gradients(remat):
    ids = jnp.asarray(_ids(2))
    params = init_params(jax.random.PRNGKey(3), _cfg())
    grads = lambda r: jax.jit(jax.grad(lambda p: CausalLM(_cfg(remat=r)).loss_fn(p, {"input_ids": ids})))(params)
    for a, b in zip(jax.tree_util.tree_leaves(grads(remat)), jax.tree_util.tree_leaves(grads("none"))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6, rtol=1e-5)


def test_the_four_shares_outputs_and_input_gradients_add_up_to_the_uncut_layers():
    total, held, d = M["deployment"]["num_experts_total"], M["num_experts"], M["hidden_size"]
    whole = dict(M, num_experts=total)
    spec = _cfg(whole).latent
    lw = latent.init_params(jax.random.PRNGKey(11), _cfg(whole))["layers"]["moe"][0]
    x = jnp.asarray(np.random.default_rng(3).standard_normal((64, d)), jnp.float32)
    ct = jnp.asarray(np.random.default_rng(4).standard_normal((64, d)), jnp.float32)

    def share(i, x):
        part = {k: (v[i * held:(i + 1) * held] if k.startswith("w_") else v) for k, v in lw.items()}
        one = _cfg(dict(M, deployment=dict(M["deployment"], expert_offset=i * held))).latent
        return moe_block_held(part, x, one)[0]

    summed = lambda x: sum(share(i, x) for i in range(total // held))
    uncut = lambda x: ARCH.uncut_expert_layer(lw, x[None], whole)[0]
    (y, dx), (y_ref, dx_ref) = (
        jax.jit(lambda x, f=f: (f(x), jax.vjp(f, x)[1](ct)[0]))(x) for f in (summed, uncut))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-5)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref), atol=2e-5)
    # ... and the whole layer in one share is the uncut layer too
    np.testing.assert_allclose(np.asarray(moe_block_held(lw, x, spec)[0]), np.asarray(y_ref), atol=2e-5)


@pytest.mark.parametrize("sizes", [[5, 0, 11, 3], [0, 0, 19, 0], [0, 0, 0, 0], [7, 7, 7, 7]],
                         ids=["uneven", "one_group", "all_empty", "even"])
def test_grouped_matmuls_gradients_match_a_dense_einsum(sizes):
    """Rows sorted by group, some groups EMPTY, rows past the last group:
    values and both gradients of a dense one-hot einsum, finite everywhere, and
    a row of no group gets no gradient."""
    rng = np.random.default_rng(1)
    m, k, n, g = 32, 16, 24, len(sizes)
    xs = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((g, k, n)), jnp.float32)
    ct = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    group = np.repeat(np.arange(g + 1), sizes + [m - sum(sizes)])  # g: no group
    onehot = jnp.asarray(group[:, None] == np.arange(g)[None, :], jnp.float32)
    live = jnp.asarray(group < g)[:, None]
    dense = lambda xs, w: jnp.einsum("mg,mk,gkn->mn", onehot, xs, w, precision="highest")
    ours = lambda xs, w: jnp.where(live, grouped_matmul(xs, w, jnp.asarray(sizes, jnp.int32)), 0.0)
    for got, want in zip(jax.tree_util.tree_leaves((ours(xs, w), jax.vjp(ours, xs, w)[1](ct))),
                         jax.tree_util.tree_leaves((dense(xs, w), jax.vjp(dense, xs, w)[1](ct)))):
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4)
    d_xs = jax.vjp(ours, xs, w)[1](ct)[0]
    assert not np.asarray(d_xs)[group == g].any()


def test_the_balance_term_is_its_formula_and_rides_the_loss():
    import dataclasses

    cfg, ids = _cfg(), jnp.asarray(_ids(2))
    params = init_params(jax.random.PRNGKey(5), cfg)
    spec = cfg.latent
    _, _, aux = jax.jit(lambda p, t: latent.forward(p, t, cfg, return_hidden=True))(params, ids[:, :-1])
    # the reference's factors, layer by layer: f_e [L, E] (shares of the pairs), P_e [L, E]
    _, (share, mean, _) = jax.jit(lambda p, t: ARCH.hidden_states(p, t, M))(params, ids[:, :-1])
    np.testing.assert_allclose(np.asarray(share).sum(-1), 1.0, atol=1e-6)
    want = spec.n_routed * np.sum(np.asarray(share) * np.asarray(mean), -1)  # a term a layer
    assert float(aux) == pytest.approx(want.sum(), abs=1e-5)
    assert (want > 0.9).all() and (want < spec.n_routed).all()  # 1 when even, E when all on one
    loss = lambda c: float(jax.jit(lambda p: CausalLM(c).loss_fn(p, {"input_ids": ids}))(params))
    with_term = loss(cfg)
    bare = cfg.replace(latent=dataclasses.replace(spec, router_aux_loss_coef=0.0))
    without = loss(bare)
    assert with_term - without == pytest.approx(
        spec.router_aux_loss_coef * want.mean(), abs=1e-6)
    # a perfectly even router reads exactly 1 / E a score
    lw = {"router": jnp.zeros((cfg.hidden_size, spec.n_routed))}
    xs = jnp.ones((spec.n_routed * 4, cfg.hidden_size))
    assert np.allclose(np.asarray(held_routing(lw, xs, spec)[2]), 1.0 / spec.n_routed)


def test_the_block_hands_out_every_experts_score_and_the_loss_its_picks():
    """What the balance term is made of (``moe_block_held``'s third routing
    result: the softmax over ALL experts, a row a token) and what a reference
    is held to (``CausalLM.loss_and_picks``: a layer's picks each, the loss
    ``loss_fn``'s own)."""
    cfg, ids = _cfg(), jnp.asarray(_ids(2))
    spec = cfg.latent
    params = init_params(jax.random.PRNGKey(2), cfg)
    lw = params["layers"]["moe"][0]
    x = jnp.asarray(np.random.default_rng(0).standard_normal((40, cfg.hidden_size)), jnp.float32)
    _, (stats, idx, scores) = moe_block_held(lw, x, spec)
    np.testing.assert_allclose(np.asarray(scores), np.asarray(jax.nn.softmax(x @ lw["router"], -1)),
                               atol=1e-6)
    assert np.array_equal(np.sort(np.asarray(idx), -1),
                          np.sort(np.argsort(-np.asarray(scores), -1)[:, :spec.experts_per_tok], -1))
    assert int(stats[0]) == 40 * spec.experts_per_tok
    model = CausalLM(cfg)
    loss, picks = model.loss_and_picks(params, {"input_ids": ids})
    assert float(loss) == float(model.loss_fn(params, {"input_ids": ids}))
    assert len(picks) == len(spec.expert_layers)
    assert all(p.shape == (2 * SEQ, spec.experts_per_tok) for p in picks)


@pytest.mark.parametrize("sizes", [[1, 650, 0, 37, 128, 300], [0, 0, 5, 0, 0, 0], [256, 128, 384, 0, 128, 128]],
                         ids=["1_to_650_rows", "five_rows", "tile_aligned"])
@pytest.mark.parametrize("k,n", [(128, 256), (256, 128)])
def test_the_kernel_paths_backward_matches_a_dense_einsum(sizes, k, n):
    """The path the CHIP takes (megablox ``gmm`` and this module's VJP: ``gmm``
    transposed for the rows, ``tgmm`` for the weights), interpreted: groups of
    1 to 650 rows that share row tiles, empty groups, rows past the last group;
    values and both gradients of a dense one-hot einsum, a group at a time."""
    rng = np.random.default_rng(2)
    g, m = len(sizes), 1280
    xs = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((g, k, n)) / np.sqrt(k), jnp.float32)
    ct = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    group = np.repeat(np.arange(g + 1), sizes + [m - sum(sizes)])
    onehot = jnp.asarray(group[:, None] == np.arange(g)[None, :], jnp.float32)
    live = jnp.asarray(group < g)[:, None]
    dense = lambda xs, w: jnp.einsum("mg,mk,gkn->mn", onehot, xs, w, precision="highest")
    ours = lambda xs, w: jnp.where(live, grouped_matmul(xs, w, jnp.asarray(sizes, jnp.int32)), 0.0)
    with interpreted():
        from deepspeed_tpu.ops.pallas import record_dispatch

        with record_dispatch() as log:
            y, vjp = jax.vjp(ours, xs, w)
            d_xs, d_w = vjp(ct)
    assert [d["ran"] for d in log if d["kernel"] == "expert_gmm"] == [True]
    y_ref, vjp_ref = jax.vjp(dense, xs, w)
    d_xs_ref, d_w_ref = vjp_ref(ct)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(d_xs), np.asarray(d_xs_ref), atol=2e-4, rtol=1e-4)
    for e in range(g):  # an expert of one row is held as closely as one of 650
        np.testing.assert_allclose(np.asarray(d_w[e]), np.asarray(d_w_ref[e]), atol=5e-4, rtol=1e-4,
                                   err_msg=f"group {e} of {sizes[e]} rows")
    assert not np.asarray(d_xs)[group == g].any()


@pytest.mark.parametrize("tile", [16, 32, 64])
@pytest.mark.parametrize("k,n", [(2688, 1024), (2304, 896), (896, 2304)],
                         ids=["k2688_in_3_steps", "k2304_in_3_steps", "k896_in_1_step"])
def test_the_kernels_at_a_short_row_tile_and_an_irregular_k_tile_match_a_dense_einsum(tile, k, n):
    """megablox ``gmm``, its transposed form and ``tgmm`` INTERPRETED at the row
    tiles the served programs get and the k tiles of cells 6 and 10 (896 and 768,
    divisors of 21 x 128 and 18 x 128): empty groups, a group of 5 x its tile, groups
    that share a tile, rows past the last group and ``M`` no whole tile, against a
    dense one-hot einsum, a group at a time."""
    from deepspeed_tpu.ops.pallas import record_dispatch

    sizes = [0, 5 * tile, 3, 0, tile, 1]
    rng = np.random.default_rng(tile + k)
    g, m = len(sizes), sum(sizes) + tile + 5
    xs = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((g, k, n)) / np.sqrt(k), jnp.float32)
    ct = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    group = np.repeat(np.arange(g + 1), sizes + [m - sum(sizes)])
    onehot = jnp.asarray(group[:, None] == np.arange(g)[None, :], jnp.float32)
    live = jnp.asarray(group < g)[:, None]
    dense = lambda xs, w: jnp.einsum("mg,mk,gkn->mn", onehot, xs, w, precision="highest")
    ours = lambda xs, w: jnp.where(
        live, grouped_matmul(xs, w, jnp.asarray(sizes, jnp.int32), tile), 0.0)
    assert layer._gmm_tiling(tile, k, n)[1] in (768, 896)
    with interpreted(), record_dispatch() as log:
        y, vjp = jax.vjp(ours, xs, w)
        d_xs, d_w = vjp(ct)
    assert [d["ran"] for d in log if d["kernel"] == "expert_gmm"] == [True]
    y_ref, vjp_ref = jax.vjp(dense, xs, w)
    d_xs_ref, d_w_ref = vjp_ref(ct)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=5e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(d_xs), np.asarray(d_xs_ref), atol=5e-4, rtol=1e-4)
    for e in range(g):
        np.testing.assert_allclose(np.asarray(d_w[e]), np.asarray(d_w_ref[e]), atol=1e-3, rtol=1e-4,
                                   err_msg=f"group {e} of {sizes[e]} rows")
    assert not np.asarray(d_xs)[group == g].any()


def test_the_gradient_at_cell_tens_tile_and_k_tiles_is_the_worst_case_layouts(monkeypatch):
    """The tiling cell 10 gets (a 128-row tile for a group that expects thousands of
    rows, ``tk`` 768 of d 2304 and 896 of f 896), on the path the CHIP takes,
    interpreted, through the bounded layout (1024 tokens x 2 picks over 4 experts, 1
    held: 1024 pairs a pass in 1152 rows): the output, dx, the router's and the held
    expert's three weight gradients are those of the function that lays out the
    worst case's 2176 rows."""
    from deepspeed_tpu.ops.pallas import record_dispatch

    t, d, f = 1024, 2304, 896
    spec = dataclasses.replace(_cfg().latent, n_routed=4, n_held=1, held_offset=0,
                               experts_per_tok=2, moe_width=f)
    assert layer.held_row_tile(t, spec) == 128 and layer.held_rows_bound(t, spec) == 1024
    assert [layer._gmm_tiling(128, *kn)[1:] for kn in ((d, f), (f, d))] == [(768, 896), (896, 1152)]
    rng = np.random.default_rng(8)
    n = lambda *s: jnp.asarray(rng.standard_normal(s) / np.sqrt(s[-2]), jnp.float32)
    lw = {"router": n(d, 4), "w_gate": n(1, d, f), "w_up": n(1, d, f), "w_down": n(1, f, d)}
    x, ct = n(t, d) * np.sqrt(t), n(t, d)

    def run():
        with interpreted(), record_dispatch() as log:
            y, pull = jax.vjp(lambda lw, x: moe_block_held(lw, x, spec)[0], lw, x)
            return y, pull(ct), {e["shape"][0] for e in log if e["kernel"] == "expert_gmm" and e["ran"]}

    y, grads, rows = run()
    assert rows == {1024 + 128}
    monkeypatch.setattr(layer, "held_rows_bound", lambda t, spec, tile=None: None)
    y0, grads0, rows0 = run()
    assert rows0 == {2048 + 128}
    for (path, got), want in zip(jax.tree_util.tree_leaves_with_path((y, grads)),
                                 jax.tree_util.tree_leaves((y0, grads0))):
        assert np.abs(np.asarray(want)).max() > 0
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4,
                                   err_msg=jax.tree_util.keystr(path))


def test_fsdp_4_on_a_cpu_mesh_equals_one_device():
    cfg, ids = _cfg(), _ids(4)
    losses = {}
    for name, mesh, micro in (("one", initialize_mesh(data=1, devices=jax.devices()[:1]), 4),
                              ("fsdp4", initialize_mesh(fsdp=4, devices=jax.devices()[:4]), 1)):
        engine = _engine(cfg, micro, mesh)
        losses[name] = [float(engine.train_batch({"input_ids": ids})) for _ in range(3)]
        if name == "fsdp4":  # the plan shards the per-kind tuples' trees, experts among them
            specs = {jax.tree_util.keystr(k): v.sharding.spec for k, v in
                     jax.tree_util.tree_leaves_with_path(engine.state.params)}
            assert any("fsdp" in str(s) for k, s in specs.items() if "moe" in k and "w_up" in k)
            assert any("fsdp" in str(s) for k, s in specs.items() if "wattn" in k and "wq" in k)
    assert losses["one"][0] > losses["one"][2]  # it trains
    np.testing.assert_allclose(losses["fsdp4"], losses["one"], atol=2e-5)


@pytest.mark.parametrize("bounded", [False, True], ids=["as_rehearsed", "a_quarter_held_bounded"])
def test_the_steps_counts_are_booked_and_the_span_carries_tokens_and_layers(monkeypatch, bounded):
    m = _a_quarter_held(monkeypatch) if bounded else M
    cfg, ids = _cfg(m), _ids()
    engine = _engine(cfg, ROWS // 8, initialize_mesh(data=8))
    steps = 3
    for _ in range(steps):
        engine.train_batch({"input_ids": ids})
    engine.get_last_loss()
    read = lambda k: engine.telemetry.registry.counter(k).value
    k, layers, tokens = M["num_experts_per_tok"], M["num_hidden_layers"], ROWS * SEQ
    assert read("expert_pairs_routed") == steps * tokens * k * layers
    assert 0 < read("expert_pairs_held") < read("expert_pairs_routed")
    assert read("expert_rows_min") * M["num_experts"] <= read("expert_pairs_held") \
        <= read("expert_rows_max") * M["num_experts"]
    # the rows the grouped matmul was handed, and the layers that took ONE bounded pass
    if bounded:
        assert read("expert_pairs_held") <= steps * layers * 576
        assert read("expert_layers_bounded") == steps * layers
        assert read("expert_rows_laid_out") == steps * layers * (576 + 4 * 8)
    else:  # half the experts held, 144 rows a group in a 128-row tile: no bound is built
        assert read("expert_layers_bounded") == 0
        assert layer.held_row_tile(tokens, cfg.latent) == 128
        assert read("expert_rows_laid_out") == steps * layers * (tokens * k + 4 * 128)
    w = M["sliding_window"]
    assert read("causal_keys") == steps * ROWS * layers * SEQ * (SEQ + 1) // 2
    assert read("window_keys_attended") == steps * ROWS * (
        3 * latent.allowed_pairs(SEQ, w) + latent.allowed_pairs(SEQ))
    assert latent.allowed_pairs(SEQ, w) == sum(min(i + 1, w) for i in range(SEQ))
    assert engine._step_facts == {"tokens": tokens, "layers_with_experts": layers}
    assert engine._last_metrics.counts.keys() >= {"expert_pairs_routed", "causal_keys"}


def _other(model_type):
    """A configuration of another architecture at its rehearsal size."""
    arch = harness.module("models", model_type)
    name = next(c["file"] for c in harness.manifest()["configs"]
                if harness.load_json(ROOT / c["file"])["model_type"] == model_type)
    m = harness.rehearsed(harness.load_json(ROOT / name), True)
    return arch.transformer_config(m, max_seq_len=32), m


@pytest.mark.parametrize("model_type,mechanism", [
    ("dots3_note", "SELECTS|latent-attention"), ("deepseek_v2", "latent-attention bodies"),
    ("nemotron_h", "state-space scan"), ("qwen3_next", "delta rule")])
def test_a_kind_with_no_backward_refuses_by_its_mechanism(model_type, mechanism):
    cfg, m = _other(model_type)
    params = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, m["vocab_size"], (1, 33)), jnp.int32)
    lm = CausalLM(cfg)
    # the forward is there (tests/benchmark holds it to its reference); tracing the backward refuses
    assert jax.eval_shape(lambda p: lm.loss_fn(p, {"input_ids": ids}), params).shape == ()
    with pytest.raises(NotImplementedError, match=mechanism):
        jax.eval_shape(jax.grad(lambda p: lm.loss_fn(p, {"input_ids": ids})), params)


def test_what_the_layers_of_several_kinds_still_refuse_and_what_they_answer():
    cfg = _cfg()
    with pytest.raises(NotImplementedError, match="model=2"):
        _engine(cfg, 1, initialize_mesh(model=2, devices=jax.devices()[:2]))
    with pytest.raises(NotImplementedError, match="stack_apply"):
        CausalLM(cfg, stack_apply=lambda *a: a[1]).loss_fn(
            init_params(jax.random.PRNGKey(0), cfg), {"input_ids": jnp.asarray(_ids(1))})
    assert CausalLM(cfg).tp_rules == []
    # flops_per_token answers from what is HELD here: the reference's count, to the FLOP
    assert CausalLM(cfg).flops_per_token(SEQ) == pytest.approx(ARCH.train_flops_per_token(M, SEQ))
    published = harness.load_json(harness.HERE / "configs" / "mellum2_l4_e16_train_1chip.json")
    big = ARCH.transformer_config(harness.rehearsed(published, False), max_seq_len=8192)
    assert CausalLM(big).flops_per_token(8192) == pytest.approx(
        ARCH.train_flops_per_token(published, 8192))
    with pytest.raises(NotImplementedError, match="flops_per_token"):
        CausalLM(_other("qwen3_next")[0]).flops_per_token(32)


def test_a_block_without_a_gate_or_a_shared_expert_has_neither_in_its_tree():
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    for kind in ("wattn", "gattn"):
        assert set(params["layers"][kind][0]) == {"wq", "wk", "wv", "wo", "q_norm", "k_norm"}
    assert set(params["layers"]["moe"][0]) == {"router", "w_gate", "w_up", "w_down"}
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params)) == cfg.param_count


# -- the bounded layout of the held experts (PR 50) ----------------------------
def test_the_kernel_path_runs_the_bounded_layout_and_its_gradients_are_the_worst_cases(monkeypatch):
    """The path the CHIP takes, interpreted, over ``held_rows_bound``'s threshold
    (2048 tokens x 2 picks, 2 of 16 experts held: 1280 rows a pass for the worst
    case's 4352): megablox ``gmm`` / ``tgmm`` are handed the bounded rows, whose
    padding rows come back NaN from an interpreted kernel, and the output, dx
    and each held expert's three weight gradients are those of the function that
    lays out the worst case."""
    from deepspeed_tpu.ops.pallas import record_dispatch

    t, d, f, total, held, k = 2048, 128, 128, 16, 2, 2
    spec = dataclasses.replace(_cfg().latent, n_routed=total, n_held=held, held_offset=0,
                               experts_per_tok=k, moe_width=f)
    rng = np.random.default_rng(6)
    n = lambda *s: jnp.asarray(rng.standard_normal(s) / np.sqrt(s[-2]), jnp.float32)
    lw = {"router": n(d, total), "w_gate": n(held, d, f), "w_up": n(held, d, f),
          "w_down": n(held, f, d)}
    x, ct = n(t, d) * np.sqrt(t), n(t, d)

    def run():
        with interpreted(), record_dispatch() as log:
            y, pull = jax.vjp(lambda lw, x: moe_block_held(lw, x, spec)[0], lw, x)
            return y, pull(ct), sorted({e["shape"][0] for e in log if e["kernel"] == "expert_gmm"
                                        and e["ran"]})

    y, (d_lw, d_x), rows = run()
    assert rows == [1024 + held * 128]
    monkeypatch.setattr(layer, "held_rows_bound", lambda t, spec, tile=None: None)
    y0, (d_lw0, d_x0), rows0 = run()
    assert rows0 == [t * k + held * 128]
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0), atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(d_x), np.asarray(d_x0), atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(d_lw["router"]), np.asarray(d_lw0["router"]),
                               atol=5e-5, rtol=1e-4)
    for name in ("w_gate", "w_up", "w_down"):
        for e in range(held):  # an expert at a time
            got, want = np.asarray(d_lw[name])[e], np.asarray(d_lw0[name])[e]
            assert np.abs(want).max() > 0
            np.testing.assert_allclose(got, want, atol=5e-5, rtol=1e-4, err_msg=f"{name}[{e}]")


def _cfg_of(config: str):
    """A benchmark configuration at its published widths."""
    m = harness.load_json(harness.HERE / "configs" / f"{config}.json")
    return harness.module("models", m["model_type"]).transformer_config(m)


def _held_layer_at_real_size(config: str, t: int):
    """(the jaxpr of ``moe_block_held`` at ``t`` tokens of a benchmark
    configuration's published widths, traced and not run; the rows each
    ``grouped_matmul`` call was handed; the spec)."""
    from deepspeed_tpu.ops.pallas import record_dispatch

    cfg = _cfg_of(config)

    def held(tree):  # the first expert layer's weights, wherever the model keeps them
        if isinstance(tree, dict):
            if "router" in tree:
                return tree
            tree = tuple(tree.values())
        for v in tree if isinstance(tree, (list, tuple)) else ():
            found = held(v)
            if found is not None:
                return found

    lw = held(jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0)))
    lead = lw["router"].ndim - 2  # a stack of layers keeps a leading axis
    lw = {k: jax.ShapeDtypeStruct(v.shape[lead:], jnp.bfloat16) for k, v in lw.items()}
    x = jax.ShapeDtypeStruct((t, cfg.hidden_size), jnp.bfloat16)
    with record_dispatch() as log:
        jaxpr = jax.make_jaxpr(lambda lw, x: moe_block_held(lw, x, cfg.latent)[0])(lw, x)
    return jaxpr, [e["shape"][0] for e in log if e["kernel"] == "expert_gmm"], cfg.latent


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


# a pack's or a tick's tokens, the rows a held group expects there, the row tile the
# rule gives it and the rows the layer lays out (ISSUE 51's table; the parent's rows,
# ``t k + g x 128``, beside them)
PROGRAMS = [
    ("dots3_note_l5_e32_serve_1chip", 2048, 64, 128, 20480, 20480),
    ("dots3_note_l5_e32_serve_1chip", 16, 0.5, 16, 640, 4224),
    ("nemotron3_super_l11_e128_serve_1chip", 512, 22, 64, 19456, 27648),
    ("nemotron3_super_l11_e128_serve_1chip", 128, 5.5, 16, 4864, 19200),
    ("qwen3_next_l8_e128_serve_1chip", 512, 10, 32, 9216, 21504),
    ("qwen3_next_l8_e128_serve_1chip", 16, 0.3125, 16, 2208, 16544),
    ("laguna_xs2_l5_serve_1chip", 512, 16, 32, 12288, 36864),
    ("laguna_xs2_l5_serve_1chip", 32, 1, 16, 4352, 33024),
    ("deepseek_v2_l5_e40_serve_1chip", 2048, 76.8, 128, 17408, 17408),
    ("deepseek_v2_l5_e40_serve_1chip", 24, 0.9, 16, 784, 5264)]
_program_ids = lambda v: v.split("_")[0] if isinstance(v, str) else None


@pytest.mark.parametrize("config,t,expected,tile,rows,before", PROGRAMS + [
    ("mellum2_l4_e16_train_1chip", 16384, 2048, 128, 67584, 67584)], ids=_program_ids)
def test_the_row_tile_is_the_smallest_that_holds_twice_a_groups_expected_rows(
        config, t, expected, tile, rows, before):
    """The rule's table at the eleven programs, from the benchmark's configuration
    files alone (nothing traced): the rows a group expects under uniform routing,
    the tile (16 for a tick, 32-64 for a 512-token pack, the 128 it had for a
    2048-token pack and for a training step's 2048 rows a group) and the rows
    laid out, ``t k + g x tile`` or the bounded pass's ``C + g x tile``."""
    spec = _cfg_of(config).latent
    assert t * spec.experts_per_tok / spec.n_routed == pytest.approx(expected)
    assert layer.held_row_tile(t, spec) == tile
    bound = layer.held_rows_bound(t, spec)
    assert (bound is None) == (config != "mellum2_l4_e16_train_1chip")
    pairs = t * spec.experts_per_tok if bound is None else bound
    assert pairs + spec.n_held * tile == rows == layer.held_rows_a_pass(t, spec) <= before
    assert int(layer.held_rows_laid_out(t, spec, jnp.int32(pairs // 2))[0]) == rows
    if bound is None:
        assert before == t * spec.experts_per_tok + spec.n_held * 128
    # short of 2 x the expectation only where the ladder ends (cells 5, 9 and 10 stay as they were)
    assert tile >= 2 * expected or tile == 128


@pytest.mark.parametrize("k,n,tk,tn", [
    (5120, 1536, 1024, 1536), (1536, 5120, 512, 2560),  # cells 5 and 9: as PR 29 measured them
    (2048, 512, 1024, 512), (512, 2048, 512, 2048),     # cells 7 and 8: as they were
    (1024, 2688, 512, 2688), (2688, 1024, 896, 1024),   # cell 6: k 2688 = 21 x 128 had tk 128
    (2304, 896, 768, 896), (896, 2304, 896, 1152)])     # cell 10: had tk 256 and 128
def test_the_weight_tile_comes_from_ks_and_ns_own_divisors(k, n, tk, tn):
    for tm in (16, 128, 512):
        assert layer._gmm_tiling(tm, k, n) == (tm, tk, tn)
    assert k % tk == 0 and n % tn == 0 and tk <= 1024 and tk * tn <= 1600 * 1024


@pytest.mark.parametrize("config,t,expected,tile,rows,before", PROGRAMS, ids=_program_ids)
def test_a_served_pack_or_tick_lays_out_what_it_did_and_holds_no_cond(config, t, expected, tile,
                                                                     rows, before):
    """Cells 5-9 at their pack's and their tick's tokens, published widths,
    traced only: ``t k + g x tile`` rows to each of the layer's grouped matmuls
    (the ``gmm`` shapes on the ledger's lines) and no ``cond`` anywhere: under
    ``held_rows_bound``'s threshold the function lays out the worst case in one
    body, as it always did."""
    jaxpr, handed, spec = _held_layer_at_real_size(config, t)
    assert rows == t * spec.experts_per_tok + spec.n_held * tile
    assert handed == [rows] * (3 if spec.expert_form == "swiglu" else 2)
    assert not [e for e in _equations(jaxpr.jaxpr) if e.primitive.name == "cond"]
    assert t * spec.experts_per_tok / (spec.n_held * tile) <= 8


# ``moe_block_held``'s jaxpr on the KERNEL path (addresses blanked) at cells 5 and 9's
# packs, as the parent of PR 51 (d57c2c8) traced it: their row tile, their rows and
# their weight tiles are what they were, character for character
PARENTS_PACKS = {
    "dots3_note_l5_e32_serve_1chip": "95d162ff7c6f792a2c51156da93098618b72560a8cdd66bc80a8c7ae61fadd8c",
    "deepseek_v2_l5_e40_serve_1chip": "2de4aec240bc4c70e8e86b71a96c68703243be1a65241fb7bd5e6aeb9c50dbdf"}


@pytest.mark.parametrize("config", sorted(PARENTS_PACKS), ids=_program_ids)
def test_cells_5_and_9s_packs_trace_to_the_parents_jaxpr(monkeypatch, config):
    import hashlib
    import re

    import deepspeed_tpu.ops.pallas as pallas_ops

    monkeypatch.setattr(pallas_ops, "on_tpu", lambda: True)
    jaxpr, handed, _ = _held_layer_at_real_size(config, 2048)
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    assert "pallas_call" in text and len(handed) == 3
    assert hashlib.sha256(text.encode()).hexdigest() == PARENTS_PACKS[config]


def test_cell_tens_step_lays_out_67584_rows_a_pass_and_holds_no_worst_case_array():
    """Cell 10's layer (16 384 tokens x 2304, top 8, 16 of 64 held), traced
    only: ONE loop of two passes (inside the function whose gradient the layer
    writes out), each a ``cond`` that skips a pass of no pair, whose body hands
    the grouped matmuls 67 584 rows; inside the loop nothing
    has 133 120 or 131 072 ROWS (an array of two or more dimensions counted by
    all but its last: the pairs' gather ``[T, k, d]`` and the padded rows ``[R,
    d]`` are what the bound is there to shrink; the sort's order and the routing
    weights, one NUMBER a pair, come in as they are), and no ``[R, d]`` of the
    worst case stands outside it either."""
    jaxpr, handed, spec = _held_layer_at_real_size("mellum2_l4_e16_train_1chip", 16384)
    assert layer.held_rows_bound(16384, spec) == 65536
    assert handed == [67584] * 3
    (loop,) = [e for e in _equations(jaxpr.jaxpr) if e.primitive.name == "scan"]
    assert loop.params["length"] == 2
    assert [e.primitive.name for e in _equations(loop.params["jaxpr"].jaxpr)].count("cond") == 1
    rows_of = lambda eqns: {int(np.prod(v.aval.shape[:-1])) for e in eqns
                            for v in e.outvars if len(v.aval.shape) >= 2}
    inside = rows_of(_equations(loop.params["jaxpr"].jaxpr))
    assert 67584 in inside and not inside & {133120, 131072}
    assert 133120 not in rows_of(_equations(jaxpr.jaxpr))
