"""The expert layer of one MEMBER of an expert-parallel deployment
(``moe/layer.py:moe_block_held``): routing over all experts (sigmoid + bias, a
softmax, or a softmax limited to a few groups of experts), a grouped matmul
over the pairs that fall on the experts held here."""
import contextlib
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.latent import LatentAttn, LatentSpec
from deepspeed_tpu.moe import layer
from deepspeed_tpu.moe.layer import grouped_matmul, held_routing, moe_block_held

D, F, E, K = 32, 16, 16, 4
_A = LatentAttn(2, 8, 8, 8, 4, 8, 1e4)
SPEC = LatentSpec(layer_kinds=(), full=_A, sliding=_A, index_heads=1, index_dim=8,
                  index_topk=4, first_dense=0, n_routed=E, n_held=E, held_offset=0,
                  experts_per_tok=K, moe_width=F, n_shared=1)


def _weights(key, bias_scale=0.02):
    ks = jax.random.split(key, 8)
    n = lambda k, *s: jax.random.normal(k, s, jnp.float32) / np.sqrt(s[-2])
    return {"router": n(ks[0], D, E), "bias": bias_scale * jax.random.normal(ks[1], (E,)),
            "w_gate": n(ks[2], E, D, F), "w_up": n(ks[3], E, D, F), "w_down": n(ks[4], E, F, D),
            "s_gate": n(ks[5], D, F), "s_up": n(ks[6], D, F), "s_down": n(ks[7], F, D)}


# the other expert form: two matrices with relu(.)^2 between, the routed experts
# in a latent LAT wide behind a projection pair, the shared expert FS wide at
# full width, top-K of E with the weights scaled by 5
LAT, FS = 16, 24
SPEC_RELU2 = replace(SPEC, expert_form="relu2", moe_latent=LAT, shared_width=FS,
                     routed_scale=5.0)


def _weights_relu2(key):
    ks = jax.random.split(key, 8)
    n = lambda k, *s: jax.random.normal(k, s, jnp.float32) / np.sqrt(s[-2])
    return {"router": n(ks[0], D, E), "bias": 0.02 * jax.random.normal(ks[1], (E,)),
            "w_up": n(ks[2], E, LAT, F), "w_down": n(ks[3], E, F, LAT),
            "w_lat_down": n(ks[4], D, LAT), "w_lat_up": n(ks[5], LAT, D),
            "s_up": n(ks[6], D, FS), "s_down": n(ks[7], FS, D)}


# the other routing: a softmax over all E, the K largest renormalised, no bias,
# and the shared expert behind a sigmoid gate of its own
SPEC_SOFTMAX = replace(SPEC, routing="softmax", shared_gate=True)


def _weights_softmax(key):
    lw = _weights(key)
    del lw["bias"]
    return dict(lw, w_sg=jax.random.normal(jax.random.fold_in(key, 9), (D, 1)) / np.sqrt(D))


# the third routing: a softmax over all E in 8 groups of 2, the 3 groups of
# largest maximum kept, the K largest inside them, NOT renormalised, x 16
SPEC_GROUPS = replace(SPEC, routing="group_limited", n_group=8, topk_group=3, routed_scale=16.0)


def _weights_groups(key):
    lw = _weights(key)
    del lw["bias"]
    return lw


def _swiglu(x, g, u, d):
    return (jax.nn.silu(x @ g) * (x @ u)) @ d


def _relu2(x, u, d):
    return jnp.square(jax.nn.relu(x @ u)) @ d


def _uncut(lw, x, spec):
    """(the uncut layer's output, its shared expert's), either form, every
    expert on every token masked by the routing; the latent form applies
    ``w_lat_up`` ONCE, to the whole weighted sum."""
    if spec.expert_form == "swiglu":
        shared = _swiglu(x, lw["s_gate"], lw["s_up"], lw["s_down"])
        if spec.shared_gate:
            shared = jax.nn.sigmoid(x @ lw["w_sg"]) * shared
        return _dense_over_experts(lw, x, spec, shared), shared
    idx, wts = held_routing(lw, x, spec)[:2]
    lat = x @ lw["w_lat_down"]
    y = jnp.zeros_like(lat)
    for e in range(spec.n_routed):
        w_e = jnp.sum(jnp.where(idx == e, wts, 0.0), -1, keepdims=True)
        y += w_e * _relu2(lat, lw["w_up"][e], lw["w_down"][e])
    shared = _relu2(x, lw["s_up"], lw["s_down"])
    return y @ lw["w_lat_up"] + shared, shared


def _dense_over_experts(lw, x, spec, shared):
    """Every expert on every token, masked by the routing: the plain form."""
    idx, wts = held_routing(lw, x, spec)[:2]
    y = jnp.zeros_like(x)
    for e in range(spec.n_routed):
        w_e = jnp.sum(jnp.where(idx == e, wts, 0.0), -1, keepdims=True)
        y += w_e * _swiglu(x, lw["w_gate"][e], lw["w_up"][e], lw["w_down"][e])
    return y + shared


@pytest.mark.parametrize("spec,make,members", [
    (SPEC, _weights, 8), (SPEC_RELU2, _weights_relu2, 4), (SPEC_SOFTMAX, _weights_softmax, 4),
    (SPEC_GROUPS, _weights_groups, 4)],
    ids=["swiglu-8", "relu2_latent-4", "softmax_gated_shared-4", "group_limited-4x2_groups"])
def test_the_shares_add_up_to_the_uncut_layer(spec, make, members):
    """The guide's share test: each of the members holds its share of the
    experts, routes over all E and computes its own; their partial sums, the
    shared expert counted ONCE (and the latent form's ``w_lat_up`` applied to
    EACH share), are the uncut layer's output."""
    lw = make(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (24, D))
    whole, shared = _uncut(lw, x, spec)
    held_pairs = 0
    total = jnp.zeros_like(x)
    for r in range(members):
        g = E // members
        mine = dict(lw, **{k: lw[k][r * g:(r + 1) * g] for k in ("w_gate", "w_up", "w_down")
                           if k in lw})
        y, (stats, _, _) = moe_block_held(mine, x, replace(spec, n_held=g, held_offset=r * g))
        total += y - shared  # every member adds the shared expert: count it once
        held_pairs += int(stats[1])
        assert int(stats[0]) == 24 * K
    assert held_pairs == 24 * K  # every pick fell on exactly one member
    assert float(jnp.abs(total + shared - whole).max()) <= 1e-5


@pytest.mark.parametrize("n_experts,k,scale", [(E, K, 1.0), (64, 22, 5.0)],
                         ids=["top4_of_16", "top22_of_64_scale5"])
def test_the_bias_selects_and_does_not_weigh(n_experts, k, scale):
    spec = replace(SPEC, n_routed=n_experts, experts_per_tok=k, routed_scale=scale)
    lw = {"router": jax.random.normal(jax.random.PRNGKey(2), (D, n_experts)) / np.sqrt(D),
          "bias": jnp.zeros(n_experts)}
    x = jax.random.normal(jax.random.PRNGKey(3), (64, D))
    idx0, w0 = held_routing(lw, x, spec)[:2]
    assert idx0.shape == (64, k) and all(len(set(r)) == k for r in np.asarray(idx0).tolist())
    np.testing.assert_allclose(np.asarray(w0.sum(-1)), scale, rtol=1e-6)
    pushed = dict(lw, bias=jnp.zeros(n_experts).at[5].set(10.0))
    idx1, w1 = held_routing(pushed, x, spec)[:2]
    assert bool(jnp.all(jnp.any(idx1 == 5, -1)))  # the bias decides the selection
    s = jax.nn.sigmoid(x @ lw["router"])
    picked = jnp.take_along_axis(s, idx1, -1)
    np.testing.assert_allclose(
        np.asarray(w1), np.asarray(scale * picked / picked.sum(-1, keepdims=True)),
        rtol=1e-6)  # ... and is no part of the weights


@pytest.mark.parametrize("n_experts,k", [(E, K), (64, 10)], ids=["top4_of_16", "top10_of_64"])
def test_softmax_routing_is_over_all_experts_and_renormalised(n_experts, k):
    """The softmax form against plain numpy: probabilities over ALL experts,
    the k largest picked, their weights the picked probabilities over their
    sum; a bias, were one there, is not read."""
    spec = replace(SPEC_SOFTMAX, n_routed=n_experts, experts_per_tok=k, routed_scale=7.0)
    lw = {"router": jax.random.normal(jax.random.PRNGKey(8), (D, n_experts)) / np.sqrt(D) * 3}
    x = jax.random.normal(jax.random.PRNGKey(9), (64, D))
    idx, w = held_routing(lw, x, spec)[:2]
    logits = np.asarray(x, np.float64) @ np.asarray(lw["router"], np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.argsort(-p, axis=-1)[:, :k]
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.sort(want, -1))
    picked = np.take_along_axis(p, np.asarray(idx), -1)
    np.testing.assert_allclose(np.asarray(w), picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-6)  # no routed_scale


def _group_limited_by_hand(logits, n_group, topk_group, k):
    """DeepSeek-V2's ``group_limited_greedy`` in numpy float64, a token at a
    time: (experts picked, their softmax scores, the kept groups)."""
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    per = p.shape[-1] // n_group
    out = []
    for row in p:
        best = row.reshape(n_group, per).max(-1)
        kept = sorted(sorted(range(n_group), key=lambda g: (-best[g], g))[:topk_group])
        inside = [e for g in kept for e in range(g * per, (g + 1) * per)]
        picks = sorted(inside, key=lambda e: (-row[e], e))[:k]
        out.append((picks, [row[e] for e in picks], kept))
    return out


@pytest.mark.parametrize("n_experts,n_group,topk_group,k", [(16, 8, 3, 4), (160, 8, 3, 6)],
                         ids=["top4_of_16_in_3_of_8", "top6_of_160_in_3_of_8"])
def test_group_limited_routing_against_the_formula_written_out(n_experts, n_group, topk_group, k):
    """Softmax over ALL experts, the groups of largest maximum kept, the picks
    the largest inside them and never outside, weights the picked scores as they
    are (not renormalised) times ``routed_scale``."""
    spec = replace(SPEC_GROUPS, n_routed=n_experts, n_group=n_group, topk_group=topk_group,
                   experts_per_tok=k)
    lw = {"router": jax.random.normal(jax.random.PRNGKey(4), (D, n_experts)) / np.sqrt(D) * 3}
    x = jax.random.normal(jax.random.PRNGKey(5), (96, D))
    idx, w = held_routing(lw, x, spec)[:2]
    logits = np.asarray(x, np.float64) @ np.asarray(lw["router"], np.float64)
    per = n_experts // n_group
    for t, (picks, scores, kept) in enumerate(_group_limited_by_hand(logits, n_group, topk_group, k)):
        assert sorted(np.asarray(idx[t]).tolist()) == sorted(picks)
        assert {e // per for e in np.asarray(idx[t]).tolist()} <= set(kept)
        order = np.argsort(np.asarray(idx[t]))
        np.testing.assert_allclose(np.asarray(w[t])[order],
                                   16.0 * np.asarray(scores)[np.argsort(picks)], rtol=1e-5)
    assert float(jnp.max(jnp.sum(w, -1))) < 16.0  # not renormalised: a sum of 6 of 160 scores x 16


def test_group_limited_routing_under_ties_and_few_groups():
    """Equal group maxima go to the LOWER group and equal scores to the lower
    expert; a token whose best experts all lie in one or two groups still picks
    ``k`` experts, the rest from the other kept groups, and none outside them;
    a score that underflows to 0 is still above a masked one."""
    spec = replace(SPEC_GROUPS, experts_per_tok=4)  # 16 experts: 8 groups of 2, 3 kept
    eye = jnp.eye(D)[:, :E]                         # router: logit e = x[e]
    rows = np.zeros((4, D), np.float32)
    # token 0: all logits equal -> groups 0, 1, 2 kept, experts 0..3 picked
    # token 1: groups 5 and 7 hold the two largest, every other group ties: 0 is the third
    rows[1, [10, 11, 14]] = [9.0, 8.0, 7.0]
    # token 2: one group towers (both its experts), the rest tie: groups 3, 0, 1; picks 6, 7, 0, 1
    rows[2, [6, 7]] = [50.0, 49.0]
    # token 3: the softmax underflows to exactly 0 outside group 4: still groups 4, 0, 1
    rows[3, [8, 9]] = [200.0, 199.0]
    idx, w = held_routing({"router": eye}, jnp.asarray(rows), spec)[:2]
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 1, 2, 3]
    assert sorted(np.asarray(idx[1]).tolist()) == [0, 10, 11, 14]
    assert sorted(np.asarray(idx[2]).tolist()) == [0, 1, 6, 7]
    assert sorted(np.asarray(idx[3]).tolist()) == [0, 1, 8, 9]
    assert float(w[3].min()) == 0.0 and np.all(np.asarray(w) >= 0)  # weights are scores, never the mask's -1
    for t in range(4):
        assert len({e // 2 for e in np.asarray(idx[t]).tolist()}) <= 3


@pytest.mark.parametrize("sizes", [[0, 50, 3, 0, 1, 10], [64, 0, 0, 0, 0, 0], [0, 0, 0, 0, 0, 0],
                                   [11, 11, 11, 11, 10, 10]])
def test_grouped_matmul_is_the_per_group_matmul(sizes):
    """Skewed groups: experts with no rows, one with most; rows past the
    groups' sum belong to nobody."""
    g, m, k, n = len(sizes), 64, 8, 12
    xs = jax.random.normal(jax.random.PRNGKey(4), (m, k))
    w = jax.random.normal(jax.random.PRNGKey(5), (g, k, n))
    got = np.asarray(grouped_matmul(xs, w, jnp.asarray(sizes, jnp.int32)))
    row = 0
    for e, size in enumerate(sizes):
        np.testing.assert_allclose(got[row:row + size], np.asarray(xs[row:row + size] @ w[e]),
                                   rtol=1e-5, atol=1e-5)
        row += size


def test_held_layer_under_skewed_routing_and_padding():
    """A router pushed so that one held expert takes nearly every token and
    one takes none, with padding rows masked out: the grouped form equals the
    dense-over-experts form on the valid rows."""
    lw = _weights(jax.random.PRNGKey(6))
    lw["bias"] = lw["bias"].at[2].set(10.0).at[3].set(-10.0)
    x = jax.random.normal(jax.random.PRNGKey(7), (40, D))
    valid = jnp.arange(40) < 33
    spec = replace(SPEC, n_held=8, held_offset=0)
    mine = dict(lw, **{k: lw[k][:8] for k in ("w_gate", "w_up", "w_down")})
    y, (stats, picked, _) = moe_block_held(mine, x, spec, valid)
    idx, wts = held_routing(lw, x, SPEC)[:2]
    want = _swiglu(x, lw["s_gate"], lw["s_up"], lw["s_down"])
    for e in range(8):
        w_e = jnp.sum(jnp.where(idx == e, wts, 0.0), -1, keepdims=True)
        want += w_e * _swiglu(x, lw["w_gate"][e], lw["w_up"][e], lw["w_down"][e])
    assert float(jnp.abs(y - want)[:33].max()) <= 1e-5
    assert int(stats[0]) == 33 * K and int(stats[2]) == 33 and int(stats[3]) == 0
    assert bool(jnp.all(picked == idx))


# -- the padded layout's map (PR 37) ---------------------------------------------
def _source_per_row(sizes, rows, tile):
    """``_padded_source`` as ``moe_block_held`` computed it before PR 37, a ROW
    at a time: the plain reference the per-tile form is held to."""
    padded = -(-sizes // tile) * tile
    start, pstart = jnp.cumsum(sizes) - sizes, jnp.cumsum(padded) - padded
    r = jnp.arange(rows)
    of = jnp.maximum(jnp.sum(r[:, None] >= pstart[None, :], axis=1) - 1, 0)
    within = r - pstart[of]
    return jnp.where(within < sizes[of], start[of] + within, 0)


def _keys(case, t, k, g, rng):
    """The sort key of ``t * k`` (token, pick) pairs: the held expert, or ``g``
    for a pair no held expert takes."""
    local = np.stack([rng.choice(4 * g, size=k, replace=False) for _ in range(t)])
    if case == "empty_groups":  # the even experts take nothing
        local = np.where(local % 2 == 0, g, local)
    elif case == "one_group_holds_all":
        local = np.full((t, k), g - 1)
    elif case == "nothing_held":
        local = np.full((t, k), g)
    held = local < g
    if case == "valid_mask":  # padding rows are masked out of routing
        held &= (rng.random(t) < 0.6)[:, None]
    return jnp.asarray(np.where(held, local, g).reshape(-1), jnp.int32)


@pytest.mark.parametrize("case", ["empty_groups", "one_group_holds_all", "nothing_held",
                                  "valid_mask"])
@pytest.mark.parametrize("t,k,g", [(512, 10, 128), (16, 10, 128), (128, 22, 128), (2048, 8, 32),
                                   (3, 2, 6)],
                         ids=["pack_512x10_of_128", "tick_16x10_not_a_whole_tile",
                              "tick_128x22_of_128", "pack_2048x8_of_32", "tiny_3x2_of_6"])
def test_the_map_a_tile_at_a_time_is_the_map_a_row_at_a_time(t, k, g, case):
    """The sorted pair every padded row holds, and the (token, pick) pair
    gathered through it, element for element."""
    key = _keys(case, t, k, g, np.random.default_rng(t + k))
    tile = layer.held_row_tile(t, replace(SPEC, n_routed=4 * g, n_held=g, experts_per_tok=k))
    assert tile == {512: 32, 16: 16, 128: 16, 2048: 128, 3: 16}[t]
    rows = t * k + g * tile
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(g)[None, :], axis=0, dtype=jnp.int32)
    want = _source_per_row(sizes, rows, tile)
    got = jax.jit(layer._padded_source, static_argnums=(1, 2))(sizes, rows, tile)
    assert got.shape == (rows,) and got.dtype == want.dtype
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.array_equal(np.asarray(order[got]), np.asarray(order[want]))
    live = np.flatnonzero(np.asarray(got))  # every held pair but the first sorted has ONE row
    assert np.array_equal(np.sort(np.asarray(got)[live]), np.arange(1, int(sizes.sum())))


@pytest.mark.parametrize("spec,make,valid", [
    (SPEC, _weights, False), (SPEC_RELU2, _weights_relu2, False),
    (SPEC_SOFTMAX, _weights_softmax, False), (replace(SPEC, n_held=8), _weights, True)],
    ids=["swiglu", "relu2_latent", "softmax_gated_shared", "half_held_skewed_valid_mask"])
def test_held_layer_is_bit_equal_under_either_map(monkeypatch, spec, make, valid):
    """The same body over the per-row map hands the grouped matmul the same
    rows: the layer's output does not move by a bit."""
    lw = make(jax.random.PRNGKey(10))
    mask = None
    if valid:
        lw["bias"] = lw["bias"].at[2].set(10.0).at[3].set(-10.0)
        lw = dict(lw, **{k: lw[k][:8] for k in ("w_gate", "w_up", "w_down")})
        mask = jnp.arange(40) < 33
    x = jax.random.normal(jax.random.PRNGKey(11), (40, D))
    y, (stats, picked, _) = moe_block_held(lw, x, spec, mask)
    monkeypatch.setattr(layer, "_padded_source", _source_per_row)
    y0, (stats0, picked0, _) = moe_block_held(lw, x, spec, mask)
    assert np.array_equal(np.asarray(y), np.asarray(y0))
    assert np.array_equal(np.asarray(stats), np.asarray(stats0))
    assert np.array_equal(np.asarray(picked), np.asarray(picked0))


# -- a group that outgrows its row tile (PR 51) ------------------------------------
@pytest.mark.parametrize("path", ["xla", "kernel_interpreted"])
@pytest.mark.parametrize("t,tile,times", [(80, 16, 5), (384, 32, 12)],
                         ids=["tick_80_rows", "pack_384_rows"])
def test_a_group_of_several_tiles_and_empty_groups_give_the_plain_layers_output(path, t, tile, times):
    """Routing skewed by the bias: EVERY token picks held expert 0 (a group of 5 and
    of 12 x the row tile its expected 2.5 and 12 rows earned it: further tiles,
    nothing dropped) and no token an odd one (half the groups hold nothing); the
    output and dx are the plain layer's, every expert on every token masked by the
    routing, on the XLA path and through the interpreted kernels."""
    from deepspeed_tpu.ops.pallas.selected_attention import interpreted

    d, f, e, g, k = 128, 128, 64, 16, 2
    spec = replace(SPEC, n_routed=e, n_held=g, experts_per_tok=k, moe_width=f, n_shared=0)
    assert layer.held_row_tile(t, spec) == tile and layer.held_rows_bound(t, spec) is None
    ks = jax.random.split(jax.random.PRNGKey(30), 6)
    n = lambda key, *s: jax.random.normal(key, s, jnp.float32) / np.sqrt(s[-2])
    bias = jnp.zeros(e).at[0].set(10.0).at[jnp.arange(1, e, 2)].set(-10.0)
    lw = {"router": n(ks[0], d, e), "bias": bias, "w_gate": n(ks[1], g, d, f),
          "w_up": n(ks[2], g, d, f), "w_down": n(ks[3], g, f, d)}
    x, ct = jax.random.normal(ks[4], (t, d)), jax.random.normal(ks[5], (t, d))

    def plain(x):
        idx, wts = held_routing(lw, x, spec)[:2]
        y = jnp.zeros_like(x)
        for i in range(g):
            w_i = jnp.sum(jnp.where(idx == i, wts, 0.0), -1, keepdims=True)
            y += w_i * _swiglu(x, lw["w_gate"][i], lw["w_up"][i], lw["w_down"][i])
        return y

    held = lambda x: moe_block_held(lw, x, spec)
    with interpreted() if path == "kernel_interpreted" else contextlib.nullcontext():
        y, pull, (stats, _, _) = jax.vjp(held, x, has_aux=True)
        dx = pull(ct)[0]
    y_ref, pull_ref = jax.vjp(plain, x)
    assert int(stats[2]) == t == times * tile and int(stats[3]) == 0
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=5e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(pull_ref(ct)[0]), atol=5e-5, rtol=1e-4)


# -- the bounded layout (PR 50) --------------------------------------------------
# over ``held_rows_bound``'s threshold: 2048 x 2 = 4096 pairs, 16 x the two held
# groups' padding; 2 of 16 experts held, so the bound is twice an eighth of the
# pairs: 1024 pairs a pass in 1280 rows where the worst case lays out 4352 at once
T_B, K_B, G_B = 2048, 2, 2
BOUND, ROWS_BOUNDED, ROWS_WORST = 1024, 1024 + G_B * 128, T_B * K_B + G_B * 128
SPEC_B = replace(SPEC, n_held=G_B, experts_per_tok=K_B, n_shared=0, routing="softmax")
SPEC_B_RELU2 = replace(SPEC_RELU2, n_held=G_B, experts_per_tok=K_B)


def _held_share(lw, spec):
    routed = ("w_gate", "w_up", "w_down")
    return {k: (v[:spec.n_held] if k in routed else v) for k, v in lw.items()
            if spec.n_shared or not k.startswith("s_")}


def _rows_that_ran(monkeypatch):
    """The rows of every ``grouped_matmul`` call that EXECUTED (a pass that
    holds no pair is traced and skipped)."""
    ran, inner = [], layer.grouped_matmul

    def noting(xs, w, sizes, tile):
        jax.debug.callback(lambda: ran.append(xs.shape[0]))
        return inner(xs, w, sizes, tile)

    monkeypatch.setattr(layer, "grouped_matmul", noting)
    return ran


def _value_and_gradients(lw, x, spec, valid, ct):
    """((y, (d_lw, d_x)), the routing's stats)."""
    y, pull = jax.vjp(lambda lw, x: moe_block_held(lw, x, spec, valid)[0], lw, x)
    return (y, pull(ct)), moe_block_held(lw, x, spec, valid)[1][0]


def _agree(got, want):
    """Output and gradients, leaf by leaf (float32; the sums' order differs)."""
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5, rtol=1e-4,
                                   err_msg=jax.tree_util.keystr(path))


def test_the_bound_is_twice_the_uniform_share_and_engages_by_shape():
    assert layer.held_rows_bound(T_B, SPEC_B) == BOUND
    assert layer.held_rows_bound(T_B - 1, SPEC_B) is None  # under 16 x the padding
    assert layer.held_rows_bound(T_B, replace(SPEC_B, n_held=8)) is None  # the padding grew
    # a bound that is no bound: a member of two holds up to every pair
    assert layer.held_rows_bound(64 * T_B, replace(SPEC_B, n_held=8)) is None
    # up to a whole row tile: 3 of 16 experts of 4100 x 2 pairs: 2 x 1538 = 3076 -> 3200
    assert layer.held_rows_bound(4100, replace(SPEC_B, n_held=3)) == 25 * 128
    for pairs, want in ((0, (0, 1)), (BOUND, (ROWS_BOUNDED, 1)), (BOUND + 1, (2 * ROWS_BOUNDED, 0)),
                        (T_B * K_B, (4 * ROWS_BOUNDED, 0))):
        assert tuple(map(int, layer.held_rows_laid_out(T_B, SPEC_B, jnp.int32(pairs)))) == want
    # under the threshold the worst case at the rule's tile: 40 x 2 / 16 = 5 rows a group -> 16
    assert tuple(map(int, layer.held_rows_laid_out(40, SPEC_B, jnp.int32(3)))) == (
        40 * K_B + G_B * 16, 0)


@pytest.mark.parametrize("spec,make", [(SPEC_B, _weights_groups), (SPEC_B_RELU2, _weights_relu2)],
                         ids=["softmax_swiglu_no_shared", "relu2_latent"])
def test_the_bounded_layout_agrees_with_the_worst_cases(monkeypatch, spec, make):
    """Uniform routing over the threshold: ONE bounded pass runs (1280 rows, the
    three products forward and again in the backward's recomputation), and the
    output, dx, the router's and every held expert's weight gradients are those
    of the function that lays out the worst case (``held_rows_bound`` answering
    None: no loop, the pairs' gather for a combine)."""
    lw = _held_share(make(jax.random.PRNGKey(20)), spec)
    x = jax.random.normal(jax.random.PRNGKey(21), (T_B, D))
    ct = jax.random.normal(jax.random.PRNGKey(22), (T_B, D))
    valid = jnp.arange(T_B) % 7 != 0
    ran = _rows_that_ran(monkeypatch)
    got, stats = _value_and_gradients(lw, x, spec, valid, ct)
    jax.effects_barrier()
    calls = 2 if spec.expert_form == "relu2" else 3  # a pass's products
    assert ran == [ROWS_BOUNDED] * 3 * calls and 0 < int(stats[1]) <= BOUND  # y; y and its recomputation
    del ran[:]
    monkeypatch.setattr(layer, "held_rows_bound", lambda t, spec, tile=None: None)
    want, stats0 = _value_and_gradients(lw, x, spec, valid, ct)
    jax.effects_barrier()
    assert set(ran) == {ROWS_WORST} and np.array_equal(np.asarray(stats), np.asarray(stats0))
    _agree(got, want)
    for name, g in want[1][0].items():  # every tensor trains (the bias selects, it does not weigh)
        assert np.abs(np.asarray(g)).max() > 0 or name == "bias", name


@pytest.mark.parametrize("case,pairs,passes", [
    ("every_pair_here", T_B * K_B, 4), ("one_over_the_bound", BOUND + 1, 2),
    ("the_bound_exactly", BOUND, 1)])
def test_past_the_bound_more_passes_run_and_no_pair_is_dropped(monkeypatch, case, pairs, passes):
    """A router forced by its bias: every token picks held expert 0 and either
    held expert 1 (all 4096 pairs fall here: four passes, the cuts inside both
    groups) or expert 9 (a pair a valid token, 1025 or 1024 of them, and held
    expert 1 has no rows).  One over the bound a second pass runs for the one
    pair left, at the bound one pass; either way the result and the gradients
    are the unbounded function's."""
    spec = replace(SPEC_B, routing="sigmoid")
    lw = _held_share(_weights(jax.random.PRNGKey(23)), spec)
    other = 1 if case == "every_pair_here" else 9
    lw["bias"] = jnp.zeros(E).at[0].set(10.0).at[other].set(9.0)
    valid = None if case == "every_pair_here" else jnp.arange(T_B) % 3 != 1
    if valid is not None:  # 1365 of 2048 rows: down to ``pairs`` valid tokens
        valid &= jnp.cumsum(valid) <= pairs
    x = jax.random.normal(jax.random.PRNGKey(24), (T_B, D))
    ct = jax.random.normal(jax.random.PRNGKey(25), (T_B, D))
    ran = _rows_that_ran(monkeypatch)
    got, stats = _value_and_gradients(lw, x, spec, valid, ct)
    jax.effects_barrier()
    assert int(stats[1]) == pairs and ran == [ROWS_BOUNDED] * 3 * 3 * passes
    assert tuple(map(int, layer.held_rows_laid_out(T_B, spec, stats[1]))) == (
        passes * ROWS_BOUNDED, int(passes == 1))
    if other == 9:
        assert int(stats[2]) == pairs and int(stats[3]) == 0  # expert 1 holds no row
    monkeypatch.setattr(layer, "held_rows_bound", lambda t, spec, tile=None: None)
    _agree(got, _value_and_gradients(lw, x, spec, valid, ct)[0])
    if valid is not None:  # a masked row gets nothing from the experts
        assert not np.asarray(got[0])[~np.asarray(valid)].any()
