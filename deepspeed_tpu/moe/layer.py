"""MoE layer: routed expert FFN with expert-parallel dispatch.

TPU-native counterpart of the reference's ``MoE`` (moe/layer.py:17) +
``Experts`` (moe/experts.py:13) + ``MOELayer`` (moe/sharded_moe.py:533).
The reference dispatches tokens with an explicit ``_AllToAll`` autograd op
(sharded_moe.py:96) over the expert process group; here the dispatched
tensor is sharding-constrained onto the ``expert`` mesh axis and XLA emits
the all-to-all (and its transpose in backward) from the layout change —
same 2-hop dispatch/combine pattern, zero comm code.

Expert weights are stacked [E, d, f] and contracted via einsum, so the
per-expert FFNs run as one batched MXU matmul (the analogue of the
reference's grouped/MoE GEMM cutlass kernels, inference/v2/kernels/
cutlass_ops/moe_gemm).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallel.sharding import shard_activation
from ..parallel.topology import DATA_AXIS, EXPERT_AXIS, FSDP_AXIS, MODEL_AXIS, SUB_AXIS
from .sharded_moe import topk_gating

BATCH = (DATA_AXIS, FSDP_AXIS, SUB_AXIS)


def routed_ffn(
    router_kernel: jnp.ndarray,
    x: jnp.ndarray,
    expert_apply: Callable,
    k: int,
    capacity_factor: float,
    min_capacity: int = 4,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Shared gate → dispatch → expert → combine pipeline.

    ``expert_apply([E, C, d]) -> [E, C, d]`` runs all experts on their
    capacity-padded token slabs.  Dispatch/combine are one-hot einsums; the
    [E, C, d] slab is sharding-constrained onto the ``expert`` axis (the
    all-to-all boundary the reference performs explicitly in
    sharded_moe.py:96 _AllToAll).
    """
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    # router math fully in fp32 (reference sharded_moe.py casts input and
    # gate weight to float before the linear) — bf16 logits would quantize
    # near-tied expert choices
    logits = xf.astype(jnp.float32) @ router_kernel.astype(jnp.float32)
    gate = topk_gating(logits, k, capacity_factor, min_capacity=min_capacity)
    xe = jnp.einsum("nec,nd->ecd", gate.dispatch.astype(x.dtype), xf)
    xe = shard_activation(xe, P(EXPERT_AXIS, BATCH, None))
    ye = expert_apply(xe)
    ye = shard_activation(ye, P(EXPERT_AXIS, BATCH, None))
    out = jnp.einsum("nec,ecd->nd", gate.combine.astype(x.dtype), ye)
    return out.reshape(b, s, d), gate.aux_loss


def moe_block_dropless(lw: Any, x: jnp.ndarray, cfg) -> tuple[jnp.ndarray, jnp.ndarray]:
    """INFERENCE MoE: exact top-k routing with NO capacity dropping.

    Token dropping is a training-time load-balancing regularizer; serving
    must route every token (the reference's inference-v2 MoE kernels gather/
    scatter without capacity, ragged_ops moe_*), and capacity competition
    would otherwise make routing depend on batch padding — a packed/padded
    prefill would route REAL tokens differently than the same prompt alone.
    Dense-all-experts formulation (E× FFN flops, exact): fine at decode
    shapes and tolerable at prefill; the grouped matmul over (token, expert)
    pairs sorted by expert is ``moe_block_held`` below (``models/latent.py``'s
    expert layer, served and trained).
    """
    from ..models.transformer import _activation

    act = _activation(cfg.activation)
    b, s, d = x.shape
    k = cfg.moe_top_k
    xf = x.reshape(b * s, d)
    logits = xf.astype(jnp.float32) @ lw["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)  # [N, k]
    weights = topv / jnp.maximum(jnp.sum(topv, axis=-1, keepdims=True), 1e-9)
    h = act(jnp.einsum("nd,edf->nef", xf, lw["w_gate"])) * jnp.einsum(
        "nd,edf->nef", xf, lw["w_up"]
    )
    y = jnp.einsum("nef,efd->ned", h, lw["w_down"])  # [N, E, d]
    picked = jnp.take_along_axis(y, topi[:, :, None], axis=1)  # [N, k, d]
    out = jnp.sum(picked * weights[:, :, None].astype(y.dtype), axis=1)
    return out.reshape(b, s, d).astype(x.dtype), jnp.asarray(0.0, jnp.float32)


def routed_ffn_ep(
    lw: Any,
    x: jnp.ndarray,
    cfg,
    mesh,
    fmt: str = "none",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel routed FFN with EXPLICIT dispatch/combine
    all-to-alls (comm/qcomm.py) instead of GSPMD layout-change inference.

    The GSPMD path (:func:`moe_block`) leaves the all-to-all to the
    partitioner, which always ships full-width activations.  This variant
    runs the whole layer inside one ``shard_map`` over the ``expert`` axis
    so the dispatch and combine slabs travel through ``q_all_to_all`` —
    int8/fp8 payload + per-chunk fp32 scales when ``fmt`` says so, the
    exact ``lax.all_to_all`` in ``'none'`` (the A/B lever).  Dispatch
    weights/masks never leave the rank; only the [E, C, d] token slabs do —
    the 2-hop pattern of the reference's ``_AllToAll`` (sharded_moe.py:96).

    Layout contract: the token batch dim ``b`` shards over the DP axes AND
    the expert axis (ep subdivides the global batch — each expert rank
    routes its own tokens, capacity is per-rank, the reference's
    per-ep-group capacity); experts shard on their leading ``E`` dim.  The
    region is FULLY manual (the ring-attention pattern — partial-auto
    shard_map miscompiles on this XLA), so it composes with the training
    jit the same way ulysses/ring do.  Requires ``b`` divisible by
    ``dp_total * W`` and ``E % W == 0``.
    """
    from ..comm import qcomm
    from ..models.transformer import _activation
    from ..parallel.sharding import shard_map_compat

    act = _activation(cfg.activation)
    b, s, d = x.shape
    e = cfg.moe_num_experts
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    w = int(sizes.get(EXPERT_AXIS, 1))
    if w <= 1:
        return moe_block(lw, x, cfg)
    tok_axes = BATCH + (EXPERT_AXIS,)
    tok_div = 1
    for a in tok_axes:
        tok_div *= int(sizes.get(a, 1))
    if b % tok_div or e % w:
        raise qcomm.QCommError(
            f"routed_ffn_ep: batch {b} must divide the dp x expert extent "
            f"({tok_div}) and num_experts {e} the expert axis ({w})"
        )
    k = cfg.moe_top_k

    def body(xl, router, w_gate, w_up, w_down):
        # xl [b_local, s, d] — this rank's tokens; w_* [E/W, ...] — its experts
        bl = xl.shape[0]
        xf = xl.reshape(bl * s, d)
        logits = xf.astype(jnp.float32) @ router.astype(jnp.float32)
        gate = topk_gating(logits, k, cfg.moe_capacity_factor)
        xe = jnp.einsum("nec,nd->ecd", gate.dispatch.astype(xl.dtype), xf)
        # dispatch hop: each destination rank's E/W expert slab quantizes
        # independently -> [E/W, W*C, d] local expert inboxes
        inbox = qcomm.q_all_to_all(
            xe, EXPERT_AXIS, fmt, split_axis=0, concat_axis=1, world=w,
            out_dtype=xl.dtype,
        )
        h = act(jnp.einsum("ecd,edf->ecf", inbox, w_gate)) * jnp.einsum(
            "ecd,edf->ecf", inbox, w_up
        )
        ye = jnp.einsum("ecf,efd->ecd", h, w_down)
        # combine hop: results return to their token's rank -> [E, C, d]
        back = qcomm.q_all_to_all(
            ye, EXPERT_AXIS, fmt, split_axis=1, concat_axis=0, world=w,
            out_dtype=xl.dtype,
        )
        out = jnp.einsum("nec,ecd->nd", gate.combine.astype(xl.dtype), back)
        aux = jax.lax.pmean(gate.aux_loss, tok_axes)
        return out.reshape(bl, s, d), aux

    mapped = shard_map_compat(
        body, mesh,
        in_specs=(
            P(tok_axes, None, None),  # tokens shard over dp x expert ranks
            P(None, None),  # router replicated
            P(EXPERT_AXIS, None, None),  # per-rank experts
            P(EXPERT_AXIS, None, None),
            P(EXPERT_AXIS, None, None),
        ),
        out_specs=(P(tok_axes, None, None), P()),
        check_vma=False,
    )
    return mapped(x, lw["router"], lw["w_gate"], lw["w_up"], lw["w_down"])


def moe_block(lw: Any, x: jnp.ndarray, cfg) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Routed gated-FFN used inside the transformer block.

    lw: {'router' [d,E], 'w_gate' [E,d,f], 'w_up' [E,d,f], 'w_down' [E,f,d]}
    x: [b, s, d] -> (out [b, s, d], aux_loss scalar)
    """
    from ..models.transformer import _activation

    act = _activation(cfg.activation)

    def experts(xe):
        h = act(jnp.einsum("ecd,edf->ecf", xe, lw["w_gate"])) * jnp.einsum(
            "ecd,edf->ecf", xe, lw["w_up"]
        )
        h = shard_activation(h, P(EXPERT_AXIS, BATCH, MODEL_AXIS))
        return jnp.einsum("ecf,efd->ecd", h, lw["w_down"])

    return routed_ffn(
        lw["router"], x, experts, k=cfg.moe_top_k,
        capacity_factor=cfg.moe_capacity_factor,
    )


class MoE:
    """API-parity wrapper (reference deepspeed.moe.layer.MoE): wraps a user
    expert apply-fn into a routed layer.

    expert_fn(expert_params, x_tokens) -> y_tokens, vmapped over the leading
    expert dim of ``expert_params``.
    """

    def __init__(
        self,
        hidden_size: int,
        expert_fn: Callable,
        num_experts: int,
        k: int = 1,
        capacity_factor: float = 1.0,
        min_capacity: int = 4,
    ):
        self.hidden_size = hidden_size
        self.expert_fn = expert_fn
        self.num_experts = num_experts
        self.k = k
        self.capacity_factor = capacity_factor
        self.min_capacity = min_capacity

    def __call__(self, router_kernel, expert_params, x):
        return routed_ffn(
            router_kernel, x,
            lambda xe: jax.vmap(self.expert_fn)(expert_params, xe),
            k=self.k, capacity_factor=self.capacity_factor,
            min_capacity=self.min_capacity,
        )


# ---------------------------------------------------------------------------
# a HELD share of routed experts (models/latent.py: served and trained)
# ---------------------------------------------------------------------------
# the grouped matmul's row tiles, and what a held group's rows are padded to
# (``held_row_tile``)
_ROW_TILES = (16, 32, 64, 128)
# the rows a tile (and ONE pass of the bounded layout, ``held_rows_bound``) has
# room for, over a group's (a member's) share under uniform routing: a share
# that drifts, or a router that prefers some experts by half again, still takes
# one tile (one pass)
_HELD_ROWS_FACTOR = 2
# ... and the pairs a layer routes, over the groups' padding, from which the
# bounded layout is built at all (``held_rows_bound``)
_BOUNDED_MIN_PAIRS_PER_PADDING = 16
# a weight tile of the grouped matmul, elements (3 MB in bf16, double-buffered)
_WEIGHT_TILE = 1600 * 1024


def held_row_tile(t: int, spec) -> int:
    """The row tile of the grouped matmuls of ``moe_block_held`` at ``t`` tokens,
    which is also what each held group's rows are padded to.  Decided from the
    rows a group EXPECTS under uniform routing, ``t x experts_per_tok /
    n_routed``: a static number of the shape and the spec, never the padded rows
    handed in, a run's routing or a model's name.

    The smallest of 16, 32, 64, 128 that holds ``_HELD_ROWS_FACTOR`` = twice the
    expectation, 128 where none does: a tick's group of under one row gets 16
    (bf16's own sublane tile: nothing shorter is a whole tile of the chip's), a
    512-token pack's group of 10-22 rows 32-64, a 2048-token pack's of 64-77
    and a training step's of 2048 the 128 they always had.  What the padding
    cost was not the products, which visit live tiles only (cell 7's pack: 0.534
    ms at 9 216 rows for 0.529 at 21 504), but every XLA op over the laid-out
    rows around them: the whole layer 1.85 for 2.23 ms there, 3.17 for 4.39 at
    cell 8's pack, 2.08 for 3.14 at its tick (my chip run, PR 51,
    ``tools/grouped_matmul_curves.py --layer``).

    A group that outgrows its tile takes further tiles (``_padded_source``), so
    nothing is dropped under any routing; each extra tile costs that expert's
    weights streamed once more where k is walked in several steps, and one more
    grid step where it is one (the weight block stays resident between
    consecutive tiles of a group).  That is why the tile holds the expectation
    TWICE: at 16 rows a tile cell 5's pack (64 expected rows: five tiles a
    group) took 3.14 ms a product for 0.97, cell 6's pack (22 expected) 1.48 for
    1.20 at 64; at the factor's two an overflow is rare (Poisson at 16 expected
    rows passes 32 for one group in 10^4, at 5.5 expected passes 16 likewise).

    The ladder ends at 128 for a group of thousands of rows too: at cell 10's
    step (2048 rows a group) tiles of 256 and 512 made the products of a layer's
    forward and backward 6% and 4% shorter over 3% and 9% more rows for the
    gathers around them to walk; the layer's forward + backward read 46.5 and
    47.6 ms for 47.7 at 128 (the same run): under 1% of a step for a second
    ladder."""
    expected = t * spec.experts_per_tok / spec.n_routed
    return next((r for r in _ROW_TILES if r >= _HELD_ROWS_FACTOR * expected), _ROW_TILES[-1])


def _gmm_tiling(tm: int, k: int, n: int) -> tuple[int, int, int]:
    """(tm, tk, tn) of a grouped-matmul kernel from the shapes it is handed: the
    caller's row tile (``held_row_tile``), and for the weights the tile (tk, tn)
    of k's and n's own divisors (multiples of 128) that covers the most of the
    matrix inside ``_WEIGHT_TILE`` = 1.6 M elements with ``tk`` <= 1024, the
    taller ``tk`` of two that cover the same.  (128, 1024, 1536) and (128, 512,
    2560) at d 5120 x f 1536, the fastest of six tried on the chip, all within
    12% (0.89 and 0.90 ms a call against 0.61 ms for reading the weights; my chip
    run, PR 29).  k 2688 = 21 x 128 and k 2304 = 18 x 128 get 896 and 768 where
    a choice among powers of two gave them 128 and 256, i.e. 21 and 9
    accumulator round trips over a 256 KB weight tile: cell 6's down projection
    1.17 ms a call for 1.56 at the same 16-row tile (1.83 at the parent's 128),
    cell 10's nine products of a layer's forward and backward 9.9 ms for 15.3 at
    the same 128-row tile (my chip run, PR 51, ``tools/grouped_matmul_curves.py``).
    The backward's kernels take the same rule at their own k and n."""
    tiles = [(tk, tn) for tk in range(128, min(k, 1024) + 1, 128) if k % tk == 0
             for tn in range(128, n + 1, 128) if n % tn == 0 and tk * tn <= _WEIGHT_TILE]
    tk, tn = max(tiles, key=lambda tile: (tile[0] * tile[1], tile[0]))
    return tm, tk, tn


def _interpreted() -> bool:
    """Whether the ``cfg.latent`` kernels' one switch asks for interpret mode
    (``ops/pallas/selected_attention.interpreted()``: the tests' way onto the
    kernel path off the chip)."""
    from ..ops.pallas.index_scores import interpret

    return interpret()


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def gmm(xs, w, sizes, tm):
    """megablox ``gmm`` at row tile ``tm`` with this module's tilings in the
    backward too: the stock VJP hands the forward's (tm, tk, tn) to both backward
    kernels, whose k and n are the forward's n and k.  Rows of no group get NO
    gradient."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as kernel

    return kernel(xs, w, sizes, preferred_element_type=xs.dtype,
                  tiling=_gmm_tiling(tm, *w.shape[1:]), interpret=_interpreted())


def _gmm_fwd(xs, w, sizes, tm):
    return gmm(xs, w, sizes, tm), (xs, w, sizes)


def _gmm_bwd(tm, res, g):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as kernel, tgmm

    xs, w, sizes = res
    (m, k), n = xs.shape, w.shape[-1]
    d_xs = kernel(g, w, sizes, preferred_element_type=xs.dtype,
                  tiling=_gmm_tiling(tm, n, k), transpose_rhs=True, interpret=_interpreted())
    # the kernel leaves the rows past the last group as they lay in memory
    d_xs = jnp.where((jnp.arange(m) < jnp.sum(sizes))[:, None], d_xs, 0)
    d_w = tgmm(xs.swapaxes(0, 1), g, sizes, preferred_element_type=w.dtype,
               tiling=_gmm_tiling(tm, k, n), num_actual_groups=w.shape[0],
               interpret=_interpreted())
    return d_xs, d_w, None


gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(xs: jnp.ndarray, w: jnp.ndarray, sizes: jnp.ndarray,
                   tile: int = _ROW_TILES[-1]) -> jnp.ndarray:
    """``xs[rows of group g] @ w[g]`` for rows sorted by group: xs [M, k],
    w [G, k, n], sizes [G] int32 (their sum may be under M: the rows past it
    belong to no group and come back undefined).  On a TPU the megablox
    Pallas kernel at row tile ``tile`` (what the caller padded its groups to:
    ``held_row_tile``), which visits only the row tiles that hold a group's rows
    and reads each group's weights once per tile (``M`` padded up to a whole
    row tile); elsewhere, and for ``k`` / ``n`` its tiles do not divide,
    ``lax.ragged_dot``.  Differentiable either way (``gmm``'s VJP above: the
    same kernel for the rows' gradient, ``tgmm`` for the weights')."""
    from ..ops.pallas import note_dispatch, on_tpu

    m, k = xs.shape
    n = w.shape[-1]
    if not (on_tpu() or _interpreted()):
        note_dispatch("expert_gmm", False, (m, k, n), reason="not on a TPU")
    elif k % 128 or n % 128:
        note_dispatch("expert_gmm", False, (m, k, n),
                      reason="k and n must be multiples of 128")
    else:
        note_dispatch("expert_gmm", True, (m, k, n))
        if m % tile:  # rows of no group, up to a whole row tile
            xs = jnp.pad(xs, ((0, -m % tile), (0, 0)))
        return gmm(xs, w, sizes, tile)[:m]
    return jax.lax.ragged_dot(xs, w, sizes, preferred_element_type=xs.dtype)


def held_routing(lw: Any, x: jnp.ndarray, spec):
    """The routing in the form the spec names (``routing``, chosen when the
    program is traced).  'sigmoid': DeepSeek-V3's ``noaux_tc`` without groups:
    sigmoid scores in float32, the ``experts_per_tok`` largest of score + bias
    picked, weights the picked scores normalised (the bias selects, it does not
    weigh), times ``routed_scale``.  'softmax' and 'group_limited': below.
    x [T, d] -> (experts [T, k], weights [T, k] float32, every expert's score
    [T, n_routed] float32: the softmax's or the sigmoid's)."""
    scores = x.astype(jnp.float32) @ lw["router"].astype(jnp.float32)
    if spec.routing == "group_limited":
        # DeepSeek-V2's ``group_limited_greedy``: softmax over ALL experts in
        # float32; the experts lie in ``n_group`` groups of consecutive ones, a
        # group scores its largest member, the ``topk_group`` best groups are
        # kept (equal scores: the lower group) and the picks are the largest
        # inside them; weights the picked scores as they are (NOT renormalised)
        # times ``routed_scale``.  A score outside the kept groups reads -1,
        # under every softmax value, so no pick ever falls there.
        s = jax.nn.softmax(scores, axis=-1)
        t, e = s.shape
        best = jnp.max(s.reshape(t, spec.n_group, e // spec.n_group), axis=-1)
        _, groups = jax.lax.top_k(best, spec.topk_group)
        kept = jnp.any(groups[:, :, None] == jnp.arange(spec.n_group)[None, None, :], axis=1)
        inside = jnp.repeat(kept, e // spec.n_group, axis=1)
        picked, idx = jax.lax.top_k(jnp.where(inside, s, -1.0), spec.experts_per_tok)
        return idx, picked * spec.routed_scale, s
    if spec.routing == "softmax":
        # softmax over ALL experts in float32, the largest picked and
        # renormalised (``norm_topk_prob``); no bias, no scale
        s = jax.nn.softmax(scores, axis=-1)
        picked, idx = jax.lax.top_k(s, spec.experts_per_tok)
        return idx, picked / jnp.sum(picked, -1, keepdims=True), s
    s = jax.nn.sigmoid(scores)
    _, idx = jax.lax.top_k(s + lw["bias"], spec.experts_per_tok)
    picked = jnp.take_along_axis(s, idx, axis=-1)
    return idx, picked / jnp.sum(picked, -1, keepdims=True) * spec.routed_scale, s


def held_rows_bound(t: int, spec, tile: Optional[int] = None) -> Optional[int]:
    """The held pairs ONE pass of ``moe_block_held``'s bounded layout has room
    for at ``t`` tokens, None where it builds no such layout.  Twice the pairs a
    member holds under uniform routing (``_HELD_ROWS_FACTOR``), up to a whole
    row tile (``held_row_tile``'s): from the spec and the shape alone, never
    tuned to a run.

    The bound pays where the pairs a layer routes, ``t k``, outnumber the
    groups' padding ``g x tile`` many times over: the padding stays whatever
    the bound, and what the bound saves is about half of ``t k`` rows.  A
    training step's 16 384 tokens stand at 64 x the padding; every served pack
    and tick at 4 x or under (a few thousand rows of a scope that is a tenth of
    a pack), and nothing was measured in between, so the threshold is the
    geometric middle of the two, ``_BOUNDED_MIN_PAIRS_PER_PADDING`` = 16:
    neither side stands near it.  Below it the function traces ONE body over the
    worst case's rows."""
    pairs, tile = t * spec.experts_per_tok, tile or held_row_tile(t, spec)
    padding = spec.n_held * tile
    if pairs < _BOUNDED_MIN_PAIRS_PER_PADDING * padding:
        return None
    bound = _HELD_ROWS_FACTOR * -(-pairs * spec.n_held // spec.n_routed)
    bound = -(-bound // tile) * tile
    return bound if bound + padding < pairs else None  # a member of two holds them all


def held_rows_a_pass(t: int, spec, tile: Optional[int] = None) -> int:
    """The rows ONE pass of ``moe_block_held`` hands the grouped matmuls at ``t``
    tokens (on row tiles of ``tile``, where the caller chose one): the worst
    case's ``t k`` pairs, or the bound's where it has one, and a tile of padding a
    held group."""
    bound = held_rows_bound(t, spec, tile)
    pairs = t * spec.experts_per_tok if bound is None else bound
    return pairs + spec.n_held * (tile or held_row_tile(t, spec))


def held_rows_laid_out(t: int, spec, pairs_held):
    """What ``moe_block_held`` ran at ``t`` tokens with ``pairs_held`` (a traced
    scalar) pairs on the held experts: (the rows it handed the grouped matmul,
    over all its passes; 1 where ONE bounded pass held every pair), int32
    scalars for the step's counts."""
    rows, bound = held_rows_a_pass(t, spec), held_rows_bound(t, spec)
    if bound is None:
        return jnp.int32(rows), jnp.int32(0)
    passes = -(-pairs_held // bound)  # a pass that holds no pair is skipped
    return (passes * rows).astype(jnp.int32), (passes <= 1).astype(jnp.int32)


def _padded_source(sizes: jnp.ndarray, rows: int, tile: int, with_live: bool = False):
    """The map from the rows handed to the grouped matmul to the (token, pick)
    pairs in sorted order.  Each group starts on a row tile of the kernel (its
    rows padded up to whole tiles): a group that straddled a tile boundary had
    its expert's weights streamed once per tile, half as many reads again at
    ~64 rows a group, and how many straddled followed the routing, so the
    layer's time did too.  sizes int32 [g], the groups' rows -> int32 [rows]:
    the sorted pair a row holds, 0 for a padding row; ``with_live`` also bool
    [rows], the rows that hold a pair.  ``rows`` is what the caller lays out:
    the groups' padded rows fit wherever ``sum(sizes) + g x tile <= rows`` (a
    group's padding is under one tile); the rows past them are padding too.

    A tile lies in ONE group, so the group, its first pair and its size are
    looked up a TILE at a time and a row adds its place in the tile: a lookup
    a row is a gather of one index a row, which the chip walks an index at a
    time, 0.17 ms each at 21 504 rows (my chip run, PR 37), and three of them
    with the pairs' gather cost as much as the matmuls they prepared."""
    ptiles = -(-sizes // tile)
    start, tstart = jnp.cumsum(sizes) - sizes, jnp.cumsum(ptiles) - ptiles
    tiles = jnp.arange(-(-rows // tile))
    of = jnp.maximum(jnp.sum(tiles[:, None] >= tstart[None, :], axis=1) - 1, 0)
    within = ((tiles - tstart[of]) * tile)[:, None] + jnp.arange(tile)
    live = within < sizes[of][:, None]
    source = jnp.where(live, start[of][:, None] + within, 0)
    source = source.reshape(-1)[:rows]  # the last tile may reach past the rows
    return (source, live.reshape(-1)[:rows]) if with_live else source


def moe_block_held(lw: Any, x: jnp.ndarray, spec, valid=None, tile: Optional[int] = None):
    """The expert layer as ONE member of an expert-parallel deployment sees
    it, without the exchange: route every token over all ``n_routed`` experts,
    compute the picks that fall on the ``n_held`` experts held here by a
    grouped matmul over (token, expert) pairs sorted by expert, add the shared
    expert (a model with none has no ``s_up``).  The result is this member's
    PARTIAL sum; the members' results, the shared expert counted once, add up
    to the uncut layer's output.  Differentiable end to end (``grouped_matmul``;
    the gathers' transposes scatter into live rows only).

    THE ROWS LAID OUT.  Pairs of no held expert sort last, so the held groups,
    each padded up to whole row tiles, occupy the FIRST ``sum(sizes) + g x tile``
    rows at most.  The tile is the grouped matmul's own and follows the rows a
    group EXPECTS at this ``T`` (``held_row_tile``: twice the expectation, 16 to
    128 rows; a group that outgrows it takes further tiles, at the price of its
    weights streamed again).  The worst case, every pair of the batch on this
    member, is ``T k + g x tile`` rows, and that is what is laid out wherever
    ``held_rows_bound`` gives no bound (every served pack and tick): the row
    gather, the three products, and a combine that gathers each pair's row
    ``[T, k, d]``.  Where it gives a bound ``C`` (a training step: the pairs
    outnumber the padding many times over) the SAME body runs over ``C + g x
    tile`` rows (67 584 for 133 120 at 16 384 tokens, top 8, 16 of 64 held) for
    the first ``C`` sorted held pairs, and again for the next ``C`` while any
    are left (one pass wherever ``sum(sizes) <= C``; ``ceil(T k / C)`` at most):
    no pair is dropped and no capacity enforced, the result for any routing is
    the unbounded function's.  Over fewer rows than pairs the combine walks the
    ROWS: a live row's product, weighted in float32, adds into its pair's token.

    The experts' form comes from the spec: ``expert_form`` 'swiglu' (``w_gate``,
    ``w_up``, ``w_down``) or 'relu2' (``w_up``, ``relu(.)^2``, ``w_down``: no gate
    matrix, the shared expert alike); with ``moe_latent`` the routed experts
    work on ``x @ w_lat_down`` and their weighted sum goes back through
    ``w_lat_up`` (linear, so each member applies it to its own share), while
    the router and the shared expert read ``x`` at full width.  With
    ``shared_gate`` the shared expert's output is scaled by ``sigmoid(x . w_sg)``.

    x [T, d]; ``valid`` [T] bool masks padding rows out of routing; ``tile``: the
    row tile where the caller knows the rows better than their count says (a
    served pack that carries a tick's slot rows behind its tokens lays its groups
    out as the pack alone does: ``latent_runner._row_tile``).  Returns
    (y [T, d], (stats int32 [4]: pairs routed, pairs on held experts, rows of
    the largest and of the smallest held expert's group; the experts picked
    [T, k]; every routed expert's score [T, n_routed] float32, what a
    load-balancing loss is made of))."""
    t, d = x.shape
    k, g = spec.experts_per_tok, spec.n_held
    gated = spec.expert_form == "swiglu"
    relu2 = lambda a: jnp.square(jax.nn.relu(a))
    with jax.named_scope("router"):
        idx, wts, scores = held_routing(lw, x, spec)
    local = idx - spec.held_offset
    held = (local >= 0) & (local < g)
    if valid is not None:
        held &= valid[:, None]
    with jax.named_scope("expert_layout"):
        key = jnp.where(held, local, g).reshape(-1)  # pairs of no held expert sort last
        order = jnp.argsort(key, stable=True)
        sizes = jnp.sum(key[:, None] == jnp.arange(g)[None, :], axis=0, dtype=jnp.int32)
        tile = tile or held_row_tile(t, spec)

    def experts(rows: int, first=None, order=order, sizes=sizes, x=x, wts=wts, ew=lw):
        """The products of ``sizes`` pairs a group, from sorted pair ``first`` on
        (None: all the held pairs), over a layout of ``rows`` rows (static), and
        their weighted sum a token, float32 [T, d'].  ``ew``: the routed experts'
        matrices."""
        walk_rows = rows < t * k  # the combine walks whichever is fewer
        with jax.named_scope("expert_layout"):
            padded = -(-sizes // tile) * tile
            start, pstart = jnp.cumsum(sizes) - sizes, jnp.cumsum(padded) - padded
            if walk_rows:
                source, live = _padded_source(sizes, rows, tile, with_live=True)
            else:
                source = _padded_source(sizes, rows, tile)  # a pair, sorted order
        x_in = x
        if spec.moe_latent:
            with jax.named_scope("latent_proj"):
                x_in = x @ ew["w_lat_down"]
        with jax.named_scope("expert_matmul"):
            pair = order[source if first is None else first + source]
            xs = x_in[pair // k]  # [rows, d]; padding rows repeat a live one
            if gated:
                h = jax.nn.silu(grouped_matmul(xs, ew["w_gate"], padded, tile)) \
                    * grouped_matmul(xs, ew["w_up"], padded, tile)
            else:
                h = relu2(grouped_matmul(xs, ew["w_up"], padded, tile))
            ys = grouped_matmul(h, ew["w_down"], padded, tile)
        if walk_rows:  # a live row's product, weighted, adds into its pair's token
            # (a padding row's product is undefined: masked BEFORE the weight meets
            # it, so that neither the sum nor the weight's gradient sees it)
            products = jnp.where(live[:, None], ys, 0).astype(jnp.float32)
            return jnp.zeros((t, ys.shape[-1]), jnp.float32).at[pair // k].add(
                products * wts.reshape(-1)[pair][:, None])
        at = jnp.argsort(order).reshape(t, k)  # where each pair sorted to
        mine = jnp.clip(local, 0, g - 1)
        dest = jnp.where(held, pstart[mine] + at - start[mine], 0)  # ... and its padded row
        pairs = ys[dest].astype(jnp.float32)  # [T, k, d]
        return jnp.sum(jnp.where(held[..., None], pairs * wts[..., None], 0.0), axis=1)

    bound, rows = held_rows_bound(t, spec, tile), held_rows_a_pass(t, spec, tile)
    if bound is None:
        y = experts(rows)
    else:
        # Exact for any routing: the sorted held pairs go through ``bound`` at a
        # time, a group's pairs divided between two passes where the cut falls
        # inside it, and a pass that holds none is skipped.  ONE body in ONE loop,
        # forward and backward (a second body for the worst case's rows behind a
        # ``cond`` made the step's program a quarter larger), and the loop's
        # gradient is written out: differentiated by JAX, the ``cond`` hands out
        # the skipped branch's residuals as zeros and the loop stacks every
        # pass's (+1.2 to +5.7 GiB of a step's temporaries at 16 384 tokens: the
        # step no longer loads beside the optimizer's state); here a pass keeps
        # its inputs and its backward recomputes it, which under the block's own
        # recomputation runs the products twice a step, as without a bound.
        zeros = lambda tree: jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), tree)

        def part(first, sizes):  # each group's pairs among the sorted pairs [first, first + bound)
            ends = jnp.cumsum(sizes)
            return jnp.clip(ends, first, first + bound) - jnp.clip(ends - sizes, first, first + bound)

        def one_pass(first, order, sizes, *operands):
            return experts(rows, first, order, part(first, sizes), *operands)

        def over_passes(run, like, sizes):
            """``run(first)`` summed over the passes that hold a pair (a tree like ``like``)."""
            def step(total, first):
                new = jax.lax.cond(jnp.sum(part(first, sizes)) > 0, lambda: run(first), lambda: zeros(like))
                return jax.tree.map(jnp.add, total, new), None
            return jax.lax.scan(step, zeros(like), jnp.arange(-(-t * k // bound)) * bound)[0]

        @jax.custom_vjp
        def in_passes(order, sizes, *operands):
            y = jax.ShapeDtypeStruct((t, lw["w_down"].shape[-1]), jnp.float32)
            return over_passes(lambda first: one_pass(first, order, sizes, *operands), y, sizes)

        def in_passes_bwd(kept, ct):
            order, sizes, *operands = kept
            pull = lambda first: jax.vjp(lambda *a: one_pass(first, order, sizes, *a), *operands)[1](ct)
            return (None, None, *over_passes(pull, tuple(operands), sizes))

        in_passes.defvjp(lambda *args: (in_passes(*args), args), in_passes_bwd)
        routed = ("w_gate", "w_up", "w_down", "w_lat_down")
        y = in_passes(order, sizes, x, wts, {name: lw[name] for name in routed if name in lw})
    if spec.moe_latent:
        with jax.named_scope("latent_proj"):
            y = y.astype(x.dtype) @ lw["w_lat_up"]
    shared = None
    if "s_up" in lw:
        with jax.named_scope("shared_expert"):
            if gated:
                shared = (jax.nn.silu(x @ lw["s_gate"]) * (x @ lw["s_up"])) @ lw["s_down"]
            else:
                shared = relu2(x @ lw["s_up"]) @ lw["s_down"]
            if spec.shared_gate:  # the shared expert behind a gate of its own
                gate = jax.nn.sigmoid((x @ lw["w_sg"]).astype(jnp.float32))
                shared = (shared.astype(jnp.float32) * gate).astype(x.dtype)
    n_valid = t if valid is None else jnp.sum(valid, dtype=jnp.int32)
    stats = jnp.stack([jnp.asarray(n_valid * k, jnp.int32),
                       jnp.sum(held, dtype=jnp.int32), jnp.max(sizes), jnp.min(sizes)])
    y = y.astype(x.dtype) if shared is None else y.astype(x.dtype) + shared
    return y, (stats, idx, scores)
