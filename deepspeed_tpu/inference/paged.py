"""Paged KV cache: device-side block pool + gather-based paged attention.

TPU-native counterpart of the reference's paged KV machinery
(``inference/v2/ragged/kv_cache.py`` + the blocked attention kernels in
``inference/v2/kernels/ragged_ops``).  The cache is one block pool per
layer stack — [L, num_blocks, block_size, hkv, hd] — and block tables map
each sequence slot to its pages.  Attention gathers a sequence's pages into
a contiguous [max_len] view and masks; static shapes throughout (the
max_blocks_per_seq bound plays the role of the reference's
max_ragged_sequence_count), so one compiled kernel serves every step.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import repeat_kv

_NEG_INF = -1e30  # finite mask value: exp(_NEG_INF - _NEG_INF) stays finite


def init_paged_cache(
    num_layers: int, num_blocks: int, block_size: int, num_kv_heads: int,
    head_dim: int, dtype=jnp.bfloat16,
) -> Tuple[Tuple[jnp.ndarray, ...], Tuple[jnp.ndarray, ...]]:
    """Per-LAYER block pools (tuple of [num_blocks, bs, hkv, hd] arrays),
    not one stacked [L, ...] array: a stacked pool forces XLA to
    materialize each layer's slice as a pallas-operand copy and to stitch
    updates back with full-slice dynamic-update-slices — measured 11.4 GB
    of HBM traffic per decode tick at 410M/batch-64 vs ~1.9 GB with
    per-layer buffers (the difference between 31 ms and single-digit-ms
    ticks)."""
    shape = (num_blocks, block_size, num_kv_heads, head_dim)
    k = tuple(jnp.zeros(shape, dtype) for _ in range(num_layers))
    v = tuple(jnp.zeros(shape, dtype) for _ in range(num_layers))
    return k, v


def write_pack_kv(cache_layer, kv, safe_pages):
    """Scatter a prefill pack's K (or V) [T, hkv, hd] into its pages.

    cache_layer [num_blocks, bs, hkv, hd]; safe_pages [T/bs] int32, the
    destination page per bs-chunk with padding chunks ALREADY routed to the
    out-of-bounds sentinel ``num_blocks`` (once a pack, not once a layer) and
    dropped by the scatter — a "safe" real block would alias its owner.  Every
    segment starts on a PAGE boundary of the pack, so a pool takes ONE
    page-granular scatter: a per-TOKEN scatter was measured at ~100 ms/pack
    on v5e (TPU serializes row scatters).  Rows past a prompt's end inside its
    last page hold garbage that attention masks by sequence length.
    """
    bs = cache_layer.shape[1]
    kvp = kv.reshape(-1, bs, *kv.shape[1:]).astype(cache_layer.dtype)
    return cache_layer.at[safe_pages].set(kvp, mode="drop")


def write_decode_kv(cache_layer, kv, block_table, positions, active):
    """Scatter one new token per sequence.

    cache_layer [num_blocks, bs, hkv, hd]; kv [B, hkv, hd];
    block_table [B, max_pages]; positions [B] (token index being written);
    active [B] bool — inactive slots are dropped from the scatter.
    """
    nb, bs = cache_layer.shape[0], cache_layer.shape[1]
    b = kv.shape[0]
    page = block_table[jnp.arange(b), positions // bs]  # [B]
    off = positions % bs
    # inactive slots scatter to an out-of-bounds sentinel and are dropped
    # (a "safe" real page would alias another sequence's block)
    sentinel = jnp.where(active & (page >= 0), page, nb)
    return cache_layer.at[sentinel, off].set(kv.astype(cache_layer.dtype), mode="drop")


def write_spec_kv(cache_layer, kv, pages, offsets):
    """Scatter a speculative verify pack's K (or V) rows token-by-token.

    cache_layer [num_blocks, bs, hkv, hd]; kv [T, hkv, hd]; pages/offsets
    [T] int32 — destination (page, row) per packed token, ``pages`` -1 for
    padding rows (dropped via the out-of-bounds sentinel, same rule as
    ``write_decode_kv``).

    Unlike chunked prefill, a verify pack starts MID-PAGE (the sequence's
    next write position is whatever decode left it at), so the page-granular
    ``at[pages].set`` trick of ``prefill_packed`` would stomp live rows at
    the head of the first page.  A row scatter is exact; verify packs are
    small — max_seqs * (k+1) rows, nowhere near the 2048-token prefill packs
    where per-row scatters were measured to serialize.
    """
    nb = cache_layer.shape[0]
    sentinel = jnp.where(pages >= 0, pages, nb)
    return cache_layer.at[sentinel, offsets].set(
        kv.astype(cache_layer.dtype), mode="drop"
    )


def paged_attention_packed_ctx(
    q, k, v, segment_ids, cache_k_layer, cache_v_layer, ctx_tables, ctx_lens,
    scale=None, logits_soft_cap=None, mesh=None, dp: int = 1,
    seq_shards: int = 1, ctx=None,
):
    """Packed-prefill attention where each pack segment ALSO attends to its
    sequence's cached KV pages (positions below its start offset) — the
    model-runner capability that prefix caching and chunked prefill both
    ride on.

    q/k/v [T, h, hd] — the packed suffix tokens (page-aligned segments);
    segment_ids [T] int32, 1-based per SLOT (slot + 1), 0 = padding;
    cache_*_layer [num_blocks, bs, hkv, hd] — pools WITH this pack's pages
    already written (the in-pack positions are masked out by ``ctx_lens``);
    ctx_tables [N, P] int32 — block table per slot row (-1 padded);
    ctx_lens [N] int32 — cached-context length per slot (start offset).

    One softmax spans [cached context | in-pack causal segment], keys in
    position order, so a suffix prefill over cached context is numerically
    the same reduction as the cold full-prompt prefill.  Dispatches to the
    flash-style Pallas kernel (ops/pallas/ctx_attention.py) on TPU —
    per-segment page routing + length-bounded DMA, one online-softmax
    reduction over [ctx | pack]; the jnp dense body (gathers all P pages
    per segment, O(T * P * bs) logits) stays the fallback + ground truth,
    and ``ctx.fused is False`` (ops.quantizer.ServingContext) pins the jnp
    body per engine — the kernel-vs-dense A/B lever.  The packed
    no-context fast path stays on ``flash_attention``.

    With ``mesh`` the call runs under ``shard_map`` exactly like
    :func:`paged_attention_decode`: q split on heads over ``model``, the
    pool split on kv heads (replicated + narrowed when hkv doesn't divide
    the axis).  ``dp > 1`` (the 2-D batch×model serve mesh) additionally
    shards the PACK dimension over ``batch`` — the engine builds ctx packs
    as ``dp`` equal per-replica chunks whose segments belong to that
    replica's slot group, so each replica attends over its own chunk
    against its LOCAL pool slice with the same global→local block-id
    translation decode already performs.  Nothing reads the pool across
    the batch axis.

    ``seq_shards > 1``: cached pages stripe across the ``seq`` shards, so
    each shard computes a flash partial over its locally-owned ctx pages —
    the pack's fresh (causal, in-flight) keys are charged to seq shard 0
    only so the log-sum-exp ring merge counts them exactly once — and the
    ``S`` partials combine with the same ``S-1``-hop ring pass as decode.
    """
    fused = getattr(ctx, "fused", None) if ctx is not None else None
    if mesh is not None and (_model_axis_size(mesh) > 1 or dp > 1
                             or seq_shards > 1):
        return _paged_attention_packed_ctx_tp(
            q, k, v, segment_ids, cache_k_layer, cache_v_layer, ctx_tables,
            ctx_lens, mesh, dp=dp, seq_shards=seq_shards, scale=scale,
            logits_soft_cap=logits_soft_cap, fused=fused,
        )
    return _paged_attention_packed_ctx_local(
        q, k, v, segment_ids, cache_k_layer, cache_v_layer, ctx_tables,
        ctx_lens, scale=scale, logits_soft_cap=logits_soft_cap, fused=fused,
    )


def _use_ctx_kernel(fused, q, cache_k_layer, ctx_tables):
    """Kernel-vs-fallback gate for the packed-ctx path, same convention as
    the decode/flash kernels: on TPU (or under ``set_interpret``) and the
    shape is supported; ``fused=False`` (the ServingContext A/B lever) pins
    the jnp body."""
    from ..ops.pallas import note_dispatch, on_tpu
    from ..ops.pallas import ctx_attention as ck

    if fused is False or not (on_tpu() or ck._INTERPRET):
        return False
    ok = ck.supports(q, cache_k_layer, ctx_tables)
    note_dispatch(
        "packed_ctx", ok, q.shape, interpret=ck._INTERPRET,
        reason="" if ok else "ctx_attention.supports() declined "
        "(VMEM budget / head_dim); dense gather body ran",
    )
    return ok


def _paged_attention_packed_ctx_local(
    q, k, v, segment_ids, cache_k_layer, cache_v_layer, ctx_tables, ctx_lens,
    scale=None, logits_soft_cap=None, fused=None,
):
    if _use_ctx_kernel(fused, q, cache_k_layer, ctx_tables):
        from ..ops.pallas import ctx_attention as ck

        return ck.paged_attention_packed_ctx_kernel(
            q, k, v, segment_ids, cache_k_layer, cache_v_layer, ctx_tables,
            ctx_lens, scale=scale, logits_soft_cap=logits_soft_cap,
        )
    return _paged_attention_packed_ctx_dense(
        q, k, v, segment_ids, cache_k_layer, cache_v_layer, ctx_tables,
        ctx_lens, scale=scale, logits_soft_cap=logits_soft_cap,
    )


def _packed_ctx_partial_local(
    q, k, v, segment_ids, cache_k_layer, cache_v_layer, ctx_tables, ctx_lens,
    include_pack, scale=None, logits_soft_cap=None, fused=None,
):
    if _use_ctx_kernel(fused, q, cache_k_layer, ctx_tables):
        from ..ops.pallas import ctx_attention as ck

        return ck.paged_attention_packed_ctx_kernel(
            q, k, v, segment_ids, cache_k_layer, cache_v_layer, ctx_tables,
            ctx_lens, scale=scale, logits_soft_cap=logits_soft_cap,
            include_pack=include_pack, partial=True,
        )
    return _packed_ctx_partial(
        q, k, v, segment_ids, cache_k_layer, cache_v_layer, ctx_tables,
        ctx_lens, include_pack, scale=scale, logits_soft_cap=logits_soft_cap,
    )


def _paged_attention_packed_ctx_tp(
    q, k, v, segment_ids, cache_k_layer, cache_v_layer, ctx_tables, ctx_lens,
    mesh, dp=1, seq_shards=1, scale=None, logits_soft_cap=None, fused=None,
):
    """Manual-region packed-ctx attention on the (batch, seq, model) serve
    mesh.

    Replica-locality contract (the engine's pack builder guarantees it):
    chunk ``r`` of the pack ([r*T/dp, (r+1)*T/dp)) holds only segments of
    replica ``r``'s slots, whose ctx rows are slots [r*N/dp, (r+1)*N/dp)
    and whose block ids live in [r*nb/dp, (r+1)*nb/dp).  Each replica then
    resolves its chunk entirely inside its local pool slice — block ids
    translate by the constant slice offset, slot rows by the slot-group
    offset — with no collective in the region at all (out rows shard the
    same way the chunk does).

    ``seq_shards > 1`` breaks that no-collective property on purpose: ctx
    pages stripe across the seq shards, so each shard flash-accumulates its
    locally-owned ctx keys (pack keys charged to seq shard 0 only) and the
    partials ring-merge over ``seq`` exactly like the decode region.
    """
    import functools

    from jax.sharding import PartitionSpec as P

    from ..comm import qcomm
    from ..parallel.sharding import shard_map_compat
    from ..parallel.topology import BATCH_AXIS, MODEL_AXIS, SEQ_AXIS

    tp = _model_axis_size(mesh)
    S = max(int(seq_shards), 1)
    t, hq, hd = q.shape
    hkv = cache_k_layer.shape[2]
    n = ctx_tables.shape[0]
    if tp > 1 and hq % tp != 0:
        raise ValueError(
            f"model axis ({tp}) must divide num_heads ({hq}) for TP serving"
        )
    if dp > 1 and (t % dp or n % dp):
        raise ValueError(
            f"batch axis ({dp}) must divide the pack length ({t}) and the "
            f"slot count ({n})"
        )
    if S > 1 and cache_k_layer.shape[0] % (dp * S) != 0:
        raise ValueError(
            f"batch x seq shards ({dp}x{S}) must divide the block pool "
            f"({cache_k_layer.shape[0]})"
        )
    kv_sharded = tp > 1 and hkv % tp == 0
    head_axis = MODEL_AXIS if tp > 1 else None
    kv_head_axis = MODEL_AXIS if kv_sharded else None
    batch_axis = BATCH_AXIS if dp > 1 else None
    block_axes = tuple(a for a, on in ((BATCH_AXIS, dp > 1),
                                       (SEQ_AXIS, S > 1)) if on)
    block_axis = (block_axes if len(block_axes) > 1
                  else (block_axes[0] if block_axes else None))
    q_spec = P(batch_axis, head_axis, None)
    pk_spec = P(batch_axis, kv_head_axis, None)
    pool_spec = P(block_axis, None, kv_head_axis, None)
    local = functools.partial(
        _paged_attention_packed_ctx_local, scale=scale,
        logits_soft_cap=logits_soft_cap, fused=fused,
    )
    rows_per = n // dp

    def narrow_kv(q_l, k_l, v_l, ck, cv):
        # replicated pool/pack kv (GQA, hkv % tp != 0): narrow both the
        # pool AND the pack's fresh kv to this shard's q heads' kv head(s)
        # so the local body sees an aligned GQA problem — the same
        # alignment paged_attention_decode's region performs
        if kv_sharded or tp == 1:
            return k_l, v_l, ck, cv
        hq_l = q_l.shape[1]
        i = jax.lax.axis_index(MODEL_AXIS)
        if tp % hkv == 0:
            k0 = i * hkv // tp
            return (jax.lax.dynamic_slice_in_dim(k_l, k0, 1, axis=1),
                    jax.lax.dynamic_slice_in_dim(v_l, k0, 1, axis=1),
                    jax.lax.dynamic_slice_in_dim(ck, k0, 1, axis=2),
                    jax.lax.dynamic_slice_in_dim(cv, k0, 1, axis=2))
        g_heads = i * hq_l + jnp.arange(hq_l)
        kv_ids = g_heads * hkv // hq
        return (jnp.take(k_l, kv_ids, axis=1), jnp.take(v_l, kv_ids, axis=1),
                jnp.take(ck, kv_ids, axis=2), jnp.take(cv, kv_ids, axis=2))

    def body(q_l, k_l, v_l, seg, ck, cv, bt, sl):
        if dp > 1 or S > 1:
            # block ids are global inside the owner shard's contiguous
            # range: translate by the local slice offset (same rule as the
            # decode region; -1 padding stays out of range, masked by
            # ctx_lens).  Under striping only the locally-owned ~1/S of a
            # row's pages land in [0, nb_local); the partial masks the rest.
            r = jax.lax.axis_index(BATCH_AXIS) if dp > 1 else 0
            s = jax.lax.axis_index(SEQ_AXIS) if S > 1 else 0
            bt = jnp.where(bt >= 0, bt - (r * S + s) * ck.shape[0], -1)
        if dp > 1:
            # segment ids are global slot+1; this replica's ctx rows start
            # at slot r * rows_per
            r = jax.lax.axis_index(BATCH_AXIS)
            seg = jnp.where(seg > 0, seg - r * rows_per, 0)
        k_l, v_l, ck, cv = narrow_kv(q_l, k_l, v_l, ck, cv)
        if S == 1:
            return local(q_l, k_l, v_l, seg, ck, cv, bt, sl)
        include_pack = jax.lax.axis_index(SEQ_AXIS) == 0
        acc, m, l = _packed_ctx_partial_local(
            q_l, k_l, v_l, seg, ck, cv, bt, sl, include_pack,
            scale=scale, logits_soft_cap=logits_soft_cap, fused=fused)
        mine = jnp.concatenate([acc, m[..., None], l[..., None]], axis=-1)
        c = mine
        # unrolled S-1 collective-permute hops, same carry as decode
        for _ in range(S - 1):
            c = qcomm.ring_permute(c, SEQ_AXIS, S)
            c = _lse_merge_packed(c, mine)
        out = c[..., :-2] / jnp.maximum(c[..., -1:], 1e-30)
        return out.astype(q_l.dtype)

    return shard_map_compat(
        body, mesh,
        in_specs=(q_spec, pk_spec, pk_spec, P(batch_axis), pool_spec,
                  pool_spec, P(batch_axis, None), P(batch_axis)),
        out_specs=q_spec,
    )(q, k, v, segment_ids, cache_k_layer, cache_v_layer, ctx_tables,
      ctx_lens)


def _paged_attention_packed_ctx_dense(
    q, k, v, segment_ids, cache_k_layer, cache_v_layer, ctx_tables, ctx_lens,
    scale=None, logits_soft_cap=None,
):
    """jnp reference body (single-shard): gathers up to P pages per segment,
    O(T * P * bs) logits.  When ``ctx_lens`` is concrete (eager / parity
    tests) the gathered page range clamps to ``ceil(max(ctx_lens)/bs)`` so
    the ground-truth path also scales with TRUE cached context rather than
    table capacity; under jit the lens are traced and P stays static."""
    t, hq, hd = q.shape
    nb, bs, hkv, _ = cache_k_layer.shape
    n, p = ctx_tables.shape
    if p > 1 and not isinstance(ctx_lens, jax.core.Tracer):
        p_live = int(-(-int(jnp.max(ctx_lens)) // bs))
        p = max(min(p, p_live), 1)
        ctx_tables = ctx_tables[:, :p]
    rep = hq // hkv
    scale = scale if scale is not None else float(hd) ** -0.5
    seg_row = jnp.clip(segment_ids - 1, 0, n - 1)  # [T] pack row per token

    safe = jnp.clip(ctx_tables, 0, nb - 1)
    ck = repeat_kv(cache_k_layer[safe].reshape(n, p * bs, hkv, hd), rep)
    cv = repeat_kv(cache_v_layer[safe].reshape(n, p * bs, hkv, hd), rep)
    ck_tok = jnp.take(ck, seg_row, axis=0)  # [T, Lc, hq, hd]
    cv_tok = jnp.take(cv, seg_row, axis=0)

    qf = q.astype(jnp.float32)
    logits_ctx = jnp.einsum("tqd,tkqd->tqk", qf, ck_tok.astype(jnp.float32))
    logits_ctx = logits_ctx * scale
    kp = repeat_kv(k[None], rep)[0].astype(jnp.float32)  # [T, hq, hd]
    vp = repeat_kv(v[None], rep)[0]
    logits_pack = jnp.einsum("tqd,kqd->tqk", qf, kp) * scale  # [T, hq, T]
    if logits_soft_cap is not None:
        logits_ctx = logits_soft_cap * jnp.tanh(logits_ctx / logits_soft_cap)
        logits_pack = logits_soft_cap * jnp.tanh(logits_pack / logits_soft_cap)

    neg = jnp.finfo(jnp.float32).min
    ctx_ok = (jnp.arange(p * bs)[None, :] < ctx_lens[seg_row][:, None]) \
        & (segment_ids > 0)[:, None]  # [T, Lc]
    logits_ctx = jnp.where(ctx_ok[:, None, :], logits_ctx, neg)
    # packed order == position order within each segment, so causality by
    # buffer index + segment equality is exact (same rule as prefill_packed)
    idx = jnp.arange(t)
    pack_ok = (idx[:, None] >= idx[None, :]) \
        & (segment_ids[:, None] == segment_ids[None, :])  # [T, T]
    logits_pack = jnp.where(pack_ok[:, None, :], logits_pack, neg)

    probs = jax.nn.softmax(
        jnp.concatenate([logits_ctx, logits_pack], axis=-1), axis=-1
    )
    pc, pp = probs[..., : p * bs], probs[..., p * bs:]
    out = jnp.einsum("tqk,tkqd->tqd", pc, cv_tok.astype(jnp.float32)) \
        + jnp.einsum("tqk,kqd->tqd", pp, vp.astype(jnp.float32))
    return out.astype(q.dtype)


def _packed_ctx_partial(
    q, k, v, segment_ids, cache_k_layer, cache_v_layer, ctx_tables, ctx_lens,
    include_pack, scale=None, logits_soft_cap=None,
):
    """Flash-style PARTIAL of the packed-ctx dense body over one seq
    shard's local pool slice.  ``ctx_tables`` carries locally-translated
    ids (out-of-range = another shard's page); ``include_pack`` (traced
    bool) gates the pack's fresh causal keys so exactly one shard charges
    them.  Returns fp32 ``(acc [T,hq,hd], m [T,hq], l [T,hq])``."""
    t, hq, hd = q.shape
    nb, bs, hkv, _ = cache_k_layer.shape
    n, p = ctx_tables.shape
    rep = hq // hkv
    scale = scale if scale is not None else float(hd) ** -0.5
    seg_row = jnp.clip(segment_ids - 1, 0, n - 1)  # [T] pack row per token

    owned = (ctx_tables >= 0) & (ctx_tables < nb)  # [N, P]
    safe = jnp.where(owned, ctx_tables, 0)
    ck = repeat_kv(cache_k_layer[safe].reshape(n, p * bs, hkv, hd), rep)
    cv = repeat_kv(cache_v_layer[safe].reshape(n, p * bs, hkv, hd), rep)
    ck_tok = jnp.take(ck, seg_row, axis=0)  # [T, Lc, hq, hd]
    cv_tok = jnp.take(cv, seg_row, axis=0)

    qf = q.astype(jnp.float32)
    logits_ctx = jnp.einsum("tqd,tkqd->tqk", qf, ck_tok.astype(jnp.float32))
    logits_ctx = logits_ctx * scale
    kp = repeat_kv(k[None], rep)[0].astype(jnp.float32)  # [T, hq, hd]
    vp = repeat_kv(v[None], rep)[0]
    logits_pack = jnp.einsum("tqd,kqd->tqk", qf, kp) * scale  # [T, hq, T]
    if logits_soft_cap is not None:
        logits_ctx = logits_soft_cap * jnp.tanh(logits_ctx / logits_soft_cap)
        logits_pack = logits_soft_cap * jnp.tanh(logits_pack / logits_soft_cap)

    own_tok = jnp.take(jnp.repeat(owned, bs, axis=1), seg_row, axis=0)
    ctx_ok = (jnp.arange(p * bs)[None, :] < ctx_lens[seg_row][:, None]) \
        & (segment_ids > 0)[:, None] & own_tok  # [T, Lc]
    idx = jnp.arange(t)
    pack_ok = (idx[:, None] >= idx[None, :]) \
        & (segment_ids[:, None] == segment_ids[None, :]) \
        & include_pack  # [T, T]
    logits_ctx = jnp.where(ctx_ok[:, None, :], logits_ctx, _NEG_INF)
    logits_pack = jnp.where(pack_ok[:, None, :], logits_pack, _NEG_INF)
    m = jnp.maximum(jnp.max(logits_ctx, axis=-1),
                    jnp.max(logits_pack, axis=-1))  # [T, hq]
    # keyless rows' exp(_NEG_INF - _NEG_INF) = 1 must not pollute l/acc
    wc = jnp.where(ctx_ok[:, None, :],
                   jnp.exp(logits_ctx - m[..., None]), 0.0)
    wp = jnp.where(pack_ok[:, None, :],
                   jnp.exp(logits_pack - m[..., None]), 0.0)
    l = jnp.sum(wc, axis=-1) + jnp.sum(wp, axis=-1)
    acc = jnp.einsum("tqk,tkqd->tqd", wc, cv_tok.astype(jnp.float32)) \
        + jnp.einsum("tqk,kqd->tqd", wp, vp.astype(jnp.float32))
    return acc, m, l


def paged_attention_decode(
    q, cache_k_layer, cache_v_layer, block_table, seq_lens, scale=None,
    logits_soft_cap=None, mesh=None, dp: int = 1, seq_shards: int = 1,
):
    """Single-token attention against paged KV.

    q [B, hq, hd]; cache_*_layer [num_blocks, bs, hkv, hd];
    block_table [B, P]; seq_lens [B] (length INCLUDING the current token; 0
    = no row in this slot: kernel and dense body both return finite zeros
    for it, and the kernel spends a scalar compare on it).
    ``logits_soft_cap`` applies cap*tanh(logits/cap) before masking, matching
    prefill's ``dot_product_attention`` (gemma-2 style).  Returns [B, hq, hd].

    Dispatches to the Pallas kernel (ops/pallas/paged_attention.py) on TPU —
    per-sequence page routing + length-bounded work; this jnp gather body is
    the fallback and ground truth (it reads all ``max_pages`` densely).

    With ``mesh`` (tensor-parallel serving — reference
    ``inference/v2/model_implementations/sharding/attn.py`` shards heads
    across the TP group): the call runs under ``shard_map`` on the ``model``
    axis, q split on query heads and the KV pool split on kv heads (kv
    replicated when hkv doesn't divide the axis).  A Pallas call cannot be
    partitioned by GSPMD — without the explicit map XLA would all-gather the
    whole block pool to every shard.

    ``dp > 1`` (the 2-D batch×model serve mesh): the region additionally
    shards the BATCH axis — slot rows of q/tables/lens and the BLOCK dim of
    the pool — and each replica translates its rows' global block ids into
    its local pool range (the engine's slot/block partitioning guarantees a
    replica's sequences only ever hold blocks from its own range).

    ``seq_shards > 1`` (long-context serving, the 3-D batch×seq×model
    mesh): the pool's block dim subdivides further over ``seq`` and a
    sequence's pages STRIPE across the seq shards (the allocator
    round-robins them), so no single shard needs to hold a whole context.
    Each shard computes a flash-style PARTIAL (running max / sum-exp /
    weighted-V accumulator) against only its locally-owned pages, then the
    partials combine with a log-sum-exp ring pass — ``S-1``
    ``collective_permute`` hops of the packed ``[B, hq, hd+2]`` accumulator
    (``comm.qcomm.ring_permute``), each hop's merge overlappable with the
    neighbour's in-flight send.  Every shard converges to the identical
    full softmax, so the output stays replicated over ``seq``.
    """
    if mesh is not None and (_model_axis_size(mesh) > 1 or dp > 1
                             or seq_shards > 1):
        return _paged_attention_decode_tp(
            q, cache_k_layer, cache_v_layer, block_table, seq_lens, mesh,
            dp=dp, seq_shards=seq_shards, scale=scale,
            logits_soft_cap=logits_soft_cap,
        )
    return _paged_attention_decode_local(
        q, cache_k_layer, cache_v_layer, block_table, seq_lens, scale=scale,
        logits_soft_cap=logits_soft_cap,
    )


def _paged_attention_decode_local(
    q, cache_k_layer, cache_v_layer, block_table, seq_lens, scale=None,
    logits_soft_cap=None,
):
    from ..ops.pallas import note_dispatch, on_tpu
    from ..ops.pallas import paged_attention as pk

    if on_tpu() or pk._INTERPRET:
        ok = pk.supports(q, cache_k_layer, logits_soft_cap)
        note_dispatch(
            "paged_decode", ok, q.shape, interpret=pk._INTERPRET,
            reason="" if ok else "paged_attention.supports() declined "
            "(soft cap / head_dim alignment / VMEM); dense gather body ran",
            tile_keys=pk.tile_keys(cache_k_layer, block_table) if ok else None,
        )
        if ok:
            return pk.paged_attention_decode_kernel(
                q, cache_k_layer, cache_v_layer, block_table, seq_lens,
                scale=scale,
            )
    return _paged_attention_decode_dense(
        q, cache_k_layer, cache_v_layer, block_table, seq_lens, scale=scale,
        logits_soft_cap=logits_soft_cap,
    )


def _model_axis_size(mesh) -> int:
    from ..parallel.topology import MODEL_AXIS

    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(MODEL_AXIS, 1)


def kv_pool_pspec(num_kv_heads: int, tp: int, dp: int = 1,
                  seq_shards: int = 1):
    """PartitionSpec for a per-layer [nb, bs, hkv, hd] block pool: kv heads
    shard on ``model`` when divisible, otherwise the pool replicates (GQA,
    hkv < tp).  ``dp > 1`` (batch×model serve mesh) additionally shards the
    BLOCK dim over ``batch`` — each serving replica owns a contiguous block
    range, so pool capacity scales with the batch axis.  ``seq_shards > 1``
    (long-context serving) splits the block dim FURTHER over ``seq``,
    batch-major: replica ``r``'s contiguous range subdivides into ``S``
    contiguous seq-shard slices, so global block ``b`` is owned by linear
    shard ``(b // (nb // (dp*S)))`` = ``r*S + s`` — the layout the in-region
    block-id translation and the allocator's striping both assume."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.topology import BATCH_AXIS, MODEL_AXIS, SEQ_AXIS

    head_axis = MODEL_AXIS if (tp > 1 and num_kv_heads % tp == 0) else None
    block_axes = tuple(
        a for a, on in ((BATCH_AXIS, dp > 1), (SEQ_AXIS, seq_shards > 1))
        if on)
    block_axis = (block_axes if len(block_axes) > 1
                  else (block_axes[0] if block_axes else None))
    # per-LAYER pool arrays [nb, bs, hkv, hd] (init_paged_cache)
    return P(block_axis, None, head_axis, None)


def _lse_merge_packed(a, b):
    """Log-sum-exp combine of two packed flash partials ``[..., hd+2]``
    (``concat([acc, m, l], -1)`` — weighted-V accumulator, running max,
    running sum-exp).  Commutative, so a 2-shard ring converges bit-
    identically on both ranks; rows with NO keys anywhere stay (0, -1e30,
    0) and are resolved by the final denominator clamp."""
    acc_a, m_a, l_a = a[..., :-2], a[..., -2], a[..., -1]
    acc_b, m_b, l_b = b[..., :-2], b[..., -2], b[..., -1]
    m = jnp.maximum(m_a, m_b)
    wa = jnp.exp(m_a - m)
    wb = jnp.exp(m_b - m)
    acc = acc_a * wa[..., None] + acc_b * wb[..., None]
    l = l_a * wa + l_b * wb
    return jnp.concatenate([acc, m[..., None], l[..., None]], axis=-1)


def _paged_attention_decode_partial(
    q, cache_k_layer, cache_v_layer, block_table, seq_lens, scale=None,
    logits_soft_cap=None,
):
    """Flash-style PARTIAL of the dense decode body over one seq shard's
    local pool slice: ``block_table`` carries locally-translated ids where
    entries outside ``[0, nb)`` mark pages another shard owns.  Returns
    fp32 ``(acc [B,hq,hd], m [B,hq], l [B,hq])`` — merging the S partials
    with :func:`_lse_merge_packed` reproduces the full softmax exactly."""
    b, hq, hd = q.shape
    nb, bs, hkv, _ = cache_k_layer.shape
    p = block_table.shape[1]
    owned = (block_table >= 0) & (block_table < nb)  # [B, P]
    safe = jnp.where(owned, block_table, 0)
    k = cache_k_layer[safe].reshape(b, p * bs, hkv, hd)
    v = cache_v_layer[safe].reshape(b, p * bs, hkv, hd)
    k = repeat_kv(k, hq // hkv)
    v = repeat_kv(v, hq // hkv)
    scale = scale if scale is not None else float(hd) ** -0.5
    logits = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if logits_soft_cap is not None:
        logits = logits_soft_cap * jnp.tanh(logits / logits_soft_cap)
    key_ok = (jnp.arange(p * bs)[None, :] < seq_lens[:, None]) \
        & jnp.repeat(owned, bs, axis=1)  # [B, p*bs]
    logits = jnp.where(key_ok[:, None, :], logits, _NEG_INF)
    m = jnp.max(logits, axis=-1)  # [B, hq]; _NEG_INF when no local keys
    w = jnp.exp(logits - m[..., None])
    # a keyless row's exp(_NEG_INF - _NEG_INF) = 1 must not pollute l/acc
    w = jnp.where(key_ok[:, None, :], w, 0.0)
    l = jnp.sum(w, axis=-1)
    acc = jnp.einsum("bhk,bkhd->bhd", w, v.astype(jnp.float32))
    return acc, m, l


def _paged_attention_decode_tp(
    q, cache_k_layer, cache_v_layer, block_table, seq_lens, mesh, dp=1,
    seq_shards=1, scale=None, logits_soft_cap=None,
):
    import functools

    from jax.sharding import PartitionSpec as P

    from ..comm import qcomm
    from ..parallel.sharding import shard_map_compat
    from ..parallel.topology import BATCH_AXIS, MODEL_AXIS, SEQ_AXIS

    tp = _model_axis_size(mesh)
    S = max(int(seq_shards), 1)
    b, hq, hd = q.shape
    hkv = cache_k_layer.shape[2]
    if tp > 1 and hq % tp != 0:
        raise ValueError(
            f"model axis ({tp}) must divide num_heads ({hq}) for TP serving"
        )
    if dp > 1 and b % dp != 0:
        raise ValueError(
            f"batch axis ({dp}) must divide the slot count ({b})"
        )
    if S > 1 and cache_k_layer.shape[0] % (dp * S) != 0:
        raise ValueError(
            f"batch x seq shards ({dp}x{S}) must divide the block pool "
            f"({cache_k_layer.shape[0]})"
        )
    kv_sharded = tp > 1 and hkv % tp == 0
    kv_head_axis = MODEL_AXIS if kv_sharded else None
    head_axis = MODEL_AXIS if tp > 1 else None
    batch_axis = BATCH_AXIS if dp > 1 else None
    block_axes = tuple(a for a, on in ((BATCH_AXIS, dp > 1),
                                       (SEQ_AXIS, S > 1)) if on)
    block_axis = (block_axes if len(block_axes) > 1
                  else (block_axes[0] if block_axes else None))
    q_spec = P(batch_axis, head_axis, None)
    kv_spec = P(block_axis, None, kv_head_axis, None)
    local = functools.partial(
        _paged_attention_decode_local, scale=scale, logits_soft_cap=logits_soft_cap
    )

    def narrow_kv(q_l, ck, cv):
        # replicated pool (hkv < tp): each shard narrows the pool to its
        # q heads' kv head(s) so the local body sees an aligned GQA
        # problem — repeat_kv(hq_local // hkv) would be 0 when
        # hkv > hq_local.  (A block-dim-sharded flash-decoding split
        # would avoid the pool copy entirely; head narrowing keeps the
        # paged kernel's per-page DMA untouched.)
        if kv_sharded or tp == 1:
            # hq/hkv is integral, so the kv heads of q shard i are exactly
            # kv shard i — local GQA ratio preserved, no gather needed
            return ck, cv
        hq_l = q_l.shape[1]
        i = jax.lax.axis_index(MODEL_AXIS)
        if tp % hkv == 0:
            # shard chunks nest inside kv groups: exactly ONE kv head per
            # shard — one contiguous O(pool/hkv) slice, not a full-pool
            # gather
            k0 = i * hkv // tp
            return (jax.lax.dynamic_slice_in_dim(ck, k0, 1, axis=2),
                    jax.lax.dynamic_slice_in_dim(cv, k0, 1, axis=2))
        g_heads = i * hq_l + jnp.arange(hq_l)
        kv_ids = g_heads * hkv // hq
        return (jnp.take(ck, kv_ids, axis=2), jnp.take(cv, kv_ids, axis=2))

    def body(q_l, ck, cv, bt, sl):
        if dp > 1 or S > 1:
            # each shard's local pool slice starts at (r*S + s) * nb_local
            # of the global (batch-major) block range, so a table row's
            # GLOBAL block ids translate by a constant offset.  Under dp
            # the allocator's replica affinity guarantees every id lands
            # in-range; under seq striping only ~1/S of a row's pages do —
            # the rest fall outside [0, nb_local) and the partial masks
            # them as another shard's work.  -1 padding stays out of range
            # either way.
            r = jax.lax.axis_index(BATCH_AXIS) if dp > 1 else 0
            s = jax.lax.axis_index(SEQ_AXIS) if S > 1 else 0
            bt = jnp.where(bt >= 0, bt - (r * S + s) * ck.shape[0], -1)
        ck, cv = narrow_kv(q_l, ck, cv)
        if S == 1:
            return local(q_l, ck, cv, bt, sl)
        acc, m, l = _paged_attention_decode_partial(
            q_l, ck, cv, bt, sl, scale=scale,
            logits_soft_cap=logits_soft_cap)
        mine = jnp.concatenate([acc, m[..., None], l[..., None]], axis=-1)
        c = mine
        # log-sum-exp ring: a PYTHON loop, not a scan, so the compiled
        # module carries exactly S-1 collective-permute hops per layer (the
        # HLO auditor counts them) and XLA can overlap each hop's send with
        # the resident merge.  Carry: the packed [B, hq_l, hd+2] partial.
        for _ in range(S - 1):
            c = qcomm.ring_permute(c, SEQ_AXIS, S)
            c = _lse_merge_packed(c, mine)
        out = c[..., :-2] / jnp.maximum(c[..., -1:], 1e-30)
        return out.astype(q_l.dtype)

    return shard_map_compat(
        body, mesh,
        in_specs=(q_spec, kv_spec, kv_spec, P(batch_axis, None), P(batch_axis)),
        out_specs=q_spec,
    )(q, cache_k_layer, cache_v_layer, block_table, seq_lens)


def _paged_attention_decode_dense(
    q, cache_k_layer, cache_v_layer, block_table, seq_lens, scale=None,
    logits_soft_cap=None,
):
    """jnp reference body: gathers every table entry (O(max_pages))."""
    b, hq, hd = q.shape
    nb, bs, hkv, _ = cache_k_layer.shape
    p = block_table.shape[1]
    safe = jnp.clip(block_table, 0, nb - 1)
    k = cache_k_layer[safe].reshape(b, p * bs, hkv, hd)
    v = cache_v_layer[safe].reshape(b, p * bs, hkv, hd)
    k = repeat_kv(k, hq // hkv)
    v = repeat_kv(v, hq // hkv)
    scale = scale if scale is not None else float(hd) ** -0.5
    logits = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if logits_soft_cap is not None:
        logits = logits_soft_cap * jnp.tanh(logits / logits_soft_cap)
    mask = jnp.arange(p * bs)[None, :] < seq_lens[:, None]
    logits = jnp.where(mask[:, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhk,bkhd->bhd", probs, v.astype(jnp.float32))
    # a row of length 0 is no row (the kernel's contract): finite zeros,
    # whatever the pages its table clips to hold
    out = jnp.where((seq_lens > 0)[:, None, None], out, 0.0)
    return out.astype(q.dtype)
