"""Inference model runner for dense GQA models: the prefill packs, the
speculative verify pack and the batched decode tick against the paged cache.

The analogue of the reference's per-family inference model implementations
(``inference/v2/model_implementations/llama_v2`` etc.), but one generic
runner covers every ``TransformerConfig`` family: architecture switches live
in the config.  It reuses the training model's norm / rope with its own
attention wiring.

The decoder block is written ONCE (``_layer``) and looped over ONCE
(``_layers``).  An entry is what differs and nothing else: how its tokens are
embedded and positioned (``[1, T, d]`` for a pack, ``[B, 1, d]`` for the
tick), how a layer's new K/V rows are written into its pools (``write``),
which attention reads them (``read``), and which rows go to the head.
A pack that CARRIES THE TICK'S STEP (``step=``) is the same loop over
``[1, T + B, d]``: the pack's T token rows and the step's B slot rows go
through every norm, projection, MLP and the head as one operand, so a weight
crosses HBM once a tick, and the seam alone is per kind (``_carrying``: each
kind of row keeps its own write and its own attention kernel).
``latent_runner.py`` has the same shape for ``cfg.latent``; the engine picks
one of the two (``DenseRunner`` here, ``LatentRunner`` there) once.

Every entry takes ``params`` (the STACKED tree), ``kv_cache`` ((K pools, V
pools), per-layer tuples), ``ctx`` (ops.quantizer ``ServingContext``: the TP /
fused serving policy), ``mesh`` (the serve mesh: the attention kernels run per
shard under it, see paged.py), ``dp`` (batch-axis replicas: a pack arrives as
``dp`` chunks, rows in slot order), ``seq_shards`` (seq-axis pool slices).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from ..models.transformer import (
    TransformerConfig,
    _activation,
    head_bias_vec,
    head_kernel,
    norm,
    rope,
)
from ..ops.pallas.flash_attention import flash_attention
from ..ops.quantizer import serving_mm
from .paged import (
    init_paged_cache,
    paged_attention_decode,
    paged_attention_packed_ctx,
    write_decode_kv,
    write_pack_kv,
    write_spec_kv,
)

def _qkv(lw, x, cfg: TransformerConfig, ctx=None):
    b, s, d = x.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    # serving_mm: transparent over quantized-weight serving (ServingQuant);
    # biases ride the call so the fused dequant-matmul kernel folds them
    # into its fp32 epilogue (on the jnp body they add post-cast, exactly
    # as before).  Under a TP mesh (``ctx``) q/k/v are column-parallel —
    # out-features (whole heads) sharded on the model axis, no collective —
    # except that wk/wv stay replicated compute ('rep') when the kv-head
    # count doesn't divide the axis (GQA, hkv < tp): sub-head sharding is
    # never produced, matching the replicated KV pool in that regime.
    kv_kind = "col" if (ctx is None or ctx.kv_cols) else "rep"
    q = serving_mm(x, lw["wq"], lw.get("bq") if cfg.qkv_bias else None,
                   kind="col", ctx=ctx)
    k = serving_mm(x, lw["wk"], lw.get("bk") if cfg.qkv_bias else None,
                   kind=kv_kind, ctx=ctx)
    v = serving_mm(x, lw["wv"], lw.get("bv") if cfg.qkv_bias else None,
                   kind=kv_kind, ctx=ctx)
    # The projections' results exist as [rows, features] before they are
    # split into heads.  XLA:TPU otherwise folds the reshape into the dot and
    # wants the weight as [heads, hd, d]: under (8, 128) tiling that is no
    # bitcast of the stored [d, heads * hd], so EVERY call of every serving
    # program re-laid wq / wk / wv of every layer (a slice + a transposing
    # copy each: ~4 ms of an 18 ms decode program at Mistral-7B widths,
    # PERF.md §6, PR 30).  Behind the barrier the stacks are read in place,
    # as wo and the MLP stacks (no reshape after their dots) always were.
    q, k, v = jax.lax.optimization_barrier((q, k, v))
    return (
        q.reshape(b, s, hq, hd),
        k.reshape(b, s, hkv, hd),
        v.reshape(b, s, hkv, hd),
    )


def _ffn(lw, x, cfg, ctx=None):
    if cfg.moe_num_experts > 0:
        # dropless at inference: capacity competition would make routing
        # depend on batch padding (moe/layer.py moe_block_dropless)
        from ..moe.layer import moe_block_dropless

        out, _ = moe_block_dropless(lw["moe"], x, cfg)
        return out
    mlp = lw["mlp"]
    act = _activation(cfg.activation)
    # gpt2/opt/phi-style biased MLP: biases fuse into the serving matmul.
    # TP placement is the Megatron pair: up/gate column-parallel (sharded
    # activations feed the elementwise gate locally), down row-parallel
    # (one psum on the partial products, bias added once post-reduce).
    up = serving_mm(x, mlp["w_up"], mlp.get("b_up"), kind="col", ctx=ctx)
    if cfg.gated_mlp:
        gate = serving_mm(x, mlp["w_gate"], mlp.get("b_gate"), kind="col",
                          ctx=ctx)
        h = act(gate) * up
    else:
        h = act(up)
    return serving_mm(h, mlp["w_down"], mlp.get("b_down"), kind="row", ctx=ctx)


def _attn_out(lw, x, ctx=None):
    """o-projection (+ bias when the family carries one).  Row-parallel
    under TP: the head-sharded attention output is exactly the in-feature
    sharding the region wants — qkv->attention->o costs ONE psum total."""
    return serving_mm(x, lw["wo"], lw.get("bo"), kind="row", ctx=ctx)


def _lm_logits(params, cfg, x, ctx=None):
    """Final head (+ gptj/phi lm_head bias) in fp32.  Vocab-sharded
    column-parallel under TP; the consumer (sampling argmax / gather)
    decides whether GSPMD materializes the full-vocab row."""
    logits = serving_mm(x, head_kernel(params, cfg), head_bias_vec(params),
                        kind="col", ctx=ctx)
    return logits.astype(jnp.float32)


def _embed(params, cfg, tokens, positions, axis: int):
    """Token embeddings (+ learned positions, + bloom's ``embedding_norm``),
    the rows' new axis at ``axis``: [1, T, d] for a pack, [B, 1, d] for the
    tick (a reshape near a dot moves XLA:TPU's layouts: PERF.md §6, PR 30)."""
    rows = lambda a: jnp.expand_dims(a, axis)
    x = rows(params["embed"]["embedding"][tokens]).astype(cfg.dtype)
    if cfg.position == "learned":
        x = x + rows(params["pos_embed"]["embedding"][
            jnp.clip(positions, 0, cfg.max_seq_len - 1)
        ]).astype(cfg.dtype)
    if cfg.embedding_norm:
        x = norm(x, params["embed_norm"], cfg.norm, cfg.norm_eps)
    return x


def _dense_only(cfg, what: str, why: str = "latent_runner.py serves it"):
    """``cfg.latent`` is refused here, never dispatched on: which runner
    serves a model is the engine's choice."""
    if cfg.latent is not None:
        from ..models.latent import refuse

        refuse(what, why)


def _layer(cfg, lw, x, positions, kv_l, write, read, ctx):
    """One decoder block on ``x`` ([1, T, d] or [B, 1, d]).  The seam:
    ``write(kv_l, k, v)`` returns the layer's (K pool, V pool) with the new
    rows in, ``read(q, k, v, kv_l)`` attends (K/V are written BEFORE the
    attention reads the post-write pools).  Returns (x, kv_l)."""
    h = norm(x, lw["attn_norm"], cfg.norm, cfg.norm_eps)
    q, k, v = _qkv(lw["attn"], h, cfg, ctx)
    if cfg.position == "rope":
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    kv_l = write(kv_l, k, v)
    attn = read(q, k, v, kv_l)
    attn = _attn_out(lw["attn"], attn.reshape(*x.shape[:2], -1), ctx)
    x = x + attn.astype(x.dtype)
    h = norm(x, lw["mlp_norm"], cfg.norm, cfg.norm_eps)
    return x + _ffn(lw, h, cfg, ctx).astype(x.dtype), kv_l


def _layers(params, cfg, x, positions, kv_cache, write, read, ctx):
    """Every layer and the final norm: a python loop (L is static) over the
    STACKED parameter tree; the KV pools are per-layer tuples, so an update
    replaces one layer's buffer in place under donation, never a slice copy."""
    new_ck, new_cv = map(list, kv_cache)
    for l in range(cfg.num_layers):
        lw = jax.tree_util.tree_map(lambda a: a[l], params["layers"])
        x, (new_ck[l], new_cv[l]) = _layer(
            cfg, lw, x, positions, (new_ck[l], new_cv[l]), write, read, ctx)
    x = norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    return x, (tuple(new_ck), tuple(new_cv))


def _read_ctx(cfg, segment_ids, ctx_tables, ctx_lens, ctx, mesh, dp, seq_shards):
    """A pack's attention over [cached context | in-pack causal segment],
    shared by chunked prefill and verify.  Context positions (< ctx_lens) read
    the pools; the pack's own fresh rows are masked out by ctx_lens and enter
    through the in-pack half, so the post-write pools are safe to pass."""
    return lambda q, k, v, kv_l: paged_attention_packed_ctx(
        q[0], k[0], v[0], segment_ids, *kv_l, ctx_tables, ctx_lens,
        logits_soft_cap=cfg.logits_soft_cap, mesh=mesh, dp=dp,
        seq_shards=seq_shards, ctx=ctx)


def _write_pages(pack_pages, kv_cache):
    """A page-aligned pack's K/V rows land page by page (``write_pack_kv``);
    padding chunks go to the out-of-bounds sentinel, found once a pack."""
    pages = jnp.where(pack_pages >= 0, pack_pages, kv_cache[0][0].shape[0])
    return lambda kv_l, k, v: (write_pack_kv(kv_l[0], k[0], pages),
                               write_pack_kv(kv_l[1], v[0], pages))


def _step_seam(cfg, seq_lens, block_tables, active, mesh, dp, seq_shards, rows):
    """A decode step's (write, read): one new K/V row a live slot, then the
    paged kernel over each slot's pages.  ``rows`` takes the ``[B, heads, hd]``
    rows out of the entry's layout (``[B, 1, ...]`` for the tick, ``[1, B,
    ...]`` behind a pack)."""
    def write(kv_l, k, v):
        return (write_decode_kv(kv_l[0], rows(k), block_tables, seq_lens, active),
                write_decode_kv(kv_l[1], rows(v), block_tables, seq_lens, active))

    def read(q, k, v, kv_l):
        # length 0 = no row in this slot: the kernel skips it
        return paged_attention_decode(
            rows(q), *kv_l, block_tables, jnp.where(active, seq_lens + 1, 0),
            logits_soft_cap=cfg.logits_soft_cap, mesh=mesh, dp=dp,
            seq_shards=seq_shards)

    return write, read


def _carrying(t, pack, step):
    """The seam of a pack that carries the tick's step: rows ``[:t]`` are the
    pack's, rows ``[t:]`` the step's slots, ``pack`` and ``step`` their
    (write, read).  The two kinds hold disjoint sequences (the scheduler's
    contract), hence disjoint pages to write; each attends with the kernel it
    has alone."""
    def write(kv_l, k, v):
        kv_l = pack[0](kv_l, k[:, :t], v[:, :t])
        return step[0](kv_l, k[:, t:], v[:, t:])

    def read(q, k, v, kv_l):
        # ([rows, heads * hd]: a flash pack attends as [1, T, ...], a context
        # pack and the step as [rows, ...]; ``_layer`` reshapes either)
        return jnp.concatenate(
            [pack[1](q[:, :t], k[:, :t], v[:, :t], kv_l).reshape(t, -1),
             step[1](q[:, t:], k[:, t:], v[:, t:], kv_l).reshape(q.shape[1] - t, -1)])

    return write, read


def _pack(params, cfg, tokens, positions, last_idx, kv_cache, seam, step, ctx,
          mesh, dp=1, seq_shards=1):
    """What the packs share: embed, every layer through the pack's ``seam``
    (its write and read), the head over each prompt's last row.  With ``step`` (the tick's decode rows: tokens, KV positions,
    block tables, live mask, each a row a slot) the step's B rows follow the
    pack's T through the same layers and ONE head matmul scores both:
    ((pack logits [N, v], step logits [B, v]), new caches)."""
    t = tokens.shape[0]
    if step is not None:
        s_tokens, s_lens, s_tables, s_active = step
        tokens = jnp.concatenate([tokens, s_tokens])
        positions = jnp.concatenate([positions, s_lens])
        seam = _carrying(t, seam, _step_seam(
            cfg, s_lens, s_tables, s_active, mesh, dp, seq_shards, lambda a: a[0]))
    x = _embed(params, cfg, tokens, positions, 0)  # [1,T(+B),d]
    x, kv = _layers(params, cfg, x, positions[None], kv_cache, *seam, ctx)
    last = x[0, jnp.clip(last_idx, 0, t - 1)]  # [N, d]
    if step is None:
        return _lm_logits(params, cfg, last, ctx), kv  # [N, v]
    logits = _lm_logits(params, cfg, jnp.concatenate([last, x[0, t:]]), ctx)
    return (logits[:last.shape[0]], logits[last.shape[0]:]), kv


def prefill_packed(
    params, cfg: TransformerConfig,
    tokens,  # [T] int32 — prompts packed at PAGE-aligned starts
    segment_ids,  # [T] int32 — 1-based per prompt, 0 = padding
    positions,  # [T] int32 — per-token position within its prompt
    pack_pages,  # [T/bs] int32 — destination page per bs-chunk (-1 pad)
    last_idx,  # [N] int32 — buffer index of each prompt's last token (-1 pad)
    kv_cache, ctx=None, mesh=None,
    step=None,  # the tick's decode rows, as ``decode_step`` takes them:
    # (tokens [B], seq_lens [B], block_tables [B, P], active [B])
):
    """Batched multi-prompt prefill under one token budget (the Dynamic
    SplitFuse-shaped dispatch; reference ``inference/v2/ragged/
    ragged_wrapper.py`` builds the same packed view as 'atoms'): one dense
    causal pass, cross-prompt attention blocked by ``segment_ids``.  Every
    prompt starts at a PAGE boundary of the pack (the engine pads with
    segment-0 gaps), so KV lands page by page (``_write_pages``).  Returns
    (logits [N, vocab], new caches); with ``step`` the tick's decode rows ride
    the same stream of the weights (``_pack``) and the logits are a pair."""
    _dense_only(cfg, "model_runner.prefill_packed")
    seg = segment_ids[None]  # [1, T]

    def read(q, k, v, kv_l):
        # packed order == position order within each segment, so causal
        # masking by buffer index + segment masking is exact; the flash
        # kernel takes packed segments natively (per-block int32 tiles)
        return flash_attention(q, k, v, causal=True, segment_ids=seg,
                               logits_soft_cap=cfg.logits_soft_cap, mesh=mesh)

    return _pack(params, cfg, tokens, positions, last_idx, kv_cache,
                 (_write_pages(pack_pages, kv_cache), read), step, ctx, mesh)


def prefill_packed_ctx(
    params, cfg: TransformerConfig,
    tokens, segment_ids,  # as prefill_packed's: suffix tokens, 1-based segments
    positions,  # [T] int32 — ABSOLUTE position (start offset baked in)
    pack_pages, last_idx,  # as prefill_packed's
    ctx_tables,  # [N, P] int32 — block table per segment (-1 pad)
    ctx_lens,  # [N] int32 — cached-context length per segment
    kv_cache, ctx=None, mesh=None, dp: int = 1, seq_shards: int = 1,
    step=None,  # as prefill_packed's
):
    """``prefill_packed`` generalized to token SUFFIXES: each segment starts
    at a per-sequence offset (``ctx_lens``) and attends over its cached pages
    (``ctx_tables``) below the offset plus the causal in-pack segment.  Both
    prefix-cache-hit prefill and chunked prefill ride on it; a no-context
    pack computes what ``prefill_packed`` does (the engine dispatches there
    for speed).  Returns (logits [N, vocab], new caches); a ``last_idx`` row
    of -1 (mid-chunk) yields garbage logits the engine never consumes."""
    _dense_only(cfg, "model_runner.prefill_packed_ctx")
    read = _read_ctx(cfg, segment_ids, ctx_tables, ctx_lens, ctx, mesh, dp, seq_shards)
    return _pack(params, cfg, tokens, positions, last_idx, kv_cache,
                 (_write_pages(pack_pages, kv_cache), read), step, ctx, mesh,
                 dp, seq_shards)


def verify_packed_ctx(
    params, cfg: TransformerConfig,
    tokens,  # [T] int32 — per slot: [last committed, d_0..d_{k-1}], padded
    segment_ids,  # [T] int32 — slot+1 per valid token, 0 = padding
    positions,  # [T] int32 — ABSOLUTE position of each token
    dst_pages, dst_offs,  # [T] int32 — KV destination per token: page (-1 pad), row
    ctx_tables,  # [N, P] int32 — block table per slot (-1 pad)
    ctx_lens,  # [N] int32 — committed (KV-written) length per slot
    kv_cache, ctx=None, mesh=None, dp: int = 1, seq_shards: int = 1,
):
    """Speculative-decode verify: score k+1 positions per sequence in ONE
    pass (one weight read serves up to k+1 emitted tokens).  A sequence's
    segment is [its last committed token, then its k draft tokens] at
    consecutive absolute positions; attention is chunked prefill's: a draft
    attends over the cached pages plus the drafts before it.  KV writes are
    per-ROW scatters (``write_spec_kv``: the pack starts mid-page); rejected
    drafts leave garbage KV past the accepted length, masked by sequence
    length everywhere, overwritten as the sequence grows (the ``step_n``
    rule), their tail BLOCKS freed by the allocator's truncate path.  Returns
    (logits [T, v], new caches): logits for ALL T pack rows, each verifying
    the next draft or sampling the correction / bonus token; the fp32 buffer
    is small at T = max_seqs * (k+1)."""
    _dense_only(cfg, "enable_speculation (verify_packed_ctx)", "a rejected "
                "draft's rows cannot be rolled back out of a sliding layer's ring")
    x = _embed(params, cfg, tokens, positions, 0)  # [1,T,d]

    def write(kv_l, k, v):
        return (write_spec_kv(kv_l[0], k[0], dst_pages, dst_offs),
                write_spec_kv(kv_l[1], v[0], dst_pages, dst_offs))

    read = _read_ctx(cfg, segment_ids, ctx_tables, ctx_lens, ctx, mesh, dp, seq_shards)
    x, kv = _layers(params, cfg, x, positions[None], kv_cache, write, read, ctx)
    return _lm_logits(params, cfg, x[0], ctx), kv  # [T, v]


def decode_step(
    params, cfg: TransformerConfig,
    tokens,  # [B] int32 — last sampled token per slot
    seq_lens,  # [B] int32 — length BEFORE this token
    block_tables,  # [B, P] int32
    active,  # [B] bool
    kv_cache, ctx=None, mesh=None, dp: int = 1, seq_shards: int = 1,
):
    """One batched decode tick: returns (logits [B, v], new caches)."""
    _dense_only(cfg, "model_runner.decode_step")
    x = _embed(params, cfg, tokens, seq_lens, 1)  # [B,1,d]
    write, read = _step_seam(cfg, seq_lens, block_tables, active, mesh, dp,
                             seq_shards, lambda a: a[:, 0])
    x, kv = _layers(params, cfg, x, seq_lens[:, None], kv_cache, write, read, ctx)
    return _lm_logits(params, cfg, x[:, 0], ctx), kv


class DenseRunner:
    """What ``InferenceEngineV2`` asks of the runner it picked, answered for
    a dense model; ``latent_runner.LatentRunner`` answers the same for
    ``cfg.latent``: the cache, the four device entries, and the kind's HOST
    accounting, which is empty for pages the allocator already audits."""

    counters = ()  # ``stats`` keys this kind adds
    packs_are_one_program = False  # a cold pack has a program of its own
    packs_carry_step = True  # a pack takes the tick's decode rows (``step=``)
    scoped_programs = False  # no named scope a trace reader looks up
    prefill_packed = staticmethod(prefill_packed)
    prefill_packed_ctx = staticmethod(prefill_packed_ctx)
    verify_packed_ctx = staticmethod(verify_packed_ctx)
    decode_step = staticmethod(decode_step)

    def __init__(self, cfg: TransformerConfig):
        self.cfg = cfg

    def init_cache(self, num_blocks, block_size, max_seqs, pack_tokens):
        cfg = self.cfg
        return init_paged_cache(cfg.num_layers, num_blocks, block_size,
                                cfg.num_kv_heads, cfg.hd, dtype=cfg.dtype)

    def dispatched(self, counters, work, pack: bool = False, tokens: int = 0,
                   carried: int = 0) -> Dict[str, int]:
        """Counts a dispatch (a prefill ``pack``, else a decode tick) over
        ``work`` = (slot, start, end) a sequence, in a program of ``tokens`` token
        rows (and ``carried`` slot rows of a step behind a pack's), into
        ``counters``; returns the dispatch span's extra arguments."""
        return {}

    def released(self, seq) -> None:
        """``seq`` let go of its slot."""

    def audit(self) -> Dict[str, int]:
        """State a sequence still owns, for ``close()``."""
        return {}

    def refresh_stats(self, counters, kv) -> None:
        """Device-side counts of ``kv`` into ``counters``."""
