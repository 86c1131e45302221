"""Inference model runner: prefill + batched decode against the paged cache.

The analogue of the reference's per-family inference model implementations
(``inference/v2/model_implementations/llama_v2`` etc.) — but one generic
runner covers every ``TransformerConfig`` family, because architecture
switches live in the config, not in code.  Reuses the training model's
building blocks (norm / rope / mlp_block / moe_block) with its own attention
wiring, mirroring how the reference keeps training and inference model code
separate (module_inject containers vs training nn.Modules).
"""
from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from ..models.transformer import (
    TransformerConfig,
    _activation,
    head_bias_vec,
    head_kernel,
    mlp_block,
    norm,
    rope,
)
from ..ops.pallas.flash_attention import flash_attention
from ..ops.quantizer import serving_mm
from .paged import (
    paged_attention_decode,
    paged_attention_packed_ctx,
    write_decode_kv,
    write_prefill_kv,
    write_spec_kv,
)

Params = Any


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


def _latent_only(ctx, mesh, dp: int = 1, seq_shards: int = 1) -> None:
    """A model with layers of several kinds runs on one chip, unsharded."""
    from ..models.latent import refuse

    if mesh is not None or (ctx is not None and ctx.size > 1):
        refuse("a tensor-parallel serve mesh (grid)", "its weights and caches have "
               "no sharding rules yet")
    if dp > 1:
        refuse("serve_replicas > 1", "its caches are not partitioned by replica")
    if seq_shards > 1:
        refuse("seq_shards > 1", "its caches are not striped over a seq axis")


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


def _embed(params, cfg, x):
    """Post-embedding layernorm (bloom-style ``embedding_norm``)."""
    if cfg.embedding_norm:
        x = norm(x, params["embed_norm"], cfg.norm, cfg.norm_eps)
    return x


def prefill(
    params: Params,
    cfg: TransformerConfig,
    tokens: jnp.ndarray,  # [s_pad] int32 (one sequence, padded)
    length: jnp.ndarray,  # scalar — true prompt length
    blocks: jnp.ndarray,  # [n_pages] int32, -1 padded
    kv_cache: Tuple[jnp.ndarray, jnp.ndarray],
    ctx=None,  # ops.quantizer.ServingContext — TP/fused serving policy
    mesh=None,  # serve mesh: the flash kernel runs per shard under it
):
    """Run the prompt, write its KV pages, return (logits_at_last, caches).

    Dense causal attention over the padded prompt (padding masked by
    causality + the final gather at ``length - 1``).
    """
    if cfg.latent is not None:
        from ..models.latent import refuse

        refuse("prefill (one padded prompt)", "the engine packs every prompt; "
               "use prefill_packed")
    s = tokens.shape[0]
    x = params["embed"]["embedding"][tokens][None].astype(cfg.dtype)  # [1,s,d]
    positions = jnp.arange(s)[None]
    if cfg.position == "learned":
        x = x + params["pos_embed"]["embedding"][jnp.arange(s)][None].astype(cfg.dtype)
    x = _embed(params, cfg, x)
    ck, cv = kv_cache
    # python loop over layers: each layer writes its cache page slab.
    # (L is static; unrolled trace is fine for inference graphs).  The KV
    # pools are per-layer tuples — updates replace one layer's buffer
    # in-place under donation, never a stacked-pool slice copy.
    new_ck, new_cv = list(ck), list(cv)
    for l in range(cfg.num_layers):
        lw = jax.tree_util.tree_map(lambda a: a[l], params["layers"])
        h = norm(x, lw["attn_norm"], cfg.norm, cfg.norm_eps)
        q, k, v = _qkv(lw["attn"], h, cfg, ctx)
        if cfg.position == "rope":
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
        new_ck[l] = write_prefill_kv(
            new_ck[l], k[0].astype(new_ck[l].dtype), blocks, length
        )
        new_cv[l] = write_prefill_kv(
            new_cv[l], v[0].astype(new_cv[l].dtype), blocks, length
        )
        # dispatcher: Pallas flash kernel on TPU when the shape qualifies
        # (prompt >= 128, tile-divisible), else the fused XLA body — serving
        # prefill is exactly where the kernel's MXU efficiency pays
        attn = flash_attention(
            q, k, v, causal=True, logits_soft_cap=cfg.logits_soft_cap,
            mesh=mesh,
        )
        attn = _attn_out(lw["attn"], attn.reshape(1, s, -1), ctx)
        x = x + attn.astype(x.dtype)
        h = norm(x, lw["mlp_norm"], cfg.norm, cfg.norm_eps)
        x = x + _ffn(lw, h, cfg, ctx).astype(x.dtype)

    x = norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    last = x[0, jnp.clip(length - 1, 0, s - 1)]  # [d]
    logits = _lm_logits(params, cfg, last, ctx)  # [v]
    return logits, (tuple(new_ck), tuple(new_cv))


def prefill_packed(
    params: Params,
    cfg: TransformerConfig,
    tokens: jnp.ndarray,  # [T] int32 — prompts packed at PAGE-aligned starts
    segment_ids: jnp.ndarray,  # [T] int32 — 1-based per prompt, 0 = padding
    positions: jnp.ndarray,  # [T] int32 — per-token position within its prompt
    pack_pages: jnp.ndarray,  # [T/bs] int32 — destination page per bs-chunk (-1 pad)
    last_idx: jnp.ndarray,  # [N] int32 — buffer index of each prompt's last token (-1 pad)
    kv_cache: Tuple[jnp.ndarray, jnp.ndarray],
    ctx=None,  # ops.quantizer.ServingContext — TP/fused serving policy
    mesh=None,  # serve mesh: the flash kernel runs per shard under it
):
    """Batched multi-prompt prefill under one token budget (the Dynamic
    SplitFuse-shaped dispatch; reference ``inference/v2/ragged/
    ragged_wrapper.py`` builds the same packed view as 'atoms').

    All prompts share one dense causal pass; cross-prompt attention is
    blocked by ``segment_ids`` masking.  Every prompt starts at a PAGE
    boundary in the pack (the engine pads with segment-0 gaps), so KV
    lands as ONE page-granular scatter per layer — a per-TOKEN scatter was
    measured at ~100 ms/pack on v5e (TPU serializes row scatters); pages
    cut the scatter index count by block_size.  Rows past a prompt's end
    inside its last page carry garbage masked by sequence length, same as
    ``write_prefill_kv``.  Returns (logits [N, vocab], new caches).
    """
    if cfg.latent is not None:
        # layers of several kinds: their own cache and bodies; a cold pack is
        # a pack whose block tables are its own pages
        from . import latent_runner

        _latent_only(ctx, mesh)
        n_pages = pack_pages.shape[0]  # a cold pack's positions end inside it
        tables = latent_runner.tables_of_pack(
            segment_ids, positions, pack_pages, last_idx.shape[0], n_pages,
            tokens.shape[0] // n_pages)
        return latent_runner.prefill_pack(
            params, cfg, tokens, segment_ids, positions, pack_pages, last_idx,
            tables, kv_cache)
    t = tokens.shape[0]
    x = params["embed"]["embedding"][tokens][None].astype(cfg.dtype)  # [1,T,d]
    if cfg.position == "learned":
        x = x + params["pos_embed"]["embedding"][
            jnp.clip(positions, 0, cfg.max_seq_len - 1)
        ][None].astype(cfg.dtype)
    x = _embed(params, cfg, x)
    ck, cv = kv_cache
    nb = ck[0].shape[0]
    bs = ck[0].shape[1]
    n_chunks = t // bs
    # padding chunks scatter out of bounds and are dropped
    safe_pages = jnp.where(pack_pages >= 0, pack_pages, nb)
    seg = segment_ids[None]  # [1, T]
    pos2 = positions[None]
    new_ck, new_cv = list(ck), list(cv)
    for l in range(cfg.num_layers):
        lw = jax.tree_util.tree_map(lambda a: a[l], params["layers"])
        h = norm(x, lw["attn_norm"], cfg.norm, cfg.norm_eps)
        q, k, v = _qkv(lw["attn"], h, cfg, ctx)
        if cfg.position == "rope":
            q = rope(q, pos2, cfg.rope_theta)
            k = rope(k, pos2, cfg.rope_theta)
        new_ck[l] = new_ck[l].at[safe_pages].set(
            k[0].reshape(n_chunks, bs, *k.shape[2:]).astype(new_ck[l].dtype),
            mode="drop",
        )
        new_cv[l] = new_cv[l].at[safe_pages].set(
            v[0].reshape(n_chunks, bs, *v.shape[2:]).astype(new_cv[l].dtype),
            mode="drop",
        )
        # packed order == position order within each segment, so causal
        # masking by buffer index + segment masking is exact.  The flash
        # kernel handles packed segments natively (per-block int32 tiles),
        # so SplitFuse prefill runs on the MXU-tiled path on TPU
        attn = flash_attention(
            q, k, v, causal=True, segment_ids=seg,
            logits_soft_cap=cfg.logits_soft_cap, mesh=mesh,
        )
        attn = _attn_out(lw["attn"], attn.reshape(1, t, -1), ctx)
        x = x + attn.astype(x.dtype)
        h = norm(x, lw["mlp_norm"], cfg.norm, cfg.norm_eps)
        x = x + _ffn(lw, h, cfg, ctx).astype(x.dtype)

    x = norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    last = x[0, jnp.clip(last_idx, 0, t - 1)]  # [N, d]
    logits = _lm_logits(params, cfg, last, ctx)  # [N, v]
    return logits, (tuple(new_ck), tuple(new_cv))


def prefill_packed_ctx(
    params: Params,
    cfg: TransformerConfig,
    tokens: jnp.ndarray,  # [T] int32 — suffix tokens packed at PAGE-aligned starts
    segment_ids: jnp.ndarray,  # [T] int32 — 1-based per prompt, 0 = padding
    positions: jnp.ndarray,  # [T] int32 — ABSOLUTE position (start offset baked in)
    pack_pages: jnp.ndarray,  # [T/bs] int32 — destination page per bs-chunk (-1 pad)
    last_idx: jnp.ndarray,  # [N] int32 — buffer index of each prompt's last token (-1 pad)
    ctx_tables: jnp.ndarray,  # [N, P] int32 — block table per segment (-1 pad)
    ctx_lens: jnp.ndarray,  # [N] int32 — cached-context length per segment
    kv_cache: Tuple[jnp.ndarray, jnp.ndarray],
    ctx=None,  # ops.quantizer.ServingContext — TP/fused serving policy
    mesh=None,  # TP/2-D serving: shard_map the ctx attention (see paged.py)
    dp: int = 1,  # batch-axis replicas — packs arrive as dp per-replica chunks
    seq_shards: int = 1,  # seq-axis pool slices (3-D mesh, ring-merged)
):
    """``prefill_packed`` generalized to token SUFFIXES: each packed segment
    starts at a per-sequence offset (``ctx_lens``) and attends over its
    pre-existing KV pages (``ctx_tables``) for positions below the offset
    plus the causal in-pack segment.  RoPE/learned positions come from the
    absolute ``positions``.  This is the one model-runner capability both
    prefix-cache-hit prefill and Dynamic-SplitFuse chunked prefill ride on;
    segments with offset 0 and the no-context pack stay byte-identical to
    ``prefill_packed`` (the engine dispatches there for speed).  Returns
    (logits [N, vocab], new caches); rows of ``last_idx`` that are -1
    (segment's prompt not yet complete — mid-chunk) yield garbage logits the
    engine never consumes.
    """
    if cfg.latent is not None:
        from . import latent_runner

        _latent_only(ctx, mesh, dp, seq_shards)
        return latent_runner.prefill_pack(
            params, cfg, tokens, segment_ids, positions, pack_pages, last_idx,
            ctx_tables, kv_cache)
    t = tokens.shape[0]
    x = params["embed"]["embedding"][tokens][None].astype(cfg.dtype)  # [1,T,d]
    if cfg.position == "learned":
        x = x + params["pos_embed"]["embedding"][
            jnp.clip(positions, 0, cfg.max_seq_len - 1)
        ][None].astype(cfg.dtype)
    x = _embed(params, cfg, x)
    ck, cv = kv_cache
    nb = ck[0].shape[0]
    bs = ck[0].shape[1]
    n_chunks = t // bs
    safe_pages = jnp.where(pack_pages >= 0, pack_pages, nb)
    pos2 = positions[None]
    new_ck, new_cv = list(ck), list(cv)
    for l in range(cfg.num_layers):
        lw = jax.tree_util.tree_map(lambda a: a[l], params["layers"])
        h = norm(x, lw["attn_norm"], cfg.norm, cfg.norm_eps)
        q, k, v = _qkv(lw["attn"], h, cfg, ctx)
        if cfg.position == "rope":
            q = rope(q, pos2, cfg.rope_theta)
            k = rope(k, pos2, cfg.rope_theta)
        new_ck[l] = new_ck[l].at[safe_pages].set(
            k[0].reshape(n_chunks, bs, *k.shape[2:]).astype(new_ck[l].dtype),
            mode="drop",
        )
        new_cv[l] = new_cv[l].at[safe_pages].set(
            v[0].reshape(n_chunks, bs, *v.shape[2:]).astype(new_cv[l].dtype),
            mode="drop",
        )
        # context positions (< ctx_lens) read from the written pools; the
        # pack's own freshly-written pages are masked out by ctx_lens, so
        # passing the post-write pool is safe and mirrors decode_step
        attn = paged_attention_packed_ctx(
            q[0], k[0], v[0], segment_ids, new_ck[l], new_cv[l],
            ctx_tables, ctx_lens, logits_soft_cap=cfg.logits_soft_cap,
            mesh=mesh, dp=dp, seq_shards=seq_shards, ctx=ctx,
        )
        attn = _attn_out(lw["attn"], attn.reshape(1, t, -1), ctx)
        x = x + attn.astype(x.dtype)
        h = norm(x, lw["mlp_norm"], cfg.norm, cfg.norm_eps)
        x = x + _ffn(lw, h, cfg, ctx).astype(x.dtype)

    x = norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    last = x[0, jnp.clip(last_idx, 0, t - 1)]  # [N, d]
    logits = _lm_logits(params, cfg, last, ctx)  # [N, v]
    return logits, (tuple(new_ck), tuple(new_cv))


def verify_packed_ctx(
    params: Params,
    cfg: TransformerConfig,
    tokens: jnp.ndarray,  # [T] int32 — per slot: [last committed, d_0..d_{k-1}], padded
    segment_ids: jnp.ndarray,  # [T] int32 — slot+1 per valid token, 0 = padding
    positions: jnp.ndarray,  # [T] int32 — ABSOLUTE position of each token
    dst_pages: jnp.ndarray,  # [T] int32 — KV destination page per token (-1 pad)
    dst_offs: jnp.ndarray,  # [T] int32 — row within the destination page
    ctx_tables: jnp.ndarray,  # [N, P] int32 — block table per slot (-1 pad)
    ctx_lens: jnp.ndarray,  # [N] int32 — committed (KV-written) length per slot
    kv_cache: Tuple[jnp.ndarray, jnp.ndarray],
    ctx=None,  # ops.quantizer.ServingContext — TP/fused serving policy
    mesh=None,  # TP/2-D serving: shard_map the ctx attention (see paged.py)
    dp: int = 1,  # batch-axis replicas (slot-ordered rows chunk naturally)
    seq_shards: int = 1,  # seq-axis pool slices (3-D mesh, ring-merged)
):
    """Speculative-decode verify: score k+1 positions per sequence in ONE
    pass — the dispatch that amortizes the weight stream across several
    emitted tokens (one weight read serves up to k+1 of them).

    Each sequence's pack segment is [its last committed token, then its k
    draft tokens] at consecutive absolute positions; attention rides the
    same machinery as chunked prefill (``paged_attention_packed_ctx``): one
    softmax over [cached context | in-pack causal draft prefix], so a draft
    token attends over the sequence's cached pages plus the drafts before
    it.  Two differences from ``prefill_packed_ctx``:

    * KV writes are per-ROW scatters (``write_spec_kv``): the pack starts
      mid-page at the decode head, where a page-granular scatter would
      stomp live rows.  Rejected drafts leave garbage KV past the accepted
      length — masked by sequence length everywhere, overwritten as the
      sequence grows (the ``step_n`` rule), and their tail BLOCKS are freed
      by the allocator's truncate path.
    * Logits return for ALL T pack rows (each one verifies the next draft
      or samples the correction/bonus token), not just a per-segment last
      row.  The [T, vocab] fp32 buffer is the price of single-pass verify —
      T = max_seqs * (k+1) stays small next to prefill packs.

    Returns (logits [T, v], new caches).
    """
    if cfg.latent is not None:
        from ..models.latent import refuse

        refuse("enable_speculation (verify_packed_ctx)", "a rejected draft's rows "
               "cannot be rolled back out of a sliding layer's ring")
    t = tokens.shape[0]
    x = params["embed"]["embedding"][tokens][None].astype(cfg.dtype)  # [1,T,d]
    if cfg.position == "learned":
        x = x + params["pos_embed"]["embedding"][
            jnp.clip(positions, 0, cfg.max_seq_len - 1)
        ][None].astype(cfg.dtype)
    x = _embed(params, cfg, x)
    ck, cv = kv_cache
    pos2 = positions[None]
    new_ck, new_cv = list(ck), list(cv)
    for l in range(cfg.num_layers):
        lw = jax.tree_util.tree_map(lambda a: a[l], params["layers"])
        h = norm(x, lw["attn_norm"], cfg.norm, cfg.norm_eps)
        q, k, v = _qkv(lw["attn"], h, cfg, ctx)
        if cfg.position == "rope":
            q = rope(q, pos2, cfg.rope_theta)
            k = rope(k, pos2, cfg.rope_theta)
        new_ck[l] = write_spec_kv(new_ck[l], k[0], dst_pages, dst_offs)
        new_cv[l] = write_spec_kv(new_cv[l], v[0], dst_pages, dst_offs)
        # context positions (< ctx_lens) read the cached pools; the pack's
        # freshly written rows are masked out by ctx_lens and enter through
        # the in-pack causal half — same split as prefill_packed_ctx
        attn = paged_attention_packed_ctx(
            q[0], k[0], v[0], segment_ids, new_ck[l], new_cv[l],
            ctx_tables, ctx_lens, logits_soft_cap=cfg.logits_soft_cap,
            mesh=mesh, dp=dp, seq_shards=seq_shards, ctx=ctx,
        )
        attn = _attn_out(lw["attn"], attn.reshape(1, t, -1), ctx)
        x = x + attn.astype(x.dtype)
        h = norm(x, lw["mlp_norm"], cfg.norm, cfg.norm_eps)
        x = x + _ffn(lw, h, cfg, ctx).astype(x.dtype)

    x = norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    logits = _lm_logits(params, cfg, x[0], ctx)  # [T, v]
    return logits, (tuple(new_ck), tuple(new_cv))


def decode_step(
    params: Params,
    cfg: TransformerConfig,
    tokens: jnp.ndarray,  # [B] int32 — last sampled token per slot
    seq_lens: jnp.ndarray,  # [B] int32 — length BEFORE this token
    block_tables: jnp.ndarray,  # [B, P] int32
    active: jnp.ndarray,  # [B] bool
    kv_cache: Tuple[jnp.ndarray, jnp.ndarray],
    ctx=None,  # ops.quantizer.ServingContext — TP/fused serving policy
    mesh=None,  # TP serving: shard_map the paged attention over 'model'
    dp: int = 1,  # batch-axis replicas (2-D batch x model serve mesh)
    seq_shards: int = 1,  # seq-axis pool slices (3-D mesh, ring-merged)
):
    """One batched decode tick: returns (logits [B, v], new caches)."""
    if cfg.latent is not None:
        from . import latent_runner

        _latent_only(ctx, mesh, dp, seq_shards)
        return latent_runner.decode_step(
            params, cfg, tokens, seq_lens, block_tables, active, kv_cache)
    b = tokens.shape[0]
    x = params["embed"]["embedding"][tokens][:, None].astype(cfg.dtype)  # [B,1,d]
    positions = seq_lens[:, None]  # the new token's position
    if cfg.position == "learned":
        pe = params["pos_embed"]["embedding"][
            jnp.clip(seq_lens, 0, cfg.max_seq_len - 1)
        ]
        x = x + pe[:, None].astype(cfg.dtype)
    x = _embed(params, cfg, x)
    ck, cv = kv_cache
    new_ck, new_cv = list(ck), list(cv)
    for l in range(cfg.num_layers):
        lw = jax.tree_util.tree_map(lambda a: a[l], params["layers"])
        h = norm(x, lw["attn_norm"], cfg.norm, cfg.norm_eps)
        q, k, v = _qkv(lw["attn"], h, cfg, ctx)  # [B,1,h,hd]
        if cfg.position == "rope":
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
        new_ck[l] = write_decode_kv(
            new_ck[l], k[:, 0], block_tables, seq_lens, active
        )
        new_cv[l] = write_decode_kv(
            new_cv[l], v[:, 0], block_tables, seq_lens, active
        )
        attn = paged_attention_decode(
            q[:, 0], new_ck[l], new_cv[l], block_tables, seq_lens + 1,
            logits_soft_cap=cfg.logits_soft_cap, mesh=mesh, dp=dp,
            seq_shards=seq_shards,
        )
        attn = _attn_out(lw["attn"], attn.reshape(b, 1, -1), ctx)
        x = x + attn.astype(x.dtype)
        h = norm(x, lw["mlp_norm"], cfg.norm, cfg.norm_eps)
        x = x + _ffn(lw, h, cfg, ctx).astype(x.dtype)
    x = norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    logits = _lm_logits(params, cfg, x[:, 0], ctx)
    return logits, (tuple(new_ck), tuple(new_cv))
