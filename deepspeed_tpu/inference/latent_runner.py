"""Serving runner for a model with layers of several kinds
(``TransformerConfig.latent``, ``models/latent.py``): the prefill pack and
the decode tick against a cache that holds, per KIND of layer, what that kind
attends over.

- a ``full`` layer keeps every position's latent row (``kv_rank + rope`` wide,
  keys and values in one array) in PAGES, block ids and block tables the
  allocator's own, and the indexer's key page beside it;
- an ``every`` layer (latent attention over EVERY cached row) keeps the same
  latent pages and nothing beside them: no index keys, no ring.  A model of
  such layers alone is one whose blocks a prefix cache may share (a pack's
  chunk that starts at a position > 0 reads the pages under it, whoever wrote
  them).  Its queries cross the seam BEFORE ``W_uk`` and its heads' values
  come back after ``W_uv``, because its one softmax has two forms and the
  pack picks by length (``_attend_every``): a run of a sequence's pages from
  ``latent_attention.run_groups`` on attends DECOMPRESSED, shorter ones and a
  tick's single rows ABSORBED as the other kinds do;
- a ``sliding`` layer keeps a RING per slot, not pages: position ``p`` of slot
  ``n`` lives in row ``p % R`` of ``win[n]``, ``R`` = one pack + the window's
  look-back, so a pack's rows can be written before it attends without
  touching a row its window still needs.  Nothing is allocated or freed: rows
  behind the window are overwritten as the sequence grows, a slot's next
  owner overwrites from position 0, and a ring row's position follows from
  the reader's own (``decode``: the latest ``p <= pos`` with ``p % R == i``), so
  a stale row is never taken for a key;
- ``stats`` counts routing on the device (``ROUTING_STATS``) and ``picks`` the
  keys the selectors took (``_tally``), fetched on demand.

A model of single-mixer blocks (``models/latent.py:SINGLE``) keeps instead:

- per state-space block and SLOT a state of FIXED size, overwritten every
  token: the recurrence's state (``ssm``, float32) and the convolution's last
  ``K - 1`` input rows (``conv``).  No pages and no positions: a sequence's
  first chunk starts from zeros whatever the slot held (its position says so,
  in the program: a stale state is never read), a prompt's later chunks and
  its decode ticks carry the slot's state on, several prompts in one pack
  each scan from their own, and a preempted sequence's state is simply left
  behind: the resume starts at position 0 and recomputes it;
- per attention block K / V pages on the allocator's block ids, written and
  read by the paged GQA code of ``paged.py`` that a dense model uses;
- an expert block keeps nothing but its routing counts (``touched``: held
  experts with a row and the pairs on them, of packs and of ticks).

A model of two-norm blocks (``models/latent.py:HYBRID``: Gated DeltaNet and
gated GQA of up to two kinds, a dense SwiGLU or the expert layer behind them)
keeps the same three things under the same names: per ``gdn`` block and slot
the delta rule's MATRIX state (``ssm``, [Hv, Dk, Dv] float32) and its
convolution's tail (``conv``), zeroed by position, carried and recomputed as
above; per ``gattn`` block K / V pages (the keys after their norm and
rotation); per expert block its routing counts.  And a FIFTH kind of state
beside the pages:

- per ``wattn`` block (gated GQA over a window) a K / V RING a slot (``wk``,
  ``wv``), with the ring arithmetic of the ``sliding`` layers above (``p % R``,
  ``R`` = ``ring_rows``: one pack + the look-back in pages) and the rows of the
  K / V pages: a ring is ``R / page`` pages of the pool ``[slots * R / page,
  page, Hkv, hd]``, written a page at a time in place like the pages.  Nothing
  is allocated or freed, a slot's next owner and a resume overwrite from 0, and
  the host mirror that ``close()`` audits is the ``sliding`` rings' own.

A model of two-norm blocks whose mixer is EVA attention (``eva``,
``ops/eva.py``) keeps K / V pages alone, and is the one kind whose TABLE SHRINKS
while the sequence lives (``ragged.WindowCompaction`` has the layout: a page a
closed window, the open window's summary page, the open window's exact pages):

- a position's exact row goes to its page as a K / V row does; every chunk of
  ``chunk`` positions that a pack or a tick COMPLETES is summarised there and
  then (``eva_summarise``) into the open window's summary page, one row a chunk,
  in a key's and a value's format.  When the window's last position is written
  the host drops its exact pages from the table and gives them back to the pool
  (``StateManager.close_window``: 16 of its 17 pages at 2048 / 16 / 128); nothing
  moves on the device;
- rotary positions follow the POSITION, cache addresses the ROW: position ``p``
  is row ``p // window * (window / chunk) + p % window`` of what its sequence
  attends, and both follow from ``p`` inside the program;
- attention (``eva_attend``) is the paged kernels' own, over the table with the
  open window's summary page taken out (a window's chunks are never summaries to
  its own queries): a pack through the packed-ctx body with the ROWS before it as
  its context, a tick through the paged decode body with its row count as its
  length.  The heads are laid out ``eva_heads_a_pool`` a pool, as many as the
  packed-ctx kernel's gate takes at the pack's size (32 K / V heads in one pool
  are past its VMEM estimate), each pool attended by a call of its own;
- a preemption drops the table and the resume recomputes from the tokens.

A model of two-norm blocks that each hold TWO PARALLEL MIXERS (``par``: a Mamba-2
recurrence AND rotary GQA on one normed input, summed; a dense SwiGLU behind
them) keeps BOTH of the above for EVERY layer of a sequence: ``ssm[i]`` / ``conv[i]``
and ``k[i]`` / ``v[i]`` are block ``i``'s (``LatentSpec.count`` counts a block as one
of each mixer), the block goes through the seam twice (``_mixer``: the recurrence,
then the K / V write and read), and what follows from position holds for both at
once: a slot's next owner and a resume start the state from zeros AND overwrite the
pages from position 0; a preemption frees the pages and leaves the state behind.

A model of two-norm blocks whose mixer is chosen BY BLOCK (``LatentSpec.two_norms``:
``mamba`` in most blocks, position-free ``gqa`` in the rest, the expert layer behind
each) keeps what the KINDS PRESENT need: a state and a convolution's tail for each
recurrent block, a layer of pages for each attention block (9 and 1 of ten at
Granite 4.0-H's 9 : 1), through the same ``_mixer``.  Wherever a slot keeps both
kinds of cache, in one block or in different ones, the host mirror sets two gauges
at every dispatch (``state_bytes_live``, ``kv_page_bytes_in_use``: ``CACHE_GAUGES``).

One layer body (``_layer``) serves the pack and the tick; the kind chooses how
the rows are written and read.  A pack reads its own rows back from the cache
it just wrote, so a cold pack and a pack over cached context are one program,
as are chunks of one prompt and several prompts in one pack: work is laid out
in groups of one page of one sequence.

A pack CARRIES THE TICK'S STEP (``prefill_pack(..., step=)``, PR 56;
``model_runner._pack``'s contract): the pack's T token rows and the step's B
slot rows are ONE ``[T + B, d]`` operand through every norm, projection,
router and ONE head matmul, and every held-expert layer is ONE layout and ONE
set of grouped products over both (on the PACK's row tile, counted on the
pack's side: ``_row_tile``, ``_experts``), so a tick streams the weights
once.  Each kind of row keeps its own cache write and its own attention or
recurrence: ``_carrying`` sends rows ``[:T]`` through the pack's seam and rows
``[T:]`` through the tick's, the cache arrays threaded pack first, then step.
The scheduler never puts a sequence in both, so slots, states, rings, pages
and table rows are disjoint and the program computes what the two computed in
turn.  The step's bodies take sibling scope names there (``full_attn_step``
beside ``full_attn``: ``la.carried_step``).  What still runs a kind of row: a
recurrence's own projections, which live inside its ``write`` (``(w, h)``).
Without ``step`` the entries trace what they always traced.  The entry takes a
step for every family; which families' ENGINES hand it one is the runner's
``packs_carry_step``, ONE expression of ``LatentSpec``'s own fields
(``LatentRunner.__init__``): two-norm blocks (``hybrid``) that keep no
recurrence's state (gated attention on pages and rings, EVA) OR hold no routed
layer (``expert_layers`` empty: two parallel mixers beside a dense SwiGLU in
every block, PR 59).  The mixed program is a THIRD XLA program whose rows differ
from the pack's and the step's by rounding; a dense model's logits move by
rounding's own size, a router's near tie behind a recurrence's state may fall
the other way.  So the families with a recurrence AND routed experts
(single-mixer blocks, Gated DeltaNet) keep two programs until their cells'
token margins allow a flip (ROADMAP S2 (0)), and the selector's and the
every-row latent families keep two for what a mixed tick cost them on the chip
(PERF.md §6, PR 56).  Plain XLA bodies
(``ops/latent_attention.py``) but for five Pallas kernels on the chip: a
pack's index scores (``ops/pallas/index_scores.py``), its shorter groups'
attention over their picks (``ops/pallas/selected_attention.py``: an ``every``
layer's short runs walk their pages through the same kernel, the causal
positions the mask), an ``every`` layer's long runs in the decompressed form
(``ops/pallas/latent_prefill.py``), its tick (``ops/pallas/latent_decode.py``)
and the expert layer's grouped matmul (``moe/layer.py``).

``LatentRunner`` is what ``InferenceEngineV2`` holds for such a model (as
``model_runner.DenseRunner`` for a dense one): these entries under the names
the engine's programs call, and the kind's host accounting beside the cache it
describes (``COUNTERS``, the rings' host mirror).
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..models import latent as lm
from ..moe.layer import held_row_tile, held_rows_a_pass
from ..ops import latent_attention as la
from ..ops.pallas import index_scores as index_kernel
from ..ops.pallas import latent_decode as decode_kernel
from ..ops.pallas import latent_prefill as prefill_kernel
from ..ops.pallas import selected_attention as selected_kernel
from ..ops.pallas import note_dispatch, on_tpu

Cache = Dict[str, Any]
# columns of cache["stats"], one row per expert layer: (token, expert) pairs
# routed and pairs that fell on held experts (running sums), rows of the
# largest and of the smallest held expert's group in any one pack so far
ROUTING_STATS = ("pairs_routed", "pairs_held", "group_rows_max", "group_rows_min")
_NO_MIN = 1 << 30
_CARRY = 30  # bits of the low word of cache["picks"]
# extra ``stats`` keys of an engine that serves such a model: what its
# selectors, windows and router did.  The causal keys, the ring rows and the
# groups follow from positions, the expert layers' laid-out rows from the program's
# shape, and are counted on the host at dispatch; the keys SELECTED
# and the routing four are counted on the device (``picks``, ROUTING_STATS)
# and read by ``refresh_routing_stats()``, which ``close()`` calls.
COUNTERS = (
    "index_keys_scored",      # (query, key) pairs the indexers scored: causal keys
    "index_keys_selected",    # ... and pairs the selectors took (device count)
    "window_rows_discarded",  # ring rows that fell out of a window
    "selected_groups",        # a pack's groups (a page of queries) x full layers
    "selected_groups_dense",  # ... whose context ends under DENSE_KEYS_MAX: walked, not gathered
    "expert_pairs_routed",    # (token, expert) pairs the routers picked
    "expert_pairs_held",      # ... that fell on experts held here
    "expert_rows_laid_out",   # rows the held experts' layers handed their grouped matmuls
    "expert_group_rows_max",  # rows of the largest held expert's group in a pack
    "expert_group_rows_min",  # ... and of the smallest
)


# ... of one whose layers are gated GQA over every key (pages) and over a window
# (rings), with the expert layer's counts of the models below
WINDOW_COUNTERS = (
    "full_keys_attended",     # (query, key) pairs of the layers over every key: causal keys
    "window_keys_attended",   # ... of the layers over a window: min(position + 1, window)
    "causal_keys",            # what the window layers would attend if they were full
    "window_rows_discarded",  # ring rows that fell out of a window
    "expert_pairs_routed", "expert_pairs_held", "expert_rows_laid_out", "expert_group_rows_max",
    "expert_group_rows_min", "experts_touched", "experts_touched_decode",
    "expert_pairs_held_decode",
)


# ... of one whose layers attend EVERY cached latent row (pages alone): all counted
# on the host at dispatch but the routing four
MLA_COUNTERS = (
    "mla_keys_attended",         # (query, key) pairs of the layers over every row: causal keys
    "mla_keys_attended_decode",  # ... of them, those of decode ticks
    "mla_keys_decompressed",     # ... and those of a pack's runs from ``la.run_groups`` pages on
    "expert_pairs_routed", "expert_pairs_held", "expert_rows_laid_out", "expert_group_rows_max",
    "expert_group_rows_min", "experts_touched", "experts_touched_decode",
    "expert_pairs_held_decode",
)


# ... and of one whose slots keep a recurrence's state (``LatentSpec.stateful``:
# state-space or delta rule; the names say ``ssm`` for either)
STATE_COUNTERS = (
    "ssm_states_reset",       # sequences that began from a zero state (admissions, resumes)
    "ssm_states_recomputed",  # states a preemption discarded: the resume scans them again
    "ssm_chunks_scanned",     # chunks (a page of one sequence) x state-space blocks, in packs
    "expert_pairs_routed", "expert_pairs_held", "expert_rows_laid_out", "expert_group_rows_max",
    "expert_group_rows_min",  # the routing four and the rows laid out, as above
    "experts_touched",        # held experts with at least one row, summed over dispatches
    "experts_touched_decode",    # ... in decode ticks alone,
    "expert_pairs_held_decode",  # and the pairs that fell on them there
)


# ... and where a slot keeps BOTH a recurrence's state and K / V pages (in one block or in
# different ones), what a slot and a page hold, as gauges set at each dispatch
CACHE_GAUGES = (
    "state_bytes_live",      # gauge: slots holding a sequence x a slot's state, every block's
    "kv_page_bytes_in_use",  # gauge: pages holding a live sequence's rows x a page, every block's
)


# ... and of one whose layers are EVA attention (pages that are given back): all
# counted on the host, ONE layer's count (every layer is of the kind and reads alike)
EVA_COUNTERS = (
    "eva_windows_closed",      # windows whose last position was written (the engine's count)
    "eva_pages_returned",      # pages those closes gave back to the pool, their sequences living on
    "eva_summary_rows_read",   # rows of closed windows' summaries the decode ticks' queries read
    "eva_exact_rows_read",     # ... and exact rows of their own windows
    "eva_rows_live",           # gauge: rows the live sequences attend next
    "eva_context_tokens_live",  # gauge: positions those sequences have written
)


def _lanes(width: int) -> int:
    """A latent row as the pages keep it: padded with zeros to whole 128-lane
    rows, which a row gather moves at twice the speed (576 -> 640)."""
    return -(-width // 128) * 128


def ring_rows(cfg, block_size: int, pack_tokens: int) -> int:
    """Rows of a slot's ring (a ``sliding`` layer's or a ``wattn`` layer's): one
    pack and the window's look-back, in pages."""
    s = cfg.latent
    window = (s.wattn if s.hybrid else s.sliding).window
    return pack_tokens + -(-(window - 1) // block_size) * block_size


def init_cache(cfg, num_blocks: int, block_size: int, max_seqs: int,
               pack_tokens: int, dtype=None) -> Cache:
    s, dtype = cfg.latent, dtype or cfg.dtype
    if pack_tokens % block_size:
        raise ValueError(f"a pack of {pack_tokens} tokens is no whole number of "
                         f"pages of {block_size}")
    if s.stateful:
        return _init_state_cache(cfg, num_blocks, block_size, max_seqs, pack_tokens, dtype)
    pages = lambda w, n: tuple(
        jnp.zeros((num_blocks, block_size, w), dtype) for _ in range(n))
    n_moe = max(cfg.num_layers - s.first_dense, 0)
    stats = jnp.zeros((n_moe, len(ROUTING_STATS)), jnp.int32)
    paged = "full" if s.indexed else "every"  # the kind whose rows live in pages
    win = ()
    if s.ringed:
        chunks = max_seqs * ring_rows(cfg, block_size, pack_tokens) // block_size
        win = tuple(jnp.zeros((chunks, block_size, s.sliding.row), dtype)
                    for _ in range(s.count("sliding")))
    # a model of pages alone counts the held experts it touched, of packs [0]
    # and of ticks [1], as the stateful models do (``_init_state_cache``)
    touched = {} if s.indexed or s.ringed else {"touched": jnp.zeros((n_moe, 2, 2), jnp.int32)}
    return {
        "lat": pages(_lanes(s.attn(paged).row), s.count(paged)),
        "idx": pages(s.index_dim, s.count("full")),
        "win": win,
        "stats": stats.at[:, 3].set(_NO_MIN),
        # keys selected so far, one row per full layer: a running count in two
        # int32 words (high, low 30 bits), since a window's sum passes 2^31
        "picks": jnp.zeros((s.count("full"), 2), jnp.int32),
        **touched,
    }


def _init_state_cache(cfg, num_blocks: int, block_size: int, max_seqs: int,
                      pack_tokens: int, dtype) -> Cache:
    """The cache of a model whose slots keep a recurrence's state, K / V pages
    and K / V rings (``LatentSpec.stateful``; module docstring)."""
    from .paged import init_paged_cache

    s = cfg.latent
    if s.eva is not None:
        return _init_eva_cache(cfg, num_blocks, block_size, pack_tokens, dtype)
    # by the KINDS PRESENT: a state and a conv tail a recurrent block, a layer of pages
    # an attention block over every key (a model may hold either, both, or both in one block)
    (rec, mb), (att, g) = s.recurrence, s.attention
    k, v = init_paged_cache(s.count(att), num_blocks, block_size, g.num_kv_heads,
                            g.head_dim, dtype=dtype) if g else ((), ())
    per = lambda shape, dt: tuple(jnp.zeros((max_seqs, *shape), dt)
                                  for _ in range(s.count(rec)))
    n_moe = len(s.expert_layers)
    rings = {}
    if s.count("wattn"):
        # a slot's ring is R / page pages of one pool, slot n's from page n * R / page
        chunks = max_seqs * ring_rows(cfg, block_size, pack_tokens) // block_size
        rings["wk"], rings["wv"] = init_paged_cache(
            s.count("wattn"), chunks, block_size, s.wattn.num_kv_heads, s.wattn.head_dim,
            dtype=dtype)
    return {
        "ssm": per(mb.state_shape, jnp.float32) if mb else (),
        "conv": per((mb.conv - 1, mb.conv_width), dtype) if mb else (),
        "k": k, "v": v, **rings,
        "stats": jnp.zeros((n_moe, len(ROUTING_STATS)), jnp.int32).at[:, 3].set(_NO_MIN),
        # per expert block, of packs [0] and of ticks [1]: (held experts with a
        # row, pairs on held experts), running sums
        "touched": jnp.zeros((n_moe, 2, 2), jnp.int32),
    }


def eva_heads_a_pool(ev, block_size: int, pack_tokens: int, dtype) -> int:
    """K / V heads a pool of an EVA layer's pages: all of them where the
    packed-ctx kernel's gate takes a pack of ``pack_tokens`` queries at that many
    heads with one query head a K / V head, else the largest halving it takes
    (its VMEM estimate: 32 heads x 512 queries read 64 MB against a budget of 32,
    16 heads 34.6, 8 heads 19.9).  From the shapes alone, so the chip and the CPU
    lay the pools out alike."""
    from ..ops.pallas import ctx_attention as ck

    heads, isz = ev.num_heads, jnp.dtype(dtype).itemsize
    pages = ev.window // block_size + 1  # a table wider than the kernel's key tile
    while heads % 2 == 0 and not ck.fits_vmem(
            pack_tokens, heads, heads, ev.head_dim, block_size, pages, isz):
        heads //= 2
    return heads


def _init_eva_cache(cfg, num_blocks: int, block_size: int, pack_tokens: int, dtype) -> Cache:
    """The cache of a model of EVA attention: K / V pages, ``eva_heads_a_pool``
    heads a pool (layer ``i``'s pools are ``[i n, (i + 1) n)`` of the tuples)."""
    from .paged import init_paged_cache

    ev = cfg.latent.eva
    if ev.window % block_size or ev.rows_a_closed_window != block_size:
        raise ValueError(
            f"a closed window of {ev.window} positions keeps {ev.rows_a_closed_window} "
            f"summary rows: they are ONE page only at a block size of that many rows "
            f"(got {block_size})")
    if cfg.latent.count("eva") != cfg.num_layers:
        raise ValueError("EVA attention beside another mixer has no cache layout yet")
    hp = eva_heads_a_pool(ev, block_size, pack_tokens, dtype)
    k, v = init_paged_cache(cfg.num_layers * (ev.num_heads // hp), num_blocks, block_size,
                            hp, ev.head_dim, dtype=dtype)
    return {"ssm": (), "conv": (), "k": k, "v": v,
            "stats": jnp.zeros((0, len(ROUTING_STATS)), jnp.int32)}


def _tally(picks, new):
    """``picks`` [F, 2] with ``new`` [F] (each under 2^30) added in."""
    lo = picks[:, 1] + new
    return jnp.stack([picks[:, 0] + (lo >> _CARRY), lo & ((1 << _CARRY) - 1)], axis=1)


def picks_total(picks) -> int:
    """The count ``cache["picks"]`` holds, all layers (host side)."""
    p = np.asarray(picks).astype(np.int64)
    return int((p[:, 0] << _CARRY).sum() + p[:, 1].sum())


def _attend_selected(s, q_abs, q_i, w, q_pos, tables, lat, idx, real, picked, probe=None):
    """Full layers: groups [G, C, ...] of queries, ``tables`` [G, P] each
    group's block table.  Index scores over the group's pages, exact top-k,
    attention over the selected rows of the latent pages, by one of two
    schedules of the same softmax over the same picks: a pack's groups whose
    last position is under ``la.DENSE_KEYS_MAX`` WALK their live pages in place
    with the picks as a mask (the Pallas kernel ``selected_attn``: no row is
    copied, no ``lat[table]`` laid out); the other groups, a decode tick's
    rows and every shape the kernel's gate declines GATHER their picked rows
    (``la.sparse_attention``).  The picks, their count and the probe come from
    ``select_topk`` on either path.  ``picked`` (a list)
    is handed how many keys the ``real`` [G, C] rows selected, ``probe`` (a
    list) what was selected: positions and their scores, [G, C, k]."""
    a, (g, c) = s.full, q_pos.shape
    nb, bs, _ = idx.shape
    kb = la.index_key_block(c, s.index_heads, tables.shape[1] * bs, bs)
    kp = kb // bs
    tables = jnp.pad(jnp.maximum(tables, 0), ((0, 0), (0, -tables.shape[1] % kp)))
    k_pad = tables.shape[1] * bs
    q_abs = jnp.pad(q_abs, ((0, 0),) * 3 + ((0, lat.shape[-1] - a.row),))

    def scores_of(q_i, w, q_pos, table):
        live = (jnp.max(q_pos) + kb) // kb  # key blocks that hold a key <= a query
        key_block = lambda b: idx[jax.lax.dynamic_slice_in_dim(table, b * kp, kp)
                                  ].reshape(kb, s.index_dim)
        return la.index_scores(q_i, w, q_pos, key_block, live, kb, k_pad, s.index_scale)

    if c == 1:
        # a decode tick: the groups are single rows, batched; a row's keys
        # come straight out of the pages
        def row(q_abs, q_i, w, q_pos, table, n_live):
            vals, ix = la.select_topk(scores_of(q_i, w, q_pos, table), s.index_topk, n_live)
            rows_of = lambda ix: lat[table[ix // bs], ix % bs]
            o = la.sparse_attention(q_abs, ix, vals > -jnp.inf, rows_of, a.kv_rank, a.scale)
            return o, ix, vals

        o, ix, vals = jax.vmap(row, in_axes=(0, 0, 0, 0, 0, None))(
            q_abs, q_i, w, q_pos, tables, jnp.max(q_pos) + 1)
    else:
        # a pack: each group is a page of one sequence's queries
        if _index_kernel_takes(c, s.index_heads, s.index_dim, bs):
            with jax.named_scope("indexer"):  # every group's scores in one call
                raw = index_kernel.paged_index_scores(
                    q_i, w, idx, tables, (jnp.max(q_pos, axis=1) + bs) // bs, s.index_scale)
                scores = jnp.where(jnp.arange(k_pad)[None, None, :] <= q_pos[:, :, None],
                                   raw, -jnp.inf)
        else:
            scores = jax.lax.map(lambda xs: scores_of(*xs), (q_i, w, q_pos, tables))

        last = jnp.max(q_pos, axis=1)  # a group's last position: its context ends there
        vals, ix = jax.lax.map(lambda xs: la.select_topk(xs[0], s.index_topk, xs[1] + 1),
                               (scores, last))

        def gathered(q_abs, ix, vals, table):
            # the sequence's pages laid out once (whole pages move at the
            # memory's speed), then rows by position; the barrier keeps the
            # page lookup out of every row's fetch
            own = jax.lax.optimization_barrier(lat[table].reshape(k_pad, lat.shape[-1]))
            return la.sparse_attention(q_abs, ix, vals > -jnp.inf, lambda r: own[r],
                                       a.kv_rank, a.scale)

        if _selected_kernel_takes(c, q_abs.shape[2], lat.shape[-1], a.kv_rank, bs):
            # a short context's groups walk their pages whole, the picks a mask
            dense = last < la.DENSE_KEYS_MAX
            with jax.named_scope("sparse_attn"):
                walked = selected_kernel.selected_attention(
                    q_abs, la.selected_mask(scores, vals, ix), lat, tables,
                    jnp.where(dense, last // bs + 1, 0), a.kv_rank, a.scale)
            o = jax.lax.map(lambda xs: jax.lax.cond(xs[0], lambda: xs[1],
                                                    lambda: gathered(*xs[2:])),
                            (dense, walked, q_abs, ix, vals, tables))
        else:
            o = jax.lax.map(lambda xs: gathered(*xs), (q_abs, ix, vals, tables))
    picked.append(jnp.sum((vals > -jnp.inf) & real[..., None], dtype=jnp.int32))
    if probe is not None:
        probe.append({"index_picked": ix, "index_values": vals})
    return o


def _attend_every(a, q, w_uk, w_uv, lat, tables, slot, live, q_pos):
    """An ``every`` layer's pack: queries ``q`` [T, H, nope + rope] BEFORE
    ``W_uk`` (rotated), a group a page of one sequence (``slot``, ``live`` [G];
    ``q_pos`` [G, C]), each over EVERY cached row of its sequence (``tables`` by
    slot) up to its own position.  ONE softmax under one scope
    (``mla_prefill``), in the form its length makes the cheaper one, decided
    here from what the program sees (``la.pack_runs``): a RUN of at least
    ``la.run_groups`` consecutive pages of one sequence (171 queries -> 2
    pages at DeepSeek-V2's widths: a document's chunk) attends DECOMPRESSED
    through the Pallas kernel ``latent_prefill`` (``W_uk`` / ``W_uv`` on a
    block of rows once for all of the run's queries); every other live group
    (a question behind a prefix hit, a document's last odd page) is folded
    through ``W_uk``, WALKS its sequence's pages through ``selected_attn`` with
    the causal positions as its mask, and is folded through ``W_uv``: the
    absorbed form.  Each kernel is handed 0 pages for the other's groups, and a
    pack with no group of a form skips that form altogether (its folds
    too).  A shape either kernel declines, and the CPU, takes the absorbed XLA
    body (``la.dense_attention_pack``) or the walk for every group.  Returns
    the heads' values [T, H, v] (after ``W_uv``)."""
    (g, c), (t, h, _) = q_pos.shape, q.shape
    bs, lanes = lat.shape[1], lat.shape[2]

    def fold():  # the absorbed queries on the pages' lanes, by group
        q_abs = lm.absorbed_queries(w_uk, q, q[..., a.nope_dim:], a)
        return jnp.pad(q_abs, ((0, 0), (0, 0), (0, lanes - a.row))).reshape(g, c, h, lanes)

    unfold = lambda o: lm.latent_values(w_uv, o.reshape(t, h, a.kv_rank), a)
    if not _selected_kernel_takes(c, h, lanes, a.kv_rank, bs):
        return unfold(la.dense_attention_pack(fold(), lat, tables[slot], live, q_pos, a))

    def walked(which):
        q_abs = fold()
        with jax.named_scope("mla_prefill"):
            own = jnp.maximum(tables[slot], 0)
            keys = jnp.arange(own.shape[1] * bs)
            mask = (keys[None, None, :] <= q_pos[:, :, None]).astype(jnp.int8)
            last = jnp.max(q_pos, axis=1)
            o = _walk(q_abs, mask, lat, own, jnp.where(which, last // bs + 1, 0), r_kv=a.kv_rank,
                      scale=a.scale, traced_at=(selected_kernel.TQ, selected_kernel.KP,
                                                selected_kernel.interpret()))
        return unfold(o)

    rows = lambda of: jnp.repeat(of, c)[:, None, None]  # a group's flag on its queries
    if not _prefill_kernel_takes(t, h, lanes, a.kv_rank, a.nope_dim, a.v_dim, bs):
        return jnp.where(rows(live), walked(live), 0)  # a dead page of the pack is left unwritten
    long, runs, run_slot = la.pack_runs(slot, live, q_pos[:, 0], c, la.run_groups(a, c))

    def decompressed():
        with jax.named_scope("mla_prefill"):
            # head-major: a head's queries on the lanes of [k_nope ; row[r_kv:]], its [W_uk | W_uv]
            q_rows = jnp.pad(q, ((0, 0), (0, 0), (0, lanes - a.row))).transpose(1, 0, 2)
            w_ukv = jnp.concatenate([w_uk.reshape(a.kv_rank, h, a.nope_dim),
                                     w_uv.reshape(a.kv_rank, h, a.v_dim)], axis=-1)
            o = prefill_kernel.latent_prefill(
                q_rows, w_ukv.transpose(1, 0, 2), lat, jnp.maximum(tables[run_slot], 0), runs,
                a.kv_rank, a.scale)
            return o.transpose(1, 0, 2)

    short = live & ~long
    absorbed = lambda others: jnp.where(rows(short), walked(short), others)
    return jax.lax.switch(  # by the forms the pack holds: none, long runs, short ones, both
        jnp.any(long) + 2 * jnp.any(short),
        [lambda: jnp.zeros((t, h, a.v_dim), q.dtype), decompressed,
         lambda: absorbed(0), lambda: absorbed(decompressed())])


@functools.partial(jax.jit, static_argnames=("r_kv", "scale", "traced_at"))
def _walk(q_abs, mask, lat, tables, live_pages, *, r_kv: int, scale: float, traced_at):
    """``selected_attention``, traced and lowered ONCE for the layers of a
    program and the branches that call it alike (``latent_prefill`` does the
    same for itself); ``traced_at``: what that kernel reads when it is traced,
    its tiles and the interpret switch."""
    return selected_kernel.selected_attention(q_abs, mask, lat, tables, live_pages, r_kv, scale)


def _kernel_takes(name: str, kernel, shape, declines: str) -> bool:
    """A Pallas kernel's gate by shape (``kernel.supports(*shape)``, on a TPU
    or interpreted), noted for ``record_dispatch()``.  The selector's two
    kernels serve a pack's pages of queries (a decode tick's single rows stay on
    its XLA bodies by design and never ask); ``latent_decode`` serves a tick's."""
    interpret = kernel.interpret()
    if not (interpret or on_tpu()):
        reason = "not on a TPU"
    elif not kernel.supports(*shape):
        reason = declines
    else:
        note_dispatch(name, True, shape, interpret=interpret)
        return True
    note_dispatch(name, False, shape, reason=reason)
    return False


def _selected_kernel_takes(c: int, h: int, w: int, r_kv: int, bs: int) -> bool:
    """The gate of the Pallas selected-attention kernel, by shape.  Which of a
    pack's groups it then serves is decided inside the program, by length
    (``latent_attention.DENSE_KEYS_MAX``)."""
    return _kernel_takes("selected_attn", selected_kernel, (c, h, w, r_kv, bs),
                         "whole query tiles; page, row and value lanes whole 128-lane tiles")


def _prefill_kernel_takes(t: int, h: int, w: int, r_kv: int, nope: int, v: int, bs: int) -> bool:
    """The gate of the Pallas kernel for a pack's runs in the decompressed form,
    by shape.  Which groups it then serves is decided inside the program, by
    the length of their run (``latent_attention.run_groups``)."""
    return _kernel_takes("latent_prefill", prefill_kernel, (t, h, w, r_kv, nope, v, bs),
                         "whole query tiles and head blocks; page, latent, rope, key and value "
                         "lanes whole 128-lane tiles")


def _decode_kernel_takes(h: int, w: int, r_kv: int, bs: int) -> bool:
    """The gate of the Pallas kernel for a tick's rows over every cached row."""
    return _kernel_takes("latent_decode", decode_kernel, (h, w, r_kv, bs),
                         "page, row and value lanes whole 128-lane tiles, heads whole sublane tiles")


def _index_kernel_takes(c: int, j: int, d: int, bs: int) -> bool:
    """The gate of the Pallas index-scores kernel (a page of queries at a time)."""
    return _kernel_takes("index_scores", index_kernel, (c, j, d, bs),
                         "queries, page and head size must be whole 128-lane tiles")


def _layer(cfg, l, layers, x, pos, valid, cache, write, read, pack_rows, probe):
    """One layer on token rows ``x`` [T, d].  The seam: ``write(kind, arrays,
    rows)`` returns the kind's cache arrays with the new rows in, ``read(kind,
    arrays, queries)`` attends over them.  ``pack_rows``: how many of the rows,
    the first, are a PACK's tokens (0: a tick's slot rows alone; fewer than T: the
    tick's ride behind them); the expert layers count on the pack's side where
    there is one (``_experts``) and lay out for its rows (``_row_tile``).
    Returns (x, cache)."""
    s = cfg.latent
    if s.single:
        return _block(cfg, l, layers, x, pos, valid, cache, write, read, pack_rows, probe)
    if s.hybrid:
        return _hybrid_block(cfg, l, layers, x, pos, valid, cache, write, read, pack_rows,
                             probe)
    kind, (n1, n2), aw, fw, is_moe = lm.layer_params(layers, l, s)
    a, i = s.attn(kind), s.layer_kinds[:l].count(kind)
    h = lm.rms(x, n1["scale"], cfg.norm_eps)
    # the kind over every row hands its queries BEFORE ``W_uk`` and takes the heads'
    # values back: its read folds where the form it chooses needs it
    every = kind == "every"
    c_q, q_abs, row, gate = lm.attn_inputs(aw, h, pos, a, cfg, absorbed=not every)
    keys, rows, queries = ("win",), (row,), (q_abs,)
    if kind == "full":
        q_i, k_i, w = lm.indexer_inputs(aw, h, c_q, pos, s, cfg)
        keys, rows, queries = ("lat", "idx"), (row, k_i), (q_abs, q_i, w)
    elif every:
        keys, queries = ("lat",), (q_abs, aw["w_uk"], aw["w_uv"])
    arrays = write(kind, tuple(cache[k][i] for k in keys), rows)
    cache = {**cache, **{k: _put(cache[k], i, v) for k, v in zip(keys, arrays)}}
    o = read(kind, arrays, queries)
    x = x + lm.attn_output(aw, o, gate, a, values=every).astype(x.dtype)
    h = lm.rms(x, n2["scale"], cfg.norm_eps)
    if is_moe and "touched" in cache:
        y, cache = _experts(cfg, l - s.first_dense, fw, h, valid, cache, pack_rows, probe)
        return x + y.astype(x.dtype), cache
    y, routing = lm.ffn(fw, h, is_moe, cfg, valid, _row_tile(cfg, h.shape[0], pack_rows))
    if routing is not None:
        routed, picked, _ = routing
        if probe is not None:
            probe.append({"experts_picked": picked})
        cache = {**cache, "stats": _routing_counted(
            cache["stats"], l - s.first_dense, routed, pack_rows > 0)}
    return x + y.astype(x.dtype), cache


def _row_tile(cfg, rows: int, pack_rows: int):
    """The row tile an expert layer lays ``rows`` rows out on: its own rule at
    its rows (None) for a pack or a tick alone.  Behind a pack's ``pack_rows``
    tokens a tick's slot rows change what a group EXPECTS by a sixteenth at most
    and are live only in part, so the groups keep the PACK's tile: 544 rows of
    cell 8 (17 expected, twice that past 32) would take 64-row tiles for the
    pack's 32 and lay out 20 736 rows for 12 288, which costs every op around
    the products (``moe/layer.py:held_row_tile``).  A group that outgrows its
    tile takes another, as ever."""
    return held_row_tile(pack_rows, cfg.latent) if 0 < pack_rows < rows else None


def _routing_counted(st, m: int, routed, track_groups: bool):
    """``stats`` with expert layer ``m``'s ``routed`` (ROUTING_STATS) counted in."""
    new = jnp.stack([st[m, 0] + routed[0], st[m, 1] + routed[1],
                     jnp.maximum(st[m, 2], routed[2]) if track_groups else st[m, 2],
                     jnp.minimum(st[m, 3], routed[3]) if track_groups else st[m, 3]])
    return st.at[m].set(new)


def _block(cfg, l, layers, x, pos, valid, cache, write, read, pack_rows, probe):
    """One single-mixer block on token rows ``x`` [T, d], through the same
    seam (``_mixer``); an expert block keeps nothing."""
    s = cfg.latent
    kind, scale, w = lm.block_params(layers, l, s)
    i = s.layer_kinds[:l].count(kind)
    h = lm.rms(x, scale, cfg.norm_eps)
    if kind == "experts":
        y, cache = _experts(cfg, i, w, h, valid, cache, pack_rows, probe)
    else:
        y, cache = _mixer(s, kind, i, w, h, pos, cache, write, read)
    return x + y.astype(x.dtype), cache


def _mixer(s, kind, i, w, h, pos, cache, write, read):
    """Block ``i`` of its kind's mixer on normed rows ``h``, through the seam, for
    every block that holds a recurrence or position-free / rotary GQA (a
    single-mixer block, a two-norm block's one mixer, either side of two parallel
    ones): a recurrence's ``write`` IS its read (the scan that carries the state
    on yields the outputs: (state, conv tail, y)), attention writes K / V rows and
    reads the pools.  Returns (y, cache)."""
    if kind in lm.RECURRENCES:
        ssm, conv, y = write(kind, (cache["ssm"][i], cache["conv"][i]), (w, h))
        return y, {**cache, "ssm": _put(cache["ssm"], i, ssm),
                   "conv": _put(cache["conv"], i, conv)}
    o, cache = _through_pools(kind, i, ("k", "v"), lm.gqa_inputs(w, h, s.gqa, pos), cache,
                              write, read)  # (under ``gqa_attn``: ``_scoped``)
    return lm.gqa_output(w, o.astype(h.dtype), s.gqa), cache


def _through_pools(kind, i, names, qkv, cache, write, read):
    """Block ``i``'s new K / V rows into its kind's pools (``names``: the cache's
    keys of the pages, or of the rings) and its queries over them: (o, cache)."""
    kk, kv = names
    pools = write(kind, (cache[kk][i], cache[kv][i]), qkv[1:])
    cache = {**cache, kk: _put(cache[kk], i, pools[0]), kv: _put(cache[kv], i, pools[1])}
    return read(kind, pools, qkv), cache


def _experts(cfg, i, w, h, valid, cache, pack_rows, probe):
    """Expert layer ``i`` on normed rows ``h``: (y, cache with its routing and
    the held experts it touched counted, a pack's and a tick's apart).  A pack
    that carries a tick's rows is ONE layout and ONE set of products, counted on
    the PACK's side whole: an expert that both kinds of row touch is read once
    and counted once (the ``any`` over all the rows), as a reader that divides
    the packs' touched experts by the pack program's time needs it."""
    s = cfg.latent
    y, (routed, picked, _) = lm.ffn(w, h, True, cfg, valid, _row_tile(cfg, h.shape[0], pack_rows))
    if probe is not None:
        probe.append({"experts_picked": picked})
    local = picked - s.held_offset
    rows = (local[..., None] == jnp.arange(s.n_held)) & valid[:, None, None]
    return y, {**cache,
               "stats": _routing_counted(cache["stats"], i, routed, pack_rows > 0),
               "touched": cache["touched"].at[i, 0 if pack_rows else 1].add(jnp.stack(
                   [jnp.sum(jnp.any(rows, axis=(0, 1)), dtype=jnp.int32), routed[1]]))}


def _hybrid_block(cfg, l, layers, x, pos, valid, cache, write, read, pack_rows, probe):
    """One two-norm block (``models/latent.py:HYBRID``) on token rows ``x``
    [T, d], through ``_block``'s seam: a Gated DeltaNet mixer's ``write`` IS its
    read, gated attention writes K / V rows (the keys normed and rotated) and
    reads the pools (its kind's: pages, or a ring a slot), with the q / k norms,
    the rotation and the output gate outside the kernels; then the feed-forward
    (a leading layer's dense SwiGLU, else the expert layer)."""
    s, eps = cfg.latent, cfg.norm_eps
    kind, (n1, n2), mw, fw, is_moe = lm.hybrid_params(layers, l, s)
    i = s.layer_kinds[:l].count(kind)
    h = lm.norm(x, n1, cfg)
    if kind in lm.RECURRENCES or kind == "gqa":  # ONE mixer, chosen by block
        y, cache = _mixer(s, kind, i, mw, h, pos, cache, write, read)
    elif kind == "par":
        # TWO mixers on the ONE normed input, summed: the recurrence through its seam
        # (state and conv tail of block ``i``) AND block ``i``'s K / V write and read
        y, cache = _mixer(s, "mamba", i, mw["mamba"], h, pos, cache, write, read)
        o, cache = _mixer(s, "gqa", i, mw["gqa"], h, pos, cache, write, read)
        y = y.astype(x.dtype) + o
    elif kind == "eva":
        q, k, v = lm.eva_inputs(mw, h, pos, s.eva)
        n = len(cache["k"]) // s.count("eva")  # pools a layer, some of the heads each
        mine = slice(i * n, (i + 1) * n)
        pools = write(kind, (cache["k"][mine], cache["v"][mine]), (k, v, mw["phi"], mw["mu"]))
        cache = {**cache, "k": cache["k"][:mine.start] + pools[0] + cache["k"][mine.stop:],
                 "v": cache["v"][:mine.start] + pools[1] + cache["v"][mine.stop:]}
        o = read(kind, pools, (q, k, v))  # (under ``eva_attend``: ``_scoped``)
        if probe is not None and i == 0:
            # the first layer's rows as attention took them and what it made of them
            probe.append({"eva_q": q, "eva_k": k, "eva_v": v, "eva_o": o})
        y = o.reshape(x.shape[0], -1).astype(x.dtype) @ mw["wo"]
    else:
        *qkv, gate = lm.gattn_inputs(mw, h, pos, s.mixer(kind), eps, s.unit_offset)
        # pages for the kind over every key, a ring a slot for the kind over a window
        o, cache = _through_pools(kind, i, ("wk", "wv") if kind == "wattn" else ("k", "v"),
                                  qkv, cache, write, read)  # (under ``_attn_scope``'s name)
        y = lm.gattn_output(mw, o.astype(x.dtype), gate)
    x = lm.residual(x, y, s.residual_multiplier)
    h = lm.norm(x, n2, cfg)
    if is_moe:
        y, cache = _experts(cfg, l - s.first_dense, fw, h, valid, cache, pack_rows, probe)
    else:
        y = lm.ffn(fw, h, False, cfg)[0]
    return lm.residual(x, y, s.residual_multiplier), cache


def _attn_scope(s, kind: str) -> str:
    """The named scope of a two-norm block's attention: a model of ONE kind of
    gated attention calls it ``gated_attn``; where two kinds stand side by side
    each has its own name, so that a trace tells their times apart."""
    if s.wattn is None:
        return "gated_attn"
    return "window_attn" if kind == "wattn" else "full_attn"


def _scoped(s, read):
    """A seam's ``read`` under the named scope of its kind's attention, where the
    blocks name it (``gqa_attn``, ``eva_attend``, ``_attn_scope``; the latent
    kinds' bodies name themselves, ``ops/latent_attention.py``).  Opened through
    ``la.scope``, so the step a pack carries has ``<name>_step`` beside it."""
    def scoped(kind, arrays, queries):
        name = {"gqa": "gqa_attn", "eva": "eva_attend"}.get(kind) or (
            _attn_scope(s, kind) if kind in ("gattn", "wattn") else None)
        if name is None:
            return read(kind, arrays, queries)
        with la.scope(name):
            return read(kind, arrays, queries)

    return scoped


# the operands a seam is handed that are the layer's WEIGHTS and no row's, by kind:
# positions in ``write``'s tuple and in ``read``'s (all else is [rows, ...]): a
# recurrence's (w, h), EVA's (k, v, phi, mu), the queries (q, w_uk, w_uv) of ``every``
_WEIGHTS = {"mamba": ((0,), ()), "gdn": ((0,), ()), "eva": ((2, 3), ()), "every": ((), (1, 2))}


def _carrying(t: int, pack, tick):
    """The seam of a pack that carries the tick's step: rows ``[:t]`` are the
    pack's tokens and go through ``pack`` = its (write, read), rows ``[t:]`` the
    tick's slot rows through ``tick``'s, the cache arrays threaded pack first,
    then step (the two programs' order), the outputs concatenated.  The two
    kinds hold disjoint sequences (the scheduler's contract; ``pack_dispatch``
    raises otherwise), hence disjoint slots, states, rings, pages and table rows:
    what this computes is what the pack and then the step computed in turn.  A
    recurrence's ``write`` IS its read and returns (state, conv tail, y): the
    pack's scan and the step's update touch different slots' states, ``y`` is
    concatenated.  The step's bodies are traced inside ``la.carried_step()``."""
    def split(kind, which, operands):
        shared = _WEIGHTS.get(kind, ((), ()))[which]
        return [tuple(a if i in shared else a[rows] for i, a in enumerate(operands))
                for rows in (slice(None, t), slice(t, None))]

    def write(kind, arrays, new):
        ours, theirs = split(kind, 0, new)
        n = len(arrays)
        out = pack[0](kind, arrays, ours)
        with la.carried_step():
            step = tick[0](kind, tuple(out[:n]), theirs)
        return (*step[:n], *(jnp.concatenate(ys) for ys in zip(out[n:], step[n:])))

    def read(kind, arrays, queries):
        ours, theirs = split(kind, 1, queries)
        o = pack[1](kind, arrays, ours)
        with la.carried_step():
            return jnp.concatenate([o, tick[1](kind, arrays, theirs)])

    return write, read


def _fit(rows, pages):
    """Rows [T, w] in the pages' dtype and width (zeros past ``w``)."""
    return jnp.pad(rows.astype(pages.dtype), ((0, 0), (0, pages.shape[-1] - rows.shape[-1])))


def _put(items: tuple, i: int, value) -> tuple:
    return items[:i] + (value,) + items[i + 1:]


def _logits(params, cfg, x):
    with jax.named_scope("lm_head"):
        x = lm.norm(x, params["final_norm"]["scale"], cfg)
        return lm.head_logits(x, params, cfg).astype(jnp.float32)


def _seams(cfg):
    """(a pack's seam, a tick's) of the model's family."""
    if cfg.latent.eva is not None:
        return _eva_pack_seam, _eva_tick_seam
    if cfg.latent.stateful:
        return _state_pack_seam, _state_tick_seam
    return _latent_pack_seam, _latent_tick_seam


def prefill_pack(params, cfg, tokens, segment_ids, positions, pack_pages, last_idx,
                 tables, cache: Cache, probe=None, step=None):
    """One prefill pack (the arguments of ``model_runner.prefill_packed_ctx``
    less ``ctx_lens``: a token's position says where its context ends).
    ``tables`` [N, P] are the block tables by slot, this pack's pages included.
    ``probe`` (a list) collects, layer by layer, what the indexers and the
    routers picked, what a state-space block's recurrence consumed, what a
    window's mask let each query see, and the first EVA layer's rows and output.
    Returns (logits [N, vocab], cache).

    With ``step`` (the tick's decode rows as ``decode_step`` takes them: tokens,
    positions, block tables, live mask, a row a slot) the step's B rows follow
    the pack's T through every norm, projection, router and grouped product and
    ONE head matmul: ONE stream of the weights.  Each kind of row keeps its own
    cache write and its own attention or recurrence (``_carrying``), and the
    result is ((pack logits [N, vocab], step logits [B, vocab]), cache):
    ``model_runner._pack``'s contract."""
    t = tokens.shape[0]
    valid = segment_ids > 0
    picked: list = []
    pack_seam, tick_seam = _seams(cfg)
    write, read = pack_seam(cfg, segment_ids, valid, positions, pack_pages, tables, cache,
                            picked, probe)
    read = _scoped(cfg.latent, read)
    if step is not None:
        s_tokens, s_pos, s_tables, s_active = step
        stepped: list = []  # what the step's selectors took, a full layer
        tick = tick_seam(cfg, s_pos, s_tables, s_active, stepped, probe)
        write, read = _carrying(t, (write, read), (tick[0], _scoped(cfg.latent, tick[1])))
        tokens, positions, valid = (jnp.concatenate(a) for a in (
            (tokens, s_tokens), (positions, s_pos), (valid, s_active)))
    x = lm.embedded(params, tokens, cfg)
    for l in range(cfg.num_layers):
        x, cache = _layer(cfg, l, params["layers"], x, positions, valid, cache,
                          write, read, t, probe)
    if picked:
        cache = {**cache, "picks": _tally(cache["picks"], jnp.stack(picked))}
    last = x[jnp.clip(last_idx, 0, t - 1)]
    if step is None:
        return _logits(params, cfg, last), cache
    if stepped:  # (tallied apart, as by the step's own program: each count under 2^30)
        cache = {**cache, "picks": _tally(cache["picks"], jnp.stack(stepped))}
    logits = _logits(params, cfg, jnp.concatenate([last, x[t:]]))
    return (logits[:last.shape[0]], logits[last.shape[0]:]), cache


def _latent_pack_seam(cfg, segment_ids, valid, positions, pack_pages, tables, cache, picked,
                      probe):
    """A pack's (write, read) for latent pages and window rings."""
    s, t = cfg.latent, segment_ids.shape[0]
    nb, bs, _ = cache["lat"][0].shape if cache["lat"] else cache["win"][0].shape
    g = t // bs
    ring = cache["win"][0].shape[0] * bs // tables.shape[0] if cache["win"] else 0
    if ring and ring < ring_rows(cfg, bs, t):
        raise ValueError(f"a pack of {t} tokens needs rings of {ring_rows(cfg, bs, t)} "
                         f"rows; the cache was built with {ring}")
    slot = jnp.maximum(segment_ids[::bs] - 1, 0)  # a page of the pack is one sequence's
    live = segment_ids[::bs] > 0
    page0 = positions[::bs] // bs                 # ... and starts on one of its pages
    grouped = lambda a: a.reshape(g, bs, *a.shape[1:])
    q_pos = grouped(positions)
    safe_pages = jnp.where(pack_pages >= 0, pack_pages, nb)
    rc = ring // bs
    back = -(-(s.sliding.window - 1) // bs) if ring else 0

    def write(kind, arrays, rows):
        if kind != "sliding":  # pages: a full layer's two arrays, an every layer's one
            return tuple(a.at[safe_pages].set(grouped(_fit(r, a)), mode="drop")
                         for a, r in zip(arrays, rows))
        (win,), (row,) = arrays, rows
        to = jnp.where(live, slot * rc + page0 % rc, win.shape[0])
        return (win.at[to].set(grouped(row).astype(win.dtype), mode="drop"),)

    def read(kind, arrays, queries):
        if kind == "full":
            q_abs, q_i, w = map(grouped, queries)
            o = _attend_selected(s, q_abs, q_i, w, q_pos, tables[slot], *arrays,
                                 grouped(valid), picked, probe=probe)
        elif kind == "every":
            return _attend_every(s.every, *queries, arrays[0], tables, slot, live, q_pos)
        else:
            # the group's own page and the ``back`` pages before it, from the ring
            j = jnp.arange(back + 1)
            pages = page0[:, None] - j[None, :]                       # [G, back+1]
            keys = arrays[0][slot[:, None] * rc + pages % rc]         # [G, back+1, bs, W]
            key_pos = jnp.where(pages[..., None] >= 0,
                                pages[..., None] * bs + jnp.arange(bs), -1)
            o = la.window_attention(
                grouped(queries[0]), q_pos, keys.reshape(g, -1, keys.shape[-1]),
                key_pos.reshape(g, -1), s.sliding.window, s.sliding.kv_rank,
                s.sliding.scale)
        return o.reshape(t, *o.shape[2:])

    return write, read


def _write_pages(pool, rows, pages):
    """rows [G, bs, hkv, hd] into pages ``pages`` [G] (-1: none) of ``pool``,
    a page at a time IN PLACE.  ``paged.write_pack_kv``'s one scatter makes
    XLA:TPU re-lay a pool of 2 KV heads out and back around it (bs becomes the
    tiled dim: four copies of the 0.4 GB pool a pack, seen in the program
    compiled for the chip); a dynamic-update-slice keeps the pool's layout."""
    for g in range(rows.shape[0]):
        at = (jnp.maximum(pages[g], 0), 0, 0, 0)
        old = jax.lax.dynamic_slice(pool, at, (1, *pool.shape[1:]))
        new = jnp.where(pages[g] >= 0, rows[g][None].astype(pool.dtype), old)
        pool = jax.lax.dynamic_update_slice(pool, new, at)
    return pool


def _slot_states(ssm, slot):
    """``ssm[slot]``: the states [G, H, P, N] of slots ``slot`` [G] (each in range)
    out of a block's ``ssm`` [slots, H, P, N], in the dtype it is kept in.  A state
    whose minor dimension is wider than one 128-lane tile is read a slot at a time:
    XLA:TPU lowers the gather of such an operand to a ``mini-gather`` that first
    slices the WHOLE operand into 128-lane halves (Falcon-H1's ``f32[48, 32, 128,
    256]``: 201 MB read and written a block to fetch 16 MiB, in the device's trace),
    and a dynamic slice names one slot and reads one.  A state of one tile or less
    keeps the gather, which reads its slots in place: the pack programs of those
    models compile to what they were, instruction for instruction."""
    if ssm.shape[-1] <= 128:
        return ssm[slot]
    return jnp.concatenate([jax.lax.dynamic_slice_in_dim(ssm, slot[g], 1)
                            for g in range(slot.shape[0])])


def _state_pack_seam(cfg, segment_ids, valid, positions, pack_pages, tables, cache, picked,
                     probe):
    """A pack's (write, read) for blocks that keep a recurrence's state, K / V
    pages or a K / V ring (``LatentSpec.stateful``).  The pack is chunks of
    one page of one sequence (``bs`` tokens: the scan's chunk); a chunk whose
    first position is 0 starts from ZEROS, a chunk that follows its own
    sequence's chunk in the pack takes the state handed over inside the scan,
    any other loads its slot's; the state after a sequence's last chunk in the
    pack is kept.  Attention reads [the cached pages under the chunk's start |
    the pack's own rows] as a dense model's chunked prefill does; attention over a
    window reads a chunk's own page and the look-back's out of its slot's ring
    (``ops/gated_attention.py``), which the whole pack's rows were written to first."""
    from ..ops import gated_attention as ga
    from .paged import paged_attention_packed_ctx

    s, t = cfg.latent, segment_ids.shape[0]
    g = pack_pages.shape[0]
    bs, n_slots = t // g, tables.shape[0]
    slot = jnp.maximum(segment_ids[::bs] - 1, 0)  # a page of the pack is one sequence's
    live = segment_ids[::bs] > 0
    start = positions[::bs]
    fresh = live & (start == 0)
    same = jnp.concatenate([jnp.zeros((1,), bool), (slot[1:] == slot[:-1]) & live[:-1]])
    cont = live & same & ~fresh
    # the sequence's last chunk in this pack: its state is what the slot keeps
    last = live & ~jnp.concatenate([cont[1:], jnp.zeros((1,), bool)])
    keep = jnp.where(last, slot, n_slots)
    # the context under each sequence's first chunk here (``ctx_lens``)
    first = live & ~cont
    ctx_lens = jnp.zeros((n_slots,), jnp.int32).at[
        jnp.where(first, slot, n_slots)].set(start, mode="drop")
    grouped = lambda a: a.reshape(g, bs, *a.shape[1:])

    def write(kind, arrays, rows):
        if kind in lm.RECURRENCES:
            (ssm, conv), (w, h) = arrays, rows
            zero = lambda a: jnp.where(fresh.reshape(g, *(1,) * (a.ndim - 1)), 0, a)
            chunks, _ = lm.RECURRENCES[kind]
            y, states, tails = chunks(
                w, grouped(h), grouped(valid), cont, zero(conv[slot]),
                zero(_slot_states(ssm, slot)), s.recurrence[1], cfg.norm_eps, probe)
            return (ssm.at[keep].set(states.astype(ssm.dtype), mode="drop"),
                    conv.at[keep].set(tails.astype(conv.dtype), mode="drop"),
                    y.reshape(t, -1))
        pages = pack_pages
        if kind == "wattn":  # the ring's page of this page of the sequence
            rc = arrays[0].shape[0] // n_slots
            if rc * bs < ring_rows(cfg, bs, t):
                raise ValueError(f"a pack of {t} tokens needs rings of {ring_rows(cfg, bs, t)} "
                                 f"rows; the cache was built with {rc * bs}")
            pages = jnp.where(live, slot * rc + start // bs % rc, -1)
        return tuple(_write_pages(a, grouped(r), pages) for a, r in zip(arrays, rows))

    def read(kind, pools, qkv):
        if kind == "wattn":
            # a page of the pack starts on one of its sequence's pages: start // bs
            o = ga.ring_attention(grouped(qkv[0]), grouped(positions), *pools, slot,
                                  start // bs, pools[0].shape[0] // n_slots, s.wattn.window,
                                  probe)
            return o.reshape(t, *o.shape[2:])
        return _by_whole_groups(
            lambda q: paged_attention_packed_ctx(q, *qkv[1:], segment_ids, *pools, tables,
                                                 ctx_lens, scale=_softmax_scale(s, kind)),
            qkv[0], qkv[1].shape[1])

    return write, read


def _softmax_scale(s, kind: str):
    """The softmax scale of a kind of attention over K / V pages where it is the
    configuration's own constant (``Gqa.scale``); None: ``head_dim ** -0.5``, the
    kernels' and their XLA fallbacks' own default."""
    return getattr(s.mixer(kind), "scale", None)


def _eva_tables(ev, tables, w, bs: int):
    """The tables EVA attention walks: each slot's with column ``w`` [N] (its
    OPEN window's summary page) taken out, so that the closed windows' pages and
    the open window's exact pages follow one another; cut to the columns a
    sequence of the tables' longest can hold."""
    p = tables.shape[1]
    cols = jnp.arange(min(p, p * bs // ev.window + ev.window // bs))
    src = jnp.where(cols[None, :] < w[:, None], cols[None, :],
                    jnp.minimum(cols[None, :] + 1, p - 1))
    return jnp.take_along_axis(tables, src, axis=1)


def _head_pools(a, n: int):
    """a [T, H, hd] as ``n`` pools' heads, in order."""
    hp = a.shape[1] // n
    return [a[:, j * hp:(j + 1) * hp] for j in range(n)]


def _eva_pack_seam(cfg, segment_ids, valid, positions, pack_pages, tables, cache, picked,
                   probe):
    """A pack's (write, read) for EVA attention (module docstring).  A page of the
    pack is one sequence's and lies inside ONE window (the scheduler cuts a
    prompt's chunks at a window's edge: ``ServeScheduler._plan_prefill``), so its
    queries' context is every row the table holds before the sequence's first
    page here, and the pack's own rows, causal."""
    from ..ops import eva
    from .paged import paged_attention_packed_ctx
    from .ragged import WindowCompaction

    ev, t = cfg.latent.eva, segment_ids.shape[0]
    g = pack_pages.shape[0]
    bs, n_slots = t // g, tables.shape[0]
    per_page = bs // ev.chunk                     # chunks a page of the pack
    slot = jnp.maximum(segment_ids[::bs] - 1, 0)  # a page of the pack is one sequence's
    live = segment_ids[::bs] > 0
    start = positions[::bs]
    same = jnp.concatenate([jnp.zeros((1,), bool), (slot[1:] == slot[:-1]) & live[:-1]])
    first = live & ~same                          # a sequence's first page in this pack
    at = jnp.where(first, slot, n_slots)
    began = jnp.zeros((n_slots,), jnp.int32).at[at].set(start, mode="drop")
    ctx_rows = WindowCompaction(ev.window, ev.chunk).rows_live(began)
    walked = _eva_tables(ev, tables, began // ev.window, bs)
    grouped = lambda a: a.reshape(g, bs, *a.shape[1:])
    # where a page's chunks' summaries go: the open window's summary page, from row
    summary_page = jnp.where(live, tables[slot, start // ev.window], -1)
    row0 = start % ev.window // ev.chunk
    whole = valid.reshape(g, per_page, ev.chunk)[..., -1]  # the chunk's last row is here

    def write(kind, pools, new):
        (kp, vp), (k, v, phi, mu) = pools, new
        n = len(kp)
        split = lambda a: _head_pools(a, n)
        kp, vp = (tuple(_write_pages(a, grouped(r), pack_pages) for a, r in zip(pool, split(x)))
                  for pool, x in ((kp, k), (vp, v)))
        with jax.named_scope("eva_summarise"):
            ks, vs = eva.summarise(*(a.reshape(g * per_page, ev.chunk, *a.shape[1:])
                                     for a in (k, v)), phi, mu)
            return tuple(
                tuple(_merge_rows(a, u.reshape(g, per_page, *u.shape[1:]), summary_page, row0,
                                  whole) for a, u in zip(pool, split(x)))
                for pool, x in ((kp, ks), (vp, vs)))

    def read(kind, pools, qkv):
        n = len(pools[0])
        outs = [paged_attention_packed_ctx(q, k, v, segment_ids, kp, vp, walked, ctx_rows)
                for q, k, v, kp, vp in zip(*(_head_pools(a, n) for a in qkv), *pools)]
        return jnp.concatenate(outs, axis=1)

    return write, read


def _merge_rows(pool, rows, pages, row0, ok):
    """rows [G, R, hkv, hd] into rows ``row0`` [G] .. + R of pages ``pages`` [G]
    (-1: none) of ``pool``, those of ``ok`` [G, R] alone, IN PLACE a group at a
    time (``_write_pages``' dynamic-update-slice: the pool keeps its layout)."""
    for g in range(rows.shape[0]):
        at = (jnp.maximum(pages[g], 0), row0[g], 0, 0)
        old = jax.lax.dynamic_slice(pool, at, (1, *rows.shape[1:]))
        take = (ok[g] & (pages[g] >= 0))[None, :, None, None]
        pool = jax.lax.dynamic_update_slice(
            pool, jnp.where(take, rows[g][None].astype(pool.dtype), old), at)
    return pool


def _eva_tick_seam(cfg, pos, block_tables, active, picked, probe):
    """A decode tick's (write, read) for EVA attention: one new exact row a live
    slot; where it completes a chunk, the chunk's rows are read back out of their
    page, summarised and put into the open window's summary page; then every
    live row of the table (the open window's summary page apart) is attended."""
    from ..ops import eva
    from .paged import paged_attention_decode, write_decode_kv
    from .ragged import WindowCompaction

    ev, b = cfg.latent.eva, pos.shape[0]
    w, inside = pos // ev.window, pos % ev.window
    lens = jnp.where(active, WindowCompaction(ev.window, ev.chunk).rows_live(pos) + 1, 0)
    rows = jnp.arange(b)

    def write(kind, pools, new):
        (kp, vp), (k, v, phi, mu) = pools, new
        n, (nb, bs) = len(kp), kp[0].shape[:2]
        # the open window's exact pages start one column past its summary page
        at = (w + 1) * bs + inside
        page = block_tables[rows, at // bs]
        r0 = inside % bs // ev.chunk * ev.chunk
        done = active & (inside % ev.chunk == ev.chunk - 1)
        to = jnp.where(done & (block_tables[rows, w] >= 0), block_tables[rows, w], nb)
        # a chunk's rows out of their page, by row of the pool's (page, row) rows
        at_rows = (jnp.maximum(page, 0) * bs + r0)[:, None] + jnp.arange(ev.chunk)[None, :]
        chunk_of = lambda a: a.reshape(nb * bs, *a.shape[2:])[at_rows]
        kp, vp = (tuple(write_decode_kv(a, r, block_tables, at, active)
                        for a, r in zip(pool, _head_pools(x, n))) for pool, x in ((kp, k), (vp, v)))
        hp = k.shape[1] // n
        with la.scope("eva_summarise"):
            ks, vs = zip(*(eva.summarise(chunk_of(a), chunk_of(c), phi[j * hp:(j + 1) * hp],
                                         mu[j * hp:(j + 1) * hp])
                           for j, (a, c) in enumerate(zip(kp, vp))))
            put = lambda a, u: a.at[to, inside // ev.chunk].set(u.astype(a.dtype), mode="drop")
            return (tuple(put(a, u) for a, u in zip(kp, ks)),
                    tuple(put(a, u) for a, u in zip(vp, vs)))

    def read(kind, pools, qkv):
        n, bs = len(pools[0]), pools[0][0].shape[1]
        walked = _eva_tables(ev, block_tables, w, bs)
        outs = [paged_attention_decode(q, kp, vp, walked, lens)
                for q, kp, vp in zip(_head_pools(qkv[0], n), *pools)]
        return jnp.concatenate(outs, axis=1)

    return write, read


def _by_whole_groups(attend, q, hkv: int):
    """``attend(q)`` for q [T, Hq, hd] whose ``Hq / hkv`` query heads a K / V head
    are no power of two (6 = 4 + 2), as one call a power of two: the packed-ctx
    kernel tiles its rows by the group, and Mosaic declines the layouts of a
    group of 6 (``Not implemented: Lane broadcast``, seen compiling the pack
    for the chip with no chip attached).  The calls read the same keys; a
    power of two is one call, as it was."""
    t, hq, hd = q.shape
    g = hq // hkv
    if g & (g - 1) == 0:
        return attend(q)
    q, at, outs = q.reshape(t, hkv, g, hd), 0, []
    for n in (1 << i for i in reversed(range(g.bit_length())) if g >> i & 1):
        o = attend(q[:, :, at:at + n].reshape(t, hkv * n, hd))
        outs.append(o.reshape(t, hkv, n, hd))
        at += n
    return jnp.concatenate(outs, axis=2).reshape(t, hq, hd)


def decode_step(params, cfg, tokens, seq_lens, block_tables, active, cache: Cache,
                probe=None):
    """One batched decode tick (``model_runner.decode_step``'s arguments).
    Returns (logits [B, vocab], cache)."""
    picked: list = []
    write, read = _seams(cfg)[1](cfg, seq_lens, block_tables, active, picked, probe)
    read = _scoped(cfg.latent, read)
    x = lm.embedded(params, tokens, cfg)
    for l in range(cfg.num_layers):
        x, cache = _layer(cfg, l, params["layers"], x, seq_lens, active, cache,
                          write, read, 0, probe)
    if picked:
        cache = {**cache, "picks": _tally(cache["picks"], jnp.stack(picked))}
    return _logits(params, cfg, x), cache


def _latent_tick_seam(cfg, pos, block_tables, active, picked, probe):
    """A decode tick's (write, read) for latent pages and window rings."""
    s, b = cfg.latent, pos.shape[0]
    rows = jnp.arange(b)

    def write(kind, arrays, new):
        if kind != "sliding":
            nb, bs, _ = arrays[0].shape
            page = jnp.take_along_axis(block_tables, (pos // bs)[:, None], axis=1)[:, 0]
            page = jnp.where(active & (page >= 0), page, nb)
            return tuple(a.at[page, pos % bs].set(_fit(r, a), mode="drop")
                         for a, r in zip(arrays, new))
        (win,), (row,) = arrays, new
        ring = win.shape[0] * win.shape[1] // b
        flat = win.reshape(-1, win.shape[-1])
        to = jnp.where(active, rows * ring + pos % ring, flat.shape[0])
        return (flat.at[to].set(row.astype(win.dtype), mode="drop").reshape(win.shape),)

    def read(kind, arrays, queries):
        one = lambda a: a[:, None]
        if kind == "full":
            q_abs, q_i, w = map(one, queries)
            return _attend_selected(s, q_abs, q_i, w, one(pos), block_tables, *arrays,
                                    one(active), picked, probe=probe)[:, 0]
        if kind == "every":
            # one query a slot: under the crossing, so absorbed whatever the context
            a, lat, (q, w_uk, w_uv) = s.every, arrays[0], queries
            q_abs = jnp.pad(lm.absorbed_queries(w_uk, q, q[..., a.nope_dim:], a),
                            ((0, 0), (0, 0), (0, lat.shape[-1] - a.row)))
            lens = jnp.where(active, pos + 1, 0)
            if _decode_kernel_takes(a.num_heads, lat.shape[-1], a.kv_rank, lat.shape[1]):
                with jax.named_scope("mla_decode"):  # each slot walks its own pages in place
                    o = decode_kernel.latent_decode(q_abs, lat, jnp.maximum(block_tables, 0),
                                                    lens, a.kv_rank, a.scale)
            else:
                o = la.dense_attention_step(q_abs, lat, block_tables, lens, a)
            return lm.latent_values(w_uv, o, a)
        win = arrays[0]
        ring = win.shape[0] * win.shape[1] // b
        keys = win.reshape(b, ring, win.shape[-1])
        # row i of a ring holds the latest position p <= pos with p % ring == i
        key_pos = pos[:, None] - (pos[:, None] - jnp.arange(ring)[None, :]) % ring
        return la.window_attention(one(queries[0]), one(pos), keys, key_pos,
                                   s.sliding.window, s.sliding.kv_rank,
                                   s.sliding.scale)[:, 0]

    return write, read


def _state_tick_seam(cfg, pos, block_tables, active, picked, probe):
    """A decode tick's (write, read) for such blocks: the recurrence's
    one step on every slot's state IN PLACE (idle slots keep their bits), one
    new K / V row a live slot, in its page or in row ``pos % R`` of its ring."""
    from ..ops import gated_attention as ga
    from .paged import paged_attention_decode, paged_attention_packed_ctx, write_decode_kv

    s = cfg.latent

    def write(kind, arrays, rows):
        if kind in lm.RECURRENCES:
            (ssm, conv), (w, h) = arrays, rows
            _, step = lm.RECURRENCES[kind]
            y, ssm, conv = step(w, h, active, conv, ssm, s.recurrence[1], cfg.norm_eps, probe)
            return ssm, conv, y
        if kind == "wattn":
            return tuple(ga.ring_write_rows(a, r, pos, active) for a, r in zip(arrays, rows))
        return tuple(write_decode_kv(a, r, block_tables, pos, active)
                     for a, r in zip(arrays, rows))

    def read(kind, pools, qkv):
        if kind == "wattn":  # a row is a group of one query, its slot's own
            b, bs = pos.shape[0], pools[0].shape[1]
            return ga.ring_attention(qkv[0][:, None], pos[:, None], *pools, jnp.arange(b),
                                     pos // bs, pools[0].shape[0] // b, s.wattn.window,
                                     probe)[:, 0]
        if qkv[0].shape[-1] > 128:
            # a head wider than one 128-lane tile: the decode kernel's view of a
            # page as (key, kv head) rows is then not the pool's own bytes, and XLA
            # re-lays the WHOLE pool out for it (18 ms a tick for 4 x 1 GiB, seen
            # on the chip); the packed-ctx kernel reads the pool as it lies, and a
            # tick is a pack of one-row segments over their cached context
            segment_ids = jnp.where(active, jnp.arange(pos.shape[0]) + 1, 0)
            return paged_attention_packed_ctx(*qkv, segment_ids, *pools, block_tables,
                                              jnp.where(active, pos, 0),
                                              scale=_softmax_scale(s, kind))
        # length 0 = no row in this slot: the kernel skips it
        return paged_attention_decode(qkv[0], *pools, block_tables,
                                      jnp.where(active, pos + 1, 0),
                                      scale=_softmax_scale(s, kind))

    return write, read


def _one_chip_only(ctx, mesh, dp: int, seq_shards: int) -> None:
    """A model with layers of several kinds runs on one chip, unsharded."""
    if mesh is not None or (ctx is not None and ctx.size > 1):
        lm.refuse("a tensor-parallel serve mesh (grid)", "its weights and caches have "
                  "no sharding rules yet")
    if dp > 1:
        lm.refuse("serve_replicas > 1", "its caches are not partitioned by replica")
    if seq_shards > 1:
        lm.refuse("seq_shards > 1", "its caches are not striped over a seq axis")


class LatentRunner:
    """``model_runner.DenseRunner``'s surface for ``cfg.latent``: the entries
    above under the dense entries' names and arguments, and the host side of
    the kind's state."""

    counters = COUNTERS
    packs_are_one_program = True  # a pack reads its own rows back from the cache
    scoped_programs = True  # indexer topk sparse_attn window_attn expert_matmul; ssm_* gqa_attn latent_proj; gdn_* gated_attn; full_attn; lm_head; shared_expert

    def __init__(self, cfg):
        self.cfg = cfg
        # A pack takes the tick's decode rows (``step=``): ONE stream of the weights
        # and ONE grouped product an expert layer for both kinds of row, each kind
        # keeping its own state, ring, page write and kernel (``_carrying``).  The
        # entry carries a step for EVERY family (``tests/test_mixed_program.py``); the
        # ENGINE is told so for the two-norm blocks that keep no recurrence's state
        # (gated attention on pages and rings, EVA) OR hold no routed layer (two
        # parallel mixers beside a dense SwiGLU in every block: PR 59).  A mixed
        # program is a THIRD XLA program of the same bodies whose rows differ from
        # the other two's by rounding, and the benchmark holds the scheduler's tokens
        # to the UNMIXED replay: rounding moves a dense model's logits by its own
        # size and no more, but where a ROUTER sits between a recurrence's state and
        # the logits a near tie may fall the other way and a token move by tenths.
        # So the families with a recurrence AND routed experts keep two programs
        # until their cells' token margins say what such a flip may cost (ROADMAP
        # S2 (0), a ``benchmark`` PR), each for what the chip read (PERF.md §6, PR
        # 56): single-mixer blocks by 0.05 a token (read 0.2265 and 0.2042: not
        # correct by the cell's own limit), Gated DeltaNet's by 0.2 (read 0.2663 on
        # one seed of four).  The other two stay for what they would pay: the
        # selector's layers LOST 3.3% (a ~150 ms pack carries 16 rows' gathers; read
        # 0.0482 of its 0.05), latent attention over every row gained nothing
        # (-0.2%: its tick is a 123 ms pack).
        s = cfg.latent
        self.packs_carry_step = bool(getattr(s, "hybrid", False) and (
            s.recurrence[1] is None or not s.expert_layers))
        self._ring_rows = np.zeros(0, np.int64)
        self._block = 1
        self._expert_layers = 0
        # a table that gives pages back while its sequence lives (``ragged.
        # WindowCompaction``), which the engine hands its block manager; None: pages only grow
        self.compaction = None
        ev = getattr(cfg.latent, "eva", None)
        if ev is not None:
            from .ragged import WindowCompaction

            self.counters = EVA_COUNTERS
            self.compaction = WindowCompaction(ev.window, ev.chunk)
        elif cfg.latent.stateful:
            both = s.recurrence[0] is not None and s.attention[0] is not None
            # (a model with no expert layer counts the states' three alone)
            self.counters = WINDOW_COUNTERS if s.ringed else \
                (STATE_COUNTERS if s.expert_layers else STATE_COUNTERS[:3]) \
                + (CACHE_GAUGES if both else ())
            self._discarded = 0  # states a preemption left behind since the last dispatch
            self._slot_bytes = self._page_bytes = 0  # of a slot's state, of a page: all blocks
        elif cfg.latent.every is not None:
            self.counters = MLA_COUNTERS

    def init_cache(self, num_blocks, block_size, max_seqs, pack_tokens) -> Cache:
        self._block = block_size
        # host mirror of the rings: positions each slot's ring has taken (a
        # ring is not allocated, so this is what ``close()`` audits)
        # ... and of the state-space states: tokens each slot's state has taken in
        self._ring_rows = np.zeros(max_seqs, np.int64)
        cache = init_cache(self.cfg, num_blocks, block_size, max_seqs, pack_tokens)
        self._expert_layers = cache["stats"].shape[0]
        if "state_bytes_live" in self.counters:
            size = lambda keys: sum(a.nbytes for k in keys for a in cache[k])
            self._slot_bytes = size(("ssm", "conv")) // max_seqs
            self._page_bytes = size(("k", "v")) // num_blocks
        return cache

    def prefill_packed(self, *args, **kw):
        lm.refuse("a cold pack's own program (prefill_packed)", "a pack reads its own "
                  "rows back from the cache: use prefill_packed_ctx")

    def prefill_packed_ctx(self, params, cfg, tokens, segment_ids, positions, pack_pages,
                           last_idx, ctx_tables, ctx_lens, kv_cache, ctx=None, mesh=None,
                           dp: int = 1, seq_shards: int = 1, step=None):
        _one_chip_only(ctx, mesh, dp, seq_shards)
        return prefill_pack(params, cfg, tokens, segment_ids, positions, pack_pages,
                            last_idx, ctx_tables, kv_cache, step=step)

    def verify_packed_ctx(self, *args, **kw):
        lm.refuse("enable_speculation (verify_packed_ctx)", "a rejected draft's rows "
                  "cannot be rolled back out of a sliding layer's ring, nor its tokens "
                  "out of a recurrence's state, nor its share out of a chunk's summary")

    def decode_step(self, params, cfg, tokens, seq_lens, block_tables, active, kv_cache,
                    ctx=None, mesh=None, dp: int = 1, seq_shards: int = 1):
        _one_chip_only(ctx, mesh, dp, seq_shards)
        return decode_step(params, cfg, tokens, seq_lens, block_tables, active, kv_cache)

    def dispatched(self, counters, work, pack: bool = False, tokens: int = 0,
                   carried: int = 0) -> Dict[str, int]:
        """What the selectors and windows are ASKED to do with queries at
        positions ``[start, end)`` of each (slot, start, end) of ``work``, all
        layers: the dispatch's span arguments.  ``tokens`` is the program's token
        rows (a pack's padded tokens, a tick's slots; ``carried``: the slot rows a
        pack's program holds behind them, whose own call passes no ``tokens``), from
        which its expert
        layers lay out ``t k + g x tile`` rows each whatever the routing
        (``moe/layer.py:held_rows_a_pass``; ONE pass of a bounded layout, which
        no served shape reaches): ``expert_rows_laid_out`` beside the device's
        ``expert_pairs_held``, the rows of them that are live.  The causal keys
        and the ring rows are counted into ``counters`` here too; the keys selected
        are counted where they are selected (``refresh_stats``), so that count moves
        if a selector breaks, and a sound run's equals the sum of these arguments.
        A pack's entry is one GROUP a page of queries, and a group whose last
        position is under ``DENSE_KEYS_MAX`` walks its pages instead of gathering
        rows (``_attend_selected``); a single position is a decode tick's row
        and no group (nor is a pack's entry of one token, which the program
        cannot tell apart here)."""
        s = self.cfg.latent
        if tokens and self._expert_layers:
            counters["expert_rows_laid_out"].inc(self._expert_layers * held_rows_a_pass(
                tokens + carried, s, held_row_tile(tokens, s) if carried else None))
        if self.compaction is not None:
            return self._eva_dispatched(counters, work, pack)
        if s.count("wattn"):
            return self._windows_dispatched(counters, work)
        if s.stateful:
            return self._states_dispatched(counters, work, pack)
        if s.every is not None:
            return self._every_dispatched(counters, work, pack)
        topk, win, bs = s.index_topk, s.sliding.window, self._block
        scored = selected = dropped = groups = dense = 0
        for slot, a, b in work:
            if b - a > 1:
                ends = [min(p + bs, b) for p in range(a, b, bs)]
                groups += len(ends)
                dense += sum(e <= la.DENSE_KEYS_MAX for e in ends)
            scored += (b * (b + 1) - a * (a + 1)) // 2  # sum of p + 1
            m = min(max(a, topk), b)  # from position m on, topk of p + 1 keys
            selected += (m * (m + 1) - a * (a + 1)) // 2 + (b - m) * topk
            dropped += max(b - max(a, win), 0)  # position p overwrites p - win
            self._ring_rows[slot] = b
        out = {"index_keys_scored": scored * s.count("full"),
               "index_keys_selected": selected * s.count("full"),
               "window_rows_discarded": dropped * s.count("sliding")}
        if groups:
            out.update(selected_groups=groups * s.count("full"),
                       selected_groups_dense=dense * s.count("full"),
                       selected_groups_dense_pct=100.0 * dense / groups)
        for k in ("index_keys_scored", "window_rows_discarded", "selected_groups",
                  "selected_groups_dense"):
            counters[k].inc(out.get(k, 0))
        return out

    def _every_dispatched(self, counters, work, pack: bool) -> Dict[str, int]:
        """Latent attention over every cached row: a query at position ``p``
        attends ``p + 1`` keys a layer.  A pack's entry is one RUN (its pages of
        queries follow one another), and a run of ``la.run_groups`` pages or
        more attends in the decompressed form (``_attend_every``'s rule)."""
        s = self.cfg.latent
        n, bs = s.count("every"), self._block
        shortest = la.run_groups(s.every, bs)
        keys = long = 0
        for _, lo, hi in work:
            pairs = (hi * (hi + 1) - lo * (lo + 1)) // 2  # sum of p + 1
            keys += pairs
            if pack and -(-(hi - lo) // bs) >= shortest:
                long += pairs
        counters["mla_keys_attended"].inc(keys * n)
        if not pack:
            counters["mla_keys_attended_decode"].inc(keys * n)
            return {"mla_keys": keys * n}
        counters["mla_keys_decompressed"].inc(long * n)
        return {"mla_keys": keys * n, "mla_keys_decompressed_pct": 100.0 * long / max(keys, 1)}

    def _eva_dispatched(self, counters, work, pack: bool) -> Dict[str, int]:
        """EVA attention, ONE layer's count: a query at position ``p`` reads a
        summary row per chunk of the ``p // window`` windows before its own and
        the ``p % window + 1`` exact rows of its own up to itself.  The span's
        ``rows_total`` is the rows the dispatch's sequences hold (read ONCE by
        whatever implements it), ``eva_pairs`` the (query, row) pairs.  The
        decode ticks' rows are counted into the counters by kind."""
        c = self.compaction
        rows = pairs = summary = exact = 0
        for slot, a, b in work:
            under, n = c.rows_live(a), b - a  # rows before the first query; queries
            rows += under + n
            pairs += n * under + n * (n + 1) // 2
            if not pack:
                summary += under - a % c.window
                exact += a % c.window + 1
            self._ring_rows[slot] = b
        if not pack:
            counters["eva_summary_rows_read"].inc(summary)
            counters["eva_exact_rows_read"].inc(exact)
        return {"rows_total": rows, "eva_pairs": pairs}

    def _windows_dispatched(self, counters, work) -> Dict[str, int]:
        """Gated GQA of two kinds: a query at position ``p`` attends ``p + 1``
        keys in each layer over every key and ``min(p + 1, window)`` in each
        layer over a window; ``causal_keys`` is what the window layers would
        attend if they were full (what the rings save is the difference)."""
        s = self.cfg.latent
        win, n_full, n_win = s.wattn.window, s.count("gattn"), s.count("wattn")
        causal = windowed = dropped = under = 0
        for slot, a, b in work:
            causal += (b * (b + 1) - a * (a + 1)) // 2  # sum of p + 1
            m = min(max(a, win), b)  # from position m on, ``win`` of p + 1 keys
            windowed += (m * (m + 1) - a * (a + 1)) // 2 + (b - m) * win
            dropped += max(b - max(a, win), 0)  # position p overwrites p - win
            under += min(a, win - 1)  # ring rows under the first query's window
            self._ring_rows[slot] = b
        out = {"full_keys": causal * n_full, "window_keys": windowed * n_win,
               "window_ctx": under * n_win}
        counters["full_keys_attended"].inc(out["full_keys"])
        counters["window_keys_attended"].inc(out["window_keys"])
        counters["causal_keys"].inc(causal * n_win)
        counters["window_rows_discarded"].inc(dropped * n_win)
        return out

    def _states_dispatched(self, counters, work, pack: bool) -> Dict[str, int]:
        """A recurrence's blocks (state-space or delta rule): a pack's entry is
        ``ceil((end - start) / page)`` chunks a block, from a zero state if it
        starts at 0; a decode tick's is one step of a live slot's state."""
        bs, n_ssm = self._block, self.cfg.latent.count(self.cfg.latent.recurrence[0])
        segments = chunks = reset = steps = 0
        for slot, a, b in work:
            if pack:
                segments += 1
                chunks += -(-(b - a) // bs)
                reset += a == 0
            else:
                steps += 1
            self._ring_rows[slot] = b
        counters["ssm_states_reset"].inc(reset)
        counters["ssm_chunks_scanned"].inc(chunks * n_ssm)
        counters["ssm_states_recomputed"].inc(self._discarded)
        self._discarded = 0
        if self._slot_bytes:  # a slot keeps both kinds of cache: what they hold now
            rows = self._ring_rows
            counters["state_bytes_live"].set(int(np.count_nonzero(rows)) * self._slot_bytes)
            counters["kv_page_bytes_in_use"].set(int((-(-rows // bs)).sum()) * self._page_bytes)
        if pack:
            return {"ssm_segments": segments, "ssm_chunks": chunks}
        return {"ssm_live_slots": steps}

    def released(self, seq) -> None:
        s = self.cfg.latent
        if s.stateful and not s.ringed and self.compaction is None and seq.preempted \
                and self._ring_rows[seq.slot]:
            self._discarded += 1
        self._ring_rows[seq.slot] = 0

    def audit(self) -> Dict[str, int]:
        """State still owned by a sequence: rows of window state (a ring is
        nobody's once its slot is released), or slots whose recurrence's state
        is a live sequence's."""
        s = self.cfg.latent
        if self.compaction is not None:  # pages alone: the allocator's audit is all of it
            return {}
        if s.stateful and not s.ringed:
            return {"ssm_states": int(np.count_nonzero(self._ring_rows))}
        return {"window_rows": int(self._ring_rows.sum())}

    def refresh_stats(self, counters, kv: Cache) -> None:
        """The selectors' and routers' device-side counts into ``counters``
        (two small device->host copies)."""
        if self.compaction is not None:
            # gauges, from the host mirror of what each slot has written
            n = self._ring_rows
            counters["eva_context_tokens_live"].set(int(n.sum()))
            counters["eva_rows_live"].set(int(self.compaction.rows_live(n).sum()))
        if self.cfg.latent.indexed:
            counters["index_keys_selected"].set(picks_total(kv["picks"]))
        if "touched" in kv and self._expert_layers:
            touched = np.asarray(kv["touched"]).astype(np.int64).sum(0)  # [pack | tick, 2]
            counters["experts_touched"].set(int(touched[:, 0].sum()))
            counters["experts_touched_decode"].set(int(touched[1, 0]))
            counters["expert_pairs_held_decode"].set(int(touched[1, 1]))
        st = np.asarray(kv["stats"]).astype(np.int64)
        if st.size:
            counters["expert_pairs_routed"].set(int(st[:, 0].sum()))
            counters["expert_pairs_held"].set(int(st[:, 1].sum()))
            counters["expert_group_rows_max"].set(int(st[:, 2].max()))
            counters["expert_group_rows_min"].set(int(st[:, 3].min()))
