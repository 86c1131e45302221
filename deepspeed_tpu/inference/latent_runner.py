"""Serving runner for a model with layers of several kinds
(``TransformerConfig.latent``, ``models/latent.py``): the prefill pack and
the decode tick against a cache that holds, per KIND of layer, what that kind
attends over.

- a ``full`` layer keeps every position's latent row (``kv_rank + rope`` wide,
  keys and values in one array) in PAGES, block ids and block tables the
  allocator's own, and the indexer's key page beside it;
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

One layer body (``_layer``) serves the pack and the tick; the kind chooses how
the rows are written and read.  A pack reads its own rows back from the cache
it just wrote, so a cold pack and a pack over cached context are one program,
as are chunks of one prompt and several prompts in one pack: work is laid out
in groups of one page of one sequence.  Plain XLA bodies
(``ops/latent_attention.py``) but for three Pallas kernels on the chip: a
pack's index scores (``ops/pallas/index_scores.py``), its shorter groups'
attention over their picks (``ops/pallas/selected_attention.py``) and the
expert layer's grouped matmul (``moe/layer.py``).

``LatentRunner`` is what ``InferenceEngineV2`` holds for such a model (as
``model_runner.DenseRunner`` for a dense one): these entries under the names
the engine's programs call, and the kind's host accounting beside the cache it
describes (``COUNTERS``, the rings' host mirror).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..models import latent as lm
from ..ops import latent_attention as la
from ..ops.pallas import index_scores as index_kernel
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
# groups follow from positions and are counted on the host at dispatch; the keys SELECTED
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
    "expert_group_rows_max",  # rows of the largest held expert's group in a pack
    "expert_group_rows_min",  # ... and of the smallest
)


def _lanes(width: int) -> int:
    """A latent row as the pages keep it: padded with zeros to whole 128-lane
    rows, which a row gather moves at twice the speed (576 -> 640)."""
    return -(-width // 128) * 128


def ring_rows(cfg, block_size: int, pack_tokens: int) -> int:
    """Rows of a slot's ring: one pack and the window's look-back, in pages."""
    back = -(-(cfg.latent.sliding.window - 1) // block_size) * block_size
    return pack_tokens + back


def init_cache(cfg, num_blocks: int, block_size: int, max_seqs: int,
               pack_tokens: int, dtype=None) -> Cache:
    s, dtype = cfg.latent, dtype or cfg.dtype
    if pack_tokens % block_size:
        raise ValueError(f"a pack of {pack_tokens} tokens is no whole number of "
                         f"pages of {block_size}")
    pages = lambda w, n: tuple(
        jnp.zeros((num_blocks, block_size, w), dtype) for _ in range(n))
    chunks = max_seqs * ring_rows(cfg, block_size, pack_tokens) // block_size
    n_moe = max(cfg.num_layers - s.first_dense, 0)
    stats = jnp.zeros((n_moe, len(ROUTING_STATS)), jnp.int32)
    return {
        "lat": pages(_lanes(s.full.row), s.count("full")),
        "idx": pages(s.index_dim, s.count("full")),
        "win": tuple(jnp.zeros((chunks, block_size, s.sliding.row), dtype)
                     for _ in range(s.count("sliding"))),
        "stats": stats.at[:, 3].set(_NO_MIN),
        # keys selected so far, one row per full layer: a running count in two
        # int32 words (high, low 30 bits), since a window's sum passes 2^31
        "picks": jnp.zeros((s.count("full"), 2), jnp.int32),
    }


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


def _kernel_takes(name: str, kernel, shape, declines: str) -> bool:
    """A Pallas kernel's gate by shape (``kernel.supports(*shape)``, on a TPU
    or interpreted), noted for ``record_dispatch()``.  Both kernels serve a
    pack's pages of queries; a decode tick's single rows stay on the XLA
    bodies by design and never ask."""
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


def _index_kernel_takes(c: int, j: int, d: int, bs: int) -> bool:
    """The gate of the Pallas index-scores kernel (a page of queries at a time)."""
    return _kernel_takes("index_scores", index_kernel, (c, j, d, bs),
                         "queries, page and head size must be whole 128-lane tiles")


def _layer(cfg, l, layers, x, pos, valid, cache, write, read, track_groups, probe):
    """One layer on token rows ``x`` [T, d].  The seam: ``write(kind, arrays,
    rows)`` returns the kind's cache arrays with the new rows in, ``read(kind,
    arrays, queries)`` attends over them.  Returns (x, cache)."""
    s = cfg.latent
    kind, (n1, n2), aw, fw, is_moe = lm.layer_params(layers, l, s)
    a, i = s.attn(kind), s.layer_kinds[:l].count(kind)
    h = lm.rms(x, n1["scale"], cfg.norm_eps)
    c_q, q_abs, row, gate = lm.attn_inputs(aw, h, pos, a, cfg)
    keys, rows, queries = ("win",), (row,), (q_abs,)
    if kind == "full":
        q_i, k_i, w = lm.indexer_inputs(aw, h, c_q, pos, s, cfg)
        keys, rows, queries = ("lat", "idx"), (row, k_i), (q_abs, q_i, w)
    arrays = write(kind, tuple(cache[k][i] for k in keys), rows)
    cache = {**cache, **{k: _put(cache[k], i, v) for k, v in zip(keys, arrays)}}
    o = read(kind, arrays, queries)
    x = x + lm.attn_output(aw, o, gate, a).astype(x.dtype)
    h = lm.rms(x, n2["scale"], cfg.norm_eps)
    y, routing = lm.ffn(fw, h, is_moe, cfg, valid)
    if routing is not None:
        routed, picked = routing
        if probe is not None:
            probe.append({"experts_picked": picked})
        st, m = cache["stats"], l - s.first_dense
        new = jnp.stack([st[m, 0] + routed[0], st[m, 1] + routed[1],
                         jnp.maximum(st[m, 2], routed[2]) if track_groups else st[m, 2],
                         jnp.minimum(st[m, 3], routed[3]) if track_groups else st[m, 3]])
        cache = {**cache, "stats": st.at[m].set(new)}
    return x + y.astype(x.dtype), cache


def _fit(rows, pages):
    """Rows [T, w] in the pages' dtype and width (zeros past ``w``)."""
    return jnp.pad(rows.astype(pages.dtype), ((0, 0), (0, pages.shape[-1] - rows.shape[-1])))


def _put(items: tuple, i: int, value) -> tuple:
    return items[:i] + (value,) + items[i + 1:]


def _logits(params, cfg, x):
    x = lm.rms(x, params["final_norm"]["scale"], cfg.norm_eps)
    return (x @ params["lm_head"]["kernel"]).astype(jnp.float32)


def prefill_pack(params, cfg, tokens, segment_ids, positions, pack_pages, last_idx,
                 tables, cache: Cache, probe=None):
    """One prefill pack (the arguments of ``model_runner.prefill_packed_ctx``
    less ``ctx_lens``: a token's position says where its context ends).
    ``tables`` [N, P] are the block tables by slot, this pack's pages included.
    ``probe`` (a list) collects, layer by layer, what the indexers and the
    routers picked.  Returns (logits [N, vocab], cache)."""
    s, t = cfg.latent, tokens.shape[0]
    nb, bs, _ = cache["lat"][0].shape if cache["lat"] else cache["win"][0].shape
    g = t // bs
    ring = cache["win"][0].shape[0] * bs // tables.shape[0] if cache["win"] else 0
    if ring and ring < ring_rows(cfg, bs, t):
        raise ValueError(f"a pack of {t} tokens needs rings of {ring_rows(cfg, bs, t)} "
                         f"rows; the cache was built with {ring}")
    valid = segment_ids > 0
    slot = jnp.maximum(segment_ids[::bs] - 1, 0)  # a page of the pack is one sequence's
    live = segment_ids[::bs] > 0
    page0 = positions[::bs] // bs                 # ... and starts on one of its pages
    grouped = lambda a: a.reshape(g, bs, *a.shape[1:])
    q_pos = grouped(positions)
    safe_pages = jnp.where(pack_pages >= 0, pack_pages, nb)
    rc = ring // bs
    back = -(-(s.sliding.window - 1) // bs)

    def write(kind, arrays, rows):
        if kind == "full":
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

    picked: list = []
    x = params["embed"]["embedding"][tokens].astype(cfg.dtype)
    for l in range(cfg.num_layers):
        x, cache = _layer(cfg, l, params["layers"], x, positions, valid, cache,
                          write, read, True, probe)
    if picked:
        cache = {**cache, "picks": _tally(cache["picks"], jnp.stack(picked))}
    return _logits(params, cfg, x[jnp.clip(last_idx, 0, t - 1)]), cache


def decode_step(params, cfg, tokens, seq_lens, block_tables, active, cache: Cache,
                probe=None):
    """One batched decode tick (``model_runner.decode_step``'s arguments).
    Returns (logits [B, vocab], cache)."""
    s, b = cfg.latent, tokens.shape[0]
    pos = seq_lens
    rows = jnp.arange(b)

    def write(kind, arrays, new):
        if kind == "full":
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
        win = arrays[0]
        ring = win.shape[0] * win.shape[1] // b
        keys = win.reshape(b, ring, win.shape[-1])
        # row i of a ring holds the latest position p <= pos with p % ring == i
        key_pos = pos[:, None] - (pos[:, None] - jnp.arange(ring)[None, :]) % ring
        return la.window_attention(one(queries[0]), one(pos), keys, key_pos,
                                   s.sliding.window, s.sliding.kv_rank,
                                   s.sliding.scale)[:, 0]

    picked: list = []
    x = params["embed"]["embedding"][tokens].astype(cfg.dtype)
    for l in range(cfg.num_layers):
        x, cache = _layer(cfg, l, params["layers"], x, pos, active, cache,
                          write, read, False, probe)
    if picked:
        cache = {**cache, "picks": _tally(cache["picks"], jnp.stack(picked))}
    return _logits(params, cfg, x), cache


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
    scoped_programs = True  # indexer topk sparse_attn window_attn expert_matmul

    def __init__(self, cfg):
        self.cfg = cfg
        self._ring_rows = np.zeros(0, np.int64)
        self._block = 1

    def init_cache(self, num_blocks, block_size, max_seqs, pack_tokens) -> Cache:
        self._block = block_size
        # host mirror of the rings: positions each slot's ring has taken (a
        # ring is not allocated, so this is what ``close()`` audits)
        self._ring_rows = np.zeros(max_seqs, np.int64)
        return init_cache(self.cfg, num_blocks, block_size, max_seqs, pack_tokens)

    def prefill_packed(self, *args, **kw):
        lm.refuse("a cold pack's own program (prefill_packed)", "a pack reads its own "
                  "rows back from the cache: use prefill_packed_ctx")

    def prefill_packed_ctx(self, params, cfg, tokens, segment_ids, positions, pack_pages,
                           last_idx, ctx_tables, ctx_lens, kv_cache, ctx=None, mesh=None,
                           dp: int = 1, seq_shards: int = 1):
        _one_chip_only(ctx, mesh, dp, seq_shards)
        return prefill_pack(params, cfg, tokens, segment_ids, positions, pack_pages,
                            last_idx, ctx_tables, kv_cache)

    def verify_packed_ctx(self, *args, **kw):
        lm.refuse("enable_speculation (verify_packed_ctx)", "a rejected draft's rows "
                  "cannot be rolled back out of a sliding layer's ring")

    def decode_step(self, params, cfg, tokens, seq_lens, block_tables, active, kv_cache,
                    ctx=None, mesh=None, dp: int = 1, seq_shards: int = 1):
        _one_chip_only(ctx, mesh, dp, seq_shards)
        return decode_step(params, cfg, tokens, seq_lens, block_tables, active, kv_cache)

    def dispatched(self, counters, work) -> Dict[str, int]:
        """What the selectors and windows are ASKED to do with queries at
        positions ``[start, end)`` of each (slot, start, end) of ``work``, all
        layers: the dispatch's span arguments.  The causal keys and the ring
        rows are counted into ``counters`` here; the keys selected are counted
        where they are selected (``refresh_stats``), so that count moves if a
        selector breaks, and a sound run's equals the sum of these arguments.
        A pack's entry is one GROUP a page of queries, and a group whose last
        position is under ``DENSE_KEYS_MAX`` walks its pages instead of gathering
        rows (``_attend_selected``); a single position is a decode tick's row
        and no group (nor is a pack's entry of one token, which the program
        cannot tell apart here)."""
        s = self.cfg.latent
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

    def released(self, seq) -> None:
        self._ring_rows[seq.slot] = 0

    def audit(self) -> Dict[str, int]:
        """Rows of window state still owned by a sequence (a ring is nobody's
        once its slot is released)."""
        return {"window_rows": int(self._ring_rows.sum())}

    def refresh_stats(self, counters, kv: Cache) -> None:
        """The selectors' and routers' device-side counts into ``counters``
        (two small device->host copies)."""
        counters["index_keys_selected"].set(picks_total(kv["picks"]))
        st = np.asarray(kv["stats"]).astype(np.int64)
        if st.size:
            counters["expert_pairs_routed"].set(int(st[:, 0].sum()))
            counters["expert_pairs_held"].set(int(st[:, 1].sum()))
            counters["expert_group_rows_max"].set(int(st[:, 2].max()))
            counters["expert_group_rows_min"].set(int(st[:, 3].min()))
