"""Latent attention over a cache of ONE row per key (DeepSeek-V2's MLA in its
absorbed form) with a learned key selector (DeepSeek-V3.2's indexer), a
sliding window, or over EVERY cached row (DeepSeek-V2's own layer:
``dense_attention_pack`` / ``dense_attention_step``).

A key is one row ``[c_kv ; k_rope]`` (``r_kv + rope`` wide) that serves every
head: the queries are folded through ``W_uk`` first (``q_abs = [q_nope W_uk ;
q_rope]``), the softmax-weighted sum of the rows' first ``r_kv`` columns is
folded through ``W_uv`` afterwards (``models/latent.py``).  What is here is
the part between: index scores, the exact top-k, attention over the selected
rows (gathered, or for a pack's shorter contexts walked in place under a mask
by the Pallas kernel ``selected_attn``), attention over a window.  That is
the ABSORBED form, and every body of this file has it.  The same softmax has
a DECOMPRESSED form (``W_uk`` / ``W_uv`` applied to a key's row once, shared by
all the queries of one sequence in a dispatch: the cheaper one from
``crossing()`` queries on); it exists as a Pallas kernel alone
(``ops/pallas/latent_prefill.py``), for the RUNS of a pack over every row that
``pack_runs()`` finds long enough, and has no XLA body: its score blocks would
go through HBM between the two matmuls (10% of the roofline, PR 45).

Work is laid out in GROUPS of ``C`` consecutive queries of one sequence (a
page of a prefill pack; one decode row), because a group shares its keys:
``key_block(g, b)`` hands block ``b`` of group ``g``'s index keys, ``row_of(g,
positions)`` the flat rows of its latent keys.  Plain XLA bodies, each under a
``jax.named_scope`` (``indexer``, ``topk``, ``sparse_attn``, ``window_attn``,
``mla_prefill``, ``mla_decode``) so that a device trace can be attributed; gathers and scores exist one query
block at a time.  A body that a pack and a tick share takes its name through
``scope()``: inside ``carried_step()``, a tick's rows traced INSIDE a pack's
program, the name ends in ``_step``, so that a pack's time is never a step's.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Callable

import jax
import jax.numpy as jnp

_SCOPE_SUFFIX = contextvars.ContextVar("latent_scope_suffix", default="")


def scope(name: str):
    """``jax.named_scope(name)``; ``name_step`` inside ``carried_step()``."""
    return jax.named_scope(name + _SCOPE_SUFFIX.get())


@contextlib.contextmanager
def carried_step():
    """While open, what is traced is a decode step's rows riding a prefill
    pack's program (``latent_runner._carrying``): every ``scope()`` opened
    meanwhile is a SIBLING of the pack's scope of that name (``full_attn_step``
    beside ``full_attn``), never a child, so that a trace reader dividing a pack's
    work by ``full_attn``'s time does not divide it by the step's too."""
    token = _SCOPE_SUFFIX.set("_step")
    try:
        yield
    finally:
        _SCOPE_SUFFIX.reset(token)

_MASKED = -1e30  # finite: a fully masked row softmaxes to uniform, not NaN
Q_BLOCK = 64     # queries whose selected rows are gathered at once
KEY_BLOCK_BYTES = 96 << 20  # cap of one [C, J, KB] float32 index-score block
# A pack's group whose last position is below this walks its pages whole with
# the picks as a mask (``ops/pallas/selected_attention.py``); from here on its
# picked rows are gathered.  Where the two schedules meet on a v5e at
# dots3-note-prev's widths (my chip runs, PR 32; ``tools/selected_attn_curves.py``),
# ms a 128-query group of one layer: the kernel 0.65 at 2048 keys, 1.47 at 6144,
# 2.71 at 12 288, 5.17 at 24 576 (0.25 + 0.2 a 1024, alone and inside the pack's
# program alike); the gathered body 2.43 whatever the context: they meet at
# ~11 000.  In the serving cell ``dots3_note_longdocs_closed`` (one seed, 45 s;
# tokens/s with this constant at 0 / 8192 / 10 240 / 12 288 / 14 336 / 20 480 /
# 28 672): 9999.0 / 11 231.5 / 11 333.6 / 11 274.9 / 11 230.1 / 10 669.6 / 10 561.3.
DENSE_KEYS_MAX = 10240


def index_key_block(c: int, j: int, k_total: int, unit: int) -> int:
    """Keys scored at once: whole ``unit``s (pages), at most 2048 keys, at most
    what keeps the ``[c, j, kb]`` float32 scores under ``KEY_BLOCK_BYTES``, and
    no more than the ``k_total`` there are."""
    cap = min(2048, KEY_BLOCK_BYTES // (4 * c * j), k_total)
    return max(cap // unit, 1) * unit


def index_scores(q_i, w, q_pos, key_block: Callable, n_blocks, kb: int, k_pad: int,
                 scale: float):
    """One group's index scores ``I[c, s] = scale * sum_j w[c, j] *
    relu(q_i[c, j] . k_i[s])`` for keys ``s <= q_pos[c]``, ``-inf`` elsewhere.

    q_i [C, J, D], w [C, J] float32, q_pos [C]; ``key_block(b)`` -> [kb, D]
    keys of positions ``b*kb ..``; ``n_blocks`` (traced) blocks hold a live
    key, the rest of the ``k_pad`` columns stay ``-inf`` unscored."""
    c = q_i.shape[0]
    kpos = jnp.arange(kb)

    def body(b, out):
        k = key_block(b)
        s = jnp.einsum("cjd,kd->cjk", q_i, k, preferred_element_type=jnp.float32)
        s = jnp.sum(jax.nn.relu(s) * w[:, :, None], axis=1) * scale
        live = (b * kb + kpos)[None, :] <= q_pos[:, None]
        return jax.lax.dynamic_update_slice(
            out, jnp.where(live, s, -jnp.inf), (0, b * kb))

    with scope("indexer"):
        return jax.lax.fori_loop(
            0, n_blocks, body, jnp.full((c, k_pad), -jnp.inf, jnp.float32))


TOPK_STEP = 4096  # widths the selection sorts at: multiples of this


def select_topk(scores, k: int, n_live=None):
    """The exact ``k`` largest of each row, equal scores to the lower
    position: (scores [C, k], positions [C, k]).  A row with fewer than ``k``
    live keys selects them all; the rest of its entries score ``-inf``.

    ``lax.top_k`` with a k in the thousands is a sort of the whole row on the
    TPU, and its cost grows faster than the row.  ``n_live`` (traced: no key at
    or past it scores above ``-inf`` in any row) picks the narrowest of a few
    static widths that holds every live key, and no sort at all while they
    all fit in ``k``: the same selection, since what is cut off is ``-inf``."""
    width = scores.shape[-1]
    k = min(k, width)
    with scope("topk"):
        if n_live is None or width <= max(k, TOPK_STEP):
            return jax.lax.top_k(scores, k)
        widths = [k] + [w for w in range(TOPK_STEP, width, TOPK_STEP) if w > k] + [width]

        def at(w):
            if w == k:  # every live key is among the first k: all are selected
                return lambda s: (s[:, :k], jnp.broadcast_to(
                    jnp.arange(k, dtype=jnp.int32), (s.shape[0], k)))
            return lambda s: tuple(jax.lax.top_k(s[:, :w], k))

        which = jnp.sum(jnp.asarray(widths[:-1]) < n_live)
        return jax.lax.switch(which, [at(w) for w in widths], scores)


def selected_mask(scores, vals, idx):
    """``select_topk``'s picks as a mask over the keys: scores [.., C, keys],
    (vals, idx) [.., C, k] what it returned for them -> int8 [.., C, keys], 1 at
    exactly the positions ``idx`` holds with a score above ``-inf``.

    No scatter: a key is in iff it scores above the lowest picked score, or
    equals it at a position no later than the last picked key that does
    (``select_topk`` hands equal scores to the lower position, so the picked
    among them are the first).  A row with fewer than ``k`` live keys has
    ``-inf`` for its lowest, and takes every live key."""
    thr = jnp.min(vals, axis=-1, keepdims=True)
    last_tie = jnp.max(jnp.where(vals == thr, idx, -1), axis=-1, keepdims=True)
    pos = jnp.arange(scores.shape[-1], dtype=idx.dtype)
    take = (scores > thr) | ((scores == thr) & (pos <= last_tie))
    return (take & (scores > -jnp.inf)).astype(jnp.int8)


def sparse_attention(q_abs, idx, valid, rows_of: Callable, r_kv: int, scale: float):
    """One group's attention over its selected rows, absorbed form.

    q_abs [C, H, W]; idx, valid [C, k]; ``rows_of(idx)`` -> [.., k, W] latent
    rows of those key positions.  Returns [C, H, r_kv] (before ``W_uv``).
    The rows of ``Q_BLOCK`` queries are gathered at once: 64 x 2048 rows of
    640 bf16 are 168 MB, and blocks of 256 ran the softmax six times slower
    (my chip run, PR 29).  This is the path of a decode tick's rows, of a
    pack's groups from ``DENSE_KEYS_MAX`` on and of every shape the Pallas
    kernel declines, and the ground truth of that kernel's tests; a pack's
    shorter groups never gather (``ops/pallas/selected_attention.py``)."""
    c = q_abs.shape[0]
    qb = min(Q_BLOCK, c)
    if c % qb:
        qb = c

    def block(args):
        q, ix, ok = args
        rows = rows_of(ix)  # [qb, k, W]
        s = jnp.einsum("chw,ckw->chk", q, rows,
                       preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(ok[:, None, :], s, _MASKED), axis=-1)
        return jnp.einsum("chk,ckr->chr", p.astype(rows.dtype), rows[..., :r_kv],
                          preferred_element_type=jnp.float32).astype(q.dtype)

    with scope("sparse_attn"):
        if qb == c:
            return block((q_abs, idx, valid))
        split = lambda a: a.reshape(c // qb, qb, *a.shape[1:])
        out = jax.lax.map(block, (split(q_abs), split(idx), split(valid)))
        return out.reshape(c, *out.shape[2:])


def window_attention(q_abs, q_pos, keys, key_pos, window: int, r_kv: int, scale: float):
    """Groups' attention over the last ``window`` positions (the query's own
    included), absorbed form.  q_abs [G, C, H, W], q_pos [G, C], keys
    [G, K, W], key_pos [G, K] (negative = no key).  Returns [G, C, H, r_kv]."""
    with scope("window_attn"):
        s = jnp.einsum("gchw,gkw->gchk", q_abs, keys,
                       preferred_element_type=jnp.float32) * scale
        d = q_pos[:, :, None] - key_pos[:, None, :]
        ok = (d >= 0) & (d < window) & (key_pos[:, None, :] >= 0)
        p = jax.nn.softmax(jnp.where(ok[:, :, None, :], s, _MASKED), axis=-1)
        return jnp.einsum("gchk,gkr->gchr", p.astype(keys.dtype), keys[..., :r_kv],
                          preferred_element_type=jnp.float32).astype(q_abs.dtype)


# ---------------------------------------------------------------------------
# attention over EVERY cached row
# ---------------------------------------------------------------------------
DENSE_KEY_BLOCK = 512  # keys scored at once: whole pages


def _online(carry, s, v, ok, eq: str):
    """One block of an online softmax: carry (m, l, acc) of the queries, scores
    ``s`` [.., q, k] float32, mask ``ok`` (broadcasts to ``s``), values ``v``
    contracted by ``eq`` (p, v -> acc's shape)."""
    m, l, acc = carry
    s = jnp.where(ok, s, _MASKED)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
    fade = jnp.exp(m - m_new)
    acc = acc * fade[..., None] + jnp.einsum(eq, p.astype(v.dtype), v,
                                             preferred_element_type=jnp.float32)
    return m_new, l * fade + jnp.sum(p, axis=-1), acc


def _key_blocks(tables, c: int):
    """(keys a block, pages a block, the tables padded to whole blocks)."""
    kp = max(DENSE_KEY_BLOCK // c, 1)
    return kp * c, kp, jnp.pad(jnp.maximum(tables, 0), ((0, 0), (0, -tables.shape[1] % kp)))


def crossing(a) -> int:
    """Queries of one sequence in a dispatch from which the DECOMPRESSED form
    (``W_uk`` / ``W_uv`` applied to a key's row once, shared by those queries:
    ``2 (nope + rope) + 2 v`` FLOPs a pair and head after ``2 r (nope + v)`` a key
    and head) needs fewer FLOPs over a long context than the ABSORBED one (``2
    (2 r + rope)`` a pair and head), from the kind's own widths: 171 at
    DeepSeek-V2's.  A kind whose absorbed form is never the dearer: none."""
    r = a.kv_rank
    saved = (2 * r + a.rope_dim) - (a.nope_dim + a.rope_dim + a.v_dim)
    return -(-r * (a.nope_dim + a.v_dim) // saved) if saved > 0 else 1 << 30


def run_groups(a, c: int) -> int:
    """``crossing()`` in whole groups of ``c`` queries: the shortest RUN of a
    pack (consecutive groups of one sequence) that attends decompressed."""
    return -(-crossing(a) // c)


def pack_runs(slot, live, first, c: int, min_groups: int):
    """The RUNS of a pack: consecutive live groups of one sequence on
    consecutive pages of it.  slot, live, first [G]: each group's sequence,
    whether it holds a query, and its first query's position; ``c`` queries a
    group.  Returns (long [G] bool: the group is in a run of at least
    ``min_groups``; runs [R, 3] int32: such a run's first group, its groups
    and its first position, rows of zeros behind the last; run_slot [R]: its
    sequence), ``R = max(G // min_groups, 1)`` the most a pack can hold."""
    g = slot.shape[0]
    follows = jnp.concatenate([jnp.zeros((1,), bool), live[1:] & live[:-1]
                               & (slot[1:] == slot[:-1]) & (first[1:] == first[:-1] + c)])
    starts = live & ~follows
    run = jnp.cumsum(starts)  # a live group's run, counted from 1
    groups = jnp.sum((run[:, None] == run[None, :]) & live[None, :], axis=1, dtype=jnp.int32)
    long = live & (groups >= min_groups)
    n_runs = max(g // min_groups, 1)
    heads = starts & long
    at = jnp.where(heads, jnp.cumsum(heads) - 1, n_runs)
    runs = jnp.stack([jnp.arange(g, dtype=jnp.int32), groups, first.astype(jnp.int32)], axis=1)
    return (long, jnp.zeros((n_runs, 3), jnp.int32).at[at].set(runs, mode="drop"),
            jnp.zeros((n_runs,), slot.dtype).at[at].set(slot, mode="drop"))


def dense_attention_pack(q_abs, lat, tables, live, q_pos, a):
    """A pack's attention over EVERY cached row of each query's sequence, its
    own rows included (they are in the pages already): the layer of a model
    whose cache is latent pages alone.  ABSORBED, as every body of this file:
    the fallback, the CPU's path and the ground truth of both kernels that
    serve such a pack on the chip (``latent_runner._attend_every``).

    q_abs [G, C, H, lanes] (``[q_nope W_uk ; q_rope]``, zeros past the row), a
    page of one sequence's queries a group; ``lat`` [blocks, C, lanes] the
    layer's pages; ``tables`` [G, P] each group's block table; ``live`` [G]
    (a dead page of the pack comes back zeros); ``q_pos`` [G, C]; ``a`` the
    kind (``LatentAttn``).  A group walks its sequence's pages a block of
    ``DENSE_KEY_BLOCK`` keys at a time, never the whole context, as far as its
    last position, with an online softmax over the blocks, causal by
    position.  Returns [G, C, H, r_kv] (before ``W_uv``)."""
    _, c, h, _ = q_abs.shape
    r = a.kv_rank
    kb, kp, tables = _key_blocks(tables, c)
    kpos = jnp.arange(kb)

    def group(xs):
        q, table, pos, n = xs  # [C, H, lanes], [P], [C], the keys it reaches (0: dead)

        def key_block(b, state):
            rows = lat[jax.lax.dynamic_slice_in_dim(table, b * kp, kp)].reshape(kb, -1)
            s = jnp.einsum("chw,kw->hck", q, rows, preferred_element_type=jnp.float32)
            ok = (b * kb + kpos)[None, :] <= pos[:, None]
            return _online(state, s * a.scale, rows[:, :r], ok[None], "hck,kr->hcr")

        state = (jnp.full((h, c), _MASKED, jnp.float32), jnp.zeros((h, c), jnp.float32),
                 jnp.zeros((h, c, r), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, (n + kb - 1) // kb, key_block, state)
        return jnp.moveaxis(acc / jnp.maximum(l, 1e-30)[..., None], 0, 1).astype(q.dtype)

    with jax.named_scope("mla_prefill"):
        reach = jnp.where(live, jnp.max(q_pos, axis=1) + 1, 0)
        return jax.lax.map(group, (q_abs, tables, q_pos, reach))


def dense_attention_step(q_abs, lat, tables, lens, a):
    """A decode tick's attention over every cached row: one query a slot.
    q_abs [B, H, lanes] (``[q_nope W_uk ; q_rope]``, zeros past the row);
    ``lat`` [blocks, C, lanes]; ``tables`` [B, P]; ``lens`` [B] keys a slot
    attends (0: an idle slot, whose row comes back zeros).  The slots' pages
    are walked a block of ``DENSE_KEY_BLOCK`` keys at a time, as far as the
    longest slot reaches.  Returns [B, H, r_kv] (before ``W_uv``)."""
    b, h, _ = q_abs.shape
    r = a.kv_rank
    kb, kp, tables = _key_blocks(tables, lat.shape[1])
    kpos = jnp.arange(kb)

    def key_block(i, state):
        pages = jax.lax.dynamic_slice_in_dim(tables, i * kp, kp, axis=1)   # [B, kp]
        rows = lat[pages].reshape(b, kb, -1)
        s = jnp.einsum("bhw,bkw->bhk", q_abs, rows, preferred_element_type=jnp.float32)
        ok = (i * kb + kpos)[None, :] < lens[:, None]
        return _online(state, s * a.scale, rows[..., :r], ok[:, None, :], "bhk,bkr->bhr")

    with jax.named_scope("mla_decode"):
        state = (jnp.full((b, h), _MASKED, jnp.float32), jnp.zeros((b, h), jnp.float32),
                 jnp.zeros((b, h, r), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, (jnp.max(lens) + kb - 1) // kb, key_block, state)
        return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q_abs.dtype)
