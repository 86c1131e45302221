"""Serving cells of a model of EVA attention (``models/latent.py``: ``eva``; K / V
pages that are GIVEN BACK while their sequence lives): ``InferenceEngineV2`` +
``ServeScheduler`` driven as the serving drivers beside this one drive them.
What could be imported is (``serve.DRAIN_CAP_S``, ``serve_latent._schedule``,
``serve_hybrid._packs`` / ``._alone`` / ``._state_error`` / ``REPLAY_SLOTS``); the
order of a run and the loop are theirs, copied once more because each keeps them
inside its ``run`` (ROADMAP D1c: one loop is a ``benchmark`` PR's).  This file's
own part is the SAMPLE: its replay over a table that shrinks, its comparison,
its controls.

TWO samples go through one comparison (``_check_sample``).  The first is the
WARM-UP: ``correctness.prompts`` requests of unequal length submitted TOGETHER
and served by the scheduler itself (prompts of 4, 2 and 0 closed windows; the
shortest CLOSES its first window during decode), ``decode_steps`` greedy tokens
each.  The second is taken from what the WINDOW served: the
``correctness.window_requests`` finished requests of the fewest tokens, no two
from one slot, and the LONGEST finished request under
``correctness.window_longest_under`` tokens (set past ``max_seq_len``: the longest
one whatever its length, ~28k bytes and 14 closed windows in the cell; its
reference is a forward of its own, 10.7 GiB with the weights by the compiler's
count, after ``close()``), every token of their answers.  Either sample's tokens
are fed through the runner's bodies again (``_Replay``: a cache and a block manager of its own, the engine's
``StateManager`` with the runner's ``WindowCompaction``, so the tables it walks
shrink as the engine's do), and the plain reference makes ONE float32 forward
over each request.  Held, per sequence:

1. ``LOGIT_TOL_MAX`` / ``LOGIT_TOL_MEAN``: the next byte's logits after EVERY
   prefill chunk and every decode step against the reference's at that position.
2. ``SUMMARY_TOL``: the KEPT summaries.  After the sequence's last token, the
   pages its table holds for closed windows, and the open window's summary page
   up to its last whole chunk, against the reference's ``k~, v~``, every layer.
3. ``ATTN_TOL`` / ``ATTN_ROW_TOL``: the first layer's attention output against
   the reference's ONE softmax on the program's own q, k, v of that layer (what
   precision the softmax and its sums were taken in, and which keys a query saw,
   apart from everything before them): over the whole sequence, and at the
   position where it is furthest off.
4. The table, exactly: the pages it holds after the last token are what
   ``n`` written positions keep (``WindowCompaction.pages_for``), its live rows
   ``128 (n // 2048) + n % 2048`` by the reference's own count; over the warm-up
   the engine gave back exactly the exact pages of the windows it closed, and the
   pool is whole again when the requests have gone.
5. ``TOKEN_MEAN`` / ``TOKEN_FAR_SHARE``: the tokens the scheduler chose against
   the best logit of the replay's rows.
6. Token counts; ``close()`` leaves 0 blocks.

``--set control='"all"'`` (builder only) plants faults and prints what the same
comparison makes of each; every one has to come out NOT correct on at least one
request of the two samples (``CONTROLS``), and one that passes on all makes the
run's ``correct`` false.
"""
from __future__ import annotations

import gc
import heapq
import time
from typing import Any, Dict, List

from .. import harness
from .serve import DRAIN_CAP_S
from .serve_hybrid import REPLAY_SLOTS, _alone, _packs, _state_error
from .serve_latent import _schedule

# Tolerances, and why.  Both sides compute from the same bf16 weights; the
# program rounds activations to bf16 (softmax, its sums, the pooling and the
# head's product float32), the reference is float32 throughout.  Each limit lies
# between the largest reading a sound run gave on the chip and what its nearest
# control reads (PERF.md section 2 has the readings; my chip runs, PR 53).
LOGIT_TOL_MAX = 0.12
LOGIT_TOL_MEAN = 0.02
#   the kept summaries against the reference's, |kept - ref| / |ref| over a
#   request's summary rows (the larger of k~'s and v~'s, the largest layer's): bf16
#   rows pooled in float32 from bf16 rows carry the activations' error
SUMMARY_TOL = 1.5e-2
#   the first layer's attention output on the program's OWN q, k, v: what is left
#   is the output's rounding to bf16, the probabilities' rounding before the
#   weighted sum and the summaries' rounding to the page's bf16
ATTN_TOL = 2.7e-3
#   the same difference POSITION BY POSITION, the worst one: a fault in which keys a
#   query sees is large where the query has few keys (the first positions past a
#   window's edge) and a thousandth over the whole sequence.  Sound 2.15e-3-2.18e-3;
#   bfloat16 sums 6.8e-3-1.3e-2, the edge a chunk off 3.1e-2-4.7e-2 (my chip runs, PR 53)
ATTN_ROW_TOL = 4e-3
TOKEN_FAR = 0.5
TOKEN_MEAN = 0.1
TOKEN_FAR_SHARE = 0.1

CONTROLS = {
    "fp8_weights": "the reference itself on float8_e4m3 weights",
    "mean_pooling": "the reference pools a chunk by the mean (phi = 0)",
    "no_key_offset": "the reference's summaries without the key offset (mu = 0)",
    "own_window_summaries": "a query also sees its own window's closed chunks' summaries",
    "summaries_unroped": "the reference pools the keys BEFORE rotary",
    "window_edge_off_by_one_chunk": "the chunk before a window's edge is not seen past it",
    "row_for_position": "the reference's rotary is fed the cache row, not the position",
    "bf16_softmax": "the softmax's running sums carried in bfloat16",
}


class _Replay:
    """Ticks again through ``latent_runner``'s bodies: request ``i`` in slot ``2 i
    + 1`` of a cache of ``REPLAY_SLOTS`` slots, its pages from a block manager of
    this replay's own (``StateManager`` with the runner's compaction: chunks
    reserve their pages, a window that fills gives its exact pages back, and the
    next request's chunk may be handed them).  The two programs are jitted once
    and serve every sample."""

    def __init__(self, jax, np, eng, cfg):
        from deepspeed_tpu.inference import latent_runner

        self.jax, self.np, self.cfg = jax, np, cfg
        self.runner = latent_runner
        self.compaction = eng.runner.compaction
        # (held here: the replay of the window's sample runs after ``close()``)
        self.params = eng.params
        self.shape = eng.block_size, eng.prefill_chunk, eng.max_pages

        def pack_fn(p, tok, seg, pos, pages, last, tab, kv):
            seen: list = []
            lg, kv = latent_runner.prefill_pack(p, cfg, tok, seg, pos, pages, last, tab, kv,
                                                probe=seen)
            return lg, kv, seen

        def dec_fn(p, tok, lens, tab, act, kv):
            seen: list = []
            lg, kv = latent_runner.decode_step(p, cfg, tok, lens, tab, act, kv, probe=seen)
            return lg, kv, seen

        self.pack = jax.jit(pack_fn, donate_argnums=(7,))
        self.dec = jax.jit(dec_fn, donate_argnums=(5,))
        self.pages = jax.jit(lambda k, v, ids: ([a[ids] for a in k], [a[ids] for a in v]))

    def __call__(self, prompts, fed, schedule, n_pages: int):
        """Returns per request (positions whose next-byte logits were taken,
        those logits [n, vocab], the first layer's (q, k, v, o) rows by position
        [s, H, hd] x 4, the kept summaries per layer (k~, v~) [chunks, H, hd], the
        pages its table held at the end, the pages it gave back)."""
        from deepspeed_tpu.inference.ragged import SequenceDescriptor, StateManager

        jax, np, c = self.jax, self.np, self.compaction
        bs, T, P = self.shape
        k, N = len(prompts), REPLAY_SLOTS
        if 2 * k > N:
            raise harness.BenchError(f"{k} sample sequences need {2 * k} slots, the replay has {N}")
        mgr = StateManager(n_pages, bs, N)
        mgr.compaction = c
        seqs = [SequenceDescriptor(uid=i, slot=2 * i + 1) for i in range(k)]
        table = np.full((N, P), -1, np.int32)

        def tabled(i):
            row = table[2 * i + 1]
            row[:] = -1
            row[:len(seqs[i].blocks)] = seqs[i].blocks

        at, rows_got = [[] for _ in prompts], [[] for _ in prompts]
        first = [[] for _ in prompts]   # (position, q, k, v, o) rows of the first layer
        back, stepped = [0] * k, [0] * k  # pages given back; decode steps taken
        cache = self.runner.init_cache(self.cfg, n_pages, bs, N, T)
        for tick_entries, decoding in schedule:
            for entries in _packs(tick_entries, bs, T):
                tok, seg, pos = (np.zeros(T, np.int32) for _ in range(3))
                pages = np.full(T // bs, -1, np.int32)
                last = np.full(N, -1, np.int32)
                cur, starts = 0, []
                for i, start, end in entries:
                    m, slot = end - start, 2 * i + 1
                    mgr.ensure_pages(seqs[i], end)
                    tabled(i)
                    tok[cur:cur + m], seg[cur:cur + m] = prompts[i][start:end], slot + 1
                    pos[cur:cur + m] = np.arange(start, end)
                    col = c.column(start, bs)
                    pages[cur // bs: cur // bs - (-m // bs)] = \
                        seqs[i].blocks[col: col - (-m // bs)]
                    last[slot] = cur + m - 1  # EVERY chunk's last row is scored
                    starts.append(cur)
                    cur += -(-m // bs) * bs  # the next prompt starts on a page
                lg, cache, seen = self.pack(self.params, tok, seg, pos, pages, last, table, cache)
                lg, seen = jax.device_get((lg, seen))  # one fetch a dispatch
                for (i, start, end), cur in zip(entries, starts):
                    mine = slice(cur, cur + end - start)
                    at[i].append(end - 1)
                    rows_got[i].append(lg[2 * i + 1])
                    first[i].append((start, *(seen[0][key][mine] for key in
                                              ("eva_q", "eva_k", "eva_v", "eva_o"))))
                    if end % c.window == 0:
                        back[i] += mgr.close_window(seqs[i], end)
                        tabled(i)
            if decoding:
                t1, lens = np.zeros(N, np.int32), np.zeros(N, np.int32)
                active = np.zeros(N, bool)
                for i in decoding:
                    j, stepped[i] = stepped[i], stepped[i] + 1
                    slot, p = 2 * i + 1, len(prompts[i]) + j
                    mgr.ensure_pages(seqs[i], p + 1)
                    tabled(i)
                    t1[slot], lens[slot], active[slot] = fed[i][j], p, True
                lg, cache, seen = self.dec(self.params, t1, lens, table, active, cache)
                lg, seen = jax.device_get((lg, seen))
                for i in decoding:
                    slot, p = 2 * i + 1, int(lens[2 * i + 1])
                    at[i].append(p)
                    rows_got[i].append(lg[slot])
                    first[i].append((p, *(seen[0][key][slot:slot + 1] for key in
                                          ("eva_q", "eva_k", "eva_v", "eva_o"))))
                    if (p + 1) % c.window == 0:
                        back[i] += mgr.close_window(seqs[i], p + 1)
                        tabled(i)
        out = []
        per = c.window // c.chunk
        most = P * bs // c.window + 1  # summary pages a table can hold: one shape, one compile
        for i in range(k):
            n = len(prompts[i]) + len(fed[i])           # positions written
            chunks = n // c.chunk                       # whole chunks: a summary row each
            held = seqs[i].blocks[:-(-chunks // per)]   # the pages that hold them, in order
            ids = np.zeros(most, np.int32)
            ids[:len(held)] = held
            ks, vs = jax.device_get(self.pages(cache["k"], cache["v"], ids))
            n_pools = len(ks) // self.cfg.num_layers
            rows = lambda pools: np.concatenate(
                [np.asarray(a, np.float32) for a in pools], axis=2).reshape(
                    -1, n_pools * pools[0].shape[2], pools[0].shape[3])[:chunks]
            kept = [(rows(ks[l * n_pools:(l + 1) * n_pools]), rows(vs[l * n_pools:(l + 1) * n_pools]))
                    for l in range(self.cfg.num_layers)]
            q, kk, v, o = (np.zeros((n, *first[i][0][1].shape[1:]), np.float32) for _ in range(4))
            for p, fq, fk, fv, fo in first[i]:
                for dst, src in ((q, fq), (kk, fk), (v, fv), (o, fo)):
                    dst[p:p + len(src)] = np.asarray(src, np.float32)
            out.append((np.asarray(at[i]), np.stack(rows_got[i]), (q, kk, v, o), kept,
                        len(seqs[i].blocks), back[i]))
        del cache
        return out


def _attn_off(np, got, ref) -> tuple:
    """(|got - ref| / |ref| over the whole sequence, the same at its worst position)."""
    n = len(got)
    d, r = (np.linalg.norm(a.reshape(n, -1), axis=1) for a in (got - ref[:n], ref[:n]))
    return (float(np.linalg.norm(d) / max(np.linalg.norm(r), 1e-30)),
            float((d / np.maximum(r, 1e-30)).max()))


def _check_sample(np, model, compaction, arch, got_at, got, first, kept, n_pages: int,
                  returned: int, ref_logits, ref_seen, ref_attn, n_prompt: int, tokens, notes,
                  what: str) -> bool:
    """The comparisons of the module docstring, for one sequence."""
    d = np.abs(got - ref_logits[got_at])
    decode = got_at >= n_prompt - 1
    # a control's replay is judged WITHOUT the tokens: they are the sound
    # engine's, and a fault in both programs would leave them agreeing
    short = np.zeros(int(decode.sum())) if tokens is None else \
        got[decode].max(-1) - got[decode][np.arange(int(decode.sum())), np.asarray(tokens)]
    n = int(got_at.max()) + 1                       # positions written
    mine = [a for kv in kept for a in kv]
    theirs = [r[key][:len(kept[0][0])] for r in ref_seen for key in ("eva_k", "eva_v")]
    summary_off = _state_error(np, mine, theirs) if len(mine) == len(theirs) and len(mine[0]) \
        else (0.0 if not len(mine[0]) else float("inf"))
    attn_off, attn_row = _attn_off(np, first[3], ref_attn)
    w = compaction.window
    bs = model["engine"]["block_size"]
    pages_ok = n_pages == (compaction.pages_for(n, bs) if n % w else n // w)
    rows_ok = compaction.rows_live(n) == arch.rows(n, model)
    back_ok = returned == n // w * (w // bs)
    ok = bool(np.all(np.isfinite(got)) and d.max() <= LOGIT_TOL_MAX and d.mean() <= LOGIT_TOL_MEAN
              and summary_off <= SUMMARY_TOL and attn_off <= ATTN_TOL and attn_row <= ATTN_ROW_TOL
              and pages_ok and rows_ok
              and back_ok and short.mean() <= TOKEN_MEAN
              and (short > TOKEN_FAR).mean() <= TOKEN_FAR_SHARE)
    notes.append(
        f"{what}: {n_prompt}-byte prompt in chunks + {int(decode.sum()) - 1} decode steps, "
        f"replayed through the runner vs plain reference: {len(got_at)} logit rows (every "
        f"chunk's last, every step) max|d| {d.max():.4f} (tol {LOGIT_TOL_MAX}), mean|d| "
        f"{d.mean():.4f} (tol {LOGIT_TOL_MEAN}), reference std {ref_logits[got_at].std():.2f}; "
        f"{len(kept[0][0])} kept summary rows x {len(kept)} layers off the reference's k~, v~ by "
        f"{summary_off:.2e} of their norm (tol {SUMMARY_TOL}); the first layer's attention on its "
        f"own q, k, v off the reference's one softmax by {attn_off:.2e} (tol {ATTN_TOL}), "
        f"{attn_row:.2e} at its worst position (tol {ATTN_ROW_TOL}); the "
        f"table holds {n_pages} pages after {n} positions ({'as' if pages_ok else 'NOT as'} "
        f"{n // w} closed windows and the open one keep), {compaction.rows_live(n)} live rows "
        f"({'the' if rows_ok else 'NOT the'} reference's count), {returned} pages given back "
        f"({'all' if back_ok else 'NOT all'} of {n // w} windows' exact pages); "
        + ("the scheduler's tokens left out of a control" if tokens is None else
           f"the scheduler's {len(short)} tokens {short.mean():.5f} under the replay's best logit "
           f"in the mean (limit {TOKEN_MEAN}), {int((short > TOKEN_FAR).sum())} of them further "
           f"than {TOKEN_FAR} under it (limit {TOKEN_FAR_SHARE:.0%} of them)")
        + f" -> {ok}")
    return ok


def _controls(jax, np, weights, arch, model, sound, names, notes) -> list:
    """Builder's controls (``CONTROLS``): the reference itself, departing in one
    place, against the program's rows of EVERY request of the two samples (a
    sound run holds on all; which request catches a control is part of the
    reading: one that only a context past the first window can show is caught by
    the longer requests alone).  Returns the controls that PASSED on all, which
    none may."""
    import jax.numpy as jnp

    passed = []
    for name in names:

        def departing(p, t, q, k, v):  # a second copy of the weights would not fit
            inside = arch.weights_rounded_to(jnp.float8_e4m3fn) if name == "fp8_weights" \
                else arch.departure(name)
            with inside:
                lg, seen = arch.probe(p, t, model)
                return lg, seen, arch.attention_on(p["layers"]["eva"][0], q, k, v, model)

        caught, fn = [], jax.jit(departing)
        for i, (prompt, buf, rows3, got_at, got, first, kept) in enumerate(sound):
            o = first[3]
            lg, seen, attn = fn(weights, buf, *rows3)
            attn = np.asarray(attn)[:, :len(o)]
            d = np.abs(np.asarray(lg)[0][got_at] - got)
            theirs = [np.asarray(r[key][0])[:len(kept[0][0])] for r in seen
                      for key in ("eva_k", "eva_v")]
            mine = [a for kv in kept for a in kv]
            s_off = _state_error(np, mine, theirs) if len(mine[0]) else 0.0
            a_off, a_row = _attn_off(np, o, attn[0])
            held = bool(d.max() <= LOGIT_TOL_MAX and d.mean() <= LOGIT_TOL_MEAN
                        and s_off <= SUMMARY_TOL and a_off <= ATTN_TOL and a_row <= ATTN_ROW_TOL)
            if not held:
                caught.append(i + 1)
            notes.append(f"control {name} ({CONTROLS[name]}): request {i + 1} ({len(prompt)} "
                         f"prompt bytes, {len(got_at)} rows) max|d| {d.max():.4f} (tol "
                         f"{LOGIT_TOL_MAX}), mean|d| {d.mean():.4f} (tol {LOGIT_TOL_MEAN}), "
                         f"summaries off {s_off:.2e} (tol {SUMMARY_TOL}), attention off "
                         f"{a_off:.2e} (tol {ATTN_TOL}), {a_row:.2e} at its worst position "
                         f"(tol {ATTN_ROW_TOL}) -> would pass: {held}")
        notes.append(f"control {name}: refused on requests {caught} of {len(sound)}")
        if not caught:
            passed.append(name)
    notes.append("controls: " + (f"PASSED AS CORRECT, and must not: {passed}" if passed
                                 else f"all of {names} came out not correct"))
    return passed


def run(*, config, traffic, chips, seed, seconds, trace, rehearse, workload,
        t_process, watch, device) -> Dict[str, Any]:
    import jax
    import numpy as np

    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.inference.sampling import SamplingParams
    from deepspeed_tpu.inference.scheduler import FINISHED, TERMINAL
    from deepspeed_tpu.models.transformer import init_params
    from deepspeed_tpu.ops.pallas import record_dispatch
    from deepspeed_tpu.telemetry import Telemetry

    notes: List[str] = []
    model = config
    control = traffic.get("control")
    planted = list(CONTROLS) if control == "all" else \
        [control] if isinstance(control, str) else list(control or ())
    for name in planted:
        if name not in CONTROLS:
            raise harness.BenchError(f"unknown control {name!r}; there are {sorted(CONTROLS)}")
    arch = harness.module("models", model["model_type"])
    e = dict(config["engine"], **traffic.get("engine", {}))  # a builder's sweep of the pack
    cfg = arch.transformer_config(model, max_seq_len=e["max_seq_len"])
    clock = time.perf_counter
    lap = harness.Laps(notes)

    params = jax.jit(lambda key: init_params(key, cfg, dtype=cfg.dtype))(
        jax.random.PRNGKey(seed % (2**31 - 1)))
    jax.block_until_ready(params)
    lap("weights on device")
    tel = Telemetry(enabled=True, jax_profiler=trace, max_spans=1 << 20)
    plan = harness.module("generators", traffic["kind"]).build(
        traffic, seed=seed, seconds=seconds, vocab=cfg.vocab_size)
    rng = np.random.default_rng([seed, 3])
    greedy = lambda n: SamplingParams(temperature=0.0, max_new_tokens=int(n))

    with record_dispatch() as dispatch_log:
        eng = InferenceEngineV2(
            params, cfg, max_seqs=e["max_seqs"], num_blocks=e["num_blocks"],
            block_size=e["block_size"], max_seq_len=e["max_seq_len"],
            prefill_buckets=(e["prefill_chunk"],), prefill_chunk=e["prefill_chunk"],
            enable_prefix_caching=e["prefix_caching"], telemetry=tel, seed=seed % (2**31 - 1),
        )
        del params
        sched = eng.scheduler
        compaction = eng.runner.compaction
        lap("engine built")

        # -- warm-up IS the correctness sample: its requests together through
        # the scheduler (each pack is the one pack program, shared by two
        # prompts where their chunks fit; then decode ticks, one of which
        # closes the shortest request's first window)
        sample = config["correctness"]
        steps = int(sample["decode_steps"])
        prompts = [rng.integers(0, cfg.vocab_size, int(n)).tolist() for n in sample["prompts"]]
        warm = [10**9 + i for i in range(len(prompts))]  # uids the loop never reaches
        for u, prompt in zip(warm, prompts):
            r = sched.try_submit(u, prompt, greedy(steps))
            if not r.accepted:
                raise harness.BenchError(f"warm-up request refused: {r.reason}")
        sched.run(wait_for=warm)
        schedule = _schedule([sched.requests[u].trace for u in warm], prompts)
        outs = [sched.pop_result(u) for u in warm]
        shared = int(eng.stats["prefill_dispatches"])
        closed, returned = int(eng.stats["eva_windows_closed"]), int(eng.stats["eva_pages_returned"])
        free = eng.mgr.allocator.free_blocks
        lap("warm-up through the scheduler")
        # -- the same sequences through the runner bodies, tokens fed back ---
        fed = [o[:-1] for o in outs]
        replay = _Replay(jax, np, eng, cfg)
        # the replay's pool: every request's table at its fullest at once, and a few more
        page_need = lambda ps, fs: sum(compaction.peak_pages(len(p) + len(f), e["block_size"])
                                       for p, f in zip(ps, fs)) + 8
        replays = replay(prompts, fed, schedule, page_need(prompts, fed))
        lap("correctness: runner replay")
    # -- the plain reference, one forward a sequence: AFTER the window and
    # ``close()``, when the pool's 9 GiB are free (its float32 rows and scores
    # for a 9k-byte request do not fit beside the pool)
    def judged(weights, prompts, outs, replays, what: str) -> tuple:
        """One reference forward a request (all padded to one length: one
        compile a sample) and the comparisons; (all held, each request's buffers
        for the controls)."""
        pad_to = -(-max(len(p) + len(o) - 1 for p, o in zip(prompts, outs)) // 128) * 128
        jitted = jax.jit(lambda p, t, q, k, v: (
            *arch.probe(p, t, model), arch.attention_on(p["layers"]["eva"][0], q, k, v, model)))
        ok, sound = True, []
        for i, (prompt, out, (got_at, got, first, kept, n_pages, back)) in enumerate(
                zip(prompts, outs, replays)):
            buf = np.zeros((1, pad_to), np.int32)
            buf[0, :len(prompt) + len(out) - 1] = prompt + list(out[:-1])
            rows3 = [np.pad(a, ((0, pad_to - len(a)), (0, 0), (0, 0)))[None] for a in first[:3]]
            lg, seen, attn = jitted(weights, buf, *rows3)
            ref_seen = [{k: np.asarray(v[0]) for k, v in layer.items()} for layer in seen]
            ok &= _check_sample(np, model, compaction, arch, got_at, got, first, kept, n_pages,
                                back, np.asarray(lg)[0], ref_seen, np.asarray(attn)[0],
                                len(prompt), out, notes, f"correct: {what} {i + 1} of {len(prompts)}")
            sound.append((prompt, buf, rows3, got_at, got, first, kept))
        return ok, sound

    correct = all(len(o) == steps for o in outs)
    alone = sum(-(-len(p) // e["prefill_chunk"]) for p in prompts)
    per_close = compaction.window // e["block_size"]
    want_closed = sum((len(p) + steps - 1) // compaction.window for p in prompts)
    tables_ok = closed == want_closed and returned == closed * per_close \
        and free == e["num_blocks"]
    notes.append(f"correct: the sample's {len(prompts)} prompts took {shared} packs through "
                 f"the scheduler ({alone} if no pack were shared); the engine closed {closed} "
                 f"windows ({want_closed} by the positions) and gave back {returned} pages "
                 f"({per_close} a window), {free} of {e['num_blocks']} pages free once the "
                 f"requests had gone -> {tables_ok}")
    correct &= tables_ok
    fallbacks = [d for d in dispatch_log if not d["ran"]]
    for d in fallbacks:
        notes.append(f"kernel gate declined: {d['kernel']} {d['shape']}: {d['reason']}")

    # -- the loop (drivers/serve_windowed.py's, itself serve.py's lines 187-314,
    # with the counters of a model whose tables shrink)
    requests: List[dict] = []      # every request ever due, in submit order
    live: Dict[int, dict] = {}
    # (t_begin, t_end, n_decoding, sum_ctx_tokens, n_in_flight, n_waiting)
    ticks: List[tuple] = []
    heap: List[tuple] = []
    order = 0
    COUNTED = ("decode_ticks", "decode_emitted", "prefill_dispatches",
               "prefill_tokens_dispatched", "eva_windows_closed", "eva_pages_returned",
               "eva_summary_rows_read", "eva_exact_rows_read")
    GAUGES = ("eva_rows_live", "eva_context_tokens_live")

    def snapshot() -> Dict[str, int]:
        eng.refresh_routing_stats()  # host arithmetic alone for this kind
        snap = {k: int(eng.stats[k]) for k in COUNTED + GAUGES}
        snap["preemptions"] = int(sched.stats["preemptions"])
        snap["prompt_tokens_total"] = eng.mgr.prompt_tokens_total
        snap["cached_prompt_tokens"] = eng.mgr.cached_prompt_tokens
        return snap

    def n_abnormal() -> int:
        return int(eng.stats["failed"]) + int(eng.stats["timed_out"])

    base = None          # counters at the window's start
    abnormal = n_abnormal()

    gc.collect()
    gc.freeze()
    gc.disable()
    t0 = clock() + plan.ramp_s
    t1 = t0 + seconds
    for due, req in plan.initial():
        heapq.heappush(heap, (t0 + due, order, req))
        order += 1
    cap = harness.Capture(trace, workload, t1, float(traffic.get("trace_s", 4.0)))
    uid = 0

    def keep_trace(rec: dict) -> None:
        """What the readers take from the program's own request trace; the
        token lists go."""
        tr = sched.requests[rec["uid"]].trace
        rec["admit"] = getattr(tr, "admit_ts", None)
        rec["chunks"] = list(getattr(tr, "chunks", ()))
        rec["req"] = None

    def finish(rec: dict, now: float) -> None:
        """Terminal: keep the request's trace, hand the plan its answer."""
        nonlocal order
        req = rec["req"]
        rec["state"] = sched.requests[rec["uid"]].state
        rec["end"] = now
        keep_trace(rec)
        out = sched.pop_result(rec["uid"])
        rec["got"] = len(out)
        rec["served"] = (req.prompt, out)  # what the window's sample is drawn from
        del live[rec["uid"]]
        if rec["state"] == FINISHED:
            for due, nxt in plan.on_finish(req, now - t0, out):
                heapq.heappush(heap, (t0 + due, order, nxt))
                order += 1

    try:
        while True:
            now = clock()
            if now >= t1:
                break
            if base is None and now >= t0:
                base = snapshot()
            cap.poll(now)
            while heap and heap[0][0] <= now:
                due, _, req = heapq.heappop(heap)
                uid += 1
                rec = {"uid": uid, "session": req.session, "turn": req.turn,
                       "due": due, "prompt_len": len(req.prompt),
                       "asked": req.max_new, "token_times": [], "state": "inflight",
                       "end": None, "got": 0, "req": req, "admit": None, "chunks": [],
                       "slot": None, "served": None}
                with cap.annotate("bench.submit"):
                    res = sched.try_submit(uid, req.prompt, greedy(req.max_new))
                rec["submit"] = clock()
                requests.append(rec)
                if res.accepted:
                    live[uid] = rec
                else:
                    rec["state"], rec["end"] = "refused", rec["submit"]
            if sched.idle:
                nxt = heap[0][0] if heap else t1
                time.sleep(max(0.0, min(nxt, t1) - clock(), 0.0002))
                continue
            tb = clock()
            with cap.annotate("bench.tick", tick=len(ticks)):
                out = sched.tick()
            te = clock()
            n_dec = ctx_sum = 0
            for u in out:
                rec = live[u]
                rec["token_times"].append(te)
                if len(rec["token_times"]) > 1:
                    n_dec += 1
                    ctx_sum += rec["prompt_len"] + len(rec["token_times"]) - 1
                elif u in eng.mgr.seqs:
                    rec["slot"] = eng.mgr.seqs[u].slot
            ticks.append((tb, te, n_dec, ctx_sum, len(live), len(sched.waiting)))
            for u in list(out):
                if sched.requests[u].state in TERMINAL:
                    finish(live[u], te)
            if n_abnormal() != abnormal:
                # a request failed or timed out: it never shows in ``out``
                abnormal = n_abnormal()
                for u in list(live):
                    if sched.requests[u].state in TERMINAL:
                        finish(live[u], te)
        end = snapshot()
        obs_trace = cap.finish()
        # after the window: no new submissions, but tick on until one more
        # request in flight has its first token, so that the curve of
        # completed prefill reaches past the window's end (readers/serve_rate);
        # none of this is inside the window or the set-up
        t_cap = clock() + DRAIN_CAP_S
        waiting_first = [r for r in live.values() if not r["token_times"]]
        while waiting_first and all(not r["token_times"] for r in waiting_first) \
                and clock() < t_cap:
            out = sched.tick()
            te = clock()
            for u in out:
                if u in live:
                    live[u]["token_times"].append(te)
    finally:
        gc.enable()
    for rec in live.values():  # still in flight: before close() cancels them
        keep_trace(rec)
    start = base or end
    counters = {k: end[k] - start[k] for k in end if k not in GAUGES}
    # a gauge has no increase: the window's two ends together
    counters.update({k: end[k] + start[k] for k in GAUGES})
    counters["eva_rows_read"] = counters["eva_summary_rows_read"] + counters["eva_exact_rows_read"]
    spans = [(ev["name"], ev["ts"] * 1e-6, (ev["ts"] + ev["dur"]) * 1e-6, ev.get("args", {}))
             for ev in tel.recorder.chrome_events() if ev.get("ph") == "X"]
    scopes = None
    if obs_trace is not None:
        # the programs' named scopes, while the engine still holds them (the
        # readers of a named XLA body run after close())
        from deepspeed_tpu import telemetry

        scopes = telemetry.program_scopes()
    # -- what the WINDOW served, re-scored (after the window: no part of it or
    # of the set-up): the finished requests of the fewest tokens, one a slot,
    # and the longest one the reference has room for
    want = int(sample.get("window_requests", 0))
    cap_tokens = int(sample.get("window_longest_under", 0))
    pool = sorted((r for r in requests if r["state"] == FINISHED and r["served"]
                   and t0 <= r["end"] < t1 and r["got"] == r["asked"] > 1),
                  key=lambda r: (r["prompt_len"] + r["got"], r["uid"]))
    taken: List[dict] = []
    for r in pool:
        if len(taken) < want and r["slot"] not in {t["slot"] for t in taken}:
            taken.append(r)
    longest = [r for r in pool if r["prompt_len"] + r["got"] < cap_tokens and r not in taken]
    if want and longest:
        taken.append(longest[-1])
    if want and not rehearse and len(taken) < want + 1:
        notes.append(f"correct: the window finished {len(pool)} requests "
                     f"({len(longest)} more under {cap_tokens} tokens), its sample needs "
                     f"{want} and one of those")
        correct = False
    for rec in requests:
        rec["req"] = None
    weights = eng.params
    audit = eng.close()   # the pool goes; the weights stay with ``weights``
    t_sample = clock()
    held, sound = judged(weights, prompts, outs, replays, "request")
    correct &= held
    del replays
    if taken:
        w_prompts = [list(r["served"][0]) for r in taken]
        w_outs = [list(r["served"][1]) for r in taken]
        w_fed = [o[:-1] for o in w_outs]
        notes.append(f"correct: the window's sample: requests of slots "
                     f"{[r['slot'] for r in taken]} with {[r['prompt_len'] for r in taken]} "
                     f"prompt and {[r['got'] for r in taken]} answer bytes, of {len(pool)} "
                     f"finished inside the window")
        w_replays = replay(w_prompts, w_fed, _alone(w_prompts, w_fed, e["prefill_chunk"]),
                           page_need(w_prompts, w_fed))
        # the shortest ones padded to their own length and the longest alone: no short
        # request's reference is a forward over the longest's 30k positions
        for part, what in ((slice(0, want), "window request"),
                           (slice(want, None), "the window's longest request")):
            if w_prompts[part]:
                held, w_sound = judged(weights, w_prompts[part], w_outs[part], w_replays[part],
                                       what)
                correct &= held
                sound += w_sound
        del w_replays
    notes.append(f"after the window and close(): the samples' references and the window "
                 f"sample's replay took {clock() - t_sample:.2f} s")
    if planted:
        if not taken:
            raise harness.BenchError("the controls are judged on the window's sample too, and "
                                     "the window finished no request")
        correct &= not _controls(jax, np, weights, arch, model, sound, planted, notes)
    for r in requests:
        r["served"] = None
    del replay, weights, sound

    done = [r for r in requests if r["state"] == FINISHED]
    wrong_count = [r for r in done if r["got"] != r["asked"]]
    if wrong_count:
        notes.append(f"correct: {len(wrong_count)} finished requests with the wrong token count")
    if audit["blocks_in_use"]:
        notes.append(f"correct: close() left {audit['blocks_in_use']} blocks in use")
    per_window = counters["eva_pages_returned"] / max(counters["eva_windows_closed"], 1)
    if counters["eva_windows_closed"] and per_window != per_close:
        notes.append(f"correct: the window's closes gave back {per_window} pages each, "
                     f"not {per_close}")
    correct = bool(correct and not wrong_count and audit["blocks_in_use"] == 0
                   and (not counters["eva_windows_closed"] or per_window == per_close))
    attempted = sum(1 for r in requests if t0 <= r["due"] < t1)
    failed = sum(1 for r in requests
                 if r["state"] not in (FINISHED, "inflight")
                 and r["end"] is not None and t0 <= r["end"] < t1)
    fifth = seconds / 5
    for k in range(5):
        part = [t for t in ticks if t0 + k * fifth <= t[1] < t0 + (k + 1) * fifth]
        if part:
            notes.append(
                f"load: window fifth {k + 1}: {len(part)} ticks, in flight mean "
                f"{sum(t[4] for t in part) / len(part):.1f} max {max(t[4] for t in part)}, "
                f"waiting max {max(t[5] for t in part)}")
    # where a run's trajectory was decided: the ramp's ticks, the longest tick and
    # the longest pause between two ticks of the whole run, compiles before the window
    ramp = [t for t in ticks if t[1] < t0]
    firsts = sorted(r["token_times"][0] for r in requests[:plan.clients] if r["token_times"])
    if len(ticks) > 1 and firsts:
        slow = max(ticks, key=lambda t: t[1] - t[0])
        gap, at = max((b[0] - a[1], a[1]) for a, b in zip(ticks, ticks[1:]))
        notes.append(
            f"load: ramp: {len(ramp)} ticks, {sum(1 for t in ramp if t[2])} with decode rows, "
            f"the {len(firsts)} callers' first answers began {firsts[0] - t0:.2f} to "
            f"{firsts[-1] - t0:.2f} s; longest tick {(slow[1] - slow[0]) * 1e3:.1f} ms at "
            f"{slow[1] - t0:.2f} s, longest pause between ticks {gap * 1e3:.1f} ms at "
            f"{at - t0:.2f} s; {watch.within(t0 - plan.ramp_s, t0)} compiles in the ramp")
        step = (plan.ramp_s + seconds) / 13
        cuts = [t0 - plan.ramp_s + step * k for k in range(14)]
        slices = [[t for t in ticks if a <= t[1] < b] for a, b in zip(cuts, cuts[1:])]
        notes.append(
            f"load: every {step:.1f} s from the ramp's start, ticks / rows decoding a tick / "
            "their context bytes a tick: " + ", ".join(
                f"{len(p)} / {sum(t[2] for t in p) / len(p):.1f} / "
                f"{sum(t[3] for t in p) / len(p):.0f}" for p in slices if p))
    inside = [r for r in done if t0 <= r["end"] < t1]
    notes.append(f"window: {len(ticks)} ticks, {len(requests)} requests submitted in "
                 f"all, {attempted} due inside the window, {len(done)} finished "
                 f"({len(inside)} inside the window), {len(live)} in flight at the end; "
                 f"ramp {plan.ramp_s:.1f} s; {counters['eva_windows_closed']} windows closed, "
                 f"{counters['eva_pages_returned']} pages given back, "
                 f"{counters['preemptions']} preemptions")
    return {
        "kind": "serve", "correct": correct, "attempted": attempted,
        "failed": failed, "window": (t0, t1), "t_process": t_process,
        "requests": requests, "ticks": ticks, "spans": spans, "counters": counters,
        "fallbacks": fallbacks, "compiles_in_window": watch.within(t0, t1),
        "trace": obs_trace, "model": model, "engine": e, "chips": chips,
        "notes": notes, **({} if scopes is None else {"_scopes": scopes}),
    }
