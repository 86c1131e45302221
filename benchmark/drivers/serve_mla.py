"""Serving cells of a model of latent attention over EVERY cached row (latent
pages alone, shared between requests by the prefix cache) with a held share of
group-limited experts: ``InferenceEngineV2`` + ``ServeScheduler`` driven as the
five serving drivers beside this one drive them.  What could be imported is
(``serve.DRAIN_CAP_S``, ``serve_latent._forced``, ``serve_hybrid._packs`` /
``._alone`` / ``REPLAY_SLOTS``); the order of a run and the loop are theirs,
copied once more because each keeps them inside its ``run`` (ROADMAP D1c: one
loop is a ``benchmark`` PR's).  This file's own part is the SAMPLE: its replay,
its comparison, its controls.

TWO samples go through one comparison (``_check_sample``).  The first is the
WARM-UP: ``correctness.prompts`` requests of unequal length submitted TOGETHER
and served by the scheduler itself (packs shared by the tail of one prompt and
the head of the next, pages handed out in turns, then decode ticks of a batch
of unequal contexts), ``decode_steps`` greedy tokens each; THEN one more
request (``correctness.hit``) whose first ``shared_tokens`` tokens are request
``of``'s, so that the prefix cache serves them: its pack starts at a position
> 0 on pages ANOTHER request wrote.  The second is taken from what the WINDOW
served, after the window and the engine's ``close()``, in no metric: the
LONGEST finished request under ``window_max_tokens`` that was served on a hit
(a session's question 2-4) and the longest that was served cold (a session's
first question; another hit where the window finished none), no two from one
slot, every token of their answers: ~20k rows, the contexts the window's packs
and ticks run over, not the shortest it finished.  Either sample's tokens are
fed through the runner's bodies again (``_Replay``: a cache of its own, each request in a slot
that is not 0, on pages interleaved with the others'; the warm-up's hit request
on the pages its donor's replay wrote, from the position the engine's hit
reached), for the logits and for what each router PICKED; the plain reference
makes ONE forward over each request's WHOLE prompt on the program's picks
(``probe(forced=)``).  Held, per sequence:

1. ``LOGIT_TOL_MAX`` / ``LOGIT_TOL_MEAN``: next-token logits at the last prompt
   position and every decode step against the reference on the program's picks;
   the hit request to the SAME limits as a cold one.
2. The picks: every token picked ``num_experts_per_tok`` DISTINCT experts in at
   most ``topk_group`` groups; every group picked from lies no further than
   ``GROUP_MARGIN`` under the reference's cut-off of groups (its ``topk_group``-th
   largest group maximum); at most ``GROUP_FLIP_SHARE`` of the tokens picked from
   a group the reference did not keep (a near tie of two groups' maxima falls
   either way under bf16 activations); where the groups agree, every expert
   picked lies no further than ``ROUTER_MARGIN`` under the reference's cut-off
   (its ``num_experts_per_tok``-th largest score inside its kept groups).
3. ``TOKEN_MEAN`` / ``TOKEN_FAR_SHARE`` (the window's: ``WINDOW_*``): the tokens
   the scheduler chose against the best logit of the replay's rows, in the mean
   and by the share of them further than ``TOKEN_FAR`` under it.
4. Token counts; ``close()`` leaves 0 blocks in use.

``--set control='"all"'`` (builder only) plants faults and prints what the same
comparison makes of each; every one has to come out NOT correct (``CONTROLS``),
and one that passes makes the run's ``correct`` false.
"""
from __future__ import annotations

import gc
import heapq
import time
from typing import Any, Dict, List

from .. import harness
from .serve import DRAIN_CAP_S
from .serve_hybrid import REPLAY_SLOTS, _alone, _packs
from .serve_latent import _forced

# Tolerances, and why.  Both sides compute from the same bf16 weights on the
# same picks; the program rounds activations to bf16, the reference is float32
# throughout.  Each limit lies between the largest reading a sound run gave on
# the chip and what its control reads (PERF.md section 2 has the readings; my
# chip runs, PR 45).
#   logits of 25 600 a row, reference std 1.00.  Sound: max 0.105-0.174, mean
#   0.0158-0.0226 (the mean hardly moves; a request behind a prefix hit reads as
#   a cold one: 0.124 / 0.0197).  The control one precision down, ``fp8_weights``,
#   reads 0.98-1.65 / 0.165-0.169; the controls of the mathematics 3.8-5.9 /
#   0.54-0.85; the hit served from another request's blocks 6.38 / 1.07: each
#   limit stands ~2 x over the largest sound reading and 2.9-4 x under the
#   nearest control
LOGIT_TOL_MAX = 0.35
LOGIT_TOL_MEAN = 0.04
#   softmax scores over 160 experts (mean 1 / 160 = 0.006; a picked one scores
#   0.02-0.05), so the margins are of SCORES.  A group is kept by its largest
#   member, and the third and fourth of eight maxima are often a near tie under
#   bf16 activations: sound runs pick from a group the reference did not keep in
#   4.1-6.3% of the router rows, from groups whose maximum lies 0.0017-0.0046
#   under the reference's third (``fp8_weights``: 34% of the rows, 0.018-0.033
#   under).  Where the groups agree a pick lies 0.0010-0.0026 under the
#   reference's cut-off (``fp8_weights`` 0.011-0.015; ``no_group_limit``, whose
#   logits are a sound run's, 0.024-0.032: refused by this alone)
GROUP_MARGIN = 0.008
GROUP_FLIP_SHARE = 0.15
ROUTER_MARGIN = 0.005
#   the engine's dispatch and the replay are two XLA programs of the same bodies
#   on other batch shapes; where a router's near tie falls the other way a logit
#   moves.  This router's weights are the raw scores (x 16, not renormalised) and
#   a flip at the cut-off moves little: the served tokens are the replay's best
#   but for 0-2 a request, the furthest 0.0227 under it, the MEAN 0.0-0.0022
#   (control ``served_tokens_swapped`` 3.83-4.11 in the mean, every token further than
#   0.5).  Held: the MEAN shortfall and the share of tokens further than
#   ``TOKEN_FAR`` under the best (the warm-up's nine tokens a request get the
#   wider share: one token is 11% of them)
TOKEN_FAR = 0.5
TOKEN_MEAN = 0.1
TOKEN_FAR_SHARE = 0.25
WINDOW_TOKEN_MEAN = 0.05
WINDOW_TOKEN_FAR_SHARE = 0.05

CONTROLS = {
    "fp8_weights": "the reference itself on float8_e4m3 weights, same picks",
    "no_softmax_mscale": "the reference's softmax scale without YaRN's mscale^2 (x 1.5896)",
    "no_yarn": "the reference's rotary table without YaRN (factor 1) and the plain scale",
    "no_group_limit": "the reference's router picks over all 160 experts: the picks",
    "routing_renormalised": "the reference renormalises the picked scores",
    "routing_not_scaled": "the reference's routed weights not times 16",
    "prefix_rows_stale": "the replay serves the hit from ANOTHER request's blocks: the hit request's logits",
    "served_tokens_swapped": "a request's tokens held to ANOTHER's replay: tokens",
}


def _ticks(traces, prompts) -> list:
    """The scheduler's ticks as its request traces tell them
    (``serve_latent._schedule``, for requests whose prefill may start behind a
    prefix hit): per tick the pack's entries [(request, start, end)], first
    come first, and the requests that decoded a token.  A request's first
    chunk starts where its hit ended: at its prompt's length less the tokens
    its chunks computed."""
    ticks: Dict[int, tuple] = {}
    for i, tr in enumerate(traces):
        start = len(prompts[i]) - sum(n for _, _, n in tr.chunks)
        if start < 0 or None in tr.chunk_ticks + tr.emission_ticks:
            raise harness.BenchError(f"sample request {i}: its trace does not tell its "
                                     f"prefill ({-start} tokens past its prompt)")
        for (_, _, n), tick in zip(tr.chunks, tr.chunk_ticks):
            ticks.setdefault(tick, ([], []))[0].append((i, start, start + n))
            start += n
        for tick in tr.emission_ticks[1:]:  # the first token is the prefill's
            ticks.setdefault(tick, ([], []))[1].append(i)
    return [ticks[t] for t in sorted(ticks)]


class _Replay:
    """Ticks again through ``latent_runner``'s bodies (``serve_windowed._Replay``
    with this model's cache): request ``i`` in slot ``2 i + 1`` on pages ``i, i +
    n, i + 2 n ..`` of a cache of ``REPLAY_SLOTS`` slots and as many pages as the
    sample needs; ``shared`` = {request: (donor, pages)} puts a request's first
    pages on its donor's (a prefix hit: the schedule then starts it behind
    them).  The two programs are jitted once and serve every sample; the
    weights are held here, so the replay outlives the engine's ``close()``."""

    def __init__(self, jax, np, eng, cfg):
        from deepspeed_tpu.inference import latent_runner

        self.jax, self.np, self.cfg = jax, np, cfg
        self.params, self.runner = eng.params, latent_runner
        self.sizes = eng.block_size, eng.prefill_chunk, eng.max_pages

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

    def __call__(self, prompts, fed, schedule, shared=None):
        """Returns per request (logits rows [1 + len(fed[i]), vocab], probes:
        (first position, positions, per expert layer its picks) a dispatch)."""
        jax, np = self.jax, self.np
        (bs, T, P), k, N = self.sizes, len(prompts), REPLAY_SLOTS
        if 2 * k > N:
            raise harness.BenchError(f"{k} sample sequences need {2 * k} slots, the replay has {N}")
        table = np.full((N, P), -1, np.int32)
        n_pages = [-(-(len(p) + len(f)) // bs) for p, f in zip(prompts, fed)]
        for i, n in enumerate(n_pages):
            table[2 * i + 1, :n] = i + k * np.arange(n)
        for i, (donor, n) in (shared or {}).items():
            table[2 * i + 1, :n] = table[2 * donor + 1, :n]
        rows = [[] for _ in prompts]
        probes = [[] for _ in prompts]
        cut = lambda seen, rows: jax.tree_util.tree_map(lambda a: a[rows], seen)
        cache = self.runner.init_cache(self.cfg, k * max(n_pages) + 1, bs, N, T)
        for tick_entries, decoding in schedule:
            for entries in _packs(tick_entries, bs, T):
                tok, seg, pos = (np.zeros(T, np.int32) for _ in range(3))
                pages = np.full(T // bs, -1, np.int32)
                last = np.full(N, -1, np.int32)
                cur, at = 0, []
                for i, start, end in entries:
                    m, slot = end - start, 2 * i + 1
                    tok[cur:cur + m], seg[cur:cur + m] = prompts[i][start:end], slot + 1
                    pos[cur:cur + m] = np.arange(start, end)
                    pages[cur // bs: cur // bs - (-m // bs)] = \
                        table[slot, start // bs: start // bs - (-m // bs)]
                    if end == len(prompts[i]):
                        last[slot] = cur + m - 1
                    at.append(cur)
                    cur += -(-m // bs) * bs  # the next prompt starts on a page
                lg, cache, seen = self.pack(self.params, tok, seg, pos, pages, last, table, cache)
                lg, seen = jax.device_get((lg, seen))  # one fetch a dispatch
                for (i, start, end), cur in zip(entries, at):
                    probes[i].append((start, end - start, cut(seen, slice(cur, cur + end - start))))
                    if end == len(prompts[i]):
                        rows[i].append(lg[2 * i + 1])
            if decoding:
                t1, lens = np.zeros(N, np.int32), np.zeros(N, np.int32)
                active = np.zeros(N, bool)
                for i in decoding:
                    j = len(rows[i]) - 1
                    t1[2 * i + 1], lens[2 * i + 1] = fed[i][j], len(prompts[i]) + j
                    active[2 * i + 1] = True
                lg, cache, seen = self.dec(self.params, t1, lens, table, active, cache)
                lg, seen = jax.device_get((lg, seen))
                for i in decoding:
                    slot = 2 * i + 1
                    probes[i].append((int(lens[slot]), 1, cut(seen, slice(slot, slot + 1))))
                    rows[i].append(lg[slot])
        del cache
        return [(np.stack(r), p) for r, p in zip(rows, probes)]


def _check_sample(np, got, probes, ref_rows, ref_seen, n_prompt: int, first: int, tokens,
                  model: dict, notes, what: str,
                  token_limits: tuple = (TOKEN_MEAN, TOKEN_FAR_SHARE)) -> bool:
    """The comparisons of the module docstring, for one sequence whose replay
    computed positions ``first`` on (0: cold; else behind a prefix hit)."""
    k, n_group, top_g = (int(model[x]) for x in ("num_experts_per_tok", "n_group", "topk_group"))
    rows = got.shape[0]
    d = np.abs(got - ref_rows[:rows])
    # a control's replay is judged WITHOUT the tokens: they are the sound
    # engine's, and a fault in both programs would leave them agreeing
    short = np.zeros(rows) if tokens is None else \
        got.max(-1) - got[np.arange(rows), np.asarray(tokens)]
    rt_under = gr_under = 0.0
    n_rt = n_tok = n_flip = n_miscount = 0
    for start, m, layers in probes:
        if len(layers) != len(ref_seen):
            n_miscount += m
            continue
        at = slice(start, start + m)
        for picks, r in zip(layers, ref_seen):
            ex = picks["experts_picked"]                                    # [m, k]
            per = r["router_scores"].shape[-1] // n_group
            theirs = np.take_along_axis(r["router_scores"][at], ex, axis=1)
            g_best = np.take_along_axis(r["group_best"][at], ex // per, axis=1)
            gr_under = max(gr_under, float((r["group_cutoff"][at, None] - g_best).max()))
            kept = np.take_along_axis(r["router_inside"][at], ex, axis=1)
            agree = kept.all(-1)
            n_flip += int((~agree).sum())
            if agree.any():
                rt_under = max(rt_under, float(
                    (r["router_cutoff"][at][agree, None] - theirs[agree]).max()))
            n_rt, n_tok = n_rt + theirs.size, n_tok + m
            n_miscount += int(sum(len(set(row)) != k or len({e // per for e in row}) > top_g
                                  for row in ex.tolist()))
    flips = n_flip / max(n_tok, 1)
    ok = bool(np.all(np.isfinite(got)) and d.max() <= LOGIT_TOL_MAX
              and d.mean() <= LOGIT_TOL_MEAN and rt_under <= ROUTER_MARGIN
              and gr_under <= GROUP_MARGIN and flips <= GROUP_FLIP_SHARE
              and short.mean() <= token_limits[0]
              and (short > TOKEN_FAR).mean() <= token_limits[1]
              and n_rt > 0 and n_miscount == 0)
    notes.append(
        f"{what}: {n_prompt}-token prompt "
        + (f"computed from position {first} on (a prefix hit under it) " if first else "in chunks ")
        + f"+ {rows - 1} decode steps, replayed through the runner vs plain reference over the "
        f"whole prompt: logits on the program's picks max|d| {d.max():.4f} (tol {LOGIT_TOL_MAX}), "
        f"mean|d| {d.mean():.4f} (tol {LOGIT_TOL_MEAN}), reference std "
        f"{ref_rows[:rows].std():.2f}; {n_rt} expert picks of {n_tok} router rows, "
        f"{n_miscount} rows with another count than {k} distinct experts in at most {top_g} "
        f"groups, furthest group {max(gr_under, 0):.5f} under the reference's cut-off of groups "
        f"(margin {GROUP_MARGIN}), {flips:.3%} of the rows picked from a group the reference did "
        f"not keep (limit {GROUP_FLIP_SHARE:.0%}), elsewhere furthest pick "
        f"{max(rt_under, 0):.5f} under the cut-off (margin {ROUTER_MARGIN}); "
        + ("the scheduler's tokens left out of a control" if tokens is None else
           f"the scheduler's {rows} tokens {short.mean():.5f} under the replay's best logit "
           f"in the mean (limit {token_limits[0]}), {int((short > TOKEN_FAR).sum())} of them "
           f"further than {TOKEN_FAR} under it (limit {token_limits[1]:.0%} of them), "
           f"{int((short > 0).sum())} under it at all, the furthest {short.max():.4f}")
        + f" -> {ok}")
    return ok


def _controls(jax, np, params, arch, model, replay, reference, warm, sound, names, notes) -> list:
    """Builder's controls (``CONTROLS``).  The reference itself, departing in
    one place, against the program's rows and picks of the warm-up's SHORTEST
    request and of the window's first; the hit served from another request's
    blocks (``reference``: the run's own program of the plain reference); a
    request's tokens against another's rows.  ``warm`` = (prompts,
    fed, schedule, shared, the sound buffers of each warm-up request), ``sound``
    = the window sample's.  Returns the controls that PASSED, which none may."""
    import jax.numpy as jnp

    k = int(model["num_experts_per_tok"])
    prompts, fed, schedule, shared, warm_sound = warm
    short = min(warm_sound[:-1], key=lambda s: len(s[0]))
    passed = []
    for name in names:
        if name == "fp8_weights" and jnp.dtype(params["lm_head"]["kernel"].dtype) == jnp.float32:
            notes.append("control fp8_weights: left out, the weights are float32 here and the "
                         "reference reads them as they are")
            continue
        if name == "served_tokens_swapped":
            (_, out, *_), (_, _, _, _, got, _) = sound[0], sound[-1]
            n = min(len(out), got.shape[0])
            under = got[:n].max(-1) - got[np.arange(n), np.asarray(out[:n])]
            far = float((under > TOKEN_FAR).mean())
            ok = bool(len(sound) > 1 and under.mean() <= WINDOW_TOKEN_MEAN
                      and far <= WINDOW_TOKEN_FAR_SHARE)
            notes.append(f"control {name} ({CONTROLS[name]}): request 1's first {n} tokens "
                         f"against request {len(sound)}'s rows: {under.mean():.4f} under the "
                         f"best logit in the mean (limit {WINDOW_TOKEN_MEAN}), {far:.0%} of them "
                         f"further than {TOKEN_FAR} under it (limit {WINDOW_TOKEN_FAR_SHARE:.0%}) "
                         f"-> would pass: {ok}")
        elif name == "prefix_rows_stale":
            hit = len(prompts) - 1
            donor, n = shared[hit]
            other = next(j for j in range(hit) if j != donor)
            got, probes = replay(prompts, fed, schedule, {hit: (other, n)})[hit]
            prompt, out, buf, _, _, first = warm_sound[hit]
            lg, _ = reference(buf, _forced(np, _whole(np, probes, warm_sound[hit][3], first),
                                           buf.shape[1], k), len(prompt) - 1, got.shape[0])
            d = np.abs(lg - got)
            ok = bool(d.max() <= LOGIT_TOL_MAX and d.mean() <= LOGIT_TOL_MEAN)
            notes.append(f"control {name} ({CONTROLS[name]}): the hit request's first {n} pages "
                         f"read from request {other + 1}'s: max|d| {d.max():.4f} (tol "
                         f"{LOGIT_TOL_MAX}), mean|d| {d.mean():.4f} (tol {LOGIT_TOL_MEAN}) "
                         f"-> would pass: {ok}")
        else:
            def departing(p, t, f, at, rows=None):  # a second copy of the weights would not fit
                inside = arch.weights_rounded_to(jnp.float8_e4m3fn) if name == "fp8_weights" \
                    else arch.departure(name)
                with inside:
                    return arch.probe(p, t, model, f, at=at, rows=rows)

            ok, fn = True, jax.jit(departing, static_argnames=("rows",))
            for what, (prompt, out, buf, probes, got, first) in (
                    ("the warm-up's shortest", short), ("the window's first", sound[0])):
                low, seen = fn(params, buf, _forced(np, probes, buf.shape[1], k),
                               len(prompt) - 1, rows=got.shape[0])
                seen = [{key: np.asarray(v[0]) for key, v in layer.items()} for layer in seen]
                ok &= _check_sample(np, got, probes, np.asarray(low)[0], seen, len(prompt), first, None, model,
                                    notes, f"control {name} ({CONTROLS[name]}) on {what} request")
        if ok:
            passed.append(name)
    notes.append("controls: " + (f"PASSED AS CORRECT, and must not: {passed}" if passed
                                 else f"all of {names} came out not correct"))
    return passed


def _whole(np, probes, donors, first: int) -> list:
    """A hit request's picks over its WHOLE prompt, as the reference takes them
    (``_forced``): its own from position ``first`` on, and under it the picks
    its donor's replay made at those positions (the rows the hit read)."""
    under = []
    for start, m, layers in donors:
        if start < first:
            n = min(m, first - start)
            under.append((start, n, [{k: v[:n] for k, v in p.items()} for p in layers]))
    return under + [p for p in probes if p[0] >= first]


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
        lap("engine built")

        # -- warm-up IS the correctness sample: its requests together through
        # the scheduler (each pack is the one pack program, over cached context
        # from the second chunk on and shared by two prompts; then decode ticks)
        sample = config["correctness"]
        steps = int(sample["decode_steps"])
        prompts = [rng.integers(0, cfg.vocab_size, int(n)).tolist() for n in sample["prompts"]]
        warm = [10**9 + i for i in range(len(prompts))]  # uids the loop never reaches
        for u, prompt in zip(warm, prompts):
            r = sched.try_submit(u, prompt, greedy(steps))
            if not r.accepted:
                raise harness.BenchError(f"warm-up request refused: {r.reason}")
        sched.run(wait_for=warm)
        n_packs = int(eng.stats["prefill_dispatches"])
        # ... THEN a request whose head is request ``of``'s: the prefix cache serves
        # its whole blocks, and its pack starts behind them on pages ``of`` wrote
        hit = sample["hit"]
        donor, n_shared = int(hit["of"]), int(hit["shared_tokens"])
        prompts.append(prompts[donor][:n_shared]
                       + rng.integers(0, cfg.vocab_size, int(hit["own_tokens"])).tolist())
        warm.append(10**9 + len(warm))
        cached = eng.mgr.cached_prompt_tokens
        r = sched.try_submit(warm[-1], prompts[-1], greedy(steps))
        if not r.accepted:
            raise harness.BenchError(f"warm-up's hit request refused: {r.reason}")
        sched.run(wait_for=warm[-1:])
        cached = eng.mgr.cached_prompt_tokens - cached
        traces = [sched.requests[u].trace for u in warm]
        schedule = _ticks(traces, prompts)
        starts = [len(p) - sum(n for _, _, n in tr.chunks) for p, tr in zip(prompts, traces)]
        outs = [sched.pop_result(u) for u in warm]
        lap("warm-up through the scheduler")
        # -- the same sequences through the runner bodies, tokens fed back ---
        fed = [o[:-1] for o in outs]
        bs = e["block_size"]
        shared = {len(prompts) - 1: (donor, starts[-1] // bs)} if starts[-1] else {}
        replay = _Replay(jax, np, eng, cfg)
        replays = replay(prompts, fed, schedule, shared)
        lap("correctness: runner replay")
    # -- the plain reference, one forward a sequence, on the program's picks --
    padded = lambda n, unit: -(-n // unit) * unit
    pad_to = padded(max(map(len, prompts)) + steps, 256)
    topk, params = int(model["num_experts_per_tok"]), eng.params
    jitted = jax.jit(lambda p, t, f, at, rows: arch.probe(p, t, model, f, at=at, rows=rows),
                     static_argnames=("rows",))

    def reference(buf, forced, at: int, rows: int):
        """(the reference's ``rows`` logit rows from position ``at``, what its
        routers' picks were made from, a dict an expert layer)."""
        lg, seen = jitted(params, buf, forced, at, rows=rows)
        return np.asarray(lg)[0], [{k: np.asarray(v[0]) for k, v in layer.items()}
                                   for layer in seen]

    def judged(prompts, outs, replays, starts, shared, pad_to: int, what: str,
               token_limits: tuple) -> tuple:
        """One reference forward a request's WHOLE prompt on the replay's picks
        and the comparisons; (all held, each request's buffers for the controls)."""
        ok, sound = True, []
        rows = max(got.shape[0] for got, _ in replays)  # ONE program for the sample's requests
        for i, (prompt, out, (got, probes)) in enumerate(zip(prompts, outs, replays)):
            if i in shared:  # a hit: under its first position, its donor's picks
                probes = _whole(np, probes, replays[shared[i][0]][1], starts[i])
            buf = np.zeros((1, pad_to), np.int32)
            buf[0, :len(prompt) + len(out) - 1] = prompt + list(out[:-1])
            ref_rows, ref_seen = reference(buf, _forced(np, probes, pad_to, topk),
                                           len(prompt) - 1, rows)
            ok &= _check_sample(np, got, probes, ref_rows, ref_seen, len(prompt), starts[i], out,
                                model, notes, f"correct: {what} {i + 1} of {len(prompts)}",
                                token_limits)
            sound.append((prompt, out, buf, probes, got, starts[i]))
        return ok, sound

    correct = all(len(o) == steps for o in outs)
    alone = sum(-(-len(p) // e["prefill_chunk"]) for p in prompts[:-1])
    notes.append(f"correct: the sample's first {len(prompts) - 1} prompts took {n_packs} packs "
                 f"through the scheduler ({alone} if no pack were shared); the last one's first "
                 f"{cached} tokens of {n_shared} shared were a prefix hit, its prefill began at "
                 f"position {starts[-1]}")
    if starts[-1] != n_shared // bs * bs or cached != starts[-1]:
        notes.append(f"correct: the hit request was to find {n_shared // bs * bs} tokens cached")
        correct = False
    held, warm_sound = judged(prompts, outs, replays, starts, shared, pad_to, "request",
                              (TOKEN_MEAN, TOKEN_FAR_SHARE))
    correct &= held
    warm_kept = (prompts, fed, schedule, shared, warm_sound) if planted else None
    del warm_sound
    lap("correctness: plain reference, comparisons")
    del replays
    fallbacks = [d for d in dispatch_log if not d["ran"]]
    for d in fallbacks:
        notes.append(f"kernel gate declined: {d['kernel']} {d['shape']}: {d['reason']}")

    # -- the loop (drivers/serve_windowed.py's, itself serve.py's lines 187-314,
    # with the counters of a model that keeps latent pages alone)
    requests: List[dict] = []      # every request ever due, in submit order
    live: Dict[int, dict] = {}
    # (t_begin, t_end, n_decoding, sum_ctx_tokens, n_in_flight, n_waiting)
    ticks: List[tuple] = []
    heap: List[tuple] = []
    order = 0

    def snapshot() -> Dict[str, int]:
        snap = {k: int(eng.stats[k]) for k in (
            "decode_ticks", "decode_emitted", "prefill_dispatches",
            "prefill_tokens_dispatched")}
        eng.refresh_routing_stats()  # one small device->host copy, at the window's two ends
        snap.update({k: int(eng.stats[k]) for k in (
            "mla_keys_attended", "mla_keys_attended_decode",
            "expert_pairs_routed", "expert_pairs_held",
            "experts_touched", "experts_touched_decode", "expert_pairs_held_decode")})
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
                       "slot": None, "served": None, "cached": 0}
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
                    rec["cached"] = eng.mgr.seqs[u].cached_tokens
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
    hits = [r for r in requests if r["cached"] and t0 <= r["due"] < t1]
    notes.append(f"prefix cache: {len(hits)} of the window's requests were served on a hit, "
                 f"{sum(r['cached'] for r in hits)} of their "
                 f"{sum(r['prompt_len'] for r in hits)} prompt tokens from the cache")
    counters = {k: end[k] - (base or end)[k] for k in end}
    spans = [(ev["name"], ev["ts"] * 1e-6, (ev["ts"] + ev["dur"]) * 1e-6, ev.get("args", {}))
             for ev in tel.recorder.chrome_events() if ev.get("ph") == "X"]
    scopes = None
    if obs_trace is not None:
        # the programs' named scopes, while the engine still holds them (the
        # readers of a named XLA body run after close())
        from deepspeed_tpu import telemetry

        scopes = telemetry.program_scopes()
    groups = {k: int(eng.stats[k]) for k in ("expert_group_rows_max",
                                             "expert_group_rows_min")}
    audit = eng.close()  # the pool goes: the window's sample needs the room
    # -- what the WINDOW served, re-scored (after the window and the engine: no
    # part of either or of the set-up): the LONGEST finished request served on a
    # HIT, and the longest served cold (another hit where the window finished
    # none), no two from one slot
    want = int(sample.get("window_requests", 0))
    pool = sorted((r for r in requests if r["state"] == FINISHED and r["served"]
                   and t0 <= r["end"] < t1 and r["got"] == r["asked"] > 1),
                  key=lambda r: (-(r["prompt_len"] + r["got"]), r["uid"]))
    # ... under ``window_max_tokens``: the reference's float32 forward over a
    # whole request has to fit beside the weights
    pool = [r for r in pool if r["prompt_len"] + r["got"] <= int(sample.get("window_max_tokens", 1 << 30))]
    taken: List[dict] = []
    for r in [r for r in pool if r["cached"]][:1] + [r for r in pool if not r["cached"]] + pool:
        if len(taken) < want and r["slot"] not in {t["slot"] for t in taken}:
            taken.append(r)
    if want and not rehearse and (len(taken) < want or not taken[0]["cached"]):
        notes.append(f"correct: the window finished {len(pool)} requests of at most "
                     f"{sample.get('window_max_tokens')} tokens, "
                     f"{sum(1 for r in pool if r['cached'])} on a hit; its sample needs {want}, "
                     f"one of them a hit")
        correct = False
    sound = []
    if taken:
        for note in notes:  # a sample that fails (memory) leaves the run's notes behind it
            harness.say(note)
        del notes[:]
        t_sample = clock()
        w_prompts = [list(r["served"][0]) for r in taken]
        w_outs = [list(r["served"][1]) for r in taken]
        w_fed = [o[:-1] for o in w_outs]
        notes.append(f"correct: the window's sample: requests of slots "
                     f"{[r['slot'] for r in taken]} with {[r['prompt_len'] for r in taken]} "
                     f"prompt tokens ({[r['cached'] for r in taken]} of them served from the "
                     f"prefix cache) and {[r['got'] for r in taken]} answer tokens, of "
                     f"{len(pool)} finished inside the window")
        w_replays = replay(w_prompts, w_fed, _alone(w_prompts, w_fed, e["prefill_chunk"]))
        held, sound = judged(w_prompts, w_outs, w_replays, [0] * len(taken), {}, max(
            pad_to, padded(max(map(len, w_prompts)) + max(map(len, w_outs)), 2048)),
            "window request", (WINDOW_TOKEN_MEAN, WINDOW_TOKEN_FAR_SHARE))
        correct &= held
        del w_replays
        notes.append(f"after the window: its sample's replay and reference took "
                     f"{clock() - t_sample:.2f} s")
    if planted:
        if not sound:
            raise harness.BenchError("the controls are judged on the window's sample too, and "
                                     "the window finished no request")
        correct &= not _controls(jax, np, params, arch, model, replay, reference, warm_kept, sound,
                                 planted, notes)
    for r in requests:
        r["served"] = None
    del replay, params
    notes.append(f"routing: over the run, the largest held expert's group in a pack "
                 f"had {groups['expert_group_rows_max']} rows, the smallest "
                 f"{groups['expert_group_rows_min']}")

    done = [r for r in requests if r["state"] == FINISHED]
    wrong_count = [r for r in done if r["got"] != r["asked"]]
    if wrong_count:
        notes.append(f"correct: {len(wrong_count)} finished requests with the wrong token count")
    if audit["blocks_in_use"]:
        notes.append(f"correct: close() left {audit['blocks_in_use']} blocks in use")
    correct = bool(correct and not wrong_count and audit["blocks_in_use"] == 0)
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
    inside = [r for r in done if t0 <= r["end"] < t1]
    notes.append(f"window: {len(ticks)} ticks, {len(requests)} requests submitted in "
                 f"all, {attempted} due inside the window, {len(done)} finished "
                 f"({len(inside)} inside the window), {len(live)} in flight at the end; "
                 f"ramp {plan.ramp_s:.1f} s")
    return {
        "kind": "serve", "correct": correct, "expert_groups": groups, "attempted": attempted,
        "failed": failed, "window": (t0, t1), "t_process": t_process,
        "requests": requests, "ticks": ticks, "spans": spans, "counters": counters,
        "fallbacks": fallbacks, "compiles_in_window": watch.within(t0, t1),
        "trace": obs_trace, "model": model, "engine": e, "chips": chips,
        "notes": notes, **({} if scopes is None else {"_scopes": scopes}),
    }
