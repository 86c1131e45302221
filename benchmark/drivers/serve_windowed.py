"""Serving cells of a model of gated GQA of TWO kinds (full layers on K / V
pages, window layers on a K / V ring a slot) with all of a router's experts
held: ``InferenceEngineV2`` + ``ServeScheduler`` driven as the four serving
drivers beside this one drive them.  What could be imported is
(``serve.DRAIN_CAP_S``, ``serve_latent._schedule`` / ``._forced``,
``serve_hybrid._packs`` / ``._alone`` / ``._state_error`` / ``REPLAY_SLOTS``); the
order of a run and the loop are theirs, copied once more because each keeps
them inside its ``run`` (ROADMAP D1c: one loop is a ``benchmark`` PR's).  This
file's own part is the SAMPLE: its replay, its comparison, its controls.

TWO samples go through one comparison (``_check_sample``), as in
``serve_deltanet.py``.  The first is the WARM-UP: ``correctness.prompts``
requests of unequal length submitted TOGETHER and served by the scheduler
itself (two prompts wrap the ring, one stays inside the window; packs shared by
the tail of one prompt and the head of the next; then decode ticks of a batch
of unequal ages), ``decode_steps`` greedy tokens each.  The second is taken from
what the WINDOW served: the ``correctness.window_requests`` finished requests of
the fewest tokens, no two from one slot, every token of their answers.  Either
sample's tokens are fed through the runner's bodies again (``_Replay``: a cache
of its own, each request in a slot that is not 0, on pages interleaved with the
others'), for the logits, for what each router PICKED and for the rows each
window layer's ring is left KEEPING; the plain reference makes ONE forward over
each request on the program's picks (``probe(forced=)``).  Held, per sequence:

1. ``LOGIT_TOL_MAX`` / ``LOGIT_TOL_MEAN``: next-token logits at the last prompt
   position and every decode step against the reference on the program's picks.
2. ``ROUTER_MARGIN``: every expert the program picked lies no further than the
   margin under the reference's cut-off (its ``num_experts_per_tok``-th largest
   sigmoid score + bias), and every token picked that many DISTINCT experts.
3. The window's EDGE, exactly: every query of every window layer saw as many
   keys, from the same oldest position on, as the reference's mask allows
   (``min(p + 1, sliding_window)`` keys from ``p + 1 -`` that on): the logits
   cannot tell one key of 513 from none under seeded, diffuse attention.
   ``RING_TOL``: the KEPT ring.  After the sequence's last token, the rows its
   slot's ring holds in each window layer for the last ``sliding_window``
   positions (position ``p`` in row ``p % R``) against the reference's k (after
   norm and rotary) and v at those positions, by position: what the window
   layers will attend next, whatever wrapped, was overwritten or was re-used.
4. ``TOKEN_MEAN`` / ``TOKEN_FAR_SHARE`` (the window's: ``WINDOW_*``): the tokens
   the scheduler chose against the best logit of the replay's rows, in the mean
   and by the share of them further than ``TOKEN_FAR`` under it.
5. Token counts; ``close()`` leaves 0 blocks and 0 ring rows.

``--set control='"all"'`` (builder only) plants faults and prints what the same
comparison makes of each; every one has to come out NOT correct (``CONTROLS``),
and one that passes makes the run's ``correct`` false: one precision down
(``fp8_weights``) and the mathematics (``arch.DEPARTURES``), each judged on the
warm-up's SHORTEST request and on the window's first, and
``served_tokens_swapped``.
"""
from __future__ import annotations

import gc
import heapq
import time
from typing import Any, Dict, List

from .. import harness
from .serve import DRAIN_CAP_S
from .serve_hybrid import REPLAY_SLOTS, _alone, _packs, _state_error
from .serve_latent import _forced, _schedule

# Tolerances, and why.  Both sides compute from the same bf16 weights on the
# same picks; the program rounds activations to bf16, the reference is float32
# throughout.  Each limit lies between the largest reading a sound run gave on
# the chip and what its control reads (PERF.md section 2 has the readings; my
# chip runs, PR 42).
#   logits of 100 352 a row, reference std 1.00.  Sound: max 0.107-0.185, mean
#   0.0170-0.0202 (90 requests of 18 runs; the mean hardly moves).  The control
#   one precision down, ``fp8_weights``, reads 1.00-1.28 / 0.155; the controls of
#   the mathematics 0.88 / 0.117 (``no_window``, on a request past the window)
#   to 7.7 / 1.10 (``rotary_sets_swapped``): each limit stands ~2 x over the
#   largest sound reading and 2.5-4 x under the nearest control
LOGIT_TOL_MAX = 0.35
LOGIT_TOL_MEAN = 0.04
#   sigmoid scores + a bias of N(0, 0.02^2): the cut-off between the 8th and
#   the 9th of 256 is a margin of scores in (0, 1), ten times finer than a
#   margin of LOGITS (cell 7's 0.3).  Sound: 0.0069-0.0175 under the cut-off at
#   the furthest of 40 800-96 256 picks a request.  No control of its own: the
#   9th-best expert lies ~0.007 under the cut-off, the 20th ~0.05, a random one
#   ~0.37
ROUTER_MARGIN = 0.05
#   the kept ring against the reference's k and v, |kept - ref| / |ref| over a
#   layer's last ``sliding_window`` rows (the larger of k's and v's, the
#   largest layer's): bf16 rows computed from bf16 activations against float32
#   ones carry the activations' ~1.8% (what the logits' mean reads, too): sound
#   1.75e-2 - 1.89e-2 over 90 requests, a norm over 512 x 1024 elements hardly
#   moves; a row of another position reads ~1.4, the other kind's rotary table
#   more than 0.1 (CPU test), a ring kept in float8_e4m3 (2^-4 a value) ~4e-2
RING_TOL = 3e-2
#   the engine's dispatch and the replay are two XLA programs of the same
#   bodies on other batch shapes, and THIS router's cut-off is often a near tie
#   (scores ~0.0066 apart at the cut-off, bf16 noise 0.002-0.014 in them) between
#   experts that each weigh ~0.31 (the picked sigmoid scores normalised, x 2.5):
#   where the engine's pick falls the other way than the replay's, a logit moves
#   by tenths.  So 13-19% of the served tokens are not the replay's best, a few
#   by 1-2.7 (the largest of a request: 1.12-2.74), while the MEAN stays at
#   0.025-0.066 over a window request's 233-374 tokens and 0.0-0.137 over the
#   warm-up's 9; another request's tokens (control ``served_tokens_swapped``)
#   read 4.42 in the mean, 1.62 at the nearest.  Held: the MEAN shortfall
#   (``*_MEAN``) and the share of tokens further than ``TOKEN_FAR`` under the best
#   (``*_FAR_SHARE``: sound 1.6-4.7% in the window, 0 of 9 in the warm-up; the
#   control 100%); no single token's distance, which has no bound a near tie
#   respects.  The warm-up's nine tokens a request get the wider pair: one token
#   at 2.7 is 0.30 of their mean
TOKEN_FAR = 0.5
TOKEN_MEAN = 0.5
TOKEN_FAR_SHARE = 0.25
WINDOW_TOKEN_MEAN = 0.25
WINDOW_TOKEN_FAR_SHARE = 0.15

CONTROLS = {
    "fp8_weights": "the reference itself on float8_e4m3 weights, same picks",
    "no_window": "the reference's window layers attend every key",
    "window_off_by_one": "the reference's window holds 513 keys (i - j <= 512)",
    "rotary_sets_swapped": "the reference's full layers rotate with the window layers' table and the reverse",
    "no_yarn": "the reference's full layers without YaRN (factor 1, attention factor 1)",
    "no_output_gate": "the reference without attention's per-head sigmoid gate",
    "routing_not_scaled": "the reference's routed weights not times 2.5",
    "served_tokens_swapped": "a window request's tokens held to ANOTHER's replay: tokens",
}


class _Replay:
    """Ticks again through ``latent_runner``'s bodies (``serve_deltanet._Replay``
    with this model's cache): request ``i`` in slot ``2 i + 1`` on pages ``i, i +
    n, i + 2 n ..`` of a cache of ``REPLAY_SLOTS`` slots and as many pages as the
    sample needs.  The two programs are jitted once and serve every sample."""

    def __init__(self, jax, np, eng, cfg):
        from deepspeed_tpu.inference import latent_runner

        self.jax, self.np, self.eng, self.cfg = jax, np, eng, cfg
        self.runner = latent_runner

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

    def __call__(self, prompts, fed, schedule):
        """Returns per request (logits rows [1 + len(fed[i]), vocab], probes,
        the ring rows each window layer keeps for its slot at the end: (k, v)
        float32 [R, Hkv, hd] a layer, what each window layer's mask let each
        query see)."""
        jax, np, eng = self.jax, self.np, self.eng
        import jax.numpy as jnp

        bs, T, P = eng.block_size, eng.prefill_chunk, eng.max_pages
        k, N = len(prompts), REPLAY_SLOTS
        if 2 * k > N:
            raise harness.BenchError(f"{k} sample sequences need {2 * k} slots, the replay has {N}")
        table = np.full((N, P), -1, np.int32)
        n_pages = [-(-(len(p) + len(f)) // bs) for p, f in zip(prompts, fed)]
        for i, n in enumerate(n_pages):
            table[2 * i + 1, :n] = i + k * np.arange(n)
        rows = [[] for _ in prompts]
        # (first position, number of positions, per expert layer its picks), and
        # the same with, per window layer, what its mask let each query see
        probes, edges = [[] for _ in prompts], [[] for _ in prompts]
        cut = lambda seen, rows: jax.tree_util.tree_map(lambda a: a[rows], seen)
        picks = lambda seen: [p for p in seen if "experts_picked" in p]
        masks = lambda seen: [p for p in seen if "window_seen" in p]
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
                lg, cache, seen = self.pack(eng.params, tok, seg, pos, pages, last, table, cache)
                lg, seen = jax.device_get((lg, seen))  # one fetch a dispatch
                for (i, start, end), cur in zip(entries, at):
                    mine = slice(cur, cur + end - start)
                    probes[i].append((start, end - start, cut(picks(seen), mine)))
                    edges[i].append((start, end - start, cut(masks(seen), mine)))
                    if end == len(prompts[i]):
                        rows[i].append(lg[2 * i + 1])
            if decoding:
                t1, lens = np.zeros(N, np.int32), np.zeros(N, np.int32)
                active = np.zeros(N, bool)
                for i in decoding:
                    j = len(rows[i]) - 1
                    t1[2 * i + 1], lens[2 * i + 1] = fed[i][j], len(prompts[i]) + j
                    active[2 * i + 1] = True
                lg, cache, seen = self.dec(eng.params, t1, lens, table, active, cache)
                lg, seen = jax.device_get((lg, seen))
                for i in decoding:
                    slot = 2 * i + 1
                    probes[i].append((int(lens[slot]), 1, cut(picks(seen), slice(slot, slot + 1))))
                    edges[i].append((int(lens[slot]), 1, cut(masks(seen), slice(slot, slot + 1))))
                    rows[i].append(lg[slot])
        ring = lambda a, slot: np.asarray(
            a.reshape(N, -1, *a.shape[2:])[slot].astype(jnp.float32))
        kept = [[(ring(wk, 2 * i + 1), ring(wv, 2 * i + 1))
                 for wk, wv in zip(cache["wk"], cache["wv"])] for i in range(k)]
        del cache
        return [(np.stack(r), p, s, e) for r, p, s, e in zip(rows, probes, kept, edges)]


def _ring_refs(np, kept, ref_seen, n_tokens: int, window: int) -> tuple:
    """(the rows each window layer's ring holds for the last ``window`` of
    ``n_tokens`` positions, the reference's k and v at those positions), each a
    list of arrays a layer, k then v, for ``_state_error``."""
    at = np.arange(max(n_tokens - window, 0), n_tokens)
    refs = [r for r in ref_seen if "ring_k" in r]
    mine = [a[at % a.shape[0]] for kv in kept for a in kv]
    theirs = [r[key][at] for r in refs for key in ("ring_k", "ring_v")]
    return mine, theirs


def _edge_misses(np, edges, ref_seen) -> tuple:
    """(queries of window layers checked, those whose mask let them see another
    number of keys or another oldest key than the reference's)."""
    refs = [r for r in ref_seen if "window_seen" in r]
    n = wrong = 0
    for start, m, layers in edges:
        if len(layers) != len(refs):
            return 0, 1
        for mine, r in zip(layers, refs):
            at = slice(start, start + m)
            wrong += int(np.sum((mine["window_seen"] != r["window_seen"][at])
                                | (mine["window_oldest"] != r["window_oldest"][at])))
            n += m
    return n, wrong


def _check_sample(np, got, probes, kept, edges, ref_rows, ref_seen, n_prompt: int, tokens,
                  k_experts: int, window: int, notes, what: str,
                  token_limits: tuple = (TOKEN_MEAN, TOKEN_FAR_SHARE)) -> bool:
    """The comparisons of the module docstring, for one sequence."""
    rows = got.shape[0]
    d = np.abs(got - ref_rows[:rows])
    # a control's replay is judged WITHOUT the tokens: they are the sound
    # engine's, and a fault in both programs would leave them agreeing
    short = np.zeros(rows) if tokens is None else \
        got.max(-1) - got[np.arange(rows), np.asarray(tokens)]
    routers = [r for r in ref_seen if "router_biased" in r]
    rt_under, n_rt, n_miscount = 0.0, 0, 0
    for start, m, layers in probes:
        for picks, r in zip(layers, routers):
            b = r["router_biased"][start:start + m]  # the reference's scores + bias
            ex = picks["experts_picked"]
            theirs = np.take_along_axis(b, ex, axis=1)
            rt_under = max(rt_under, float(
                (r["router_cutoff"][start:start + m, None] - theirs).max()))
            n_rt += theirs.size
            n_miscount += int(sum(len(set(row)) != k_experts for row in ex.tolist()))
    n_tokens = n_prompt + rows - 1
    mine, theirs = _ring_refs(np, kept, ref_seen, n_tokens, window)
    ring_off = _state_error(np, mine, theirs) if len(mine) == len(theirs) else float("inf")
    n_edge, edge_wrong = _edge_misses(np, edges, ref_seen)
    ok = bool(n_edge > 0 and edge_wrong == 0 and np.all(np.isfinite(got)) and d.max() <= LOGIT_TOL_MAX
              and d.mean() <= LOGIT_TOL_MEAN and rt_under <= ROUTER_MARGIN
              and short.mean() <= token_limits[0]
              and (short > TOKEN_FAR).mean() <= token_limits[1]
              and n_rt > 0 and n_miscount == 0 and ring_off <= RING_TOL)
    notes.append(
        f"{what}: {n_prompt}-token prompt in chunks + {rows - 1} decode steps, replayed "
        f"through the runner vs plain reference: logits on the program's picks max|d| "
        f"{d.max():.4f} (tol {LOGIT_TOL_MAX}), mean|d| {d.mean():.4f} (tol "
        f"{LOGIT_TOL_MEAN}), reference std {ref_rows[:rows].std():.2f}; "
        f"{n_rt} expert picks, furthest {max(rt_under, 0):.5f} under the cut-off (margin "
        f"{ROUTER_MARGIN}), {n_miscount} tokens with another count than {k_experts} "
        f"distinct experts; {n_edge} window-layer queries, {edge_wrong} whose mask let "
        f"them see other keys than the reference's; the rings kept after {n_tokens} tokens, {len(kept)} window "
        f"layers, their last {min(window, n_tokens)} positions off the reference's k and v "
        f"by {ring_off:.2e} of their norm (tol {RING_TOL}); "
        + ("the scheduler's tokens left out of a control" if tokens is None else
           f"the scheduler's {rows} tokens {short.mean():.5f} under the replay's best logit "
           f"in the mean (limit {token_limits[0]}), {int((short > TOKEN_FAR).sum())} of them "
           f"further than {TOKEN_FAR} under it (limit {token_limits[1]:.0%} of them), "
           f"{int((short > 0).sum())} under it at all, the furthest {short.max():.4f}")
        + f" -> {ok}")
    return ok


def _controls(jax, np, eng, arch, model, short, sound, names, notes) -> list:
    """Builder's controls (``CONTROLS``): the reference itself, departing in one
    place, against the program's rows of the warm-up's SHORTEST request and of
    the window's first (a sound run holds on both; attention's share of a logit
    falls with the context's length under seeded weights, so which request
    catches a control is part of the reading).  Returns the controls that
    PASSED on both, which none may."""
    import jax.numpy as jnp

    k = int(model["num_experts_per_tok"])
    passed, first = [], sound[0]
    for name in names:
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
                         f"further than {TOKEN_FAR} under it (limit {WINDOW_TOKEN_FAR_SHARE:.0%}), "
                         f"median {np.median(under):.4f}, nearest {under.min():.4f}, furthest "
                         f"{under.max():.4f} -> would pass: {ok}")
            if ok:
                passed.append(name)
            continue

        def departing(p, t, f, at, rows=None):  # a second copy of the weights would not fit
            inside = arch.weights_rounded_to(jnp.float8_e4m3fn) if name == "fp8_weights" \
                else arch.departure(name)
            with inside:
                return arch.probe(p, t, model, f, at=at, rows=rows)

        ok, fn = True, jax.jit(departing, static_argnames=("rows",))
        for what, (prompt, out, buf, probes, got, edges) in (("the warm-up's shortest", short),
                                                             ("the window's first", first)):
            low, seen = fn(eng.params, buf, _forced(np, probes, buf.shape[1], k),
                           len(prompt) - 1, rows=got.shape[0])
            d = np.abs(np.asarray(low)[0] - got)
            n_edge, edge_wrong = _edge_misses(np, edges, [
                {key: np.asarray(v[0]) for key, v in layer.items() if key.startswith("window_")}
                for layer in seen])
            held = bool(d.max() <= LOGIT_TOL_MAX and d.mean() <= LOGIT_TOL_MEAN
                        and edge_wrong == 0)
            ok &= held
            notes.append(f"control {name} ({CONTROLS[name]}): against the program's "
                         f"{got.shape[0]} rows of {what} request ({len(prompt)} prompt "
                         f"tokens) max|d| {d.max():.4f} (tol {LOGIT_TOL_MAX}), mean|d| "
                         f"{d.mean():.4f} (tol {LOGIT_TOL_MEAN}); {edge_wrong} of {n_edge} "
                         f"window-layer queries saw other keys -> would pass: {held}")
        if ok:
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
        schedule = _schedule([sched.requests[u].trace for u in warm], prompts)
        outs = [sched.pop_result(u) for u in warm]
        shared = int(eng.stats["prefill_dispatches"])
        lap("warm-up through the scheduler")
        # -- the same sequences through the runner bodies, tokens fed back ---
        fed = [o[:-1] for o in outs]
        replay = _Replay(jax, np, eng, cfg)
        replays = replay(prompts, fed, schedule)
        lap("correctness: runner replay")
    # -- the plain reference, one forward a sequence, on the program's picks --
    pad_to = -(-(max(map(len, prompts)) + steps) // 128) * 128
    topk, window = int(model["num_experts_per_tok"]), int(model["sliding_window"])
    jitted = jax.jit(lambda p, t, f, at, rows: arch.probe(p, t, model, f, at=at, rows=rows),
                     static_argnames=("rows",))

    def reference(buf, forced, at: int, rows: int):
        """(the reference's ``rows`` logit rows from position ``at``, what its
        layers saw: per window layer the rings' rows, per expert layer the scores)."""
        lg, seen = jitted(eng.params, buf, forced, at, rows=rows)
        return np.asarray(lg)[0], [{k: np.asarray(v[0]) for k, v in layer.items()}
                                   for layer in seen]

    def judged(prompts, outs, replays, pad_to: int, what: str, token_limits: tuple) -> tuple:
        """One reference forward a request on the replay's picks and the
        comparisons; (all held, each request's buffers for the controls)."""
        ok, sound = True, []
        for i, (prompt, out, (got, probes, kept, edges)) in enumerate(
                zip(prompts, outs, replays)):
            buf = np.zeros((1, pad_to), np.int32)
            buf[0, :len(prompt) + len(out) - 1] = prompt + list(out[:-1])
            ref_rows, ref_seen = reference(buf, _forced(np, probes, pad_to, topk),
                                           len(prompt) - 1, got.shape[0])
            ok &= _check_sample(np, got, probes, kept, edges, ref_rows, ref_seen, len(prompt), out,
                                topk, window, notes,
                                f"correct: {what} {i + 1} of {len(prompts)}", token_limits)
            sound.append((prompt, out, buf, probes, got, edges))
        return ok, sound

    correct = all(len(o) == steps for o in outs)
    alone = sum(-(-len(p) // e["prefill_chunk"]) for p in prompts)
    notes.append(f"correct: the sample's {len(prompts)} prompts took {shared} packs through "
                 f"the scheduler ({alone} if no pack were shared)")
    held, warm_sound = judged(prompts, outs, replays, pad_to, "request",
                              (TOKEN_MEAN, TOKEN_FAR_SHARE))
    correct &= held
    short = min(warm_sound, key=lambda s: len(s[0])) if planted else None
    del warm_sound
    lap("correctness: plain reference, comparisons")
    del replays
    fallbacks = [d for d in dispatch_log if not d["ran"]]
    for d in fallbacks:
        notes.append(f"kernel gate declined: {d['kernel']} {d['shape']}: {d['reason']}")

    # -- the loop (drivers/serve_deltanet.py's, itself serve.py's lines 187-314,
    # with the counters of a model that keeps pages and rings)
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
            "full_keys_attended", "window_keys_attended", "causal_keys",
            "window_rows_discarded", "expert_pairs_routed", "expert_pairs_held",
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
    # -- what the WINDOW served, re-scored (after the window: no part of it or
    # of the set-up): the finished requests of the fewest tokens, one a slot
    want = int(sample.get("window_requests", 0))
    pool = sorted((r for r in requests if r["state"] == FINISHED and r["served"]
                   and t0 <= r["end"] < t1 and r["got"] == r["asked"] > 1),
                  key=lambda r: (r["prompt_len"] + r["got"], r["uid"]))
    taken: List[dict] = []
    for r in pool:
        if len(taken) < want and r["slot"] not in {t["slot"] for t in taken}:
            taken.append(r)
    if want and not rehearse and len(taken) < want:
        notes.append(f"correct: the window finished {len(pool)} requests, its sample needs {want}")
        correct = False
    sound = []
    if taken:
        t_sample = clock()
        w_prompts = [list(r["served"][0]) for r in taken]
        w_outs = [list(r["served"][1]) for r in taken]
        w_fed = [o[:-1] for o in w_outs]
        notes.append(f"correct: the window's sample: requests of slots "
                     f"{[r['slot'] for r in taken]} with {[r['prompt_len'] for r in taken]} "
                     f"prompt and {[r['got'] for r in taken]} answer tokens, of {len(pool)} "
                     f"finished inside the window")
        w_replays = replay(w_prompts, w_fed, _alone(w_prompts, w_fed, e["prefill_chunk"]))
        held, sound = judged(w_prompts, w_outs, w_replays, max(
            pad_to, -(-max(len(p) + len(o) for p, o in zip(w_prompts, w_outs)) // 128) * 128),
            "window request", (WINDOW_TOKEN_MEAN, WINDOW_TOKEN_FAR_SHARE))
        correct &= held
        del w_replays
        notes.append(f"after the window: its sample's replay and reference took "
                     f"{clock() - t_sample:.2f} s")
    if planted:
        if not sound:
            raise harness.BenchError("the controls are judged on the window's sample too, and "
                                     "the window finished no request")
        correct &= not _controls(jax, np, eng, arch, model, short, sound, planted, notes)
    for r in requests:
        r["served"] = None
    del replay
    audit = eng.close()
    notes.append(f"routing: over the run, the largest held expert's group in a pack "
                 f"had {groups['expert_group_rows_max']} rows, the smallest "
                 f"{groups['expert_group_rows_min']}")

    done = [r for r in requests if r["state"] == FINISHED]
    wrong_count = [r for r in done if r["got"] != r["asked"]]
    if wrong_count:
        notes.append(f"correct: {len(wrong_count)} finished requests with the wrong token count")
    if audit["blocks_in_use"]:
        notes.append(f"correct: close() left {audit['blocks_in_use']} blocks in use")
    if audit.get("window_rows"):
        notes.append(f"correct: close() left {audit['window_rows']} ring rows owned")
    correct = bool(correct and not wrong_count and audit["blocks_in_use"] == 0
                   and not audit.get("window_rows"))
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
