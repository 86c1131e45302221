"""Serving cells of a model of two-norm blocks (a Gated DeltaNet matrix state
beside gated GQA pages, a held share of softmax-routed experts in every block):
``InferenceEngineV2`` + ``ServeScheduler`` driven as ``drivers/serve.py``,
``serve_latent.py`` and ``serve_hybrid.py`` drive them.  The order of a run, the
loop and the observations are theirs, copied a FOURTH time (``run`` is one
function in each; ROADMAP D1c); what could be imported is (``serve.DRAIN_CAP_S``,
``serve_latent._schedule`` / ``._forced``, ``serve_hybrid._packs`` / ``._alone`` /
``._state_error`` / ``REPLAY_SLOTS``).  ``serve_hybrid.run`` and its ``_Replay``
could not: they hand Mamba-2's x / B / C / step sizes to ``arch.recurrence(x, b,
c, dt, a)``, and a delta rule consumes q / k / v / log decay / beta.

TWO samples go through one comparison (``_check_sample``), as there.  The first
is the WARM-UP: ``correctness.prompts`` requests of unequal length submitted
TOGETHER and served by the scheduler itself (prompts of several chunks, packs
shared by the tail of one prompt and the head of the next, then decode ticks of
a batch of unequal ages), ``decode_steps`` greedy tokens each.  The second is
taken from what the WINDOW served: the ``correctness.window_requests`` finished
requests of the fewest tokens, no two from one slot, every token of their
answers.  Either sample's tokens are fed through the runner's bodies again
(``_Replay``: a cache of its own with a few slots, each request in a slot that
is not 0, on pages interleaved with the others'), for the logits, for what each
router PICKED, and for what each Gated DeltaNet block consumed and is left
KEEPING; the plain reference makes ONE forward over each request on the
program's picks (``probe(forced=)``).  Held, for every sequence:

1. ``LOGIT_TOL_MAX`` / ``LOGIT_TOL_MEAN``: next-token logits at the last prompt
   position and every decode step against the reference on the program's picks.
2. ``ROUTER_MARGIN``: every expert the program picked lies no further than the
   margin under the reference's cut-off (its ``num_experts_per_tok``-th largest
   router LOGIT: the softmax is monotone), and every token picked that many
   DISTINCT experts.
3. ``TOKEN_MARGIN`` / ``TOKEN_MEAN``: each token the scheduler chose scores
   within the margin of the best logit of the replay's row, and a request's
   tokens in the mean; over the window's hundreds of tokens a request the MEAN
   is held (``WINDOW_TOKEN_MEAN``) and each token to ``WINDOW_TOKEN_MARGIN``
   (``serve_hybrid.py`` says why).
4. ``STATE_TOL``: the matrix state each Gated DeltaNet block KEEPS for the slot
   after the sequence's last token against a float32 delta rule run one token
   at a time, from zeros, over the q / k / v / log decays / betas the PROGRAM's
   own blocks consumed (the replay's probe hands them out;
   ``qwen3_next.recurrence``): the arithmetic of chunks, hand-overs and steps
   and the precision the state is STORED in; what the blocks consume is held by
   the logits.
5. Token counts; ``close()`` leaves 0 blocks and 0 live states.

``--set control='"all"'`` (builder only) plants faults and prints what the same
comparison makes of each; every one has to come out NOT correct (``CONTROLS``),
and one that passes makes the run's ``correct`` false: one precision down
(``fp8_weights``, ``gdn_state_bf16``), the mathematics (the reference with the
output gate left out, rotary on the whole head, ``beta`` or the decay left out,
routing not renormalised: ``arch.DEPARTURES``) and ``served_tokens_swapped``.
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
# same picks; the program rounds activations to bf16 and keeps the delta rule's
# state, the log decays and the betas in float32, the reference is float32
# throughout.  Each limit lies between the largest reading a sound run gave on
# the chip and what its control reads (PERF.md section 2; my chip runs, PR 36:
# 115 requests of 23 runs, 69 of the warm-up and 46 of the window).
#   logits, std 1.00, 9 rows (warm-up) or 204-261 rows (window) of 37984 a
#   sequence.  Program max 0.103-0.166, mean 0.0174-0.0202.  MEAN: the control
#   one precision down, ``fp8_weights``, reads 0.0306-0.0329 (its max 0.199-0.248
#   lies under the MAX limit: it is refused by one limit, not by each).  MAX: the
#   controls of the mathematics read 0.337-0.369 (``rotary_on_whole_head`` on the
#   200-token request; over 10k keys of seeded, diffuse attention it reads
#   0.171-0.211 / 0.0242-0.0247 and would pass: the warm-up's short request is
#   what holds it), 1.04-1.30 ``no_output_gate``, 2.1-3.1
#   ``routing_not_renormalised``, 3.0-5.4 ``no_beta``, 5.8-6.8 ``no_decay``
LOGIT_TOL_MAX = 0.25
LOGIT_TOL_MEAN = 0.025
#   router LOGITS of a normed row are about N(0, 1); float32 routing on bf16
#   activations: program 0.058-0.161 under the cut-off over 16 640-877 520
#   picks a request.  No control of its own; an unrelated expert reads ~1-3.
ROUTER_MARGIN = 0.3
#   the engine's dispatch and the replay are two XLA programs of the same
#   bodies.  The warm-up's 9 tokens a request: 63 of 69 requests read 0.0 under
#   the replay's best, six 0.0049-0.0243 (mean 0.0008-0.0027); the window's
#   204-261 tokens a request read at most 0.0022-0.0354 and 0.00001-0.00049 in
#   the mean (control ``served_tokens_swapped``: furthest 6.4-6.8, nearest
#   1.17-1.52, mean 3.97-4.03).  The warm-up's single-token margin stands 8 x
#   over its largest sound reading and 6 x under the control's nearest (fresh
#   seeds read higher, and a first limit of 0.05 stood only 2 x over); the
#   window's is ``serve_hybrid``'s, which says why a near tie may read tenths
TOKEN_MARGIN = 0.2
TOKEN_MEAN = 0.05
WINDOW_TOKEN_MARGIN = 1.5
WINDOW_TOKEN_MEAN = 0.05
#   the kept state against the one-token delta rule on the same inputs,
#   relative (``_state_error``): 3.1e-5 - 1.43e-4 after the warm-up's chunks,
#   3.3e-6 - 6.9e-5 after the window's 10k-11k tokens; control
#   ``gdn_state_bf16`` 9.8e-3 - 1.15e-2
STATE_TOL = 1e-3

CONTROLS = {
    "gdn_state_bf16": "the replay keeps the delta rule's state in bfloat16: kept state",
    "fp8_weights": "the reference itself on float8_e4m3 weights, same picks: logits",
    "no_output_gate": "the reference without attention's sigmoid output gate: logits",
    "rotary_on_whole_head": "the reference with rotary on all of the head, not its quarter: logits",
    "no_beta": "the reference's delta rule with beta = 1: logits",
    "no_decay": "the reference's delta rule with no decay (g = 0): logits",
    "routing_not_renormalised": "the reference's picked probabilities not renormalised: logits",
    "served_tokens_swapped": "a window request's tokens held to ANOTHER's replay: tokens",
}
_STATE_AS = {"gdn_state_bf16": "bfloat16"}
_INPUTS = ("gdn_q", "gdn_k", "gdn_v", "gdn_g", "gdn_beta")  # what a block's delta rule consumed


class _Replay:
    """Ticks again through ``latent_runner``'s bodies (``serve_hybrid._Replay``
    with this model's probe): request ``i`` in slot ``2 i + 1`` on pages ``i, i +
    n, i + 2 n ..`` of a cache of ``REPLAY_SLOTS`` slots.  The two programs are
    jitted once and serve every sample."""

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

    def __call__(self, prompts, fed, schedule, state_as=None):
        """Returns per request (logits rows [1 + len(fed[i]), vocab], probes,
        the state each Gated DeltaNet block keeps for its slot at the end [Hv, Dk,
        Dv] float32, what each of those blocks' delta rule consumed, token by token:
        a dict of [tokens, ...] a block).  ``state_as``: a control's precision
        for the kept state."""
        jax, np, eng = self.jax, self.np, self.eng
        import jax.numpy as jnp

        bs, T, P = eng.block_size, eng.prefill_chunk, eng.max_pages
        k, N = len(prompts), REPLAY_SLOTS
        if 2 * k > N:
            raise harness.BenchError(f"{k} sample sequences need {2 * k} slots, the replay has {N}")
        table = np.full((N, P), -1, np.int32)
        for i, (p, f) in enumerate(zip(prompts, fed)):
            n = -(-(len(p) + len(f)) // bs)
            table[2 * i + 1, :n] = i + k * np.arange(n)
        rows = [[] for _ in prompts]
        probes = [[] for _ in prompts]  # (first position, number of positions, per-layer picks)
        consumed = [[] for _ in prompts]  # per dispatch, per Gated DeltaNet block: its inputs
        picks = lambda seen: [p for p in seen if "experts_picked" in p]
        inputs = lambda seen: [p for p in seen if _INPUTS[0] in p]
        cut = lambda seen, rows: jax.tree_util.tree_map(lambda a: a[rows], seen)
        cache = self.runner.init_cache(self.cfg, (N // 2) * P + 1, bs, N, T)
        if state_as is not None:  # the ops keep the state in the precision they find it in
            cache = {**cache, "ssm": tuple(a.astype(jnp.dtype(state_as)) for a in cache["ssm"])}
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
                seen = jax.tree_util.tree_map(np.asarray, seen)
                for (i, start, end), cur in zip(entries, at):
                    mine = slice(cur, cur + end - start)
                    probes[i].append((start, end - start, cut(picks(seen), mine)))
                    consumed[i].append(cut(inputs(seen), mine))
                    if end == len(prompts[i]):
                        rows[i].append(np.asarray(lg[2 * i + 1]))
            if decoding:
                t1, lens = np.zeros(N, np.int32), np.zeros(N, np.int32)
                active = np.zeros(N, bool)
                for i in decoding:
                    j = len(rows[i]) - 1
                    t1[2 * i + 1], lens[2 * i + 1] = fed[i][j], len(prompts[i]) + j
                    active[2 * i + 1] = True
                lg, cache, seen = self.dec(eng.params, t1, lens, table, active, cache)
                seen = jax.tree_util.tree_map(np.asarray, seen)
                for i in decoding:
                    slot = 2 * i + 1
                    probes[i].append((int(lens[slot]), 1, cut(picks(seen), slice(slot, slot + 1))))
                    consumed[i].append(cut(inputs(seen), slice(slot, slot + 1)))
                    rows[i].append(np.asarray(lg[slot]))
        kept = [[np.asarray(a[2 * i + 1].astype(jnp.float32)) for a in cache["ssm"]]
                for i in range(k)]
        del cache
        joined = [[{k: np.concatenate([d[b][k] for d in mine]) for k in mine[0][b]}
                   for b in range(len(mine[0]))] for mine in consumed]
        return [(np.stack(r), p, s, c) for r, p, s, c in zip(rows, probes, kept, joined)]


def _check_sample(np, got, probes, kept, ref_logits, ref_seen, again, n_prompt: int, tokens,
                  k_experts: int, notes, what: str,
                  token_limits: tuple = (TOKEN_MARGIN, TOKEN_MEAN)) -> bool:
    """The comparisons of the module docstring, for one sequence."""
    rows = got.shape[0]
    d = np.abs(got - ref_logits[n_prompt - 1: n_prompt - 1 + rows])
    # a control's replay is judged WITHOUT the tokens: they are the sound
    # engine's, and a fault in both programs would leave them agreeing
    short = np.zeros(rows) if tokens is None else \
        got.max(-1) - got[np.arange(rows), np.asarray(tokens)]
    rt_under, n_rt, n_miscount = 0.0, 0, 0
    for start, m, layers in probes:
        for picks, r in zip(layers, ref_seen):
            b = r["router_biased"][start:start + m]  # the reference's router logits
            ex = picks["experts_picked"]
            theirs = np.take_along_axis(b, ex, axis=1)
            rt_under = max(rt_under, float(
                (r["router_cutoff"][start:start + m, None] - theirs).max()))
            n_rt += theirs.size
            n_miscount += int(sum(len(set(row)) != k_experts for row in ex.tolist()))
    state_off = _state_error(np, kept, again)
    ok = bool(np.all(np.isfinite(got)) and d.max() <= LOGIT_TOL_MAX
              and d.mean() <= LOGIT_TOL_MEAN and rt_under <= ROUTER_MARGIN
              and short.max() <= token_limits[0] and short.mean() <= token_limits[1]
              and n_rt > 0 and n_miscount == 0
              and len(kept) == len(again) and state_off <= STATE_TOL)
    notes.append(
        f"{what}: {n_prompt}-token prompt in chunks + {rows - 1} decode steps, replayed "
        f"through the runner vs plain reference: logits on the program's picks max|d| "
        f"{d.max():.4f} (tol {LOGIT_TOL_MAX}), mean|d| {d.mean():.4f} (tol "
        f"{LOGIT_TOL_MEAN}), reference std {ref_logits[:n_prompt + rows].std():.2f}; "
        f"{n_rt} expert picks, furthest {max(rt_under, 0):.5f} under the cut-off (margin "
        f"{ROUTER_MARGIN}), {n_miscount} tokens with another count than {k_experts} "
        f"distinct experts; the state kept after {n_prompt + rows - 1} tokens, "
        f"{len(kept)} blocks, off the one-token float32 recurrence on the same inputs by "
        f"{state_off:.2e} of its norm (tol {STATE_TOL}); "
        + ("the scheduler's tokens left out of a control" if tokens is None else
           f"the scheduler's {rows} tokens at most {short.max():.4f} under the replay's "
           f"best logit (margin {token_limits[0]}) and {short.mean():.5f} in the mean (margin "
           f"{token_limits[1]}), {int((short > 0).sum())} of them under it at all, "
           f"{int((short > 0.05).sum())} by more than 0.05") + f" -> {ok}")
    return ok


def _controls(jax, np, eng, cfg, arch, model, replay, reference, again, short, sound, ticks,
              names, notes) -> list:
    """Builder's controls, judged on the window's sample (``CONTROLS``): each
    planted fault goes through the comparison that decides ``correct``, a kept
    state's on every request of the sample as a sound run's does, the
    reference's own on ``short`` (the warm-up's shortest request) and on the
    window's first.  Returns the controls that PASSED, which none may."""
    import jax.numpy as jnp

    k = int(model["num_experts_per_tok"])
    passed = []
    for name in names:
        if name == "served_tokens_swapped":
            (_, out, *_), (*_, got) = sound[0], sound[-1]
            n = min(len(out), got.shape[0])
            short = got[:n].max(-1) - got[np.arange(n), np.asarray(out[:n])]
            ok = bool(len(sound) > 1 and short.max() <= WINDOW_TOKEN_MARGIN
                      and short.mean() <= WINDOW_TOKEN_MEAN)
            notes.append(f"control {name} ({CONTROLS[name]}): request 1's first {n} tokens "
                         f"against request {len(sound)}'s rows: at most {short.max():.4f} "
                         f"under the best logit (margin {WINDOW_TOKEN_MARGIN}), median "
                         f"{np.median(short):.4f}, nearest {short.min():.4f}, mean "
                         f"{short.mean():.4f} (margin {WINDOW_TOKEN_MEAN}) -> would pass: {ok}")
        elif name not in _STATE_AS:
            # the reference itself, departing in one place, against the program's
            # rows of the warm-up's shortest request and of the window's first (a
            # sound run has to hold on both: attention's share of a logit falls
            # with the context's length under seeded weights)
            def departing(p, t, f):  # a second copy of the weights would not fit
                inside = arch.weights_rounded_to(jnp.float8_e4m3fn) if name == "fp8_weights" \
                    else arch.departure(name)
                with inside:
                    return arch.probe(p, t, model, f)[0]

            ok, fn = True, jax.jit(departing)
            for what, (prompt, out, buf, probes, _, got) in (("the warm-up's shortest", short),
                                                             ("the window's first", sound[0])):
                low = np.asarray(fn(eng.params, buf, _forced(np, probes, buf.shape[1], k)))[0]
                d = np.abs(low[len(prompt) - 1: len(prompt) - 1 + got.shape[0]] - got)
                held = bool(d.max() <= LOGIT_TOL_MAX and d.mean() <= LOGIT_TOL_MEAN)
                ok &= held
                notes.append(f"control {name} ({CONTROLS[name]}): against the program's "
                             f"{got.shape[0]} rows of {what} request ({len(prompt)} prompt "
                             f"tokens) max|d| {d.max():.4f} (tol {LOGIT_TOL_MAX}), mean|d| "
                             f"{d.mean():.4f} (tol {LOGIT_TOL_MEAN}) -> would pass: {held}")
        else:
            ok = True
            for i, ((got, seen, kept, consumed), (prompt, out, buf, *_)) in enumerate(
                    zip(replay(*ticks, state_as=_STATE_AS[name]), sound)):
                # the reference on THESE picks, so that the logits see the same experts
                lg, theirs = reference(buf, _forced(np, seen, buf.shape[1], k))
                ok &= _check_sample(np, got, seen, kept, lg, theirs,
                                    again(consumed, buf.shape[1]), len(prompt), None, k, notes,
                                    f"control {name} ({CONTROLS[name]}), window request {i + 1}")
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
    e = config["engine"]
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
    topk = int(model["num_experts_per_tok"])
    jitted = jax.jit(lambda p, t, f: arch.probe(p, t, model, f))
    recur = jax.jit(lambda *consumed: arch.recurrence(*(a[None] for a in consumed))[1][0])

    def reference(buf, forced):
        lg, seen = jitted(eng.params, buf, forced)
        return np.asarray(lg)[0], [{k: np.asarray(v[0]) for k, v in layer.items()}
                                   for layer in seen]

    def again(consumed, pad_to: int) -> list:
        """Per Gated DeltaNet block, the one-token delta rule's last state on
        what the block consumed (padded with tokens of g = beta = 0, which change
        nothing)."""
        pad = lambda a: np.concatenate([a, np.zeros((pad_to - len(a), *a.shape[1:]), a.dtype)])
        return [np.asarray(recur(*(pad(c[k]) for k in _INPUTS))) for c in consumed]

    def judged(prompts, outs, replays, pad_to: int, what: str, token_limits: tuple) -> tuple:
        """One reference forward a request on the replay's picks and the
        comparisons; (all held, each request's buffers for the controls)."""
        ok, sound = True, []
        for i, (prompt, out, (got, probes, kept, consumed)) in enumerate(
                zip(prompts, outs, replays)):
            buf = np.zeros((1, pad_to), np.int32)
            buf[0, :len(prompt) + len(out) - 1] = prompt + list(out[:-1])
            ref_logits, ref_seen = reference(buf, _forced(np, probes, pad_to, topk))
            ok &= _check_sample(np, got, probes, kept, ref_logits, ref_seen,
                                again(consumed, pad_to), len(prompt), out, topk, notes,
                                f"correct: {what} {i + 1} of {len(prompts)}", token_limits)
            sound.append((prompt, out, buf, probes, ref_logits, got))
        return ok, sound

    correct = all(len(o) == steps for o in outs)
    alone = sum(-(-len(p) // e["prefill_chunk"]) for p in prompts)
    notes.append(f"correct: the sample's {len(prompts)} prompts took {shared} packs through "
                 f"the scheduler ({alone} if no pack were shared)")
    held, warm_sound = judged(prompts, outs, replays, pad_to, "request",
                              (TOKEN_MARGIN, TOKEN_MEAN))
    correct &= held
    short = min(warm_sound, key=lambda s: len(s[0])) if planted else None
    del warm_sound
    lap("correctness: plain reference, comparisons")
    del replays
    fallbacks = [d for d in dispatch_log if not d["ran"]]
    for d in fallbacks:
        notes.append(f"kernel gate declined: {d['kernel']} {d['shape']}: {d['reason']}")

    # -- the loop (drivers/serve_hybrid.py lines 455-600, itself serve.py's
    # lines 187-314 with the counters of a model that keeps a recurrence's state)
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
            "ssm_states_reset", "ssm_states_recomputed", "ssm_chunks_scanned",
            "expert_pairs_routed", "expert_pairs_held", "experts_touched",
            "experts_touched_decode", "expert_pairs_held_decode")})
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
    if taken:
        t_sample = clock()
        w_prompts = [list(r["served"][0]) for r in taken]
        w_outs = [list(r["served"][1]) for r in taken]
        w_fed = [o[:-1] for o in w_outs]
        notes.append(f"correct: the window's sample: requests of slots "
                     f"{[r['slot'] for r in taken]} with {[r['prompt_len'] for r in taken]} "
                     f"prompt and {[r['got'] for r in taken]} answer tokens, of {len(pool)} "
                     f"finished inside the window")
        w_ticks = (w_prompts, w_fed, _alone(w_prompts, w_fed, e["prefill_chunk"]))
        w_replays = replay(*w_ticks)
        held, sound = judged(w_prompts, w_outs, w_replays, max(
            pad_to, -(-max(len(p) + len(o) for p, o in zip(w_prompts, w_outs)) // 128) * 128),
            "window request", (WINDOW_TOKEN_MARGIN, WINDOW_TOKEN_MEAN))
        correct &= held
        del w_replays
        notes.append(f"after the window: its sample's replay and reference took "
                     f"{clock() - t_sample:.2f} s")
    if planted:
        # on the window's sample: hundreds of decode steps, each of which STORES
        # the state (a prompt's chunks hand it over in float32 and store it once a pack)
        if not taken:
            raise harness.BenchError("the controls are judged on the window's sample, and the "
                                     "window finished no request")
        correct &= not _controls(jax, np, eng, cfg, arch, model, replay, reference, again,
                                 short, sound, w_ticks, planted, notes)
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
    if audit.get("ssm_states"):
        notes.append(f"correct: close() left {audit['ssm_states']} live recurrent states")
    correct = bool(correct and not wrong_count and audit["blocks_in_use"] == 0
                   and not audit.get("ssm_states"))
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
    notes.append(f"window: {len(ticks)} ticks, {len(requests)} requests submitted in "
                 f"all, {attempted} due inside the window, {len(done)} finished, "
                 f"{len(live)} in flight at the end; ramp {plan.ramp_s:.1f} s")
    return {
        "kind": "serve", "correct": correct, "expert_groups": groups, "attempted": attempted, "failed": failed,
        "window": (t0, t1), "t_process": t_process,
        "requests": requests, "ticks": ticks, "spans": spans, "counters": counters,
        "fallbacks": fallbacks, "compiles_in_window": watch.within(t0, t1),
        "trace": obs_trace, "model": model, "engine": e, "chips": chips,
        "notes": notes, **({} if scopes is None else {"_scopes": scopes}),
    }
