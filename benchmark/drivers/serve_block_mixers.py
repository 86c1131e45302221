"""Serving cells of a model of two-norm blocks whose mixer is chosen BY BLOCK
(``models/latent.py``: ``LatentSpec.two_norms``; a Mamba-2 state for each recurrent
block and K / V pages for each attention block of a sequence, a held share of
softmax-routed SwiGLU experts and a shared one behind every mixer, a tied head):
``InferenceEngineV2`` + ``ServeScheduler`` driven as the serving drivers beside
this one drive them.  What could be imported is (``serve.DRAIN_CAP_S``,
``serve_latent._schedule``, ``serve_hybrid._alone`` / ``._state_error``,
``serve_parallel._Replay`` / ``._f32_share`` / ``._logits_off``); the order of a run
and the loop are theirs, copied once more because each keeps them inside its
``run`` (ROADMAP D1c: one loop is a ``benchmark`` PR's); the copied block is
marked.  ``serve_parallel`` itself cannot run this model: its comparison has no
expert picks to hold the reference to; ``serve_hybrid`` cannot either: its
reference is one forward beside the engine's pool, where this cell's pool fills
the chip.  This file's own part is the SAMPLE.

TWO samples go through one comparison (``_check_sample``), as in
``serve_parallel``: the WARM-UP (``correctness.prompts`` requests of unequal length
submitted TOGETHER and served by the scheduler itself, ``decode_steps`` greedy
tokens each) and what the WINDOW served (the ``correctness.window_requests``
finished requests of the fewest tokens, no two from one slot, every token of
their answers).  AFTER the window and ``close()`` (the pool fills the chip) either
sample's tokens are fed through the runner's bodies again (``serve_parallel.
_Replay``: a cache of its own with a few slots), for the logits, for what each
block's router PICKED and for what each Mamba block consumed and is left KEEPING;
the plain reference makes ONE float32 forward over each request ON THE PROGRAM'S
PICKS, a block and a column block of the head at a time (``logits_in_blocks``).
Held, per sequence:

1. ``LOGIT_TOL_MAX`` / ``LOGIT_TOL_MEAN``: next-token logits at the last prompt
   position and every decode step against the reference's on the same picks, in
   units of the reference's std over those rows (seeded weights under
   ``logits_scaling`` 16 give logits of std ~0.06).
2. ``LOGIT_F32_SHARE``: the rows ARE float32: the share of their values that a
   bfloat16 cannot hold (low mantissa bits set).
3. ``ROUTER_MARGIN``: every expert the program picked lies no further than the
   margin under the reference's cut-off (its ``num_experts_per_tok``-th largest
   router LOGIT: the softmax is monotone), and every token picked that many
   DISTINCT experts.
4. ``STATE_TOL``: the state EVERY Mamba block keeps for the slot after the
   sequence's last token against a float32 recurrence run one token at a time,
   from zeros, over the x / B / C / step sizes the PROGRAM's own blocks consumed.
5. ``TOKEN_MARGIN`` / ``TOKEN_MEAN``: each token the scheduler chose, with the
   other requests live beside it, against the best logit of the replay's row, in
   the same units, each and in the mean; over the window's hundreds of tokens a
   request a router's near tie may fall the other way in one of the two programs
   and the state carries that on, so there the MEAN is held
   (``WINDOW_TOKEN_MEAN``) and each token to ``WINDOW_TOKEN_MARGIN``
   (``serve_hybrid.py`` says why).
6. Token counts; ``close()`` leaves 0 blocks and 0 live states.

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
from .serve_hybrid import _alone, _state_error
from .serve_latent import _schedule
from .serve_parallel import _f32_share, _logits_off, _Replay

# Tolerances, and why.  Both sides compute from the same bf16 weights on the same
# picks; the program rounds activations to bf16 and keeps the recurrence's state,
# the step sizes, the decays, the routing, the constants' products and the logits
# in float32, the reference is float32 throughout.  Each limit lies between the
# largest reading a sound run gave on the chip and what its nearest control reads
# (PERF.md section 2 has the readings; my chip runs, PR 62: 84 requests of 14 runs on
# 14 seeds, one of them the controls').
#   logits in units of the reference's std (0.0624-0.0631) over the compared rows,
#   9 rows (warm-up) or 281-520 (window) of 50 176.  Program max 0.067-0.106, mean
#   0.0097-0.0138.  The nearest precision below, ``fp8_weights``: 1.07-1.27 /
#   0.177-0.215.  ``softmax_scale_rsqrt``: 0.099-0.299 / 0.0167-0.0361 (seeded
#   weights attend near uniformly at 1/128 AND at 1/11.3: its 1400-token request
#   reads 0.0985 / 0.0167 and passes these two, five of six do not);
#   ``residual_multiplier_one`` 10.9-15.0 / 0.72-0.85
LOGIT_TOL_MAX = 0.2
LOGIT_TOL_MEAN = 0.02
#   a float32 value's low 16 mantissa bits are all zero once in 65 536; a row
#   that went through bfloat16 has none set (program 0.9999-1.0000, control 0.0000)
LOGIT_F32_SHARE = 0.99
#   router LOGITS of a normed row are about N(0, 1); float32 routing on bf16
#   activations: program 0.042-0.080 under the cut-off over 20 800-256 700 picks a
#   request.  Controls: ``softmax_scale_rsqrt`` 0.258-0.448, ``fp8_weights``
#   0.75-1.18, ``residual_multiplier_one`` 3.4-3.8
ROUTER_MARGIN = 0.2
#   the engine's dispatch and the replay are two XLA programs of the same bodies at
#   other batch sizes.  EVERY token of the 84 requests (9 of the warm-up's, 281-520
#   of the window's) is the replay's best: 0.0000 in every request, where
#   ``served_tokens_swapped`` reads 15.1-17.7 std at the furthest and 13.2-16.5 in
#   the mean.  The margins are cells 6, 7 and 12's: a near tie of two logits, or of
#   a router behind a recurrence's state, may fall the other way on a fresh seed and
#   reads as the tie's gap, tenths of a std at most (``serve_hybrid.py``: 7 requests
#   of 66 there); the MEAN over a request is what a drifted state would move
TOKEN_MARGIN = 0.2
TOKEN_MEAN = 0.05
WINDOW_TOKEN_MARGIN = 1.5
WINDOW_TOKEN_MEAN = 0.05
#   the kept state against the one-token recurrence on the same inputs, relative
#   (``serve_hybrid._state_error``), the largest block's: program 2.6e-5 - 1.35e-4
#   (the chunked form's own arithmetic), control ``ssm_state_bf16`` 3.8e-3 - 0.156
STATE_TOL = 1e-3

CONTROLS = {
    "ssm_state_bf16": "the replay keeps the recurrence's state in bfloat16: kept state",
    "fp8_weights": "the reference itself on float8_e4m3 weights, same picks: logits",
    "softmax_scale_rsqrt": "the reference's softmax at head_dim^-1/2, not attention_multiplier: logits",
    "residual_multiplier_one": "the reference's residual branches added as they are: logits",
    "bf16_logits": "the replay's logit rows rounded to bfloat16: the rows' float32 share",
    "served_tokens_swapped": "a request's tokens held to ANOTHER's replay: tokens",
}


def _as_the_readers_look_it_up(model: dict) -> dict:
    """The configuration as ``readers/state_roofline`` / ``costs_ssm`` and
    ``readers/gdn_roofline`` / ``costs_gdn.expert_matmul`` look one up: this
    family's sizes of the recurrence and of an expert under the names they read,
    and the blocks that run the recurrence as an ``M`` of the pattern (the
    attention block a ``*``).  The recurrence's work a block and a SwiGLU expert's a
    pair are the same functions of these sizes, so the accepted entries read this
    cell with the readers and the costs they have."""
    return {**model, "mamba_num_heads": model["mamba_n_heads"],
            "mamba_head_dim": model["mamba_d_head"], "n_groups": model["mamba_n_groups"],
            "ssm_state_size": model["mamba_d_state"],
            "moe_intermediate_size": model["intermediate_size"],
            "hybrid_override_pattern": "".join(
                "M" if kind == "mamba" else "*" for kind in model["layer_types"])}


def _split(joined) -> tuple:
    """A request's probes, a dict a probing body in layer order (``_Replay``), as
    (each block's expert picks [tokens, k], what each Mamba block consumed)."""
    return ([p["experts_picked"] for p in joined if "experts_picked" in p],
            [p for p in joined if "ssm_x" in p])


def _forced(np, picks, pad_to: int) -> list:
    """The program's picks as the reference takes them: per block experts
    [1, pad_to, k] (positions past the sequence take expert 0: never compared)."""
    out = []
    for p in picks:
        buf = np.zeros((1, pad_to, p.shape[1]), np.int32)
        buf[0, :len(p)] = p
        out.append(buf)
    return out


def _check_sample(np, got, picks, kept, ref_logits, ref_seen, again, n_prompt: int, tokens,
                  notes, what: str, token_limits: tuple = (TOKEN_MARGIN, TOKEN_MEAN)) -> bool:
    """The comparisons of the module docstring, for one sequence: ``got`` and
    ``ref_logits`` [1 + steps, vocab], row for row; ``picks`` and ``ref_seen`` a
    block each, a row a position."""
    rows, std = got.shape[0], max(float(ref_logits.std()), 1e-30)
    d_max, d_mean = _logits_off(np, got, ref_logits)
    share = _f32_share(np, got)
    # a control's replay is judged WITHOUT the tokens: they are the sound
    # engine's, and a fault in both programs would leave them agreeing
    short = np.zeros(rows) if tokens is None else \
        (got.max(-1) - got[np.arange(rows), np.asarray(tokens)]) / std
    rt_under, n_rt, n_miscount = 0.0, 0, 0
    for ex, r in zip(picks, ref_seen):
        theirs = np.take_along_axis(r["router_biased"][:len(ex)], ex, axis=1)
        rt_under = max(rt_under, float((r["router_cutoff"][:len(ex), None] - theirs).max()))
        n_rt += theirs.size
        n_miscount += int((np.diff(np.sort(ex, axis=1), axis=1) == 0).any(axis=1).sum())
    state_off = _state_error(np, kept, again)
    ok = bool(np.all(np.isfinite(got)) and d_max <= LOGIT_TOL_MAX and d_mean <= LOGIT_TOL_MEAN
              and share >= LOGIT_F32_SHARE and rt_under <= ROUTER_MARGIN
              and n_rt > 0 and n_miscount == 0 and len(picks) == len(ref_seen)
              and short.max() <= token_limits[0] and short.mean() <= token_limits[1]
              and len(kept) == len(again) and state_off <= STATE_TOL)
    notes.append(
        f"{what}: {n_prompt}-token prompt in chunks + {rows - 1} decode steps through pages "
        f"and state, replayed through the runner vs plain reference on the program's picks: "
        f"logits max|d| {d_max:.4f} (tol {LOGIT_TOL_MAX}), mean|d| {d_mean:.5f} (tol "
        f"{LOGIT_TOL_MEAN}) of the reference's std {std:.5f}; {share:.4f} of the rows' values "
        f"are no bfloat16's (at least {LOGIT_F32_SHARE}); {n_rt} expert picks in {len(picks)} "
        f"blocks, furthest {max(rt_under, 0):.5f} under the cut-off (margin {ROUTER_MARGIN}), "
        f"{n_miscount} tokens with a repeated expert; the state kept after "
        f"{n_prompt + rows - 1} tokens, {len(kept)} blocks, off the one-token float32 "
        f"recurrence on the same inputs by {state_off:.2e} of its norm (tol {STATE_TOL}); "
        + ("the scheduler's tokens left out of a control" if tokens is None else
           f"the scheduler's {rows} tokens at most {short.max():.4f} std under the replay's "
           f"best logit (margin {token_limits[0]}) and {short.mean():.5f} in the mean (margin "
           f"{token_limits[1]}), {int((short > 0).sum())} of them under it at all")
        + f" -> {ok}")
    return ok


def _controls(np, arch, model, reference, replay, again, sound, samples, names, notes) -> list:
    """Builder's controls (``CONTROLS``): each planted fault goes through the
    comparison that decides ``correct`` on EVERY request of the two samples (a
    sound run holds on all; which request catches a control is part of the
    reading).  ``sound``: per request (prompt, out, buf, rows, ref logits, ref
    seen, got, picks, kept, again, token limits); ``samples``: each sample's
    (prompts, fed, schedule), in ``sound``'s order.  Returns the controls that
    PASSED on all, which none may."""
    import jax.numpy as jnp

    passed = []
    for name in names:
        caught = []
        if name == "served_tokens_swapped":
            for i, (prompt, out, *_rest) in enumerate(sound):
                got, limits = sound[(i + 1) % len(sound)][6], sound[i][10]
                n = min(len(out), got.shape[0])
                std = max(float(sound[i][4].std()), 1e-30)
                short = (got[:n].max(-1) - got[np.arange(n), np.asarray(out[:n])]) / std
                held = bool(len(sound) > 1 and short.max() <= limits[0]
                            and short.mean() <= limits[1])
                notes.append(f"control {name} ({CONTROLS[name]}): request {i + 1}'s first {n} "
                             f"tokens against the next request's rows: at most {short.max():.4f} "
                             f"std under the best logit (margin {limits[0]}), mean "
                             f"{short.mean():.4f} (margin {limits[1]}) -> would pass: {held}")
                if not held:
                    caught.append(i + 1)
        elif name == "ssm_state_bf16":
            i = 0
            for prompts, fed, schedule in samples:
                for got, kept, joined in replay(prompts, fed, schedule, state_as="bfloat16"):
                    prompt, out, buf, rows, *_ = sound[i]
                    i += 1
                    picks, consumed = _split(joined)
                    # the reference on THESE picks, so that the logits see the same experts
                    ref_logits, ref_seen = reference(buf, rows, _forced(np, picks, buf.shape[1]))
                    if not _check_sample(np, got, picks, kept, ref_logits, ref_seen,
                                         again(consumed, buf.shape[1]), len(prompt), None, notes,
                                         f"control {name} ({CONTROLS[name]}), request {i}"):
                        caught.append(i)
        else:
            for i, (prompt, out, buf, rows, ref_logits, ref_seen, got, picks, kept, ag,
                    _) in enumerate(sound):
                if name == "bf16_logits":
                    got = np.asarray(jnp.asarray(got).astype(jnp.bfloat16).astype(jnp.float32))
                else:
                    with (arch.weights_rounded_to(jnp.float8_e4m3fn) if name == "fp8_weights"
                          else arch.departure(name)):
                        ref_logits, ref_seen = reference(buf, rows, _forced(np, picks, buf.shape[1]))
                if not _check_sample(np, got, picks, kept, ref_logits, ref_seen, ag, len(prompt),
                                     None, notes,
                                     f"control {name} ({CONTROLS[name]}), request {i + 1}"):
                    caught.append(i + 1)
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
    e = dict(config["engine"], **traffic.get("engine", {}))  # a builder's sweep of the engine
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
    correct = all(len(o) == steps for o in outs)
    alone = sum(-(-len(p) // e["prefill_chunk"]) for p in prompts)
    notes.append(f"correct: the sample's {len(prompts)} prompts took {shared} packs through "
                 f"the scheduler ({alone} if no pack were shared)")
    fallbacks = [d for d in dispatch_log if not d["ran"]]
    for d in fallbacks:
        notes.append(f"kernel gate declined: {d['kernel']} {d['shape']}: {d['reason']}")

    # -- the loop (drivers/serve.py lines 187-314, with this model's counters
    # in ``snapshot``, its two gauges summed a tick, and a finished request's
    # tokens and slot kept) ---------------------------------------------------
    requests: List[dict] = []      # every request ever due, in submit order
    live: Dict[int, dict] = {}
    # (t_begin, t_end, n_decoding, sum_ctx_tokens, n_in_flight, n_waiting)
    ticks: List[tuple] = []
    heap: List[tuple] = []
    order = 0
    COUNTED = ("decode_ticks", "decode_emitted", "prefill_dispatches",
               "prefill_tokens_dispatched", "ssm_states_reset", "ssm_states_recomputed",
               "ssm_chunks_scanned")
    GAUGES = ("state_bytes_live", "kv_page_bytes_in_use")  # set at every dispatch
    held_bytes = dict.fromkeys(GAUGES, 0)  # ... and summed here a tick of the window

    ROUTED = ("expert_pairs_routed", "expert_pairs_held", "experts_touched",
              "experts_touched_decode", "expert_pairs_held_decode")

    def snapshot() -> Dict[str, int]:
        snap = {k: int(eng.stats[k]) for k in COUNTED}
        eng.refresh_routing_stats()  # one small device->host copy, at the window's two ends
        snap.update({k: int(eng.stats[k]) for k in ROUTED})
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
            if base is not None:  # what both kinds of cache hold, sampled a tick of the window
                for k in GAUGES:
                    held_bytes[k] += int(eng.stats[k])
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
    counters.update(held_bytes)  # sums over the window's ticks: their ratio is the ticks' mean
    spans = [(ev["name"], ev["ts"] * 1e-6, (ev["ts"] + ev["dur"]) * 1e-6, ev.get("args", {}))
             for ev in tel.recorder.chrome_events() if ev.get("ph") == "X"]
    scopes = None
    if obs_trace is not None:
        # the programs' named scopes, while the engine still holds them (the
        # readers of a named XLA body run after close())
        from deepspeed_tpu import telemetry

        scopes = telemetry.program_scopes()
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
    for rec in requests:
        rec["req"] = None
    groups = {k: int(eng.stats[k]) for k in ("expert_group_rows_max", "expert_group_rows_min")}
    replay = _Replay(jax, np, eng, cfg)
    weights = eng.params
    audit = eng.close()   # the pool and the slots' states go; the weights stay with ``weights``
    t_sample = clock()
    recur = jax.jit(lambda x, b, c, dt, a_log: arch.recurrence(
        x[None], b[None], c[None], dt[None], -jax.numpy.exp(a_log))[1][0])

    def again(consumed, pad_to: int) -> list:
        """Per block, the one-token recurrence's last state on what the block
        consumed (padded with steps of size 0, which change nothing)."""
        pad = lambda a: np.concatenate([a, np.zeros((pad_to - len(a), *a.shape[1:]), a.dtype)])
        return [np.asarray(recur(*(pad(c[k]) for k in ("ssm_x", "ssm_b", "ssm_c", "ssm_dt")),
                                 w["a_log"]))
                for c, w in zip(consumed, weights["layers"]["mamba"])]

    def reference(buf, rows, forced) -> tuple:
        """(logits at ``rows``, each block's router logits and cut-offs) of ONE
        float32 forward over ``buf`` on the picks ``forced``, a block at a time."""
        return arch.logits_in_blocks(weights, buf, model, rows, forced)

    def judged(prompts, outs, replays, what: str, token_limits: tuple) -> tuple:
        """One reference forward a request on the replay's picks (all padded to one
        length: one compile a sample) and the comparisons; (all held, each
        request's buffers for the controls)."""
        pad_to = -(-max(len(p) + len(o) - 1 for p, o in zip(prompts, outs)) // 128) * 128
        ok, sound = True, []
        for i, (prompt, out, (got, kept, joined)) in enumerate(zip(prompts, outs, replays)):
            picks, consumed = _split(joined)
            buf = np.zeros((1, pad_to), np.int32)
            buf[0, :len(prompt) + len(out) - 1] = prompt + list(out[:-1])
            rows = np.arange(len(prompt) - 1, len(prompt) - 1 + got.shape[0])
            ref_logits, ref_seen = reference(buf, rows, _forced(np, picks, pad_to))
            ag = again(consumed, pad_to)
            ok &= _check_sample(np, got, picks, kept, ref_logits, ref_seen, ag, len(prompt), out,
                                notes, f"correct: {what} {i + 1} of {len(prompts)}", token_limits)
            sound.append((prompt, out, buf, rows, ref_logits, ref_seen, got, picks, kept, ag,
                          token_limits))
        return ok, sound

    fed = [o[:-1] for o in outs]
    samples = [(prompts, fed, schedule)]
    held, sound = judged(prompts, outs, replay(*samples[0]), "request",
                         (TOKEN_MARGIN, TOKEN_MEAN))
    correct &= held
    if taken:
        w_prompts = [list(r["served"][0]) for r in taken]
        w_outs = [list(r["served"][1]) for r in taken]
        w_fed = [o[:-1] for o in w_outs]
        notes.append(f"correct: the window's sample: requests of slots "
                     f"{[r['slot'] for r in taken]} with {[r['prompt_len'] for r in taken]} "
                     f"prompt and {[r['got'] for r in taken]} answer tokens, of {len(pool)} "
                     f"finished inside the window")
        samples.append((w_prompts, w_fed, _alone(w_prompts, w_fed, e["prefill_chunk"])))
        held, w_sound = judged(w_prompts, w_outs, replay(*samples[1]), "window request",
                               (WINDOW_TOKEN_MARGIN, WINDOW_TOKEN_MEAN))
        correct &= held
        sound += w_sound
    notes.append(f"after the window and close(): the samples' replays and references took "
                 f"{clock() - t_sample:.2f} s")
    if planted:
        if not taken:
            raise harness.BenchError("the controls are judged on the window's sample too, and "
                                     "the window finished no request")
        correct &= not _controls(np, arch, model, reference, replay, again, sound, samples,
                                 planted, notes)
    for r in requests:
        r["served"] = None
    del replay, weights, sound
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
        notes.append(f"correct: close() left {audit['ssm_states']} live state-space states")
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
                f"waiting max {max(t[5] for t in part)}, rows decoding a tick "
                f"{sum(t[2] for t in part) / len(part):.1f}, their context "
                f"{sum(t[3] for t in part) / max(sum(t[2] for t in part), 1):.0f} tokens a row")
    inside = [r for r in done if t0 <= r["end"] < t1]
    n_ticks = max(sum(1 for t in ticks if t0 <= t[1] < t1), 1)
    notes.append(f"window: {len(ticks)} ticks, {len(requests)} requests submitted in "
                 f"all, {attempted} due inside the window, {len(done)} finished "
                 f"({len(inside)} inside the window), {len(live)} in flight at the end; ramp "
                 f"{plan.ramp_s:.1f} s; {counters['preemptions']} preemptions; a tick of the "
                 f"window held {held_bytes['state_bytes_live'] / n_ticks / 2**30:.3f} GiB of state "
                 f"and {held_bytes['kv_page_bytes_in_use'] / n_ticks / 2**30:.3f} GiB of K / V pages")
    return {
        "kind": "serve", "correct": correct, "attempted": attempted, "failed": failed,
        "window": (t0, t1), "t_process": t_process,
        "requests": requests, "ticks": ticks, "spans": spans, "counters": counters,
        "fallbacks": fallbacks, "compiles_in_window": watch.within(t0, t1),
        "trace": obs_trace, "model": _as_the_readers_look_it_up(model), "engine": e, "chips": chips,
        "notes": notes, **({} if scopes is None else {"_scopes": scopes}),
    }
