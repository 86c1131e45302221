"""Serving cells: ``InferenceEngineV2`` + ``ServeScheduler`` driven by ONE host
thread that submits what is due, ticks, and stamps every token it is handed.

Order of a run: weights on the device from the seed (one jitted call, bf16) ->
engine -> the correctness sample -> warm-up of the cell's three dispatch
shapes through the scheduler itself -> ``gc.freeze`` -> the traffic's ramp ->
the measured window.  Everything before the window is ``setup_s``.
"""
from __future__ import annotations

import gc
import heapq
import time
from typing import Any, Dict, List

from .. import harness, reference
from ..modelcfg import transformer_config

# Tolerances of the logits comparison, and why (PR 21's, kept): both sides
# compute from the same bf16 weights; the engine rounds activations to bf16
# (2^-9 relative a rounding) in kernel tile order, the reference keeps
# float32.  Logits are O(1) (std 1) and themselves rounded to bf16 by the
# engine (half-ulp 0.016 in [4, 8)); one independent rounding per layer op
# accumulates to ~0.01 mean, ~0.07 max over 16 layers (measured, PR 21).  A
# masking, paging or position fault moves logits by O(1).  0.25 max / 0.05
# mean sit between the two, and running in a lower precision than bf16
# activations would exceed them.
LOGIT_TOL_MAX = 0.25
LOGIT_TOL_MEAN = 0.05
DRAIN_CAP_S = 60.0  # longest wait after the window for in-flight first tokens
MARGIN_TOL = 2 * LOGIT_TOL_MAX  # how far under the reference's best logit a greedy token may score


def _runner_logits(jax, np, eng, cfg, prompt: List[int], steps: int):
    """Next-token logits through the engine's own runner, cache layout and
    mesh: one cold pack (flash), then ``steps`` greedy paged-decode steps.
    The engine's jitted dispatches fuse sampling and return tokens only."""
    from deepspeed_tpu.inference import model_runner
    from deepspeed_tpu.inference.paged import init_paged_cache

    bs, T, N, P = eng.block_size, eng.prefill_chunk, eng.mgr.max_seqs, eng.max_pages
    ctx, mesh = eng.serving_ctx, eng._mesh
    n_pages = -(-(len(prompt) + steps) // bs)
    kv = init_paged_cache(cfg.num_layers, n_pages + 1, bs, cfg.num_kv_heads,
                          cfg.hd, dtype=cfg.dtype)
    blocks = np.arange(n_pages, dtype=np.int32)
    table = np.full((N, P), -1, np.int32)
    table[0, :n_pages] = blocks
    cold = jax.jit(
        lambda p, tok, seg, pos, pages, last, kv: model_runner.prefill_packed(
            p, cfg, tok, seg, pos, pages, last, kv, ctx=ctx, mesh=mesh),
        donate_argnums=(6,))
    dec = jax.jit(
        lambda p, tok, lens, tb, act, kv: model_runner.decode_step(
            p, cfg, tok, lens, tb, act, kv, ctx=ctx, mesh=mesh),
        donate_argnums=(5,))
    n = len(prompt)
    tok = np.zeros(T, np.int32)
    seg = np.zeros(T, np.int32)
    pos = np.zeros(T, np.int32)
    tok[:n], seg[:n], pos[:n] = prompt, 1, np.arange(n)
    pages = np.full(T // bs, -1, np.int32)
    pages[: -(-n // bs)] = blocks[: -(-n // bs)]
    last = np.full(N, -1, np.int32)
    last[0] = n - 1
    lg, kv = cold(eng.params, tok, seg, pos, pages, last, kv)
    rows = [np.asarray(lg[0].astype("float32"))]
    seq = list(prompt)
    active = np.zeros(N, bool)
    active[0] = True
    for _ in range(steps):
        seq.append(int(np.argmax(rows[-1])))
        t1 = np.zeros(N, np.int32)
        lens = np.zeros(N, np.int32)
        t1[0], lens[0] = seq[-1], len(seq) - 1
        lg, kv = dec(eng.params, t1, lens, table, active, kv)
        rows.append(np.asarray(lg[0].astype("float32")))
    del kv
    return seq, np.stack(rows)  # rows[i] predicts position len(prompt) + i


def _check_logits(jax, np, eng, cfg, model, ref_fn, pad_to, rng, sample: dict,
                  notes: List[str]) -> bool:
    prompt = rng.integers(0, cfg.vocab_size, int(sample["prompt_tokens"])).tolist()
    steps = int(sample["decode_steps"])
    seq, got = _runner_logits(jax, np, eng, cfg, prompt, steps)
    buf = np.zeros((1, pad_to), np.int32)
    buf[0, :len(seq)] = seq
    ref = np.asarray(ref_fn(eng.params, buf))[0]
    ref = ref[len(prompt) - 1: len(prompt) + steps]
    d = np.abs(got - ref)
    ok = bool(np.all(np.isfinite(got)) and d.max() <= LOGIT_TOL_MAX
              and d.mean() <= LOGIT_TOL_MEAN)
    notes.append(f"correct: runner logits vs plain reference, {len(prompt)}-token "
                 f"prompt + {steps} decode steps: max|d| {d.max():.4f} "
                 f"(tol {LOGIT_TOL_MAX}), mean|d| {d.mean():.4f} (tol "
                 f"{LOGIT_TOL_MEAN}), reference std {ref.std():.2f} -> {ok}")
    return ok


def _check_tokens(np, ref_fn, params, pad_to, prompt, generated, notes) -> bool:
    """The scheduler's own greedy tokens (flash pack, packed-ctx pack, paged
    decode, fused sampling) against the plain reference on the same sequence:
    at EVERY position the token the engine chose must score within
    ``MARGIN_TOL`` of the reference's best logit (either logit may be off by
    ``LOGIT_TOL_MAX``).  With random weights the best of 32000 logits of std 1
    stands ~4 above a random token's, so a paging, masking or position fault
    fails this at once, and rounding cannot."""
    full = list(prompt) + list(generated)
    buf = np.zeros((1, pad_to), np.int32)
    buf[0, :len(full)] = full
    ref = np.asarray(ref_fn(params, buf))[0][len(prompt) - 1: len(full) - 1]
    short = ref.max(axis=-1) - ref[np.arange(len(generated)), np.asarray(generated)]
    ok = bool(len(generated) and short.max() <= MARGIN_TOL)
    notes.append(f"correct: scheduler tokens vs plain reference at {len(generated)} "
                 f"positions: chosen token at most {short.max():.4f} under the "
                 f"reference's best logit (tol {MARGIN_TOL}) -> {ok}")
    return ok


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
    e = config["engine"]
    cfg = transformer_config(model, max_seq_len=e["max_seq_len"])
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

        # -- correctness sample (outside the window, inside setup_s) ---------
        sample = config["correctness"]
        pad_to = int(sample["reference_pad_to"])
        ref_fn = jax.jit(lambda p, t: reference.logits(p, t, model))
        correct = _check_logits(jax, np, eng, cfg, model, ref_fn, pad_to, rng,
                                sample, notes)
        lap("correctness: runner logits")

        # -- warm-up: the three dispatch shapes, through the scheduler -------
        # a prompt longer than one chunk: first chunk cold (flash pack),
        # second attends cached pages (packed-ctx pack), then decode ticks
        warm_prompt = rng.integers(
            0, cfg.vocab_size, int(sample["warmup_prompt_tokens"])).tolist()
        r = sched.try_submit(0, warm_prompt, greedy(sample["warmup_new_tokens"]))
        if not r.accepted:
            raise harness.BenchError(f"warm-up request refused: {r.reason}")
        sched.run(wait_for=[0])
        warm_out = sched.pop_result(0)
        lap("warm-up through the scheduler")
    correct &= len(warm_out) == int(sample["warmup_new_tokens"])
    correct &= _check_tokens(np, ref_fn, eng.params, pad_to, warm_prompt, warm_out, notes)
    lap("correctness: scheduler tokens")
    fallbacks = [d for d in dispatch_log if not d["ran"]]
    for d in fallbacks:
        notes.append(f"kernel gate declined: {d['kernel']} {d['shape']}: {d['reason']}")

    # -- the loop ----------------------------------------------------------
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
                       "end": None, "got": 0, "req": req, "admit": None, "chunks": []}
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
    audit = eng.close()

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
    notes.append(f"window: {len(ticks)} ticks, {len(requests)} requests submitted in "
                 f"all, {attempted} due inside the window, {len(done)} finished, "
                 f"{len(live)} in flight at the end; ramp {plan.ramp_s:.1f} s")
    return {
        "kind": "serve", "correct": correct, "attempted": attempted, "failed": failed,
        "window": (t0, t1), "t_process": t_process,
        "requests": requests, "ticks": ticks, "spans": spans, "counters": counters,
        "fallbacks": fallbacks, "compiles_in_window": watch.within(t0, t1),
        "trace": obs_trace, "model": model, "engine": e, "chips": chips,
        "notes": notes,
    }
