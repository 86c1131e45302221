"""Serving cells of a model whose layers are of several kinds (latent pages
with a key selector, a window ring per slot, a held share of experts):
``InferenceEngineV2`` + ``ServeScheduler`` driven as ``drivers/serve.py``
drives them.  The order of a run, the loop and the observations are
``serve.py``'s, copied (its ``run`` is one function; the imports below are what
could be imported).  What differs is the correctness sample, which
``serve.py`` builds from ``init_paged_cache(..., num_kv_heads, hd)`` and one
cold 256-token pack: that neither fits this cache nor reaches past a window
or a selector.

The sample here is the WARM-UP: ``correctness.prompts`` requests of unequal
length (the longest past the selector's ``index_topk`` and several windows),
submitted TOGETHER and served by the scheduler itself, ``decode_steps`` greedy
tokens each.  So the engine's own compiled programs run with several slots
live, the tail of one prompt and the head of the next in one pack, pages handed
out in turns, and a decode batch of unequal contexts; each request's tokens are
held.  The scheduler's TICKS (from its request traces: the same chunks side by
side in the same packs, the same decode batches) are then replayed through the
runner's bodies, the scheduler's tokens fed back in, for the logits and for what
each indexer and router PICKED (one cache of the engine's layout, each request
in a slot that is not the engine's nor 0, on pages interleaved with the
others'), and the plain reference makes one forward over each request.
Selection is discontinuous (a key at
the selector's cut-off may carry a large attention weight, and with seeded
weights the indexer knows nothing of attention), so a flipped pick moves a
token's logits by O(1) in either side's arithmetic.  Five things are therefore
held separately, for every sequence:

1. ``INDEX_TOL`` / ``INDEX_TOL_MEAN``: the score the program gave each key it
   selected, against the reference's score of that key: the largest
   difference, and the mean.
2. ``INDEX_MARGIN`` / ``ROUTER_MARGIN``: every key (expert) the program picked
   lies no further than the margin under the reference's cut-off (its
   ``index_topk``-th largest score; the 8th largest biased router score).  A
   pick the reference also made passes trivially.
3. The COUNT of keys picked at position ``t`` is ``min(t + 1, index_topk)``.
4. ``LOGIT_TOL_MAX`` / ``LOGIT_TOL_MEAN``: next-token logits at the last
   prompt position and every decode step, against the reference computed ON
   THE PROGRAM'S OWN PICKS (``probe(forced=...)``), so that the comparison sees
   arithmetic and not which side of a cut-off a key fell.  The reference on
   its OWN picks is printed beside it as a reading, with no limit.
5. ``TOKEN_MARGIN``: each token the scheduler chose, with the other requests
   live beside it, scores within the margin of the best logit of the replay's
   row (two programs of the same bodies).

``--set control='"all"'`` (builder only) plants faults and prints what the same
comparisons make of each; every one has to come out NOT correct
(``CONTROLS``).  The run's own ``correct`` is the sound sample's.
"""
from __future__ import annotations

import contextlib
import gc
import heapq
import time
from typing import Any, Dict, List

from .. import harness
from .serve import DRAIN_CAP_S

# Tolerances, and why.  Both sides compute from the same bf16 weights on the
# same selections; the program rounds activations to bf16 (2^-9 relative a
# rounding), the reference keeps float32.  This architecture amplifies that
# rounding far more than a dense GQA block does (0.07 / 0.012 at 16 Mistral
# layers): with seeded weights and ``apply_mla_qkv_lora_rescale`` the attention
# logits have a standard deviation of ~7, so the softmax is sharply peaked and
# a 0.03 error of a logit moves a weight by 3%; the headwise gates and the
# normalised routing weights multiply it on.  Each limit is about twice the
# largest reading a sound run gave on the chip (59 runs with the first form
# of the sample, 18 with this one: PERF.md section 2, PR 29), and under what
# its CONTROL reads.
#   logits, std 1, 9 rows of 19008 a sequence.  Program 0.55-0.91 max,
#   0.09-0.13 mean; control ``fp8_weights`` 4.09-4.42 max, 0.52 mean.  A masking,
#   ring, paging or position fault moves logits by O(1) at every position.
LOGIT_TOL_MAX = 1.8
LOGIT_TOL_MEAN = 0.26
#   index scores are O(1) (std 1.5): sums over 64 heads of relu(q.k), bf16
#   operands, float32 accumulation.  Program 0.24-0.32 over 8.4 M picked keys;
#   controls ``index_keys_fp8`` and ``index_rope_shift``.
INDEX_TOL = 0.5
#   ... and their mean, which is what tells a precision from the next (the
#   largest of 8.4 M differences hardly does: ``index_keys_fp8`` read 0.33 and
#   0.38).  Program 0.0181-0.0193; control ``index_keys_fp8`` 0.0394.
INDEX_TOL_MEAN = 0.028
#   how far under the reference's cut-off a picked key may score (program
#   0.11-0.21 over ~10 k picks the reference did not make, of 8.4 M;
#   ``index_keys_fp8`` 0.23-0.29 over ~21.5 k)
INDEX_MARGIN = 0.35
#   biased sigmoid scores lie in (0, 1); float32 routing on bf16 activations:
#   program 0.05-0.11 over 98 560 picks a request, once 0.144 (54 requests of
#   this form): twice that.  No control of its own (PERF.md section 7); a
#   router that picks by other scores reads the scores' range, 0.5 and more.
ROUTER_MARGIN = 0.3
#   the engine's dispatch and the replay are two XLA programs of the same
#   bodies run on the same ticks: fusion order may differ, selections may not
#   (program 0.0-0.017).  The ticks have to be the same: replayed ALONE, the
#   700-token request read 0.16 (its decode rows then take the selector's
#   branch without a sort, the summation order moves a bf16 rounding, and a
#   router near-tie falls the other way); on the engine's ticks it reads 0.01.
TOKEN_MARGIN = 0.05

# Planted faults (``--set control=``): where each is planted, and the
# comparison that has to refuse it.
CONTROLS = {
    "fp8_weights": "the reference itself on float8_e4m3 weights, same picks: logits",
    "index_keys_fp8": "the replay's index keys rounded to float8_e4m3: index scores / cut-off margin",
    "index_rope_shift": "the replay's index keys rotated one position late: index scores / margin",
    "topk_minus_one": "the replay's selector takes one key too few (its weakest): the count alone",
}


@contextlib.contextmanager
def _planted(fault):
    """The runner's bodies with ``fault`` in them while a replay is traced."""
    if fault is None:
        yield
        return
    import jax.numpy as jnp

    from deepspeed_tpu.inference import latent_runner

    lm, la = latent_runner.lm, latent_runner.la
    sound = lm.indexer_inputs, la.select_topk

    def inputs(aw, h, c_q, pos, s, cfg):
        q_i, k_i, w = sound[0](aw, h, c_q, pos, s, cfg)
        if fault == "index_keys_fp8":
            k_i = k_i.astype(jnp.float8_e4m3fn).astype(k_i.dtype)
        elif fault == "index_rope_shift":
            k_i = sound[0](aw, h, c_q, pos + 1, s, cfg)[1]
        return q_i, k_i, w

    def select(scores, k, n_live=None):
        vals, ix = sound[1](scores, k, n_live)
        if fault == "topk_minus_one":
            live = vals > -jnp.inf
            weakest = jnp.argmin(jnp.where(live, vals, jnp.inf), axis=-1)
            strike = (jnp.arange(vals.shape[-1]) == weakest[..., None]) \
                & (jnp.sum(live, -1, keepdims=True) > 1)  # a lone key is the query's own
            vals = jnp.where(strike, -jnp.inf, vals)
        return vals, ix

    lm.indexer_inputs, la.select_topk = inputs, select
    try:
        yield
    finally:
        lm.indexer_inputs, la.select_topk = sound


def _schedule(traces, prompts) -> list:
    """The scheduler's ticks as its request traces tell them: per tick the
    pack's entries [(request, start, end)], first come first, and the
    requests that decoded a token."""
    ticks: Dict[int, tuple] = {}
    for i, tr in enumerate(traces):
        start = 0
        for (_, _, n), tick in zip(tr.chunks, tr.chunk_ticks):
            ticks.setdefault(tick, ([], []))[0].append((i, start, start + n))
            start += n
        if start != len(prompts[i]) or None in tr.chunk_ticks + tr.emission_ticks:
            raise harness.BenchError(f"sample request {i}: its trace does not tell its "
                                     f"prefill ({start} of {len(prompts[i])} tokens)")
        for tick in tr.emission_ticks[1:]:  # the first token is the prefill's
            ticks.setdefault(tick, ([], []))[1].append(i)
    return [ticks[t] for t in sorted(ticks)]


def _runner_replay(jax, np, eng, cfg, prompts, fed, schedule, fault=None):
    """The scheduler's ticks again through ``latent_runner``'s bodies: the same
    packs (the same chunks of the same prompts side by side, each starting on a
    page of the pack) and the same decode batches, ``fed[i]`` the tokens
    request ``i`` was given back.  ONE cache of the engine's layout, request
    ``i`` in slot ``2 i + 1`` on pages ``i, i + n, i + 2 n ..``: not the
    engine's slots, never contiguous.  Returns per request (logits rows
    [1 + len(fed[i]), vocab], probes): the probes hold, per chunk and decode
    step, what each full layer's indexer and each expert layer's router
    picked."""
    from deepspeed_tpu.inference import latent_runner

    bs, T, N, P = eng.block_size, eng.prefill_chunk, eng.mgr.max_seqs, eng.max_pages
    k = len(prompts)
    if 2 * k > N:
        raise harness.BenchError(f"{k} sample sequences need {2 * k} slots, the engine has {N}")
    n_pages = [-(-(len(p) + len(f)) // bs) for p, f in zip(prompts, fed)]
    table = np.full((N, P), -1, np.int32)
    for i, n in enumerate(n_pages):
        table[2 * i + 1, :n] = i + k * np.arange(n)

    def pack_fn(p, tok, seg, pos, pages, last, tab, kv):
        seen: list = []
        lg, kv = latent_runner.prefill_pack(p, cfg, tok, seg, pos, pages, last, tab, kv,
                                            probe=seen)
        return lg, kv, seen

    def dec_fn(p, tok, lens, tab, act, kv):
        seen: list = []
        lg, kv = latent_runner.decode_step(p, cfg, tok, lens, tab, act, kv, probe=seen)
        return lg, kv, seen

    rows = [[] for _ in prompts]
    probes = [[] for _ in prompts]  # (first position, number of positions, per-layer picks)
    with _planted(fault):
        cache = latent_runner.init_cache(cfg, k * max(n_pages) + 1, bs, N, T)
        pack = jax.jit(pack_fn, donate_argnums=(7,))
        dec = jax.jit(dec_fn, donate_argnums=(5,))
        for entries, decoding in schedule:
            if entries:
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
                lg, cache, seen = pack(eng.params, tok, seg, pos, pages, last, table, cache)
                seen = jax.tree_util.tree_map(
                    lambda a: np.asarray(a).reshape(T, *a.shape[2:]) if a.ndim == 3
                    else np.asarray(a), seen)
                for (i, start, end), cur in zip(entries, at):
                    probes[i].append((start, end - start, jax.tree_util.tree_map(
                        lambda a: a[cur:cur + end - start], seen)))
                    if end == len(prompts[i]):
                        rows[i].append(np.asarray(lg[2 * i + 1]))
            if decoding:
                t1, lens = np.zeros(N, np.int32), np.zeros(N, np.int32)
                active = np.zeros(N, bool)
                for i in decoding:
                    j = len(rows[i]) - 1
                    t1[2 * i + 1], lens[2 * i + 1] = fed[i][j], len(prompts[i]) + j
                    active[2 * i + 1] = True
                lg, cache, seen = dec(eng.params, t1, lens, table, active, cache)
                seen = jax.tree_util.tree_map(
                    lambda a: np.asarray(a)[:, 0] if a.ndim == 3 else np.asarray(a), seen)
                for i in decoding:
                    slot = 2 * i + 1
                    probes[i].append((int(lens[slot]), 1, jax.tree_util.tree_map(
                        lambda a: a[slot:slot + 1], seen)))
                    rows[i].append(np.asarray(lg[slot]))
        del cache
    return [(np.stack(r), p) for r, p in zip(rows, probes)]


def _forced(np, probes, pad_to: int, k_experts: int) -> list:
    """The program's picks as the reference takes them (``probe(forced=)``):
    per full layer a key mask [1, s, s], per expert layer experts [1, s, k],
    in layer order.  Positions past the sequence attend themselves."""
    out = None
    for start, m, layers in probes:
        if out is None:
            out = [np.eye(pad_to, dtype=bool)[None] if "index_picked" in p
                   else np.zeros((1, pad_to, k_experts), np.int32) for p in layers]
        for dst, p in zip(out, layers):
            if "index_picked" in p:
                ok = np.isfinite(p["index_values"])
                rows = np.broadcast_to(np.arange(start, start + m)[:, None], ok.shape)
                dst[0, start:start + m] = False
                dst[0, rows[ok], p["index_picked"][ok]] = True
            else:
                dst[0, start:start + m] = p["experts_picked"]
    return out


def _check_sample(np, got, probes, ref_logits, ref_seen, n_prompt: int, tokens, topk: int,
                  notes, what: str) -> bool:
    """The five comparisons of the module docstring, for one sequence."""
    rows = got.shape[0]
    d = np.abs(got - ref_logits[n_prompt - 1: n_prompt - 1 + rows])
    # a control's replay is judged WITHOUT the tokens: they are the sound
    # engine's, and a fault in both programs would leave them agreeing
    short = np.zeros(rows) if tokens is None else \
        got.max(-1) - got[np.arange(rows), np.asarray(tokens)]
    idx_err = idx_sum = idx_under = rt_under = 0.0
    n_idx = n_rt = n_other = n_miscount = 0
    for start, m, layers in probes:
        for picks, r in zip(layers, ref_seen):
            if "index_picked" in picks:
                sc = r["index_scores"][start:start + m]          # [m, s]
                cut = r["index_cutoff"][start:start + m, None]
                ix, vals = picks["index_picked"], picks["index_values"]
                ok = np.isfinite(vals)
                theirs = np.take_along_axis(sc, np.where(ok, ix, 0), axis=1)
                apart = np.abs(np.where(ok, vals - theirs, 0))
                idx_err, idx_sum = max(idx_err, float(apart.max())), idx_sum + float(apart.sum())
                idx_under = max(idx_under, float(np.where(ok, cut - theirs, 0).max()))
                n_idx += int(ok.sum())
                n_other += int((ok & (theirs < cut)).sum())
                due = np.minimum(np.arange(start, start + m) + 1, topk)
                n_miscount += int((ok.sum(-1) != due).sum())
            else:
                b = r["router_biased"][start:start + m]
                theirs = np.take_along_axis(b, picks["experts_picked"], axis=1)
                rt_under = max(rt_under, float(
                    (r["router_cutoff"][start:start + m, None] - theirs).max()))
                n_rt += theirs.size
    ok = bool(np.all(np.isfinite(got)) and d.max() <= LOGIT_TOL_MAX
              and d.mean() <= LOGIT_TOL_MEAN and idx_err <= INDEX_TOL
              and idx_sum <= INDEX_TOL_MEAN * n_idx
              and idx_under <= INDEX_MARGIN and rt_under <= ROUTER_MARGIN
              and short.max() <= TOKEN_MARGIN and n_idx > 0 and n_rt > 0
              and n_miscount == 0)
    notes.append(
        f"{what}: {n_prompt}-token prompt in chunks + {rows - 1} decode steps, replayed "
        f"through the runner vs plain reference: logits on the program's picks max|d| "
        f"{d.max():.4f} (tol {LOGIT_TOL_MAX}), mean|d| {d.mean():.4f} (tol "
        f"{LOGIT_TOL_MEAN}), reference std {ref_logits[:n_prompt + rows].std():.2f}; index "
        f"scores of {n_idx} picked keys max|d| {idx_err:.4f} (tol {INDEX_TOL}), mean|d| "
        f"{idx_sum / max(n_idx, 1):.5f} (tol {INDEX_TOL_MEAN}), {n_other} "
        f"picks the reference did not make, furthest {max(idx_under, 0):.4f} under its "
        f"cut-off (margin {INDEX_MARGIN}), {n_miscount} rows with another count than "
        f"min(t + 1, {topk}); {n_rt} expert picks, furthest {max(rt_under, 0):.5f} under "
        f"the cut-off (margin {ROUTER_MARGIN}); "
        + ("the scheduler's tokens left out of a control" if tokens is None else
           f"the scheduler's {rows} tokens at most {short.max():.4f} under the replay's "
           f"best logit (margin {TOKEN_MARGIN})") + f" -> {ok}")
    return ok


def _controls(jax, np, eng, cfg, arch, model, reference, sound, ticks, names, notes) -> None:
    """Builder's controls, judged on the sample's first request (``CONTROLS``):
    each planted fault goes through the comparison that decides ``correct``,
    and a note says what it made of it.  ``sound`` = (prompt, tokens, buf,
    probes, ref_logits, ref_seen) of the sound check, ``ticks`` = (prompts,
    fed, schedule) of the replay."""
    import jax.numpy as jnp

    prompt, out, buf, probes, ref_logits, _ = sound
    pad_to, topk = buf.shape[1], int(model["index_topk"])
    passed = []
    for name in names:
        if name == "fp8_weights" and jnp.dtype(cfg.dtype) == jnp.float32:
            notes.append("control fp8_weights: left out, the weights are float32 here and the "
                         "reference reads them as they are")
            continue
        if name == "fp8_weights":
            def rounded(p, t, f):  # a second copy of 8 GB of weights would not fit
                with arch.weights_rounded_to(jnp.float8_e4m3fn):
                    return arch.probe(p, t, model, f)[0]

            forced = _forced(np, probes, pad_to, model["num_experts_per_tok"])
            low = np.asarray(jax.jit(rounded)(eng.params, buf, forced))[0]
            d = np.abs(low - ref_logits)[:len(prompt) + len(out)]
            ok = bool(d.max() <= LOGIT_TOL_MAX and d.mean() <= LOGIT_TOL_MEAN)
            notes.append(f"control fp8_weights ({CONTROLS[name]}): max|d| {d.max():.4f} (tol "
                         f"{LOGIT_TOL_MAX}), mean|d| {d.mean():.4f} (tol {LOGIT_TOL_MEAN}) "
                         f"-> would pass: {ok}")
        else:
            got, seen = _runner_replay(jax, np, eng, cfg, *ticks, name)[0]
            # the reference on THESE picks, so that the logits see the same keys
            lg, theirs = reference(eng.params, buf, _forced(np, seen, pad_to,
                                                            model["num_experts_per_tok"]))
            ok = _check_sample(np, got, seen, lg, theirs, len(prompt), None, topk, notes,
                               f"control {name} ({CONTROLS[name]})")
        if ok:
            passed.append(name)
    notes.append("controls: " + (f"PASSED AS CORRECT, and must not: {passed}" if passed
                                 else f"all of {names} came out not correct"))


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
        replays = _runner_replay(jax, np, eng, cfg, prompts, fed, schedule)
        lap("correctness: runner replay")
    # -- the plain reference, one forward a sequence, on the program's picks --
    pad_to = -(-(max(map(len, prompts)) + steps) // 128) * 128
    jitted = jax.jit(lambda p, t, f: arch.probe(p, t, model, f))

    def reference(params, buf, forced):
        lg, seen = jitted(params, buf, forced)
        return np.asarray(lg)[0], [{k: np.asarray(v[0]) for k, v in layer.items()}
                                   for layer in seen]

    correct = all(len(o) == steps for o in outs)
    alone = sum(-(-len(p) // e["prefill_chunk"]) for p in prompts)
    notes.append(f"correct: the sample's {len(prompts)} prompts took {shared} packs through "
                 f"the scheduler ({alone} if no pack were shared)")
    topk, sound = int(model["index_topk"]), None
    for i, (prompt, out, (got, probes)) in enumerate(zip(prompts, outs, replays)):
        buf = np.zeros((1, pad_to), np.int32)
        buf[0, :len(prompt) + len(out) - 1] = prompt + list(out[:-1])
        ref_logits, ref_seen = reference(
            eng.params, buf, _forced(np, probes, pad_to, model["num_experts_per_tok"]))
        correct &= _check_sample(np, got, probes, ref_logits, ref_seen, len(prompt), out,
                                 topk, notes, f"correct: request {i + 1} of {len(prompts)}")
        if sound is None:
            sound = (prompt, out, buf, probes, ref_logits, ref_seen)
    lap("correctness: plain reference, comparisons")
    # a reading, no limit: the reference left to its OWN picks
    own = np.asarray(jax.jit(lambda p, t: arch.probe(p, t, model)[0])(eng.params, sound[2]))[0]
    n0 = len(prompts[0])
    d = np.abs(replays[0][0] - own[n0 - 1: n0 - 1 + steps])
    notes.append(f"reading: request 1 against the reference on its OWN picks (a pick that "
                 f"falls the other side of a cut-off moves a row by O(1); no limit): logits "
                 f"max|d| {d.max():.4f}, mean|d| {d.mean():.4f}")
    lap("correctness: reference on its own picks")
    if planted:
        _controls(jax, np, eng, cfg, arch, model, reference, sound,
                  (prompts, fed, schedule), planted, notes)
        lap("controls")
    del sound, replays, ref_seen, probes, own
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
        eng.refresh_routing_stats()  # one small device->host copy, at the window's two ends
        snap.update({k: int(eng.stats[k]) for k in (
            "index_keys_scored", "index_keys_selected", "window_rows_discarded",
            "expert_pairs_routed", "expert_pairs_held")})
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
    scopes = None
    if obs_trace is not None:
        # the programs' named scopes, while the engine still holds them (the
        # readers of a named XLA body run after close())
        from deepspeed_tpu import telemetry

        scopes = telemetry.program_scopes()
    groups = {k: int(eng.stats[k]) for k in ("expert_group_rows_max",
                                             "expert_group_rows_min")}
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
        notes.append(f"correct: close() left {audit['window_rows']} rows of window state")
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
