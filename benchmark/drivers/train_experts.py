"""Training cells of a model whose layers differ in KIND and hold a share of
routed experts (``TransformerConfig.latent``): ``deepspeed_tpu.initialize`` ->
``train_on_loader``, the loop of ``drivers/train.py`` (engine -> correctness ->
step 0 -> warm-up -> ``gc.freeze`` -> the window, the device two steps behind
the host).  The file's own part is ``correct``: a loss alone says little of a
step whose new work is a backward pass and an update.

``correct``, at the timed sizes, of what the timed path computes, on the
engine's float32 master weights w0 and batch 0:

(a) LOSS: the step's own loss (``train_batch``, step 0) and the loss of the
    function the step differentiates (``engine.loss_fn``) against the plain
    float32 reference's on the SAME expert picks: picks are discontinuous, so
    the reference takes the program's (``CausalLM.loss_and_picks``: the picks
    leave the SAME program that computes the loss and the gradient they are
    compared on), and every pick is held to the reference router's own cut-off
    within ``PICK_MARGIN``.
(b) GRADIENTS, twice, a relative error a tensor (``|g - g_ref| / max(|g_ref|,
    |g|)``, Frobenius) against ``jax.grad`` of the reference: THE STEP'S OWN
    (what AdamW's first moment holds after step 0, ``mu / (1 - b1)``: no second
    program computes it) and ``jax.grad`` of the function the step
    differentiates.  Every router, the embedding and the head, W_q / W_k / W_v /
    W_o and both head norms of the first layer of each kind, the norms, and the
    three matrices of EVERY held expert: of the step, a layer's sixteen as one
    tensor; of the differentiated function, whose picks are the reference's,
    each expert by itself, the one of the fewest rows as closely as the one of
    the most.  (The picks have to leave the program whose gradient is compared:
    a forward-only program does not pick alike where a token's eighth and ninth
    scores are a rounding apart, and one row more or fewer shows as tens of
    percent in a gradient summed over a handful of rows: PERF.md section 7.)
    The reference runs BEFORE the engine exists (every held expert's float32
    gradient does not fit beside the optimizer's state) and its gradients wait
    on the host for the step; the comparisons themselves run on the device.
(c) THE UPDATE: the master weights' change over step 0, ``w1 - w0``, against
    AdamW applied to the reference's float32 gradient on w0 at the step's own
    learning rate (``|d - d_ref| / |d_ref|``, the worst tensor: AdamW's first
    step is ``lr x g / (|g| + eps)``, the gradient's SIGN, so an element whose
    gradient lies within bf16's noise of zero reads 2 and a sound tensor tenths;
    a state left unchanged, a stale or a zero update read 1), and against AdamW
    applied to the step's OWN moments (``mu``, ``nu``), which the sign does not
    blur: the learning rate, the decay, the bias correction and the master copy.
(d) THE WINDOW'S EDGE, exactly: which keys a sliding layer's query saw, read
    off the attention the program dispatches at the timed shape (q = k = 0, so
    every key a query sees weighs the same; a one-hot cotangent on the query's
    output row; the rows of dv that are not zero), against the reference's mask.
(e) every loss of the warm-up and the window's last finite.

The weights: the engine's own jitted init (``models/latent.py: init_params``)
with the embedding's rows scaled to ``training.embedding_std``, and the
learning rate on ``training.lr_schedule``: the configuration's ``assumed`` says
why (a window whose routing neither drifts nor depends on the seed).

``--set control='"all"'`` (builder only) plants faults in the REFERENCE (and
one in the program's reading) and prints what the same limits say of each:
every one has to be refused.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import math
import time
from typing import Any, Dict, List

from .. import harness

# The limits, each between the largest sound reading over the seeds measured on
# the chip and the nearest control's (PERF.md section 2 has the readings):
LOSS_TOL = 4e-4      # |loss - reference's| on the same picks (losses of ~10.6)
GRAD_TOL = {         # relative error of a tensor's gradient, by the tensor's class
    "router": 0.035, "experts, a layer": 0.035, "expert, each": 0.035, "attention": 0.045,
    "norm": 0.045, "embedding and head": 0.025}
UPDATE_TOL = 0.7     # |dw - AdamW(reference's gradient)| / |that|, the worst tensor; unchanged: 1
MOMENTS_TOL = 0.15   # |dw - AdamW(the step's own mu, nu)| / |that|, the worst tensor
PICK_MARGIN = 4e-3   # a pick's score under the reference router's cut-off, at most
LAG = 2              # steps the device may trail the host's dispatch
CONTROLS = {
    "weights_fp8": "the reference reads every weight matrix rounded to float8_e4m3: one "
                   "precision below the bf16 the step computes in",
    "no_window": "the reference's sliding layers attend every key",
    "window_off_by_one": "the reference's window is one key wider",
    "no_yarn": "the reference's full layers rotate by the plain table",
    "routing_not_renormalised": "the reference weighs a pick by its raw softmax score",
    "aux_loss_dropped": "the reference's loss has no balance term",
    "expert_offset_shifted": "the reference holds experts 1-16",
    "stale_expert_gradient": "the gradient of the last layer's held expert of the FEWEST rows is zero",
    "update_dropped": "the master weights after step 0 are read as they were before it",
}
EXPERT_MATRICES = ("w_gate", "w_up", "w_down")


def _selected(tree, kinds):
    """The tensors ``correct`` compares, out of a parameter-shaped tree: name ->
    (class, array).  ``kinds``: the program's kind of each layer."""
    layers, out = tree["layers"], {"final_norm": ("norm", tree["final_norm"]["scale"])}
    out["attn_norm"] = ("norm", layers["attn_norm"]["scale"])
    out["mlp_norm"] = ("norm", layers["mlp_norm"]["scale"])
    out["embedding"] = ("embedding and head", tree["embed"]["embedding"])
    out["lm_head"] = ("embedding and head", tree["lm_head"]["kernel"])
    for l, moe in enumerate(layers["moe"]):
        out[f"L{l}.router"] = ("router", moe["router"])
        for name in EXPERT_MATRICES:  # [held, ., .]: every held expert's
            out[f"L{l}.experts.{name}"] = ("experts, a layer", moe[name])
    for kind in dict.fromkeys(kinds):  # the first layer of each kind
        w = layers[kind][0]
        for name in ("wq", "wk", "wv", "wo"):
            out[f"L{kinds.index(kind)}({kind}).{name}"] = ("attention", w[name])
        for name in ("q_norm", "k_norm"):
            out[f"L{kinds.index(kind)}({kind}).{name}"] = ("norm", w[name])
    return out


def _gap(a, b, over=None):
    """|a - b| / max(|a|, |b|) (or / |``over``|), Frobenius, on the device ->
    (of the whole tensor, of a [held, ., .] tensor also an expert at a time:
    [held], else None).  The host's numpy took 90 s a reference for this."""
    import jax.numpy as jnp

    axes = (1, 2) if a.ndim == 3 else None
    sq = lambda x: jnp.sum(jnp.square(x.astype(jnp.float32)), axis=axes)  # [held], or a scalar
    d, na, nb = sq(a - b), sq(a), sq(b)
    den = sq(over) if over is not None else jnp.maximum(na, nb)
    whole = jnp.sum(d) / jnp.maximum(
        jnp.sum(den) if over is not None else jnp.maximum(jnp.sum(na), jnp.sum(nb)), 1e-30)
    return jnp.sqrt(whole), (jnp.sqrt(d / jnp.maximum(den, 1e-30)) if a.ndim == 3 else None)


def _seen_keys(jax, jnp, cfg, b, n, ga, queries):
    """Which keys each of ``queries`` saw in the attention the program
    dispatches for a layer of spec ``ga`` at [b, n]: bool [len(queries), n]."""
    from deepspeed_tpu.ops.attention import get_attention_impl

    attn = get_attention_impl(cfg.attn_impl)
    kw = {"window": ga.window} if ga.window else {}
    q = jnp.zeros((b, n, ga.num_heads, ga.head_dim), cfg.dtype)
    k = jnp.zeros((b, n, ga.num_kv_heads, ga.head_dim), cfg.dtype)

    @jax.jit
    def seen(i):
        out, vjp = jax.vjp(lambda v: attn(q, k, v, causal=True, **kw), jnp.ones_like(k))
        (dv,) = vjp(jnp.zeros_like(out).at[0, i].set(1))
        return jnp.any(dv[0] != 0, axis=(1, 2))

    return [jax.device_get(seen(i)) for i in queries]


def run(*, config, traffic, chips, seed, seconds, trace, rehearse, workload,
        t_process, watch, device) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.ops.pallas import record_dispatch
    from deepspeed_tpu.parallel.topology import initialize_mesh
    from deepspeed_tpu.runtime import precision

    notes: List[str] = []
    clock = time.perf_counter
    lap = harness.Laps(notes)
    control = traffic.get("control")
    planted = list(CONTROLS) if control == "all" else \
        [control] if isinstance(control, str) else list(control or ())
    for name in planted:
        if name not in CONTROLS:
            raise harness.BenchError(f"unknown control {name!r}; there are {sorted(CONTROLS)}")

    model = config
    arch = harness.module("models", model["model_type"])
    tr = config["training"]
    plan = harness.module("generators", traffic["kind"]).build(
        traffic, seed=seed, seconds=seconds, vocab=model["vocab_size"])
    cfg = arch.transformer_config(
        model, max_seq_len=plan.seq, remat=tr["remat"],
        loss_chunk_size=tr["loss_chunk_size"], attn_impl=tr["attn_impl"])
    ds_config = {
        "train_micro_batch_size_per_gpu": plan.micro,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": tr["optimizer"], "params": {
            "lr": tr["lr"], "weight_decay": tr["weight_decay"]}},
        "zero_optimization": {"stage": tr["zero_stage"],
                              "param_persistence_threshold": 0},
        "bf16": {"enabled": model["torch_dtype"] == "bfloat16"},
        "steps_per_print": 1_000_000_000,
        "seed": seed % (2**31 - 1),
    }
    rows = plan.micro * chips
    batches = plan.batches(rows)
    spec, kinds = cfg.latent, list(cfg.latent.layer_kinds)
    if tr.get("lr_schedule"):
        ds_config["scheduler"] = tr["lr_schedule"]
    betas, eps = (0.9, 0.999), 1e-8  # the optimizer's defaults: the configuration names none
    lm = CausalLM(cfg)

    @jax.jit
    def init(key):
        """The engine's own init, the embedding's rows N(0, 1 / d) scaled to the
        configuration's standard deviation (``assumed.weights``)."""
        p = lm.init_params(key)
        if tr.get("embedding_std") is not None:
            p["embed"]["embedding"] = p["embed"]["embedding"] * (
                tr["embedding_std"] * math.sqrt(cfg.hidden_size))
        return p

    with record_dispatch() as dispatch_log:
        grid = initialize_mesh(fsdp=chips)
        w0 = init(jax.random.PRNGKey(ds_config["seed"]))  # the master weights step 0 will move
        first = next(batches)
        ids = jnp.asarray(first["input_ids"])
        compute = precision.compute_dtype("bfloat16" if ds_config["bf16"]["enabled"] else "float32")
        cast = lambda p: precision.cast_floating(p, compute)
        sel = lambda tree: {k: v for k, (_, v) in _selected(tree, kinds).items()}
        classes = {k: c for k, (c, _) in _selected(w0, kinds).items()}
        gaps = jax.jit(lambda a, b: {k: _gap(a[k], b[k]) for k in a})

        # the function the step differentiates: its loss, its gradient and, out of the
        # SAME program, its picks (the seed's ids are an ARGUMENT: one program for every seed)
        @jax.jit
        def program_grads(p, ids):
            (loss, picks), g = jax.value_and_grad(
                lambda p: lm.loss_and_picks(cast(p), {"input_ids": ids}, None), has_aux=True)(p)
            return loss, [x.reshape(rows, plan.seq, -1) for x in picks], sel(g)

        loss_diff, picks, g_fn = program_grads(w0, ids)
        loss_diff = float(loss_diff)
        local = [np.asarray(x).reshape(-1) - spec.held_offset for x in picks]
        loads = np.stack([np.bincount(x[(x >= 0) & (x < spec.n_held)], minlength=spec.n_held)
                          for x in local])  # [L, held]: rows of each held expert, batch 0
        lap("correctness: the differentiated function's picks, loss and gradients on w0")

        def reference(name=None):
            """Of the reference on w0 and the program's picks, departing as control
            ``name`` says: its loss, the picks' lowest margin, the sliding layers'
            window, the differentiated function's gradients against its own ("fn":
            compared at once, on the device) and its selected gradients, on the host
            until the step has run (a control's in bfloat16).  BEFORE the engine
            exists: every held expert's gradient in float32 would not fit beside the
            optimizer's state."""
            how = arch.departure(name) if name in arch.DEPARTURES else \
                arch.weights_rounded_to(jnp.float8_e4m3fn) if name == "weights_fp8" else \
                contextlib.nullcontext()
            with how:
                window = arch.window_of(model, "sliding_attention")

                @jax.jit
                def grads(p, ids, picks):
                    (loss, margin), g = jax.value_and_grad(
                        lambda p: arch.loss_on(p, ids, model, forced=picks, margins=True),
                        has_aux=True)(p)
                    return loss, sel(g), jnp.min(margin)

                loss, g, margin = grads(w0, ids, picks)
            if name == "stale_expert_gradient":
                for m in EXPERT_MATRICES:
                    key = f"L{len(loads) - 1}.experts.{m}"
                    g[key] = g[key].at[int(np.argmin(loads[-1]))].set(0.0)
            fn = jax.device_get(gaps(g_fn, g))
            if name is not None:
                g = {k: v.astype(jnp.bfloat16) for k, v in g.items()}
            return {"loss": float(loss), "margin": float(margin), "window": window, "fn": fn,
                    "g": jax.device_get(g)}

        sound = reference()
        lap("correctness: the plain reference's loss and gradients on w0 and the same picks")
        faulty = {name: reference(name) for name in planted}
        if planted:
            lap(f"{len(planted)} controls' references")

        del g_fn
        w0_sel = {k: jnp.copy(v) for k, v in sel(w0).items()}
        engine, _, _, _ = ds.initialize(model=lm, params=w0, config=ds_config, mesh=grid)
        assert engine.loss_fn == lm.loss_fn and engine.compute_dtype == compute
        del w0
        lap("engine built")
        # THE STEP: its loss, and what it left in the optimizer's state and the weights
        lr0 = float(engine.lr_schedule_fn(0))
        loss0 = float(engine.train_batch(first))
        moments = next(x for x in jax.tree_util.tree_leaves(
            engine.state.opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(x, "mu"))
        mu, nu, w1 = sel(moments.mu), sel(moments.nu), sel(engine.state.params)
        lap("step 0 (compile or cache load)")

        def adamw(w, m_hat, v_hat):
            """AdamW's first step on a tensor from bias-corrected moments."""
            return -lr0 * (m_hat / (jnp.sqrt(v_hat) + eps) + tr["weight_decay"] * w)

        @jax.jit
        def after_step(mu, nu, w1, w0, g):
            """name -> (the step's own gradient against ``g``: whole, an expert at a
            time; its update against AdamW of ``g``; against AdamW of its own moments)."""
            out = {}
            for k, ref in g.items():
                ref, moved = ref.astype(jnp.float32), w1[k] - w0[k]
                by_ref = adamw(w0[k], ref, jnp.square(ref))
                own = adamw(w0[k], mu[k] / (1.0 - betas[0]), nu[k] / (1.0 - betas[1]))
                out[k] = (_gap(mu[k] / (1.0 - betas[0]), ref), _gap(moved, by_ref, over=by_ref)[0],
                          _gap(moved, own, over=own)[0])
            return out

        def compared(ref, name=None):
            """``reference()``'s result with what only the step could add: "step" (its
            own gradient's gaps), "update" and "moments" (a number a tensor)."""
            after = jax.device_get(after_step(
                mu, nu, w0_sel if name == "update_dropped" else w1, w0_sel, ref.pop("g")))
            return {**ref, "step": {k: v[0] for k, v in after.items()},
                    "update": {k: float(v[1]) for k, v in after.items()},
                    "moments": {k: float(v[2]) for k, v in after.items()}}

        sound = compared(sound)
        faulty = {name: compared(faulty.pop(name), name) for name in planted}
        lap("correctness: the step's own gradients and its update compared")
        del mu, nu, w1, w0_sel, moments
        ga = spec.wattn if "wattn" in kinds else spec.gattn
        w = ga.window or plan.seq
        queries = sorted({q for q in (0, 1, w - 1, w, w + 1, plan.seq // 2, plan.seq - 1)
                          if 0 <= q < plan.seq})
        seen = _seen_keys(jax, jnp, cfg, rows, plan.seq, ga, queries)
        lap("correctness: the keys a sliding layer's queries saw")
        warm = [float(x) for x in engine.train_on_loader(
            batches, num_steps=int(tr["warmup_steps"]))]
        lap(f"{len(warm)} warm-up steps through train_on_loader")
    fallbacks = [d for d in dispatch_log if not d["ran"]]
    for d in fallbacks:
        notes.append(f"kernel gate declined: {d['kernel']} {d['shape']}: {d['reason']}")

    def judge(ref, say):
        """Whether the program agrees with this reference, by the limits above."""
        d_loss = max(abs(loss0 - ref["loss"]), abs(loss_diff - ref["loss"]))
        worst: Dict[str, tuple] = {}

        def note(cls, err, where):
            if err >= worst.get(cls, (-1.0, ""))[0]:
                worst[cls] = (err, where)

        for source in ("fn", "step"):
            for k, (whole, each) in ref[source].items():
                note(classes[k], float(whole), f"{k} ({source})")
                if classes[k] == "experts, a layer" and source == "fn":
                    e = int(np.argmax(each))
                    note("expert, each", float(each[e]),
                         f"{k}[{e}] of {loads[int(k[1:k.index('.')])][e]} rows (fn)")
        update = max(ref["update"].items(), key=lambda kv: kv[1])
        own = max(ref["moments"].items(), key=lambda kv: kv[1])
        edge = all(
            np.array_equal(np.flatnonzero(s),
                           np.arange(max(0, q - (ref["window"] or plan.seq) + 1), q + 1))
            for q, s in zip(queries, seen))
        finite = all(math.isfinite(x) for x in [loss0, ref["loss"]] + warm)
        ok = (d_loss <= LOSS_TOL and ref["margin"] >= -PICK_MARGIN and edge and finite
              and all(err <= GRAD_TOL[c] for c, (err, _) in worst.items())
              and update[1] <= UPDATE_TOL and own[1] <= MOMENTS_TOL)
        notes.append(
            f"{say}: step-0 loss {loss0:.5f}, the differentiated function's "
            f"{loss_diff:.5f}, reference {ref['loss']:.5f}: |d| {d_loss:.2e} (tol {LOSS_TOL}); "
            + "gradients (step: the step's own first moment; fn: jax.grad of its loss), "
              "worst relative error by class: "
            + ", ".join(f"{c} {err:.2e} at {k} (tol {GRAD_TOL[c]})"
                        for c, (err, k) in sorted(worst.items()))
            + f"; the update against AdamW of the reference's gradient at lr {lr0:.3g}: worst "
              f"{update[1]:.3f} at {update[0]} (tol {UPDATE_TOL}), against AdamW of the step's "
              f"own moments {own[1]:.2e} at {own[0]} (tol {MOMENTS_TOL})"
            + f"; picks' lowest margin over the cut-off {ref['margin']:.2e} "
              f"(tol -{PICK_MARGIN}); window's edge at queries {queries} "
            + ("exact" if edge else "NOT the reference's")
            + f"; losses finite: {finite} -> {ok}")
        return ok

    correct = judge(sound, "correct")
    few = np.argsort(loads, axis=1)[:, :2]  # a layer's two held experts of the fewest rows
    notes.append("every tensor, relative error (fn / step / update): " + ", ".join(
        f"{k} {sound['fn'][k][0]:.2e} / {sound['step'][k][0]:.2e} / {sound['update'][k]:.2f}"
        for k in sound["fn"]))
    notes.append("held experts' rows a layer (batch 0) and their gradients' error, the three "
                 "matrices' worst (fn / step): " + "; ".join(
        f"L{l} of {x.sum()}: " + ", ".join(
            f"{x[e]} rows {max(sound['fn'][f'L{l}.experts.{m}'][1][e] for m in EXPERT_MATRICES):.2e}"
            f" / {max(sound['step'][f'L{l}.experts.{m}'][1][e] for m in EXPERT_MATRICES):.2e}"
            for e in (*few[l], int(np.argmax(x))))
        for l, x in enumerate(loads)))
    if planted:
        passed = [name for name in planted
                  if judge(faulty[name], f"control {name} ({CONTROLS[name]})")]
        # a control's line ends in "-> False" when it is refused, as it must be
        notes[:] = [n.replace("-> False", "-> refused").replace("-> True", "-> PASSED")
                    if n.startswith("control ") else n for n in notes]
        notes.append("controls: " + (f"PASSED AS CORRECT, and must not: {passed}" if passed
                                     else f"all {len(planted)} refused"))
        correct &= not passed
    del sound, faulty

    gc.collect()
    gc.freeze()
    gc.disable()
    gen = engine.train_on_loader(batches)
    counter = engine.telemetry.registry.counter
    counted = ("expert_pairs_routed", "expert_pairs_held", "expert_rows_max", "expert_rows_min",
               "window_keys_attended", "causal_keys")
    engine.get_last_loss()  # flushes: the set-up's steps are booked before the window's base
    base = {k: counter(k).value for k in counted}
    pending = collections.deque()
    steps = 0
    t0 = clock()
    t1 = t0 + seconds
    cap = harness.Capture(trace, workload, t1, float(traffic.get("trace_s", 3.0)))
    try:
        while True:
            now = clock()
            if now >= t1:
                break
            cap.poll(now)
            with cap.annotate("bench.step", step=steps):
                pending.append(next(gen))
            steps += 1
            if len(pending) > LAG:
                jax.block_until_ready(pending.popleft())
        last = pending[-1] if pending else None
        jax.block_until_ready(last)
        t_end = clock()  # the window ends when the last step's loss is ready
    finally:
        gc.enable()
    obs_trace = cap.finish()
    last_loss = float(last) if last is not None else float("nan")
    gen.close()
    engine.get_last_loss()  # flushes the window's steps' counts into the registry
    counters = {k: counter(k).value - base[k] for k in counted}
    correct = bool(correct and math.isfinite(last_loss))
    # the routing must neither drift over the window nor sit far from the deployment's share
    ended = np.stack([np.asarray(x).reshape(-1) for x in program_grads(engine.state.params, ids)[1]])
    ended = np.mean((ended >= spec.held_offset) & (ended < spec.held_offset + spec.n_held))
    notes.append(
        f"pairs on the held experts: {100 * loads.sum() / (loads.shape[0] * rows * plan.seq * spec.experts_per_tok):.2f}% "
        f"of batch 0's before step 0, {100 * counters['expert_pairs_held'] / max(counters['expert_pairs_routed'], 1):.2f}% "
        f"over the window's steps, {100 * ended:.2f}% of batch 0's after the window; the largest "
        f"held expert's rows over the mean's: {loads.max(1).sum() * spec.n_held / loads.sum():.3f} "
        f"before step 0, {counters['expert_rows_max'] * spec.n_held / max(counters['expert_pairs_held'], 1):.3f} "
        "over the window")
    notes.append(f"window: {steps} steps of {rows} x {plan.seq} tokens in "
                 f"{t_end - t0:.3f} s; last loss {last_loss:.4f}; counted "
                 + ", ".join(f"{k} {v}" for k, v in counters.items()))
    return {
        "kind": "train", "correct": correct, "attempted": steps, "failed": 0,
        "window": (t0, t_end), "t_process": t_process, "steps": steps,
        "tokens_per_step": rows * plan.seq, "chips": chips,
        "fallbacks": fallbacks, "compiles_in_window": watch.within(t0, t_end),
        "trace": obs_trace, "model": model, "seq": plan.seq, "micro": plan.micro,
        "counters": counters, "notes": notes,
    }
