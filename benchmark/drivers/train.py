"""Training cells: ``deepspeed_tpu.initialize`` -> ``train_on_loader`` (the
pipelined loop users run), on one chip or on ``initialize_mesh(fsdp=chips)``.

Order of a run: engine (weights made on the device from the seed by the
engine's own jitted init) -> the plain reference's loss on batch 0
(``eval_batch``) -> step 0 through ``train_batch`` (compiles, and is the loss
compared) -> a few steps through ``train_on_loader`` (its own program, if any)
-> ``gc.freeze`` -> the measured window.  The loop keeps the device two steps
behind the host: it waits for step k-2's loss before it dispatches step k, so
the queue stays full and the host cannot run away from the window's end.
"""
from __future__ import annotations

import collections
import gc
import math
import time
from typing import Any, Dict, List

from .. import harness

# Step-0 loss, kernel path against the plain reference on the same weights
# and batch: a mean over >= 4096 token losses of ~10.4 (ln 32000 at random
# weights).  Per-token bf16 rounding noise of ~1e-2 averages down to
# ~1e-4..1e-3 (PR 21 measured 4e-5 and 1.4e-4); 5e-3 absolute is 10x that and
# 0.05% of the loss, and a masking or position fault moves it by far more.
LOSS_TOL = 5e-3
LAG = 2  # steps the device may trail the host's dispatch


def run(*, config, traffic, chips, seed, seconds, trace, rehearse, workload,
        t_process, watch, device) -> Dict[str, Any]:
    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.ops.pallas import record_dispatch
    from deepspeed_tpu.parallel.topology import initialize_mesh

    notes: List[str] = []
    clock = time.perf_counter
    lap = harness.Laps(notes)

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
    with record_dispatch() as dispatch_log:
        grid = initialize_mesh(fsdp=chips)
        engine, _, _, _ = ds.initialize(
            model=CausalLM(cfg), config=ds_config, mesh=grid,
            eval_fn=arch.make_loss_fn(model))
        lap("engine built")
        first = next(batches)
        loss_ref = float(engine.eval_batch(first))
        lap("correctness: plain reference loss")
        loss0 = float(engine.train_batch(first))
        lap("step 0 (compile or cache load)")
        warm = [float(x) for x in engine.train_on_loader(
            batches, num_steps=int(tr["warmup_steps"]))]
        lap(f"{len(warm)} warm-up steps through train_on_loader")
    fallbacks = [d for d in dispatch_log if not d["ran"]]
    for d in fallbacks:
        notes.append(f"kernel gate declined: {d['kernel']} {d['shape']}: {d['reason']}")
    finite = all(math.isfinite(x) for x in [loss_ref, loss0] + warm)
    correct = finite and abs(loss0 - loss_ref) <= LOSS_TOL
    notes.append(f"correct: step-0 loss {loss0:.5f} vs plain reference "
                 f"{loss_ref:.5f}, |d| {abs(loss0 - loss_ref):.2e} (tol {LOSS_TOL}); "
                 f"losses finite: {finite}")

    gc.collect()
    gc.freeze()
    gc.disable()
    gen = engine.train_on_loader(batches)
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
    correct = bool(correct and math.isfinite(last_loss))
    notes.append(f"window: {steps} steps of {rows} x {plan.seq} tokens in "
                 f"{t_end - t0:.3f} s; last loss {last_loss:.4f}")
    return {
        "kind": "train", "correct": correct, "attempted": steps, "failed": 0,
        "window": (t0, t_end), "t_process": t_process, "steps": steps,
        "tokens_per_step": rows * plan.seq, "chips": chips,
        "fallbacks": fallbacks, "compiles_in_window": watch.within(t0, t_end),
        "trace": obs_trace, "model": model, "seq": plan.seq, "micro": plan.micro,
        "notes": notes,
    }
