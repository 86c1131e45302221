#!/usr/bin/env python3
"""chip_smoke.py — train and serve end to end on the TPU, once, and check it.

    python chip_smoke.py            # on the chip (one v5e chip, or a 4-chip host)
    python chip_smoke.py --rehearse # CPU pre-flight at toy size; never a pass

One process drives the two normal entry points at the FULL WIDTH of
``mistral_7b`` (d 4096, 32 q / 8 kv heads x 128, ffn 14336, vocab 32000, RoPE
1e4, bf16) with seeded random weights.  Only ``num_layers`` is cut (train 2 of
32, serve 16 of 32 — see ``FULL`` below for why).

- train:  ``ds.initialize`` -> ``train_batch`` x4 -> ``train_on_loader`` x4
          (ZeRO-3, bf16, AdamW, selective remat, chunked CE, seq 4096)
- serve:  ``InferenceEngineV2`` -> ``eng.scheduler`` (``try_submit`` / ``run`` /
          ``pop_result``) -> ``eng.close()`` on twelve requests chosen so that
          flash packed prefill, the packed-ctx kernel (chunked prefill and
          prefix-cache hits) and the paged decode kernel all run.

With four chips visible the same phases run on them: train on
``initialize_mesh(fsdp=4)``, serve on ``initialize_mesh(model=4)``.

It exits non-zero — and prints no result line — when JAX reports no TPU, when
any phase raises, or when a check fails.  On success the LAST line of stdout is
one JSON object: ``{"ok": true, "device": {...}, ...}``.

JAX is touched only inside ``main()``; no child process is started.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time
import zlib
from typing import Dict, List, Sequence, Tuple


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Sizes:
    preset: str
    overrides: dict          # TransformerConfig overrides beside num_layers
    train_layers: int
    serve_layers: int
    seq: int                 # training sequence length
    loss_chunk: int
    lr: float
    block: int               # KV page size
    chunk: int               # prefill_chunk == the one prefill bucket
    max_seqs: int
    num_blocks: int
    serve_max_len: int
    short_lens: Tuple[int, ...]   # <= chunk: one cold pack each (flash)
    long_lens: Tuple[int, ...]    # > chunk: later chunks attend cached pages
    prefix_len: int               # shared prefix of the third group
    suffix_lens: Tuple[int, ...]
    new_tokens: Tuple[int, ...]   # cycled over the requests
    parity_seq: int               # flash fwd/bwd parity input length
    decode_checked: int           # decode steps in the logits check


# Depth cuts, and why.
#  train 2/32 layers: 698 M parameters.  ZeRO-3 state is 12 B/param fp32 master
#    + Adam (8.4 GB) + bf16 compute copy + activations at micro 1 x seq 4096 —
#    the compiler's own accounting (memory_analysis) puts the step near
#    11-13 GB of the 15.75 GB a v5e chip exposes.  3 layers (916 M, 11 GB of
#    state alone) does not fit.
#  serve 16/32 layers: 7.5 GB of bf16 weights, which leaves > 6 GB for the KV
#    pool (the pool below, 2 GiB, is sized for the traffic, not to fill the
#    chip).  The runner unrolls layers in Python, so XLA's compile time grows
#    with depth; the Mosaic compile of the packed-ctx kernel (~6-10 s a shape)
#    does not, identical kernel instances compile once.
FULL = Sizes(
    preset="mistral_7b", overrides={}, train_layers=2, serve_layers=16,
    seq=4096, loss_chunk=2048, lr=1e-4,
    block=32, chunk=256, max_seqs=16, num_blocks=1024, serve_max_len=2048,
    short_lens=(200, 224, 240, 256),
    long_lens=(1024, 1184, 1376, 1536),
    prefix_len=512, suffix_lens=(64, 96, 128, 160),
    new_tokens=(32, 40, 48, 64),
    parity_seq=1024, decode_checked=8,
)

# CPU pre-flight: same control flow, toy widths, kernels in interpret mode.
REHEARSAL = Sizes(
    preset="tiny",
    overrides={"head_dim": 64, "max_seq_len": 1024, "attn_impl": "auto"},
    train_layers=2, serve_layers=2,
    seq=256, loss_chunk=128, lr=3e-3,
    block=16, chunk=128, max_seqs=8, num_blocks=256, serve_max_len=512,
    short_lens=(100, 112, 120, 128),
    long_lens=(272, 304, 336, 384),
    prefix_len=128, suffix_lens=(32, 48, 64, 80),
    new_tokens=(8, 10, 12, 16),
    parity_seq=256, decode_checked=8,
)

SEED = 0

# Tolerances, and why.
#  Both sides of every comparison compute in bf16 (8 mantissa bits: one
#  rounding is 2^-9 relative) with fp32 accumulation inside each matmul and
#  softmax; they differ in the ORDER of the roundings (tile order in the
#  kernels, paged vs dense layout, bf16 rounding of probabilities before PV).
#  - LOSS_TOL: the step-0 loss is a mean over >= 4096 token losses of ~10.4
#    (ln 32000 with random weights); per-token rounding noise of ~1e-2
#    averages down to ~1e-4..1e-3.  5e-3 absolute is 10x that and 0.05 %.
#  - LOGIT_TOL: logits are O(1) (unit-RMS hidden x 1/sqrt(d) head) and are
#    themselves rounded to bf16 (half-ulp 0.016 for |x| in [4, 8)); the
#    residual stream collects one independent rounding per layer op.  A
#    masking, paging or position bug moves logits by O(1).  Max |delta| 0.25
#    and mean |delta| 0.05 sit between the two (expected noise: ~0.02 mean,
#    ~0.1 max over 3e5 logits at 8 layers).
#  - GRAD_TOL: flash dq/dk/dv vs the jnp body on unit-variance inputs, as a
#    fraction of the reference gradient's max magnitude; the backward
#    recomputes p from the saved log-sum-exp, so its bf16 roundings differ
#    from the forward's.  2e-2 of the max is ~5 bf16 ulps.
#  - MARGIN_TOL: a greedy token is compared with the reference arg-max only
#    where the reference's top-1/top-2 gap exceeds LOGIT_TOL x 2 (either
#    logit may move by LOGIT_TOL) — below that the arg-max flips on rounding.
LOSS_TOL = 5e-3
LOGIT_TOL_MAX = 0.25
LOGIT_TOL_MEAN = 0.05
GRAD_TOL = 2e-2
MARGIN_TOL = 2 * LOGIT_TOL_MAX


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str = "") -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# compile accounting (jax.monitoring: one backend-compile event per XLA
# compile request, whether the persistent cache answered it or not; tracing
# and lowering events nest inside each other, so only this one is summed)
# ---------------------------------------------------------------------------
class CompileWatch:
    _COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.compiles: List[Tuple[str, float]] = []
        self.cache_hits = 0
        self.cache_misses = 0

    def install(self) -> None:
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event == self._COMPILE:
            self.compiles.append((str(kw.get("fun_name")), secs))

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def mark(self) -> int:
        return len(self.compiles)

    def since(self, mark: int) -> Tuple[List[Tuple[str, float]], float]:
        new = self.compiles[mark:]
        return new, sum(s for _, s in new)


def device_memory(jax) -> List[Dict[str, int]]:
    """Per-device allocator stats, through the repo's own accessor (which no
    longer swallows a TPU error)."""
    from deepspeed_tpu.utils.memory import memory_stats

    rows = []
    for d in jax.devices():
        s = memory_stats(d)
        rows.append({"id": d.id, "in_use": s["device_bytes_in_use"],
                     "peak": s["device_peak_bytes"],
                     "limit": s["device_bytes_limit"]})
    return rows


def gib(n: float) -> str:
    return f"{n / 2**30:.2f} GiB"


def say_memory(mem: Sequence[Dict[str, int]], peak_note: str = "") -> None:
    for r in mem:
        say(f"    device {r['id']}: in use {gib(r['in_use'])}, peak "
            f"{gib(r['peak'])}{peak_note}, limit {gib(r['limit'])}")


def check_memory(mem: Sequence[Dict[str, int]], what: str) -> None:
    """Peak HBM is reported and under the limit on every device, and what is
    resident is spread over the chips — nothing piled on device 0."""
    check(all(0 < r["peak"] < r["limit"] for r in mem),
          f"peak HBM not inside (0, limit): {mem}")
    use = [r["in_use"] for r in mem]
    check(max(use) <= 1.25 * min(use),
          f"{what} not spread evenly over the chips: {use}")


def kernel_report(log: Sequence[dict], wanted: Sequence[str], rehearse: bool):
    """Which bodies the phase compiled, from the dispatchers' own trace-time
    notes (ops.pallas.note_dispatch).  ``mosaic`` is True only when the
    Pallas body was chosen outside interpret mode — on a TPU backend that
    call lowers to a Mosaic custom call and nothing else."""
    ran: Dict[str, set] = {}
    declined: Dict[Tuple[str, tuple], str] = {}
    for e in log:
        if e["ran"] and (e["mosaic"] or rehearse):
            ran.setdefault(e["kernel"], set()).add(e["shape"])
        elif not e["ran"]:
            declined[(e["kernel"], e["shape"])] = e["reason"]
    for k in wanted:
        mode = "interpreted (rehearsal)" if rehearse else "as a Mosaic kernel"
        if k in ran:
            say(f"    {k:<13} ran {mode} at shapes {sorted(ran[k])}")
        else:
            say(f"    {k:<13} DID NOT RUN")
    for (k, shape), why in sorted(declined.items()):
        say(f"    gate declined: {k} at per-shard shape {shape}: {why}")
    if not declined:
        say("    no gate declined a shape in this phase")
    missing = [k for k in wanted if k not in ran]
    check(not missing, f"kernels that qualify but did not run: {missing}")
    return ({k: sorted(v) for k, v in ran.items()},
            [{"kernel": k, "shape": s, "reason": w}
             for (k, s), w in sorted(declined.items())])


# ---------------------------------------------------------------------------
# phase 0: the flash kernel against the jnp body on a small input
# ---------------------------------------------------------------------------
def parity_phase(sz: Sizes, cfg, rehearse: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.attention import dot_product_attention
    from deepspeed_tpu.ops.pallas import record_dispatch
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    say("== flash kernel vs jnp body (fwd + bwd), small input, real head "
        "widths ==")
    rng = np.random.default_rng(SEED)
    s, hq, hkv, hd = sz.parity_seq, cfg.num_heads, cfg.num_kv_heads, cfg.hd

    def rand(h):
        return jnp.asarray(rng.normal(size=(1, s, h, hd)), cfg.dtype)

    q, k, v, ct = rand(hq), rand(hkv), rand(hkv), rand(hq)

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v, causal=True).astype(jnp.float32)
            * ct.astype(jnp.float32))

    with record_dispatch() as log:
        # no ambient mesh is installed yet: this is the bare one-chip kernel
        out_k, g_k = jax.jit(lambda q, k, v: (
            flash_attention(q, k, v, causal=True),
            jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v),
        ))(q, k, v)
    out_r, g_r = jax.jit(lambda q, k, v: (
        dot_product_attention(q, k, v, causal=True),
        jax.grad(loss(dot_product_attention), argnums=(0, 1, 2))(q, k, v),
    ))(q, k, v)
    ran, declined = kernel_report(log, ("flash_fwd", "flash_bwd"), rehearse)
    f32 = lambda x: np.asarray(x.astype(jnp.float32))
    d_out = float(np.max(np.abs(f32(out_k) - f32(out_r))))
    rel = {}
    for name, a, b in zip(("dq", "dk", "dv"), g_k, g_r):
        a, b = f32(a), f32(b)
        check(np.all(np.isfinite(a)), f"flash {name} has non-finite values")
        rel[name] = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
    say(f"    out max|delta| {d_out:.4f}; grad max|delta|/max|ref| "
        + ", ".join(f"{n} {r:.4f}" for n, r in rel.items())
        + f" (tolerance {GRAD_TOL})")
    # forward: GRAD_TOL of the output's max magnitude plus one bf16 ulp at
    # |x| in [2, 4) for the output's own rounding
    check(d_out <= GRAD_TOL * float(np.max(np.abs(f32(out_r)))) + 2 ** -6,
          f"flash fwd disagrees with the jnp body: {d_out}")
    check(max(rel.values()) <= GRAD_TOL,
          f"flash bwd disagrees with the jnp body: {rel}")
    return {"out_max_abs": d_out, "grad_rel": rel, "kernels": ran,
            "declined": declined}


# ---------------------------------------------------------------------------
# phase 1: train
# ---------------------------------------------------------------------------
def train_phase(sz: Sizes, n_dev: int, watch: CompileWatch,
                rehearse: bool) -> dict:
    import itertools

    import jax
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import CausalLM, get_preset
    from deepspeed_tpu.ops.pallas import record_dispatch
    from deepspeed_tpu.parallel.sharding import mesh_disabled, set_current_mesh
    from deepspeed_tpu.parallel.topology import initialize_mesh

    t_phase = time.perf_counter()
    m_phase = watch.mark()
    cfg = get_preset(sz.preset, num_layers=sz.train_layers, remat="selective",
                     loss_chunk_size=sz.loss_chunk, **sz.overrides)
    model = CausalLM(cfg)
    # same params, same batch, jnp attention body: the engine's eval path
    ref_model = CausalLM(cfg.replace(attn_impl="reference"))
    micro = 1
    say(f"== train: {sz.preset} widths, {sz.train_layers} of "
        f"{get_preset(sz.preset).num_layers} layers "
        f"({cfg.param_count / 1e6:.0f} M params), ZeRO-3 bf16 AdamW, "
        f"remat=selective, loss_chunk={sz.loss_chunk}, "
        f"attn_impl={cfg.attn_impl!r}, seq {sz.seq}, micro {micro} x "
        f"{n_dev} chip(s) ==")
    config = {
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw",
                      "params": {"lr": sz.lr, "weight_decay": 0.1}},
        "zero_optimization": {"stage": 3, "param_persistence_threshold": 0},
        "bf16": {"enabled": True},
        "steps_per_print": 1_000_000,
        "seed": SEED,
    }
    grid = initialize_mesh(fsdp=n_dev)
    with record_dispatch() as log:
        engine, _, _, _ = ds.initialize(
            model=model, config=config, mesh=grid, eval_fn=ref_model.loss_fn)
        rng = np.random.default_rng(SEED)
        batch = {"input_ids": rng.integers(
            0, cfg.vocab_size, (micro * n_dev, sz.seq + 1), dtype=np.int32)}

        loss_ref = float(engine.eval_batch(batch))
        loss_one = None
        if n_dev > 1:
            # the same global batch, same (initial) params, flash kernel, on
            # ONE chip: no mesh at trace time -> the bare kernel on device 0
            from deepspeed_tpu.runtime import precision

            dev0 = jax.sharding.SingleDeviceSharding(jax.devices()[0])
            p1 = jax.device_put(
                precision.cast_floating(engine.state.params, cfg.dtype), dev0)
            b1 = jax.device_put(batch, dev0)
            with mesh_disabled():
                loss_one = float(jax.jit(model.loss_fn)(p1, b1))
            del p1, b1

        # -- loop 1: train_batch -------------------------------------------
        losses: List[float] = []
        t0 = time.perf_counter()
        losses.append(float(engine.train_batch(batch)))
        first_step_s = time.perf_counter() - t0
        m_warm = watch.mark()
        for _ in range(2):
            losses.append(float(engine.train_batch(batch)))
        # one step with the enqueue and the barrier timed apart
        t0 = time.perf_counter()
        loss_dev = engine.train_batch(batch)
        t1 = time.perf_counter()
        jax.block_until_ready(loss_dev)
        t2 = time.perf_counter()
        losses.append(float(loss_dev))
        recompiles_a, _ = watch.since(m_warm)

        # -- loop 2: train_on_loader ---------------------------------------
        loader_losses = []
        m_warm2 = None
        for loss_dev in engine.train_on_loader(itertools.repeat(batch, 4)):
            loader_losses.append(loss_dev)
            if m_warm2 is None:
                jax.block_until_ready(loss_dev)
                m_warm2 = watch.mark()
        losses += [float(x) for x in loader_losses]
        recompiles_b, _ = watch.since(m_warm2)

    say(f"    step-0 loss: flash {losses[0]:.5f} | reference body "
        f"{loss_ref:.5f} | delta {abs(losses[0] - loss_ref):.2e} "
        f"(tolerance {LOSS_TOL})")
    if loss_one is not None:
        say(f"    step-0 loss on ONE chip, same global batch: "
            f"{loss_one:.5f} | delta {abs(losses[0] - loss_one):.2e}")
    say("    losses: " + " ".join(f"{x:.4f}" for x in losses))
    say(f"    first step (compile + run) {first_step_s:.1f} s; one warm step:"
        f" enqueue {1e3 * (t1 - t0):.1f} ms, block_until_ready "
        f"{1e3 * (t2 - t1):.1f} ms")
    say(f"    compilations after the first step: train_batch loop "
        f"{len(recompiles_a)}, train_on_loader loop {len(recompiles_b)}")
    ran, declined = kernel_report(log, ("flash_fwd", "flash_bwd"), rehearse)
    mem = device_memory(jax)
    say_memory(mem)

    check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    check(len(losses) >= 8, "fewer than 8 steps ran")
    check(losses[-1] < losses[0],
          f"loss did not fall on the repeated batch: {losses}")
    check(abs(losses[0] - loss_ref) <= LOSS_TOL,
          f"flash vs reference step-0 loss: {losses[0]} vs {loss_ref}")
    if loss_one is not None:
        check(abs(losses[0] - loss_one) <= LOSS_TOL,
              f"{n_dev}-chip vs one-chip step-0 loss: {losses[0]} vs "
              f"{loss_one}")
    check(not recompiles_a and not recompiles_b,
          f"recompiled after warm-up: {recompiles_a + recompiles_b}")
    if not rehearse:
        check_memory(mem, "ZeRO-3 train state")
        # the flash kernel saw one chip's slice, not the gathered batch
        bh = micro * cfg.num_heads
        check(all(s[0] == bh for s in ran["flash_bwd"]),
              f"flash bwd ran on {ran['flash_bwd']}, expected {bh} "
              "batch*heads per chip")

    compiles, compile_s = watch.since(m_phase)
    wall = time.perf_counter() - t_phase
    say(f"    phase: {wall:.1f} s wall, of which XLA/Mosaic compile (or "
        f"cache load) {compile_s:.1f} s in {len(compiles)} compilations")
    # free the trainer before the serving engine sizes its KV pool
    del engine, loss_dev, loader_losses
    set_current_mesh(None)
    gc.collect()
    say("    trainer freed; in use now: " + ", ".join(
        f"device {r['id']} {gib(r['in_use'])}" for r in device_memory(jax)))
    return {
        "layers": sz.train_layers, "params": cfg.param_count,
        "losses": losses, "loss_reference_body": loss_ref,
        "loss_one_chip": loss_one, "first_step_s": first_step_s,
        "enqueue_ms": 1e3 * (t1 - t0), "blocked_ms": 1e3 * (t2 - t1),
        "recompiles_after_warmup": len(recompiles_a) + len(recompiles_b),
        "kernels": ran, "declined": declined, "memory": mem,
        "wall_s": wall, "compile_s": compile_s,
    }


# ---------------------------------------------------------------------------
# phase 2: serve
# ---------------------------------------------------------------------------
def _reference_logits(ref_fn, params, tokens: Sequence[int], pad_to: int):
    """``models.transformer.forward`` with the jnp attention body over the
    whole sequence -> float32 [len(tokens), vocab].  Causal, so right padding
    changes nothing to the left of it."""
    import numpy as np

    buf = np.zeros((1, pad_to), np.int32)
    buf[0, :len(tokens)] = tokens
    out = ref_fn(params, buf)
    return np.asarray(out.astype("float32"))[:len(tokens)]


def _runner_logits(jax, eng, cfg, sz: Sizes, prompt: Sequence[int]):
    """Next-token logits of the engine's own model runner through a paged
    cache: cold chunk via ``prefill_packed`` (flash), later chunks via
    ``prefill_packed_ctx`` (packed-ctx kernel over cached pages), then
    ``decode_checked`` greedy ``decode_step`` ticks (paged decode kernel).
    Same params, serving context, mesh and pack shapes as the engine's jitted
    dispatches — which fuse sampling and return tokens only."""
    import numpy as np

    from deepspeed_tpu.inference import model_runner
    from deepspeed_tpu.inference.paged import init_paged_cache

    bs, T, N, P = sz.block, sz.chunk, sz.max_seqs, eng.max_pages
    ctx, mesh = eng.serving_ctx, eng._mesh
    total = len(prompt) + sz.decode_checked
    n_pages = -(-total // bs)
    kv = init_paged_cache(cfg.num_layers, max(64, n_pages + 1), bs,
                          cfg.num_kv_heads, cfg.hd, dtype=cfg.dtype)
    if eng._kv_shardings is not None:
        kv = jax.device_put(kv, eng._kv_shardings)
    blocks = np.arange(n_pages, dtype=np.int32)  # slot 0 owns pages 0..n-1
    table = np.full((N, P), -1, np.int32)
    table[0, :n_pages] = blocks

    cold = jax.jit(
        lambda p, tok, seg, pos, pages, last, kv: model_runner.prefill_packed(
            p, cfg, tok, seg, pos, pages, last, kv, ctx=ctx, mesh=mesh),
        donate_argnums=(6,))
    warm = jax.jit(
        lambda p, tok, seg, pos, pages, last, tb, ln, kv:
        model_runner.prefill_packed_ctx(
            p, cfg, tok, seg, pos, pages, last, tb, ln, kv, ctx=ctx,
            mesh=mesh),
        donate_argnums=(8,))
    dec = jax.jit(
        lambda p, tok, lens, tb, act, kv: model_runner.decode_step(
            p, cfg, tok, lens, tb, act, kv, ctx=ctx, mesh=mesh),
        donate_argnums=(5,))

    logits = None
    for start in range(0, len(prompt), T):
        end = min(start + T, len(prompt))
        n = end - start
        tok = np.zeros(T, np.int32)
        seg = np.zeros(T, np.int32)
        pos = np.zeros(T, np.int32)
        tok[:n], seg[:n], pos[:n] = prompt[start:end], 1, np.arange(start, end)
        pages = np.full(T // bs, -1, np.int32)
        k = -(-n // bs)
        pages[:k] = blocks[start // bs: start // bs + k]
        last = np.full(N, -1, np.int32)
        last[0] = n - 1
        if start == 0:
            logits, kv = cold(eng.params, tok, seg, pos, pages, last, kv)
        else:
            lens = np.zeros(N, np.int32)
            lens[0] = start
            logits, kv = warm(eng.params, tok, seg, pos, pages, last, table,
                              lens, kv)
    rows = [np.asarray(logits[0])]  # next-token logits after the prompt
    seq = list(prompt)
    active = np.zeros(N, bool)
    active[0] = True
    for _ in range(sz.decode_checked):
        seq.append(int(np.argmax(rows[-1])))
        tok = np.zeros(N, np.int32)
        lens = np.zeros(N, np.int32)
        tok[0], lens[0] = seq[-1], len(seq) - 1
        logits, kv = dec(eng.params, tok, lens, table, active, kv)
        rows.append(np.asarray(logits[0]))
    del kv
    # rows[i] predicts position len(prompt) + i
    return seq, np.stack(rows)


def serve_phase(sz: Sizes, n_dev: int, watch: CompileWatch,
                rehearse: bool) -> dict:
    import jax
    import numpy as np

    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.inference.sampling import SamplingParams
    from deepspeed_tpu.models import get_preset
    from deepspeed_tpu.models.transformer import forward, init_params
    from deepspeed_tpu.ops.pallas import record_dispatch
    from deepspeed_tpu.parallel.topology import initialize_mesh

    t_phase = time.perf_counter()
    m_phase = watch.mark()
    cfg = get_preset(sz.preset, num_layers=sz.serve_layers, **sz.overrides)
    ref_cfg = cfg.replace(attn_impl="reference")
    ref_fn = jax.jit(lambda p, t: forward(p, t, ref_cfg)[0][0])
    say(f"== serve: {sz.preset} widths, {sz.serve_layers} of "
        f"{get_preset(sz.preset).num_layers} layers "
        f"({cfg.param_count / 1e6:.0f} M params, bf16), block {sz.block}, "
        f"prefill_chunk {sz.chunk}, one prefill bucket ({sz.chunk},) — every "
        f"pack is one chunk, so one flash and one packed-ctx shape compile — "
        f"prefix caching on, {n_dev} chip(s) ==")
    params = jax.jit(
        lambda key: init_params(key, cfg, dtype=cfg.dtype)
    )(jax.random.PRNGKey(SEED + 1))
    grid = initialize_mesh(model=n_dev) if n_dev > 1 else None
    with record_dispatch() as log:
        eng = InferenceEngineV2(
            params, cfg, max_seqs=sz.max_seqs, num_blocks=sz.num_blocks,
            block_size=sz.block, max_seq_len=sz.serve_max_len,
            prefill_buckets=(sz.chunk,), prefill_chunk=sz.chunk,
            enable_prefix_caching=True, telemetry=True, grid=grid, seed=SEED,
        )
        del params  # the engine holds the (sharded) tree now

        # -- requests ------------------------------------------------------
        rng = np.random.default_rng(SEED + 2)
        draw = lambda n: rng.integers(0, cfg.vocab_size, n).tolist()
        prefix = draw(sz.prefix_len)
        shorts = [draw(n) for n in sz.short_lens]
        longs = [draw(n) for n in sz.long_lens]
        shared = [prefix + draw(n) for n in sz.suffix_lens]
        prompts: Dict[int, List[int]] = {}
        for uid, p in enumerate(shorts + longs + shared):
            prompts[uid] = p
        new = {uid: sz.new_tokens[uid % len(sz.new_tokens)] for uid in prompts}
        first_shared = len(shorts) + len(longs)
        wave1 = list(range(first_shared + 1))
        wave2 = list(range(first_shared + 1, len(prompts)))

        # -- logits through the cache, one short and one long prompt -------
        logit_rows = {}
        pad_to = -(-(max(map(len, prompts.values())) + max(new.values()))
                   // 128) * 128
        for name, p in (("short", shorts[0]), ("long", longs[0])):
            seq, got = _runner_logits(jax, eng, cfg, sz, p)
            ref = _reference_logits(ref_fn, eng.params, seq, pad_to)
            ref = ref[len(p) - 1: len(p) + sz.decode_checked]
            check(np.all(np.isfinite(got)), f"non-finite {name} logits")
            d = np.abs(got - ref)
            logit_rows[name] = {
                "prompt": len(p), "steps": int(got.shape[0]),
                "max_abs": float(d.max()), "mean_abs": float(d.mean()),
                "ref_std": float(ref.std()),
            }
            say(f"    logits vs reference forward, {name} prompt "
                f"({len(p)} tokens, prefill + {sz.decode_checked} decode "
                f"steps): max|delta| {d.max():.4f}, mean|delta| "
                f"{d.mean():.4f} (tolerances {LOGIT_TOL_MAX} / "
                f"{LOGIT_TOL_MEAN}; reference std {ref.std():.2f})")
            check(d.max() <= LOGIT_TOL_MAX and d.mean() <= LOGIT_TOL_MEAN,
                  f"{name}-prompt logits disagree with the reference: "
                  f"max {d.max()}, mean {d.mean()}")

        # -- the scheduler, as a user drives it ----------------------------
        sched = eng.scheduler
        t_serve = time.perf_counter()
        for wave in (wave1, wave2):
            # wave 2 shares wave 1's last prompt's prefix and is submitted
            # only after that request FINISHED: its pages are in the cache
            for uid in wave:
                r = sched.try_submit(uid, prompts[uid], SamplingParams(
                    temperature=0.0, max_new_tokens=new[uid]))
                check(r.accepted, f"request {uid} rejected: {r.reason} "
                      f"{r.detail}")
            sched.run(wait_for=wave)
        serve_s = time.perf_counter() - t_serve
        states = {u: sched.requests[u].state for u in prompts}
        errors = {u: sched.requests[u].error for u in prompts
                  if sched.requests[u].error}
        sig = sched.signals()
        outs = {u: sched.pop_result(u) for u in prompts}
        stats = {k: int(eng.stats[k]) for k in (
            "failed", "timed_out", "cancelled", "retries", "nan_failures",
            "isolation_probes", "prefill_dispatches", "decode_ticks",
            "prefill_tokens_dispatched")}
        ref_params = eng.params
        mem = device_memory(jax)
        audit = eng.close()

    # -- greedy tokens vs the reference arg-max, where the margin allows ---
    checked = skipped = 0
    for uid, p in prompts.items():
        full = p + outs[uid]
        ref = _reference_logits(ref_fn, ref_params, full, pad_to)
        ref = ref[len(p) - 1: len(full) - 1]
        top2 = np.partition(ref, -2, axis=-1)[:, -2:]
        margin = top2[:, 1] - top2[:, 0]
        for i, tok in enumerate(outs[uid]):
            if margin[i] > MARGIN_TOL:
                checked += 1
                check(int(np.argmax(ref[i])) == tok,
                      f"request {uid} token {i}: engine {tok}, reference "
                      f"{int(np.argmax(ref[i]))} at margin {margin[i]:.3f}")
            else:
                skipped += 1
    del ref_params

    say(f"    {len(prompts)} requests: states "
        f"{sorted(set(states.values()))}; tokens out "
        f"{sum(map(len, outs.values()))}; serve loop {serve_s:.1f} s "
        "(cold compile included)")
    say(f"    prefill dispatches {stats['prefill_dispatches']} "
        f"({stats['prefill_tokens_dispatched']} prompt tokens), decode ticks "
        f"{stats['decode_ticks']}, prefix-cache hit tokens "
        f"{sig['cached_prompt_tokens']} of {sig['prompt_tokens_total']}")
    say(f"    failed {stats['failed']}, timed_out {stats['timed_out']}, "
        f"retries {stats['retries']}, isolation probes "
        f"{stats['isolation_probes']}, nan {stats['nan_failures']}; "
        f"close() audit {audit}")
    say(f"    greedy tokens equal to the reference arg-max at all {checked} "
        f"positions with margin > {MARGIN_TOL} ({skipped} closer calls "
        "skipped); a second run — one chip or four — is held to the same "
        "reference, so the runs agree with each other there")
    digest = {u: zlib.crc32(np.asarray(t, np.int32).tobytes())
              for u, t in outs.items()}
    say("    token digests: " + " ".join(f"{u}:{d:08x}"
                                         for u, d in sorted(digest.items())))
    ran, declined = kernel_report(
        log, ("flash_fwd", "packed_ctx", "paged_decode"), rehearse)
    say_memory(mem, " (high-water mark of the process, train phase and "
               "the smoke's unsharded seeded init included)")

    check(all(s == "finished" for s in states.values()),
          f"terminal states: {states} errors: {errors}")
    check(all(len(outs[u]) == new[u] for u in prompts),
          f"token counts: { {u: len(t) for u, t in outs.items()} } vs {new}")
    for k in ("failed", "timed_out", "cancelled", "retries", "nan_failures",
              "isolation_probes"):
        check(stats[k] == 0, f"{k} = {stats[k]}")
    check(audit["blocks_in_use"] == 0, f"leaked blocks: {audit}")
    check(sig["cached_prompt_tokens"] >= len(wave2) * (
        sz.prefix_len - sz.block), f"prefix cache did not hit: {sig}")
    check(checked > 0, "no position had a margin wide enough to compare")
    if not rehearse:
        check_memory(mem, "weights + KV pool")
        # heads split over the chips: each kernel saw its share
        hq_l = cfg.num_heads // n_dev
        check(all(s[-2] == hq_l for k in ("flash_fwd", "packed_ctx",
                                          "paged_decode") for s in ran[k]),
              f"kernels did not run on per-chip head slices: {ran}")

    compiles, compile_s = watch.since(m_phase)
    wall = time.perf_counter() - t_phase
    say(f"    phase: {wall:.1f} s wall, of which XLA/Mosaic compile (or "
        f"cache load) {compile_s:.1f} s in {len(compiles)} compilations "
        "(the rest is mostly Python tracing and Mosaic lowering of the "
        f"unrolled layers); slowest: "
        + ", ".join(f"{n} {s:.0f}s" for n, s in
                    sorted(compiles, key=lambda c: -c[1])[:4]))
    return {
        "layers": sz.serve_layers, "params": cfg.param_count,
        "requests": len(prompts), "states": sorted(set(states.values())),
        "tokens_out": sum(map(len, outs.values())), "stats": stats,
        "prefix_hit_tokens": sig["cached_prompt_tokens"],
        "close_audit": audit, "logits": logit_rows,
        "tokens_checked": checked, "tokens_skipped_low_margin": skipped,
        "token_digests": digest, "tokens": outs, "kernels": ran,
        "declined": declined,
        "memory": mem, "wall_s": wall, "compile_s": compile_s,
    }


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU pre-flight: tiny preset, JAX_PLATFORMS=cpu, "
                    "kernels under set_interpret(True); never a pass")
    ap.add_argument("--chips", type=int, default=None,
                    help="with --rehearse: number of virtual CPU devices")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        # CPU executables are not the subject; keep them out of the cache
        os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.chips or 1}")
    elif args.chips is not None:
        ap.error("--chips only applies to --rehearse")

    t_start = time.perf_counter()
    import jax
    import jaxlib

    from deepspeed_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — reporting only
        libtpu = "not installed"
    devs = jax.devices()
    dev = devs[0]
    n_dev = len(devs)
    say(f"chip_smoke: jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
        f"libtpu {libtpu}, python {sys.version.split()[0]}")
    say(f"chip_smoke: platform {dev.platform}, device_kind "
        f"{dev.device_kind}, devices {n_dev}, compile cache {cache_dir}")
    if args.rehearse:
        say("chip_smoke: REHEARSAL on the CPU at toy size — this is a "
            "pre-flight of the control flow and can never be read as a pass")
    elif dev.platform != "tpu":
        print(f"chip_smoke: JAX reports platform {dev.platform!r} "
              f"({dev.device_kind}), not a TPU; there is no CPU fallback. "
              "Run it on the chip (chiprun -- python chip_smoke.py), or "
              "pass --rehearse for the CPU pre-flight.", file=sys.stderr)
        return 2
    if n_dev not in (1, 4):
        print(f"chip_smoke: {n_dev} devices; the smoke is sized for one "
              "chip or one four-chip host", file=sys.stderr)
        return 2

    sz = REHEARSAL if args.rehearse else FULL
    if args.rehearse:
        from deepspeed_tpu.ops.pallas import (ctx_attention, flash_kernel,
                                              paged_attention)

        for mod in (ctx_attention, flash_kernel, paged_attention):
            mod.set_interpret(True)

    from deepspeed_tpu.models import get_preset

    watch = CompileWatch()
    watch.install()
    report = {
        "parity": parity_phase(
            sz, get_preset(sz.preset, **sz.overrides), args.rehearse),
        "train": train_phase(sz, n_dev, watch, args.rehearse),
        "serve": serve_phase(sz, n_dev, watch, args.rehearse),
    }
    total = time.perf_counter() - t_start
    say(f"== all phases passed in {total:.0f} s; persistent-cache hits "
        f"{watch.cache_hits}, misses {watch.cache_misses} ==")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_dev}
    full = {
        "device": device, "rehearsal": args.rehearse,
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu},
        "compile_cache": {"dir": cache_dir, "hits": watch.cache_hits,
                          "misses": watch.cache_misses},
        "total_s": round(total, 1), "phases": report,
    }
    say("chip_smoke report: " + json.dumps(full, default=str))
    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": True, "device": device}),
              flush=True)
        return 3
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", f"chip_smoke_{n_dev}chip.json"),
              "w") as f:
        json.dump(full, f, indent=1, default=str)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
