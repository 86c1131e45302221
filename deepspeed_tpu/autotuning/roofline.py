"""Roofline cost model: the static half of the autotuner.

Predicts, per candidate, the dominant resource terms of one training step
or one serving decode tick from first principles — HBM weight-stream
bytes, wire bytes per collective (the ``comm/qcomm.wire_bytes``
accounting the quantized-collective layer's telemetry uses), and model
FLOPs — and checks memory/structural feasibility so the search never
compiles a candidate the hardware cannot run.  The
prediction is a *ranking and pruning* signal: knobs with no roofline
coordinate (``kv_watermark``, ``prefill_chunk``) rank flat here and are
differentiated by the measured trials instead.

The constants (:class:`RooflineConstants`) are ANALYTIC DEFAULTS, not
measurements: v5e datasheet numbers derated to sustained fractions, good
for ordering candidates and nothing else.  The measured side lives with the
benchmark (``benchmark/peaks.py`` for the device's peaks, ``benchmark/
costs*.py`` for what a program needs); no rate in this module comes from a
chip run.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

# bytes one weight element costs on the wire/HBM per serving quant format
_WEIGHT_BYTES = {None: 2.0, "none": 2.0, "bf16": 2.0,
                 "int8": 1.0, "fp8": 1.0, "fp6": 0.75}
# recompute overhead multipliers on the backward pass (coarse: full remat
# re-runs the forward, selective re-runs the MLP intermediates)
_REMAT_FLOPS = {"none": 1.0, "selective": 1.15, "full": 4.0 / 3.0}


@dataclass(frozen=True)
class RooflineConstants:
    """Achievable (not peak) rates the cost terms divide by."""

    compute_flops: float = 100e12     # sustained bf16 FLOP/s (v5e ~0.5 MFU)
    hbm_gbps: float = 700.0           # sustained HBM stream GB/s (819 peak)
    ici_gbps: float = 40.0            # interconnect GB/s per device
    hbm_bytes: float = 16e9           # HBM capacity
    host_tick_s: float = 200e-6       # per-dispatch host overhead
    ici_hop_s: float = 1e-6           # per-collective-permute hop latency


# ---------------------------------------------------------------------------
# model-shape helpers
# ---------------------------------------------------------------------------
def _flops_per_token(model_cfg) -> float:
    n = float(model_cfg.param_count)
    # 6N forward+backward for training callers; serving callers use 2N
    return 6.0 * n


def weight_stream_bytes(model_cfg, quant) -> float:
    """HBM bytes one full forward must stream for the weights (the decode
    roofline term — decode matmuls are weight-bound)."""
    per = _WEIGHT_BYTES.get(quant, 2.0)
    scale_overhead = 0.0 if quant in (None, "none", "bf16") else 0.02
    return float(model_cfg.param_count) * (per + scale_overhead * 4)


def kv_pool_bytes(model_cfg, num_blocks: int, block_size: int) -> float:
    import jax.numpy as jnp

    el = jnp.dtype(model_cfg.dtype).itemsize
    return (2.0 * model_cfg.num_layers * num_blocks * block_size
            * model_cfg.num_kv_heads * model_cfg.hd * el)


# ---------------------------------------------------------------------------
# serving: feasibility + predicted tick cost
# ---------------------------------------------------------------------------
def serving_feasible(cand: Dict[str, Any], model_cfg, base: Dict[str, Any],
                     n_devices: int,
                     consts: Optional[RooflineConstants] = None,
                     ) -> Tuple[bool, str]:
    """Mirror of the engine's own constructor rejections + the memory
    model, evaluated WITHOUT building anything.  ``base`` carries the
    non-searched engine shape (max_seqs, num_blocks, block_size, ...).
    Returns ``(ok, reason)`` — reasons become leaderboard verdicts."""
    tp = int(cand.get("tp", 1))
    dp = int(cand.get("serve_replicas", 1))
    sq = int(cand.get("seq_shards", 1) or 1)
    if tp < 1 or dp < 1 or sq < 1:
        return False, "structural: tp/serve_replicas/seq_shards must be >= 1"
    if tp * dp * sq > n_devices:
        return False, (f"structural: tp*replicas*seq_shards {tp * dp * sq} "
                       f"exceeds {n_devices} devices")
    if model_cfg.num_heads % tp:
        return False, (f"structural: num_heads {model_cfg.num_heads} "
                       f"not divisible by tp {tp}")
    if dp > 1:
        # prefix caching / chunked prefill / speculation are replica-affine
        # now (per-replica cache namespaces + replica-local ctx packs) —
        # the old engine gate is gone, so the serve_replicas x
        # {prefix_caching, prefill_chunk, spec} region of the grid is
        # feasible and searchable; only the structural pool split remains
        if base.get("max_seqs", 0) % dp or base.get("num_blocks", 0) % dp:
            return False, "structural: max_seqs/num_blocks must divide replicas"
    if sq > 1 and base.get("num_blocks", 0) % (dp * sq):
        # the engine's own bring-up gate: each replica's pool must split
        # into sq equal contiguous stripes (seq-axis device slices)
        return False, ("structural: num_blocks must divide "
                       "replicas x seq_shards")
    if cand.get("quant_comm", "none") != "none" and tp <= 1:
        return False, "structural: quant_comm needs a TP mesh"
    megastep = cand.get("decode_megastep", 1)
    if megastep is not None and int(megastep) < 1:
        return False, "structural: decode_megastep must be >= 1"
    consts = consts or RooflineConstants()
    need = (weight_stream_bytes(model_cfg, cand.get("quant")) / tp
            + kv_pool_bytes(model_cfg, base.get("num_blocks", 0),
                            base.get("block_size", 32)) / max(dp * sq, 1)
            + 0.05 * consts.hbm_bytes)  # activation/jit slack
    if need > consts.hbm_bytes:
        return False, (f"memory: est {need / 1e9:.2f} GB per device > "
                       f"HBM {consts.hbm_bytes / 1e9:.1f} GB")
    return True, "ok"


def predict_serve_cost(cand: Dict[str, Any], model_cfg,
                       base: Dict[str, Any],
                       consts: Optional[RooflineConstants] = None) -> float:
    """Predicted seconds per *emitted token* of one decode tick (lower is
    better): weight-stream HBM time + collective wire time (the shared
    ``comm/budget`` tick plan — row-parallel transports at the candidate's
    format plus GSPMD's format-independent overhead, the same enumeration
    the engine accounts and the Graft Auditor verifies against compiled
    HLO) + host dispatch, divided by the tick's emitted tokens (batch x
    speculative amortization)."""
    from ..comm.budget import plan_bytes, serving_tick_plan

    consts = consts or RooflineConstants()
    tp = max(int(cand.get("tp", 1)), 1)
    dp = max(int(cand.get("serve_replicas", 1)), 1)
    sq = max(int(cand.get("seq_shards", 1) or 1), 1)
    B = max(int(base.get("max_seqs", 1)), 1)
    t = weight_stream_bytes(model_cfg, cand.get("quant")) / tp \
        / (consts.hbm_gbps * 1e9)
    # KV-read roofline (the term seq sharding actually moves): a decode
    # tick streams the live context KV, bounded by one device's pool slice
    # — splitting the pool over dp x sq slices multiplies the effective
    # KV-streaming bandwidth per token by the slice count.  Callers that
    # pass no ``num_blocks`` in ``base`` (format-ordering comparisons)
    # charge nothing here, as before.
    kv_read = kv_pool_bytes(model_cfg, base.get("num_blocks", 0),
                            base.get("block_size", 32)) / (dp * sq) \
        / (consts.hbm_gbps * 1e9)
    t += kv_read
    # prefill/verify attention KV traffic (the packed-ctx kernel's own
    # roofline: pages touched x bytes/page at the pool's element format,
    # which is what kv_pool_bytes already encodes).  A spec-verify tick
    # re-streams each live sequence's cached context pages through the
    # ctx-attention kernel ON TOP of the decode read above, and chunked
    # prefill co-scheduled with decode touches roughly half the live pool
    # per tick — without these terms long-context spec/chunked candidates
    # rank as if verify attention were free.
    if cand.get("spec"):
        t += kv_read
    if cand.get("prefill_chunk"):
        t += 0.5 * kv_read
    if tp > 1 or sq > 1:
        plan = serving_tick_plan(
            model_cfg, B, tp, cand.get("quant_comm", "none"),
            sample_rows=B, compute_itemsize=2, seq_shards=sq, replicas=dp,
        )
        t += plan_bytes(plan) / (consts.ici_gbps * 1e9)
        # the ring's cost at decode widths is hop LATENCY, not bytes: S-1
        # nearest-neighbour permutes per layer sit on the critical path
        t += (sq - 1) * model_cfg.num_layers * consts.ici_hop_s
    # megastep fuses n decode ticks into ONE device burst (one host sync),
    # amortizing the host dispatch across the fused ticks; the device time
    # per tick is unchanged.  _canon_serving pins megastep to 1 under spec
    # (the scheduler collapses it there), so no interaction term is needed.
    t += consts.host_tick_s / max(int(cand.get("decode_megastep", 1) or 1), 1)
    emitted = float(B)
    if cand.get("spec"):
        # prompt-lookup acceptance on mixed workloads lands ~0.3; each
        # verify tick emits accepted + 1 per sequence
        emitted *= 1.0 + 0.3 * float(cand.get("spec_max_draft", 0) or 0)
    return t / emitted


# ---------------------------------------------------------------------------
# training: feasibility + predicted step cost
# ---------------------------------------------------------------------------
def train_memory_bytes(cand: Dict[str, Any], model_cfg, seq_len: int) -> int:
    """Per-device state + activation estimate (the model-info pruning pass
    carried over from the pre-rewrite autotuner)."""
    n_params = float(model_cfg.param_count)
    mesh = cand.get("mesh") or {}
    shard = max(int(mesh.get("fsdp", 1)), 1)
    stage = int(cand.get("zero_stage", 0))
    micro = int(cand.get("micro_batch", 1))
    remat = cand.get("remat", "none")
    state = n_params * 4 * 3 / (shard if stage >= 1 else 1)
    compute = n_params * 2 / (shard if stage >= 3 else 1)
    d = model_cfg.hidden_size
    L = model_cfg.num_layers
    f = model_cfg.intermediate_size
    v = model_cfg.vocab_size
    tok = micro * seq_len
    act_per_layer = {
        "none": tok * (2 * f + 6 * d) * 2,
        "selective": tok * 5 * d * 2,
        "full": tok * d * 2,
    }.get(remat, tok * 5 * d * 2)
    acts = L * act_per_layer + tok * v * 6  # + fp32 logits fwd/bwd
    return int(state + compute + acts)


def training_feasible(cand: Dict[str, Any], model_cfg, seq_len: int,
                      n_devices: int,
                      consts: Optional[RooflineConstants] = None,
                      hbm_bytes: Optional[float] = None,
                      ) -> Tuple[bool, str]:
    mesh = cand.get("mesh") or {}
    extent = 1
    for v in mesh.values():
        extent *= max(int(v), 1)
    if extent > n_devices or (extent and n_devices % extent):
        return False, (f"structural: mesh extent {extent} does not divide "
                       f"{n_devices} devices")
    cap = hbm_bytes if hbm_bytes is not None \
        else (consts.hbm_bytes if consts else None)
    if cap:
        est = train_memory_bytes(cand, model_cfg, seq_len)
        if est > cap:
            return False, (f"memory: est {est / 1e9:.2f} GB > "
                           f"HBM {cap / 1e9:.1f} GB")
    return True, "ok"


def predict_train_cost(cand: Dict[str, Any], model_cfg, seq_len: int,
                       consts: Optional[RooflineConstants] = None) -> float:
    """Predicted seconds per trained token (lower is better): compute with
    the remat recompute factor + the ZeRO-3 gather/reduce wire time at the
    candidate's fsdp extent (the shared ``comm/budget.zero3_step_plan``;
    int8 when ZeRO++ qwZ/qgZ is on)."""
    from ..comm.budget import plan_bytes, zero3_step_plan

    consts = consts or RooflineConstants()
    mesh = cand.get("mesh") or {}
    fsdp = max(int(mesh.get("fsdp", 1)), 1)
    micro = max(int(cand.get("micro_batch", 1)), 1)
    tokens = micro * seq_len
    t = tokens * _flops_per_token(model_cfg) \
        * _REMAT_FLOPS.get(cand.get("remat", "none"), 1.0) \
        / consts.compute_flops
    if int(cand.get("zero_stage", 0)) >= 3 and fsdp > 1:
        fmt = "int8" if cand.get("zero_quant") else "none"
        wire = plan_bytes(zero3_step_plan(
            int(model_cfg.param_count), fsdp, fmt))
        t += wire / (consts.ici_gbps * 1e9)
    t += consts.host_tick_s
    # tiny per-micro-batch penalty so under equal rates smaller dispatch
    # counts (bigger micro) rank first, matching the measured r3 trend
    return t / tokens
