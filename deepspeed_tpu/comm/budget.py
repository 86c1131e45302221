"""Shared collective-enumeration for one serving dispatch.

One place owns the knowledge of WHICH collectives a TP serving dispatch
issues and at what shapes — previously duplicated (and drifting) between
``engine_v2._account_comm`` (telemetry wire bytes) and ``autotuning.
roofline.predict_serve_cost`` (the cost model's wire term).  The Graft
Auditor's ``collective_budget`` checker compares the
compiled program's enumerated collectives against exactly this plan, so a
drift between the analytic model and what XLA actually emits fails a test
instead of silently mis-reporting.

A plan is a list of :class:`PlannedCollective`; bytes follow the
``qcomm.wire_bytes`` ring convention.  Two groups per dispatch:

- ``row_psum`` — the per-layer row-parallel partial-sum transports (o +
  down projections), ``[n_tokens, hidden]`` each at the engine's transport
  format.  These are the ONLY format-dependent wires, and the ones the
  ``comm/bytes_on_wire`` counter accounts.
- overhead — format-INDEPENDENT collectives GSPMD inserts around the
  sharded embedding/head and the residual stream: the vocab-sharded
  embedding-gather combine (``[n_tokens, hidden]`` all-reduce), one
  activation all-gather per column-parallel block input (GSPMD keeps the
  residual stream SHARDED on hidden between the row psums, so each
  qkv/up-gate region re-gathers its ``[n_tokens, hidden]`` input — 2 per
  layer), and the pre-head gather of the sampled rows.  Greedy sampling
  itself lowers to per-shard argmax + an O(tp) pair exchange, NOT a
  full-vocab gather — byte-negligible and unplanned.  Accounted
  separately (``comm/bytes_on_wire_overhead``) so the A/B delta semantics
  of the transport counter survive the reconciliation.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

from . import qcomm


@dataclass(frozen=True)
class PlannedCollective:
    """``count`` identical collectives of one dispatch."""

    op: str  # qcomm op: 'all_reduce' | 'all_gather' | 'reduce_scatter' | 'all_to_all'
    n_elements: int  # full logical tensor elements (qcomm convention)
    fmt: str  # qcomm wire format ('none' | 'int8' | 'fp8')
    world: int
    count: int = 1
    none_bytes_per_el: int = 4
    label: str = ""
    overhead: bool = False  # format-independent GSPMD-inserted wire

    @property
    def bytes_on_wire(self) -> int:
        """Per-device sent bytes for all ``count`` instances."""
        return self.count * qcomm.wire_bytes(
            self.op, self.n_elements, self.fmt, self.world,
            none_bytes_per_el=self.none_bytes_per_el,
        )


def plan_bytes(plan: List[PlannedCollective],
               overhead: Optional[bool] = None) -> int:
    """Total per-device wire bytes of a plan; ``overhead`` filters to the
    transport (False) or GSPMD-overhead (True) subset."""
    return sum(c.bytes_on_wire for c in plan
               if overhead is None or c.overhead == overhead)


def serving_tick_plan(
    cfg,
    n_tokens: int,
    tp: int,
    fmt: str = "none",
    *,
    tiles: int = 1,
    sample_rows: int = 0,
    compute_itemsize: Optional[int] = None,
    seq_shards: int = 1,
    replicas: int = 1,
) -> List[PlannedCollective]:
    """Collectives of ONE serving dispatch (decode tick / packed prefill /
    verify) running ``n_tokens`` activation rows on a ``tp``-way model
    axis.  Empty without TP and without seq sharding.

    - 2 row-parallel transports per layer (o + down), ``n_tokens x hidden``
      at the engine's ``fmt`` (the exact set ``_account_comm`` counts).  With ``tiles`` > 1 each
      projection splits into free-dim tiles reduced independently, and a
      QUANTIZED tile pads to a ``tp * chunk`` multiple before it ships —
      at small widths that padding is real extra wire (the Graft Auditor
      surfaced the tiled int8 plan under-reporting it), so the plan
      models per-tile padded payloads instead of the naive
      ``n_tokens x hidden`` total;
    - 1 embedding-combine all-reduce, ``n_tokens x hidden`` in the compute
      dtype (the vocab-sharded table's gather reduces partial rows);
    - 2 activation all-gathers per layer, ``n_tokens x hidden`` (GSPMD
      keeps the residual stream hidden-sharded between row psums; each
      column-parallel block input re-gathers), plus the pre-head gather
      of the ``sample_rows`` rows actually scored;
    - with ``seq_shards`` (S) > 1, the paged-attention log-sum-exp ring:
      ``S-1`` nearest-neighbour ``collective_permute`` hops per layer, each
      carrying the fp32 ``[rows, heads, head_dim+2]`` flash accumulator at
      its LOCAL shard shape (``rows/replicas`` batch rows, ``heads/tp``
      query heads) — the one transport the seq axis costs, issued from
      ``qcomm.ring_permute`` inside the decode/packed-ctx shard_map.
    """
    if tp <= 1 and seq_shards <= 1:
        return []
    import jax.numpy as jnp

    itemsize = (compute_itemsize if compute_itemsize is not None
                else jnp.dtype(cfg.dtype).itemsize)
    d = cfg.hidden_size
    n_proj = 2 * cfg.num_layers  # o + down per layer, both [n_tokens, d]
    plan: List[PlannedCollective] = []
    if seq_shards > 1:
        hq_local = (cfg.num_heads // tp if tp > 1 and cfg.num_heads % tp == 0
                    else cfg.num_heads)
        rows = -(-n_tokens // max(replicas, 1))
        plan.append(PlannedCollective(
            op="collective_permute",
            n_elements=rows * hq_local * (cfg.hd + 2),
            fmt="none", world=seq_shards,
            count=(seq_shards - 1) * cfg.num_layers,
            none_bytes_per_el=4,  # fp32 accumulator, regardless of cfg dtype
            label="seq_ring",
        ))
    if tp <= 1:
        return plan
    tiles_eff = tiles if (tiles > 1 and d >= tiles) else 1
    if tiles_eff == 1 and fmt == "none":
        plan.append(PlannedCollective(
            op="all_reduce", n_elements=n_tokens * d, fmt=fmt, world=tp,
            count=n_proj, none_bytes_per_el=itemsize, label="row_psum",
        ))
    else:
        # per-tile widths (ceil split of the out dim, trailing remainder)
        tile_n = -(-d // tiles_eff)
        widths: dict = {}
        lo = 0
        while lo < d:
            w_i = min(tile_n, d - lo)
            widths[w_i] = widths.get(w_i, 0) + 1
            lo += tile_n
        for w_i, k in sorted(widths.items()):
            n_el = n_tokens * w_i
            if fmt != "none":
                # qcomm pads each quantized all-reduce to a tp*chunk
                # multiple before the wire hops
                n_el = -(-n_el // (tp * qcomm.DEFAULT_CHUNK)) \
                    * tp * qcomm.DEFAULT_CHUNK
            plan.append(PlannedCollective(
                op="all_reduce", n_elements=n_el, fmt=fmt, world=tp,
                count=n_proj * k, none_bytes_per_el=itemsize,
                label="row_psum",
            ))
    plan.append(PlannedCollective(
        op="all_reduce", n_elements=n_tokens * d, fmt="none", world=tp,
        count=1, none_bytes_per_el=itemsize, label="embed_combine",
        overhead=True,
    ))
    plan.append(PlannedCollective(
        op="all_gather", n_elements=n_tokens * d, fmt="none", world=tp,
        count=2 * cfg.num_layers, none_bytes_per_el=itemsize,
        label="block_input_gather", overhead=True,
    ))
    if sample_rows > 0:
        plan.append(PlannedCollective(
            op="all_gather", n_elements=sample_rows * d, fmt="none",
            world=tp, count=1, none_bytes_per_el=itemsize,
            label="head_input_gather", overhead=True,
        ))
    return plan


def zero3_step_plan(n_params: int, fsdp: int, fmt: str = "none",
                    micro_batches: int = 1,
                    gather_bytes_per_el: int = 2) -> List[PlannedCollective]:
    """Per-micro-step ZeRO-3 wire plan: one parameter all-gather (bf16, or
    int8 under ZeRO++ qwZ) + one gradient reduce-scatter (fp32, or int8
    under qgZ) over the full parameter count — the arithmetic
    ``roofline.predict_train_cost`` uses."""
    if fsdp <= 1:
        return []
    return [
        PlannedCollective(
            op="all_gather", n_elements=n_params, fmt=fmt, world=fsdp,
            count=micro_batches, none_bytes_per_el=gather_bytes_per_el,
            label="param_gather",
        ),
        PlannedCollective(
            op="reduce_scatter", n_elements=n_params, fmt=fmt, world=fsdp,
            count=micro_batches, none_bytes_per_el=4, label="grad_reduce",
        ),
    ]
