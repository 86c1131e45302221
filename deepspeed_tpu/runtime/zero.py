"""ZeRO on TPU: partitioning expressed as sharding plans, not runtime hooks.

The reference implements ZeRO with imperative machinery — per-param grad hooks
and bucketed reduce-scatter (``runtime/zero/stage_1_and_2.py:97
DeepSpeedZeroOptimizer``), fetch/release param hooks + a trace-driven
prefetcher for stage 3 (``runtime/zero/stage3.py:112``,
``partitioned_param_coordinator.py:63``).  On TPU the same memory savings fall
out of *where arrays live on the mesh*:

- **Stage 1/2** (optimizer-state + gradient partitioning): fp32 master params
  and optimizer state are sharded along the ``fsdp`` axis.  The jitted step
  computes bf16 grads, XLA reduce-scatters them to the master sharding,
  applies the sharded update, and all-gathers bf16 params for the next
  forward — the classic weight-update-sharding schedule, generated by GSPMD
  from sharding constraints alone.  (Stage 1 vs 2 is a torch-memory
  distinction with no analogue here: grads are consumed fused into the
  reduce-scatter, i.e. stage-2 behaviour "for free".)
- **Stage 3** (parameter partitioning): the bf16 compute params are *also*
  sharded along ``fsdp``; XLA inserts just-in-time all-gathers per layer
  (the fetch hooks' job) and frees gathered copies after use (release).
  Prefetch-ahead = XLA's latency-hiding scheduler.  The reference's
  ``stage3_param_persistence_threshold`` (small params stay resident) maps to
  "small params stay replicated".
- **Offload**: optimizer state / master params can live in host memory
  (``memory_kind='pinned_host'``) — the analogue of
  ``offload_optimizer/offload_param`` (runtime/zero/offload_config.py).

This module is pure host-side planning: no collective is issued here.
"""
from __future__ import annotations

import contextlib
import re
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..config.config import ZeroConfig
from ..parallel.topology import FSDP_AXIS, SUB_AXIS, MeshSpec

PathRules = List[Tuple[str, P]]  # (regex over 'a/b/c' param path, base spec)


def path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def match_rules(path: str, shape: Tuple[int, ...], rules: Optional[PathRules]) -> P:
    """First-match regex rules -> base (tensor-parallel) PartitionSpec.

    This replaces the reference's AutoTP module surgery
    (``module_inject/auto_tp.py:193``): instead of swapping nn.Linear for
    LinearAllreduce/LinearLayer, a rule maps a param path to a spec that puts
    its row or column dimension on the ``model`` axis.
    """
    if rules:
        for pattern, spec in rules:
            if re.search(pattern, path):
                spec_t = tuple(spec) + (None,) * (len(shape) - len(tuple(spec)))
                return P(*spec_t[: len(shape)])
    return P(*([None] * len(shape)))


def _add_axis(spec: P, shape: Tuple[int, ...], axis_name, axis_size: int) -> P:
    """Shard the largest free, divisible dimension of ``shape`` on
    ``axis_name`` (a name or tuple of names whose product is ``axis_size``);
    return the spec unchanged if no dimension qualifies."""
    entries = list(tuple(spec) + (None,) * (len(shape) - len(tuple(spec))))
    best, best_dim = -1, -1
    for i, d in enumerate(shape):
        if entries[i] is None and d % axis_size == 0 and d > best:
            best, best_dim = d, i
    if best_dim < 0:
        return P(*entries)
    entries[best_dim] = axis_name
    return P(*entries)


@dataclass
class ShardingPlan:
    """Per-param sharding decisions for one model.

    ``param_specs``  — compute (bf16) params used in fwd/bwd
    ``master_specs`` — fp32 master params + optimizer state
    Both are pytrees of ``PartitionSpec`` matching the params pytree.
    """

    param_specs: Any
    master_specs: Any
    zero: ZeroConfig
    shape_tree: Any = None

    def named(self, mesh, specs, memory_kind: Optional[str] = None):
        def mk(spec):
            if memory_kind is not None:
                try:
                    return NamedSharding(mesh, spec, memory_kind=memory_kind)
                except Exception:
                    pass
            return NamedSharding(mesh, spec)

        return jax.tree_util.tree_map(mk, specs)

    def param_shardings(self, mesh):
        return self.named(mesh, self.param_specs)

    def _shape_at(self, path: str):
        if not hasattr(self, "_shape_by_path"):
            self._shape_by_path = {
                path_str(kp): tuple(leaf.shape)
                for kp, leaf in jax.tree_util.tree_flatten_with_path(self.shape_tree)[0]
            }
        return self._shape_by_path.get(path, ())

    @property
    def wants_cpu_offload(self) -> bool:
        """Master/opt state belongs in host memory (the ZeRO-Offload CPU tier;
        offload_param also routes here because fp32 masters are this design's
        single persistent parameter copy)."""
        return "cpu" in (self.zero.offload_optimizer, self.zero.offload_param)

    def master_shardings(self, mesh, allow_offload: bool = False):
        kind = "pinned_host" if (allow_offload and self.wants_cpu_offload) else None
        return self.named(mesh, self.master_specs, memory_kind=kind)

    def opt_state_shardings(self, mesh, opt_state_shapes, allow_offload: bool = False):
        """Shardings for an optax opt_state pytree.

        Optimizer-state leaves that mirror parameters (Adam mu/nu etc.) live
        in param-tree-shaped subtrees, so their tree paths END with the
        parameter's path — match by path suffix first (two same-shaped params
        with different TP specs must not share a layout), then by shape as a
        fallback, else replicate (step counters, scalars)."""
        by_path = {}
        by_shape = {}
        for (kp, leaf), spec in zip(
            jax.tree_util.tree_flatten_with_path(self.shape_tree)[0],
            jax.tree_util.tree_leaves(
                self.master_specs, is_leaf=lambda x: isinstance(x, P)
            ),
        ):
            by_path[path_str(kp)] = spec
            by_shape.setdefault(tuple(leaf.shape), spec)
        kind = "pinned_host" if (allow_offload and self.wants_cpu_offload) else None

        def spec_for(kp, leaf):
            # longest param-path suffix of this opt-state path wins
            shape = tuple(getattr(leaf, "shape", ()))
            for cut in range(len(kp)):
                cand = path_str(kp[cut:])
                spec = by_path.get(cand)
                if spec is not None and tuple(
                    self._shape_at(cand)
                ) == shape:
                    return spec
            return by_shape.get(shape, P())

        def mk(kp, leaf):
            spec = spec_for(kp, leaf)
            if kind is not None:
                try:
                    return NamedSharding(mesh, spec, memory_kind=kind)
                except Exception:
                    pass
            return NamedSharding(mesh, spec)

        return jax.tree_util.tree_map_with_path(mk, opt_state_shapes)


def plan_sharding(
    params_shapes: Any,
    zero: ZeroConfig,
    spec: MeshSpec,
    tp_rules: Optional[PathRules] = None,
) -> ShardingPlan:
    """Build the ZeRO sharding plan for a params pytree of
    ``jax.ShapeDtypeStruct`` (or arrays).

    reference parity:
    - stage 0: params/opt replicated over data+fsdp (plain DDP).
    - stage 1/2: master+opt sharded on ``fsdp`` (stage_1_and_2.py's
      partitioning of the flat fp32 buffer; here per-tensor on its largest
      free dim).
    - stage 3: compute params sharded too (partition_parameters.py:1550
      ``_partition_param``), except params smaller than
      ``param_persistence_threshold`` which stay replicated.
    """
    fsdp, sub = spec.fsdp, spec.sub
    fsdp_total = fsdp * sub
    # hpZ (reference utils/groups.py:650 _create_zero_param_parallel_group):
    # compute params keep a SECONDARY partition over the inner ``sub`` group
    # only, so forward/backward gathers ride the node-local ICI neighbourhood
    # while masters/optimizer stay sharded over the full fsdp extent.
    hpz = zero.zero_hpz_partition_size > 1
    # MiCS (reference runtime/zero/mics.py:64): EVERYTHING shards within the
    # ``sub`` group and replicates across groups — gathers and reduce-
    # scatters never leave the group; cross-group traffic is only the grad
    # psum implied by replication.
    mics = zero.mics_shard_size > 1

    if hpz and mics:
        raise ValueError("zero_hpz_partition_size and mics_shard_size are exclusive")
    if (hpz or mics) and sub == 1:
        # never accept-and-ignore (the r2 defect): an explicit mesh must
        # carry the sub axis these features shard on
        raise ValueError(
            "zero_hpz_partition_size/mics_shard_size require a mesh with a "
            "'sub' axis of that size — pass initialize_mesh(..., sub=G) or "
            "omit mesh= and let initialize() factor the fsdp axis"
        )

    full_extent = (FSDP_AXIS, SUB_AXIS) if sub > 1 else FSDP_AXIS

    def param_spec(path, leaf):
        shape = tuple(leaf.shape)
        size = int(np.prod(shape)) if shape else 1
        base = match_rules(path_str(path), shape, tp_rules)
        if zero.stage >= 3 and fsdp_total > 1 and size >= zero.param_persistence_threshold:
            if hpz or mics:
                return _add_axis(base, shape, SUB_AXIS, sub)
            return _add_axis(base, shape, full_extent, fsdp_total)
        return base

    def master_spec(path, leaf):
        shape = tuple(leaf.shape)
        base = match_rules(path_str(path), shape, tp_rules)
        if zero.stage >= 1 and fsdp_total > 1:
            if mics:
                return _add_axis(base, shape, SUB_AXIS, sub)
            return _add_axis(base, shape, full_extent, fsdp_total)
        return base

    return ShardingPlan(
        param_specs=jax.tree_util.tree_map_with_path(param_spec, params_shapes),
        master_specs=jax.tree_util.tree_map_with_path(master_spec, params_shapes),
        zero=zero,
        shape_tree=params_shapes,
    )


def constrain(tree, specs, scope: Optional[str] = None):
    """Apply sharding constraints leaf-wise inside jit.  ``scope`` names the
    constraint in the HLO's ``op_name`` (``zero/gather`` where the sharded
    masters become compute parameters, ``zero/reduce`` where gradients return
    to the masters' layout): it is where the partitioner hangs the step's
    collectives."""
    with jax.named_scope(scope) if scope else contextlib.nullcontext():
        return jax.tree_util.tree_map(
            lambda x, s: jax.lax.with_sharding_constraint(x, s), tree, specs
        )


def init_sharded_params(init_fn: Callable, rng, plan: ShardingPlan, mesh):
    """``zero.Init`` analogue (reference runtime/zero/partition_parameters.py:824
    ``Init`` — partition-at-construction so a model larger than one host's
    RAM can be built at all).

    The reference patches ``nn.Module.__init__`` to partition each tensor as
    it is created.  Here the same guarantee is one jit: ``init_fn(rng)`` is
    compiled with the plan's **master shardings** as ``out_shardings``, so
    XLA materializes every parameter directly into its fsdp/TP shard — the
    full tree never exists on any single device or in host RAM.

    Returns the sharded params pytree (fp32 leaves as produced by init_fn).
    """
    shardings = plan.master_shardings(mesh)
    return jax.jit(init_fn, out_shardings=shardings)(rng)


class Init:
    """Reference-shaped ``zero.Init`` context.

    Because params here are pure pytrees (no nn.Module constructor to
    patch), the context simply carries the plan and exposes
    ``materialize(init_fn, rng)``::

        with deepspeed_tpu.runtime.zero.Init(config, grid, tp_rules) as zi:
            params = zi.materialize(model.init_params, key)

    ``initialize(model=...)`` does this automatically — the context exists
    for API parity and for custom init flows.
    """

    def __init__(self, config, grid, tp_rules=None):
        from ..config.config import parse_config

        cfg = parse_config(config) if not hasattr(config, "zero_optimization") else config
        self.grid = grid
        self.zero = cfg.zero_optimization
        self.tp_rules = tp_rules
        self._plan: Optional[ShardingPlan] = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def materialize(self, init_fn: Callable, rng):
        shapes = jax.eval_shape(init_fn, rng)
        self._plan = plan_sharding(shapes, self.zero, self.grid.spec, self.tp_rules)
        return init_sharded_params(init_fn, rng, self._plan, self.grid.mesh)
