"""The training engine: DeepSpeed's ``DeepSpeedEngine`` re-imagined for XLA.

The reference engine (``runtime/engine.py:184 DeepSpeedEngine``, 3,884 LoC)
orchestrates fwd/bwd/step imperatively: grad hooks, bucketed allreduce,
stream juggling, loss scaling, GAS boundaries.  Here the entire training step
— gradient-accumulation loop, mixed precision, ZeRO reduce-scatter /
all-gather, loss scaling, clipping, optimizer update, LR schedule — is one
jit-compiled function over a named mesh; XLA generates the collective
schedule from the ZeRO sharding plan (see ``runtime/zero.py``).

API parity with the reference:

- ``engine(batch)`` / ``engine.forward``  (engine.py:1926)
- ``engine.backward(loss)``               (engine.py:2085)
- ``engine.step()``                       (engine.py:2282)
- ``engine.train_batch(data_iter)``       (pipe/engine.py:338 — offered on the
  base engine too, as the recommended fused path)
- ``engine.eval_batch``, ``engine.save_checkpoint``, ``engine.load_checkpoint``,
  ``engine.global_steps``, ``engine.get_lr``, ``engine.gradient_accumulation_steps()``

The forward/backward/step triple is preserved by a micro-batch staging shim:
``forward`` runs the jitted value-and-grad on the staged micro-batch and
caches gradients, ``backward`` accumulates them into a (ZeRO-sharded) buffer,
``step`` applies the update at the GAS boundary — same user-visible contract
(including ``is_gradient_accumulation_boundary``, engine.py:2166) without
eager autograd.
"""
from __future__ import annotations

import functools
from contextlib import contextmanager
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..comm import comm as dist
from ..config.config import Config, parse_config
from ..ops.optimizers import build_optimizer
from ..parallel.topology import (
    BATCH_AXES,
    DATA_AXIS,
    FSDP_AXIS,
    Grid,
    MeshSpec,
    initialize_mesh,
)
from ..utils.logging import log_dist, logger
from ..utils.timer import (
    BACKWARD_GLOBAL_TIMER,
    FORWARD_GLOBAL_TIMER,
    STEP_GLOBAL_TIMER,
    SynchronizedWallClockTimer,
    ThroughputTimer,
)
from . import precision, zero
from .lr_schedules import LRScheduler, get_lr_schedule_fn
from .prefetch import DevicePrefetcher, MetricsBuffer, host_scalar
from ..telemetry import Telemetry, step_counts, track_program


def _now() -> float:
    import time

    return time.perf_counter()


import atexit
import weakref

# ONE process-wide exit hook draining every live engine's deferred-metrics
# buffer (bare train_batch loops have no end-of-loop hook; without this,
# async-buffered tail metrics — monitor rows, fp16 skip counts past the
# last steps_per_print boundary — would be lost on plain process exit).
# WeakSet: the hook must never keep engines (and their device state) alive,
# and per-instance atexit.register would accumulate one closure per engine
# for the life of the process.
_LIVE_ENGINES: "weakref.WeakSet" = weakref.WeakSet()
_EXIT_HOOK_REGISTERED = False


def _drain_metrics_at_exit():
    for engine in list(_LIVE_ENGINES):
        try:
            engine._flush_step_metrics()
        except Exception:  # noqa: BLE001 — backend may be torn down
            pass
        try:
            # settles deferred spans and writes the Chrome trace file when
            # telemetry.chrome_trace_path is configured
            engine.telemetry.close()
        except Exception:  # noqa: BLE001
            pass


def _register_exit_flush(engine) -> None:
    global _EXIT_HOOK_REGISTERED
    _LIVE_ENGINES.add(engine)
    if not _EXIT_HOOK_REGISTERED:
        _EXIT_HOOK_REGISTERED = True
        atexit.register(_drain_metrics_at_exit)


def _gas_fold(batch, gas: int, micro_global: int):
    """Fold a flat ``[global_batch, ...]`` pytree into ``[gas, micro, ...]``
    if it isn't folded already — the ONE folding rule shared by
    ``train_batch`` and the prefetch placement path.

    ``micro_global`` (= micro_batch * dp) disambiguates the
    ``micro_global == 1`` corner where a flat batch's leading dim also
    equals ``gas``: there a folded batch is recognizable by its size-1
    second axis, while a flat one must still be folded."""
    x = jax.tree_util.tree_leaves(batch)[0]
    already_folded = x.shape[0] == gas and (
        micro_global > 1 or (x.ndim >= 2 and x.shape[1] == 1)
    )
    if already_folded:
        return batch
    return jax.tree_util.tree_map(
        lambda v: v.reshape((gas, v.shape[0] // gas) + v.shape[1:]), batch
    )


class TrainState(NamedTuple):
    """All mutable training state, as one pytree carried through jit."""

    step: jnp.ndarray  # i32 global step
    params: Any  # fp32 master params (ZeRO-sharded per plan)
    opt_state: Any
    loss_scale: precision.LossScaleState


class StepMetrics(NamedTuple):
    loss: jnp.ndarray
    grad_norm: jnp.ndarray
    lr: jnp.ndarray
    loss_scale: jnp.ndarray
    skipped: jnp.ndarray  # bool — fp16 overflow skipped the update
    # what the traced loss counted of itself (telemetry.count_in_step): name ->
    # scalar, summed over the step's micro-batches; None where it counted nothing
    counts: Any = None


class DeepSpeedTpuEngine:
    """Wraps a loss function + params into a sharded, jitted training loop.

    Contract: ``loss_fn(params, batch, rng) -> scalar loss`` — a pure function
    of the *compute-dtype* params.  ``models/`` provides adapters that build
    this from flax modules.
    """

    def __init__(
        self,
        loss_fn: Callable,
        params: Any,
        config: Config,
        grid: Grid,
        tp_rules=None,
        eval_fn: Optional[Callable] = None,
        seed: Optional[int] = None,
        remat_policy: Optional[str] = None,
        trainable_mask: Any = None,
    ):
        self.config = config
        self.grid = grid
        self.mesh = grid.mesh
        self.loss_fn = loss_fn
        self.eval_fn = eval_fn
        self.tp_rules = tp_rules
        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=config.train_batch_size,
            steps_per_output=config.steps_per_print,
        )
        self.monitor = None  # attached by initialize()
        # unified telemetry (telemetry/): a span around train_batch, closed
        # at dispatch; registry snapshot fan-out to the monitor at flush
        # boundaries; near-zero no-ops unless config.telemetry.enabled
        self.telemetry = Telemetry(config.telemetry)
        self.lr_schedule_fn = self._build_lr_schedule()
        self.lr_scheduler = LRScheduler(self.lr_schedule_fn)
        self._onebit = config.optimizer.type.lower().replace("_", "") in (
            "onebitadam",
            "zerooneadam",
            "onebitlamb",
        )
        if self._onebit:
            from . import onebit

            onebit.check_supported(config)
            self.optimizer = None  # the compressed step owns the update math
        else:
            self.optimizer = build_optimizer(
                config.optimizer.type, config.optimizer.params, learning_rate=self.lr_schedule_fn
            )
            if trainable_mask is not None:
                # frozen leaves (LoRA base weights) carry no optimizer state
                # and receive no update — reference OptimizedLinear freezes
                # the base the same way (linear/optimized_linear.py:76)
                self.optimizer = optax.masked(self.optimizer, trainable_mask)
        self.compute_dtype = precision.compute_dtype(config.precision_dtype)
        self._rng = jax.random.PRNGKey(seed if seed is not None else config.seed)

        # ---- sharding plan ----
        shapes = jax.eval_shape(lambda p: p, params)
        self.plan = zero.plan_sharding(shapes, config.zero_optimization, grid.spec, tp_rules)
        self.param_shardings = self.plan.param_shardings(self.mesh)
        self._scalar_sharding = NamedSharding(self.mesh, P())

        # ---- ZeRO++ quantized collectives (runtime/zeropp.py) ----
        zcfg = config.zero_optimization
        self._zeropp_vag = None
        self._loco_state = None  # LoCo error-feedback buffers (zeropp.py)
        if (
            zcfg.stage >= 3
            and (zcfg.zero_quantized_weights or zcfg.zero_quantized_gradients)
            and grid.spec.fsdp * grid.spec.sub > 1
        ):
            if grid.spec.sub > 1:
                from ..config.config import ConfigError

                raise ConfigError(
                    "zero_quantized_weights/gradients cannot combine with "
                    "zero_hpz_partition_size/mics_shard_size yet (the int8 "
                    "collective path shards on the plain fsdp axis)"
                )
            from . import zeropp

            loco = zcfg.zeropp_loco_param
            if loco is not None and (
                config.fp16.enabled
                or zcfg.offload_optimizer is not None
                or zcfg.offload_param is not None
            ):
                from ..config.config import ConfigError

                raise ConfigError(
                    "zeropp_loco_param requires bf16 and no optimizer/param "
                    "offload — the error-feedback buffer does not track "
                    "dynamic loss scales and is not threaded through the "
                    "offload step wrappers"
                )
            self._zeropp_vag = zeropp.make_micro_value_and_grad(
                self.loss_fn,
                self.mesh,
                self.plan.master_specs,
                self.compute_dtype,
                zcfg.zero_quantized_weights,
                zcfg.zero_quantized_gradients,
                loco_param=loco,
            )
            if loco is not None:
                self._loco_state, self._loco_shardings = zeropp.init_loco_state(
                    self.mesh, shapes, self.plan.master_specs
                )
                self._loco_reset_T = int(loco.get("reset_T", 1024))
                self._loco_calls = 0  # shim-path reset counter
            log_dist(
                f"ZeRO++ enabled: qwZ={zcfg.zero_quantized_weights} "
                f"qgZ={zcfg.zero_quantized_gradients} loco={loco is not None} "
                f"(int8 collectives on fsdp)"
            )

        # ---- offload tiers (reference: runtime/zero/offload_config.py) ----
        self._offload_nvme = zcfg.offload_optimizer == "nvme"
        self._offload_cpu = (not self._offload_nvme) and self.plan.wants_cpu_offload
        # device-kind shardings always exist; host-kind variants overlay them
        # when the CPU tier is on (memory_kind='pinned_host')
        self.master_shardings_dev = self.plan.master_shardings(self.mesh)
        self.master_shardings = self.plan.master_shardings(
            self.mesh, allow_offload=True
        )
        self._nvme_opt = None

        if self._offload_nvme:
            # NVMe tier: only bf16 compute params live on device; fp32
            # masters + Adam moments go to local SSD (runtime/offload.py)
            master_params, opt_state = self._init_nvme_offload(params, zcfg)
        elif self._onebit:
            from . import onebit

            place_masters = jax.jit(
                lambda p: jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), p),
                out_shardings=self.master_shardings_dev,
            )
            master_params = place_masters(params)
            opt_state, self.opt_shardings = onebit.init_state(self, master_params)
            self.opt_shardings_dev = self.opt_shardings
        else:
            # place masters sharded-at-creation via a device-kind jit (host
            # out_shardings inside jit are TPU-only), then hop memory kinds.
            # Frozen leaves (LoRA base, trainable_mask=False) keep their
            # storage dtype: fp32 master precision is only for weights that
            # actually update (the reference OptimizedLinear's frozen base
            # likewise never gets an fp32 copy).
            if trainable_mask is not None:
                cast = lambda p: jax.tree_util.tree_map(
                    lambda x, m: x.astype(jnp.float32) if m else x,
                    p, trainable_mask,
                )
            else:
                cast = lambda p: jax.tree_util.tree_map(
                    lambda x: x.astype(jnp.float32), p
                )
            place_masters = jax.jit(cast, out_shardings=self.master_shardings_dev)
            master_params = place_masters(params)
            opt_shapes = jax.eval_shape(self.optimizer.init, master_params)
            self.opt_shardings_dev = self.plan.opt_state_shardings(self.mesh, opt_shapes)
            self.opt_shardings = self.plan.opt_state_shardings(
                self.mesh, opt_shapes, allow_offload=True
            )
            opt_state = jax.jit(
                self.optimizer.init, out_shardings=self.opt_shardings_dev
            )(master_params)
            if self._offload_cpu:
                master_params = jax.device_put(master_params, self.master_shardings)
                opt_state = jax.device_put(opt_state, self.opt_shardings)
                # report where the state ACTUALLY landed: backends without a
                # registered pinned_host memory space (jax 0.4.37's CPU
                # client exposes only unpinned_host) fall back to default
                # placement in plan_sharding, and the log must not claim
                # otherwise
                kinds = sorted({
                    str(getattr(l.sharding, "memory_kind", None))
                    for l in jax.tree_util.tree_leaves(master_params)
                })
                log_dist(
                    "ZeRO-Offload(cpu): fp32 masters + optimizer state "
                    f"placed in {'/'.join(kinds)} memory"
                )

        fp16 = config.fp16.enabled
        loss_scale_state = precision.init_loss_scale(
            dynamic=fp16 and config.fp16.loss_scale == 0,
            initial_scale_power=config.fp16.initial_scale_power,
            static_scale=config.fp16.loss_scale if fp16 else 1.0,
            hysteresis=config.fp16.hysteresis,
        )
        loss_scale_state = jax.device_put(
            loss_scale_state,
            jax.tree_util.tree_map(lambda _: self._scalar_sharding, loss_scale_state),
        )
        self.state = TrainState(
            step=jax.device_put(jnp.zeros((), jnp.int32), self._scalar_sharding),
            params=master_params,
            opt_state=opt_state,
            loss_scale=loss_scale_state,
        )
        self.state_shardings = TrainState(
            step=self._scalar_sharding,
            params=self.master_shardings,
            opt_state=self.opt_shardings,
            loss_scale=jax.tree_util.tree_map(
                lambda _: self._scalar_sharding, loss_scale_state
            ),
        )

        self._train_step = None  # built lazily (needs batch sharding)
        self._step_program = None  # its TrackedProgram (telemetry/programs.py)
        self._step_facts: dict = {}  # what the traced loss noted of itself (span arguments)
        self._grad_fn = None
        self._apply_fn = None
        self._eval_step = None
        # forward/backward/step shim state
        self._pending: Optional[Dict[str, Any]] = None
        self._grad_buffer = None
        self._micro_steps = 0
        self._inside_no_sync = False
        self.global_steps = 0
        self.skipped_steps = 0
        self._last_metrics: Optional[StepMetrics] = None
        # latency-hiding input/step pipeline (runtime/prefetch.py)
        self._metrics_buffer = MetricsBuffer()
        self._active_prefetcher: Optional[DevicePrefetcher] = None
        self._prefetch_loader = None
        self._prefetch_shardings = None
        _register_exit_flush(self)
        self.model = None  # attached by initialize() for the flops profiler
        self.training_dataloader = None  # attached by initialize(); its
        # sampler position rides engine checkpoints (checkpoint/saving.py)
        self._compression = None
        cc = config.compression_training
        if cc.any_technique:
            from ..compression.compress import CompressionManager

            manager = CompressionManager(cc.as_dict())
            if manager.any_weight_transform:
                if self._onebit or self._zeropp_vag is not None:
                    from ..config.config import ConfigError

                    raise ConfigError(
                        "compression_training is not supported with 1-bit "
                        "optimizers or ZeRO++ quantized collectives (their "
                        "steps bypass the weight transform)"
                    )
                # weight-side transforms run in the step; activation quant is
                # wired into the model forward by initialize()
                self._compression = manager
                log_dist(
                    f"compression: wq={manager.weight_quant.enabled} "
                    f"prune={manager.pruning.enabled}"
                )
        self.progressive_layer_drop = None
        if config.progressive_layer_drop.enabled:
            from .progressive_layer_drop import ProgressiveLayerDrop

            if self._zeropp_vag is not None or self._onebit:
                from ..config.config import ConfigError

                raise ConfigError(
                    "progressive_layer_drop is not supported with 1-bit "
                    "optimizers or ZeRO++ quantized collectives (their fused "
                    "steps bypass the per-step theta injection)"
                )
            p = config.progressive_layer_drop
            self.progressive_layer_drop = ProgressiveLayerDrop(p.theta, p.gamma)
            log_dist(
                f"progressive layer drop enabled: theta={p.theta} gamma={p.gamma}"
            )
        self.eigenvalue = None
        self.block_eigenvalues: list = []
        if config.eigenvalue.enabled:
            from .eigenvalue import Eigenvalue

            e = config.eigenvalue
            self.eigenvalue = Eigenvalue(
                verbose=e.verbose, max_iter=e.max_iter, tol=e.tol,
                stability=e.stability,
                gas_boundary_resolution=e.gas_boundary_resolution,
                layer_name=e.layer_name, layer_num=e.layer_num,
            )
            log_dist(
                f"eigenvalue estimation enabled: max_iter={e.max_iter} "
                f"resolution={e.gas_boundary_resolution}"
            )
        self.curriculum_scheduler = None
        cl = (config.data_efficiency.curriculum_learning or {})
        if config.data_efficiency.enabled and cl.get("enabled"):
            from ..data.curriculum_scheduler import CurriculumScheduler

            self.curriculum_scheduler = CurriculumScheduler(cl)
            self._curriculum_metric = cl.get("curriculum_type", "seqlen")
            log_dist(
                f"curriculum learning enabled: metric={self._curriculum_metric} "
                f"schedule={cl.get('schedule_type')}"
            )
        log_dist(
            f"engine ready: zero_stage={config.zero_optimization.stage} "
            f"mesh={grid.spec.sizes} dtype={config.precision_dtype} "
            f"micro_batch={config.train_micro_batch_size_per_gpu} "
            f"gas={config.gradient_accumulation_steps}"
        )

    # ------------------------------------------------------------------
    # construction helpers
    # ------------------------------------------------------------------
    def _build_lr_schedule(self):
        sched = self.config.scheduler
        if sched.type is None and "lr" in (self.config.optimizer.params or {}):
            base = float(self.config.optimizer.params["lr"])
            return lambda step: jnp.asarray(base, jnp.float32)
        return get_lr_schedule_fn(sched.type, sched.params)

    def _jit(self, fn, **kw):
        """jax.jit unless ``compile.disable`` (the torch.compile-disable
        analogue, reference runtime/compiler.py): eager per-op execution for
        debugging.  Sharding/donation hints are compile-time concepts and are
        skipped; static args are passed through as plain values."""
        if self.config.compile.disable:
            return fn
        return jax.jit(fn, **kw)

    def batch_sharding(self, batch, batch_dim: int = 0):
        """Shard the batch dim of every leaf over the DP axes.  The fused
        train path stacks micro-batches as ``[gas, global_micro, ...]`` so its
        batch dim is 1; the forward() shim takes bare micro-batches (dim 0)."""
        def spec_for(x):
            nd = getattr(x, "ndim", 0)
            if nd <= batch_dim:
                return NamedSharding(self.mesh, P())
            entries = [None] * nd
            entries[batch_dim] = BATCH_AXES
            return NamedSharding(self.mesh, P(*entries))

        return jax.tree_util.tree_map(spec_for, batch)

    # ------------------------------------------------------------------
    # the jitted train step
    # ------------------------------------------------------------------
    def _micro_value_and_grad(
        self, master_params, micro_batch, rng, scale, step=None, loco_err=None,
        counted: Optional[list] = None,
    ):
        """Loss+grads for one micro-batch, w.r.t. fp32 masters, computed
        through compute-dtype casts (the BF16_Optimizer linkage, bf16_optimizer.py:34).
        With LoCo active, also takes/returns the error-feedback pytree:
        ``(loss, grads, new_err)``.  ``counted`` (a list) is handed what the
        traced loss counted of itself (``telemetry.count_in_step``: a dict of
        scalars, or None); what it noted as facts rides the ``train_batch`` span."""
        if self._zeropp_vag is not None:
            if loco_err is not None:
                loss, grads, new_err = self._zeropp_vag(
                    master_params, loco_err, micro_batch, rng, scale
                )
                return loss / scale, grads, new_err
            loss, grads = self._zeropp_vag(master_params, micro_batch, rng, scale)
            return loss / scale, grads

        def scaled_loss(p):
            cp = precision.cast_floating(p, self.compute_dtype)
            cp = zero.constrain(cp, self.param_shardings, scope="zero/gather")
            if self._compression is not None and step is not None:
                # QAT fake-quant / pruning via STE inside the traced step
                # (compression/compress.py; reference init_compression)
                cp = self._compression.transform(cp, step)
            batch_ = micro_batch
            if (
                self.progressive_layer_drop is not None
                and step is not None
                and hasattr(batch_, "get")
            ):
                # traced per-step keep probability; the model draws the
                # layer mask from it (CausalLM.loss_fn; reference
                # engine.py:1959 pld theta update)
                batch_ = dict(batch_)
                batch_["pld_theta"] = self.progressive_layer_drop.theta_at(step)
            with step_counts() as notes:
                loss = self.loss_fn(cp, batch_, rng)
            self._step_facts = dict(notes.facts)
            return loss * scale, (notes.counts or None)

        with jax.named_scope("grad"):
            (loss, counts), grads = jax.value_and_grad(scaled_loss, has_aux=True)(master_params)
        if counted is not None:
            counted.append(counts)
        return loss / scale, grads

    def _apply_grads(self, state: TrainState, grad_sum, divisor):
        """Shared epilogue of both step paths: unscale, overflow check, clip,
        optimizer update, overflow-skip select, loss-scale update.  ``grad_sum``
        is the (possibly accumulated) fp32 gradient pytree; ``divisor`` folds
        in the loss scale and any GAS averaging."""
        cfg = self.config
        fp16 = cfg.fp16.enabled
        dynamic = fp16 and cfg.fp16.loss_scale == 0
        clip = cfg.gradient_clipping
        grads = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32) / divisor, grad_sum
        )
        finite = precision.grads_finite(grads) if fp16 else jnp.asarray(True)
        grad_norm = precision.global_grad_norm(grads)
        if clip and clip > 0:
            grads, grad_norm = precision.clip_by_global_norm(grads, clip, grad_norm)
        updates, new_opt_state = self.optimizer.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        if fp16:
            sel = lambda a, b: jax.tree_util.tree_map(
                lambda x, y: jnp.where(finite, x, y), a, b
            )
            new_params = sel(new_params, state.params)
            new_opt_state = sel(new_opt_state, state.opt_state)
            new_scale_state = (
                precision.update_loss_scale(
                    state.loss_scale,
                    finite,
                    loss_scale_window=cfg.fp16.loss_scale_window,
                    min_scale=cfg.fp16.min_loss_scale,
                    init_hysteresis=cfg.fp16.hysteresis,
                )
                if dynamic
                else state.loss_scale
            )
        else:
            new_scale_state = state.loss_scale
        new_state = TrainState(
            step=state.step + jnp.where(finite, 1, 0).astype(jnp.int32),
            params=new_params,
            opt_state=new_opt_state,
            loss_scale=new_scale_state,
        )
        return new_state, grad_norm, finite

    def _make_train_step(self):
        cfg = self.config
        gas = cfg.gradient_accumulation_steps
        fp16 = cfg.fp16.enabled

        loco = self._loco_state is not None

        def train_step(state: TrainState, batch, rng, loco_err=None):
            scale = state.loss_scale.scale if fp16 else jnp.asarray(1.0, jnp.float32)
            divisor = scale
            if loco:
                # the reference resets the error buffer every reset_T steps
                # (coalesced_collectives.py:112 loco_idx > reset_T)
                reset = (state.step % self._loco_reset_T) == 0
                loco_err = jax.tree_util.tree_map(
                    lambda e: jnp.where(reset, jnp.zeros_like(e), e), loco_err
                )

            def one_micro(p, micro, r, err):
                counted = []
                out = self._micro_value_and_grad(
                    p, micro, r, scale, state.step, loco_err=err, counted=counted
                )
                loss, grads = out[0], out[1]
                # device-kind layout: grads live in HBM even when masters are
                # offloaded (only the state pytree itself rides pinned_host)
                grads = zero.constrain(grads, self.master_shardings_dev,
                                       scope="zero/reduce")
                return loss, grads, (out[2] if loco else None), (counted or [None])[0]

            if gas == 1:
                micro = jax.tree_util.tree_map(lambda x: x[0], batch)
                loss, grads, loco_err, counts = one_micro(state.params, micro, rng, loco_err)
            else:
                # lax.scan over the gas dimension: grads accumulate in fp32 in
                # the *master* (ZeRO-sharded) layout, so accumulation memory is
                # already partitioned — the analogue of the reference's
                # contiguous sharded gradient buffer (stage_1_and_2.py).
                def body(carry, inp):
                    acc, lsum, err = carry
                    micro, r = inp
                    loss, grads, err, counts = one_micro(state.params, micro, r, err)
                    acc = jax.tree_util.tree_map(jnp.add, acc, grads)
                    return (acc, lsum + loss, err), counts

                zeros = jax.tree_util.tree_map(
                    lambda x: jnp.zeros(x.shape, jnp.float32), state.params
                )
                rngs = jax.random.split(rng, gas)
                (grads, loss_sum, loco_err), counts = jax.lax.scan(
                    body, (zeros, jnp.asarray(0.0, jnp.float32), loco_err), (batch, rngs)
                )
                counts = jax.tree_util.tree_map(lambda c: jnp.sum(c, axis=0), counts)
                loss = loss_sum / gas
                divisor = scale * gas  # fold GAS averaging into the unscale divisor

            # fp16 overflow handling (reference: fp16/loss_scaler.py overflow
            # path + engine.py skipped-step count) lives in _apply_grads.
            with jax.named_scope("optimizer"):
                new_state, grad_norm, finite = self._apply_grads(state, grads, divisor)
            metrics = StepMetrics(
                loss=loss,
                grad_norm=grad_norm,
                lr=jnp.asarray(self.lr_schedule_fn(state.step), jnp.float32),
                loss_scale=scale,
                skipped=jnp.logical_not(finite),
                counts=counts,
            )
            if loco:
                return new_state, metrics, loco_err
            return new_state, metrics

        return train_step

    def _get_train_step(self, batch):
        if self._train_step is None:
            if self._offload_nvme:
                self._train_step = self._make_nvme_train_step(batch)
                return self._train_step
            if self._onebit:
                self._train_step = self._make_onebit_train_step(batch)
                return self._train_step
            step_fn = self._make_train_step()
            # (a prefix: ``counts`` is a dict of scalars the trace decides, or None)
            metrics_shardings = StepMetrics(
                *([self._scalar_sharding] * len(StepMetrics._fields))
            )
            if self._loco_state is not None:
                jitted = self._jit(
                    step_fn,
                    in_shardings=(
                        self.state_shardings,
                        self.batch_sharding(batch, batch_dim=1),
                        None,
                        self._loco_shardings,
                    ),
                    out_shardings=(
                        self.state_shardings,
                        metrics_shardings,
                        self._loco_shardings,
                    ),
                    donate_argnums=(0, 3),
                )

                def call(state, batch_, rng):
                    new_state, metrics, self._loco_state = jitted(
                        state, batch_, rng, self._loco_state
                    )
                    return new_state, metrics

                self._train_step = call
                return self._train_step
            jitted = self._jit(
                step_fn,
                in_shardings=(self.state_shardings, self.batch_sharding(batch, batch_dim=1), None),
                out_shardings=(self.state_shardings, metrics_shardings),
                donate_argnums=(0,),
            )
            # tracked: telemetry.program_scopes() can name the step's
            # instructions after the fact (nothing is lowered for it here;
            # train_batch notes the shapes of the call that compiled)
            self._step_program = None if self._offload_cpu else track_program(jitted)
            if self._offload_cpu:
                jitted = self._wrap_offload_step(jitted, step_fn, batch, metrics_shardings)
            self._train_step = jitted
        return self._train_step

    def _dev_state_shardings(self):
        """state_shardings with every leaf in device memory (no host kinds)."""
        return self.state_shardings._replace(
            params=self.master_shardings_dev, opt_state=self.opt_shardings_dev
        )

    def _wrap_offload_step(self, jit_host, step_fn, batch, metrics_shardings):
        """CPU-offload execution strategy.  On TPU, jit takes/returns the
        masters + opt state directly in pinned_host memory and XLA streams
        them through HBM (the performant ZeRO-Offload schedule).  Backends
        that reject host-memory shardings inside jit (the CPU test mesh) fall
        back to staging the transfers around a device-kind step."""
        state_sh_dev = self._dev_state_shardings()
        jit_dev = self._jit(
            step_fn,
            in_shardings=(state_sh_dev, self.batch_sharding(batch, batch_dim=1), None),
            out_shardings=(state_sh_dev, metrics_shardings),
            donate_argnums=(0,),
        )
        mode = {"v": None}

        def unsupported_host_memory(e: Exception) -> bool:
            # Only lowering/compile failures about host memory kinds mean
            # "backend unsupported"; anything else (OOM, user loss error at
            # first execution) must propagate, not silently switch modes.
            if not isinstance(e, (ValueError, TypeError, NotImplementedError,
                                  jax.errors.JaxRuntimeError)):
                return False
            msg = str(e).lower()
            return any(k in msg for k in (
                "memory kind", "memory_kind", "pinned_host", "host memory",
                "memory space", "memory_space",
            ))

        def call(state, batch_, rng):
            if mode["v"] in (None, "host"):
                try:
                    out = jit_host(state, batch_, rng)
                    mode["v"] = "host"
                    return out
                except Exception as e:  # noqa: BLE001 — backend capability probe
                    if mode["v"] == "host" or not unsupported_host_memory(e):
                        raise
                    log_dist(
                        f"host-memory jit unsupported here ({type(e).__name__}); "
                        "staging offload transfers around the device step"
                    )
                    mode["v"] = "staged"
            dev_state = jax.device_put(state, state_sh_dev)
            new_state, metrics = jit_dev(dev_state, batch_, rng)
            new_state = new_state._replace(
                params=jax.device_put(new_state.params, self.master_shardings),
                opt_state=jax.device_put(new_state.opt_state, self.opt_shardings),
            )
            return new_state, metrics

        return call

    def _make_onebit_train_step(self, batch):
        """Compressed-momentum optimizer family (runtime/onebit.py)."""
        from . import onebit

        raw_step = onebit.make_train_step(self)

        def step_fn(state, batch_, rng):
            new_state, (loss, gnorm, lr) = raw_step(state, batch_, rng)
            metrics = StepMetrics(
                loss=loss,
                grad_norm=gnorm,
                lr=lr,
                loss_scale=jnp.asarray(1.0, jnp.float32),
                skipped=jnp.asarray(False),
            )
            return new_state, metrics

        metrics_shardings = StepMetrics(
            *([self._scalar_sharding] * len(StepMetrics._fields))
        )
        return self._jit(
            step_fn,
            in_shardings=(self.state_shardings, self.batch_sharding(batch, batch_dim=1), None),
            out_shardings=(self.state_shardings, metrics_shardings),
            donate_argnums=(0,),
        )

    # ------------------------------------------------------------------
    # NVMe offload path (reference: partitioned_optimizer_swapper.py)
    # ------------------------------------------------------------------
    def _init_nvme_offload(self, params, zcfg):
        from ..config.config import ConfigError
        from .offload import NVMeOptimizer

        if self.config.fp16.enabled:
            raise ConfigError("offload_optimizer=nvme requires bf16 (no fp16 loss scaling)")
        if self.config.optimizer.type.lower() not in ("adam", "adamw"):
            raise ConfigError(
                f"offload_optimizer=nvme supports adam/adamw (host fused kernel), "
                f"got {self.config.optimizer.type}"
            )
        op = self.config.optimizer.params or {}
        self._nvme_opt = NVMeOptimizer(
            zcfg.offload_nvme_path,
            lr=float(op.get("lr", 1e-3)),
            betas=tuple(op.get("betas", (0.9, 0.999))),
            eps=float(op.get("eps", 1e-8)),
            weight_decay=float(op.get("weight_decay", 0.0)),
            num_threads=self.config.aio.thread_count,
            queue_depth=self.config.aio.queue_depth,
        )
        place = jax.jit(
            lambda p: precision.cast_floating(p, self.compute_dtype),
            out_shardings=self.param_shardings,
        )
        compute_params = place(params)
        self._nvme_opt.init(
            jax.tree_util.tree_map(lambda x: np.asarray(x, np.float32), params)
        )
        # state.params holds the bf16 compute copy; masters are on disk
        self.master_shardings = self.param_shardings
        self.master_shardings_dev = self.param_shardings
        self._nvme_pending = None
        self._nvme_walk_span = None
        # bounded: instrumentation for tests/diagnostics, not a step log
        from collections import deque

        self._nvme_timeline: "deque" = deque(maxlen=512)
        if zcfg.offload_pipeline:
            from concurrent.futures import ThreadPoolExecutor

            # ONE worker: walks are strictly ordered (step k joins before
            # step k+1 dispatches)
            self._nvme_executor = ThreadPoolExecutor(max_workers=1)
            log_dist(
                "nvme offload: pipelined (delayed parameter update — the "
                "host Adam walk overlaps the next step's grad computation)"
            )
        self.opt_shardings = ()
        self.opt_shardings_dev = ()
        return compute_params, ()

    def _make_nvme_train_step(self, batch):
        cfg = self.config
        gas = cfg.gradient_accumulation_steps
        clip = cfg.gradient_clipping
        # bf16 D2H halves the host-link bytes per step; accumulation and the
        # norm stay fp32, only the transfer narrows (host Adam re-widens)
        wire_dtype = (
            jnp.bfloat16 if cfg.zero_optimization.offload_grad_dtype == "bf16"
            else jnp.float32
        )

        def grad_step(params, batch_, rng, step):
            def one(p, micro, r):
                loss, grads = self._micro_value_and_grad(
                    p, micro, r, jnp.asarray(1.0, jnp.float32), step
                )
                grads = jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32), grads
                )
                return loss, zero.constrain(grads, self.master_shardings_dev)

            if gas == 1:
                micro = jax.tree_util.tree_map(lambda x: x[0], batch_)
                loss, grads = one(params, micro, rng)
            else:
                def body(carry, inp):
                    acc, lsum = carry
                    micro, r = inp
                    loss, g = one(params, micro, r)
                    return (jax.tree_util.tree_map(jnp.add, acc, g), lsum + loss), None

                zeros = jax.tree_util.tree_map(
                    lambda x: jnp.zeros(x.shape, jnp.float32), params
                )
                (grads, lsum), _ = jax.lax.scan(
                    body,
                    (zeros, jnp.asarray(0.0, jnp.float32)),
                    (batch_, jax.random.split(rng, gas)),
                )
                loss = lsum / gas
                grads = jax.tree_util.tree_map(lambda g: g / gas, grads)
            gnorm = precision.global_grad_norm(grads)
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(wire_dtype), grads
            )
            return loss, grads, gnorm

        jit_grad = self._jit(
            grad_step,
            in_shardings=(
                self.param_shardings,
                self.batch_sharding(batch, batch_dim=1),
                None,
                self._scalar_sharding,
            ),
            out_shardings=(
                self._scalar_sharding,
                self.master_shardings_dev,
                self._scalar_sharding,
            ),
        )
        upload = self._jit(
            lambda m: precision.cast_floating(m, self.compute_dtype),
            out_shardings=self.param_shardings,
        )

        # upload each master INTO its sharding: an unsharded device_put would
        # commit every full fp32 master to device 0 before the upload jit
        # reshards — a transient HBM spike proportional to the fp32 model
        # size, on the path that exists because memory is tight
        master_sh = jax.tree_util.tree_leaves(self.master_shardings_dev)

        def host_walk(grads, lr, step_num, coef):
            """Step k's host side: disk IO + fused Adam + per-leaf H2D
            uploads (which begin the moment each master updates, overlapping
            the remaining walk).  Returns the bf16 compute params."""
            device_masters: list = [None] * self._nvme_opt.num_leaves

            def on_leaf(i, master):
                device_masters[i] = jax.device_put(master, master_sh[i])

            self._nvme_opt.step(grads, lr, step_num, coef, on_leaf=on_leaf)
            masters = jax.tree_util.tree_unflatten(
                self._nvme_opt.treedef, device_masters
            )
            return upload(masters)

        def call(state: TrainState, batch_, rng):
            pipelined = self.config.zero_optimization.offload_pipeline
            # ZeRO-Offload delayed parameter update: DISPATCH this step's
            # grads (async) against the params we already have — one walk
            # stale — so the device computes them while the host joins step
            # k-1's background Adam walk below.  Join-before-dispatch would
            # serialize the pipeline.
            loss, grads, gnorm = jit_grad(state.params, batch_, rng, state.step)
            if pipelined:
                self._nvme_timeline.append(("dispatch", _now()))
            # start every grad leaf's D2H copy before blocking on the norm:
            # transfers run while we wait and while early leaves update
            for leaf in jax.tree_util.tree_leaves(grads):
                try:
                    leaf.copy_to_host_async()
                except AttributeError:
                    pass
            joined = self._join_nvme_walk()  # host blocks; device is busy
            gn = float(gnorm)
            coef = min(1.0, clip / (gn + 1e-6)) if clip and clip > 0 else 1.0
            lr = float(self.lr_schedule_fn(state.step))
            step_num = int(state.step) + 1
            if pipelined:
                self._nvme_pending = self._nvme_executor.submit(
                    self._timed_walk, host_walk, grads, lr, step_num, coef
                )
                # params advance by the JOINED walk (step k-1); this step's
                # walk lands at the next call/flush — one-step staleness
                new_params = joined if joined is not None else state.params
            else:
                new_params = host_walk(grads, lr, step_num, coef)
            new_state = TrainState(
                step=state.step + 1,
                params=new_params,
                opt_state=state.opt_state,
                loss_scale=state.loss_scale,
            )
            metrics = StepMetrics(
                loss=loss,
                grad_norm=gnorm,
                lr=jnp.asarray(lr, jnp.float32),
                loss_scale=jnp.asarray(1.0, jnp.float32),
                skipped=jnp.asarray(False),
            )
            return new_state, metrics

        return call

    def _timed_walk(self, host_walk, grads, lr, step_num, coef):
        t0 = _now()
        self._nvme_timeline.append(("walk_start", t0))
        params = host_walk(grads, lr, step_num, coef)
        t1 = _now()
        self._nvme_timeline.append(("walk_end", t1))
        # locals, not timeline[-2:]: the main thread appends 'dispatch'
        # entries to the shared deque concurrently
        self._nvme_walk_span = (t0, t1)
        return params

    def _join_nvme_walk(self):
        """Adopt the pending background walk's params (pipelined NVMe mode);
        None when nothing is pending."""
        pending = getattr(self, "_nvme_pending", None)
        if pending is None:
            return None
        self._nvme_pending = None
        return pending.result()

    def flush_nvme_pipeline(self) -> None:
        """Complete any in-flight host Adam walk and adopt its params —
        called before checkpoint save/load and eval so the visible state is
        exact (and no worker thread races the swap files)."""
        params = self._join_nvme_walk()
        if params is not None:
            self.state = self.state._replace(params=params)

    # ------------------------------------------------------------------
    # public API — fused path
    # ------------------------------------------------------------------
    def train_batch(self, batch) -> jnp.ndarray:
        """Run one full optimizer step on a global batch shaped
        ``[gas, global_micro_batch, ...]`` (or ``[global_micro_batch, ...]``
        when gradient_accumulation_steps == 1)."""
        # accept flat [global_batch, ...] and fold into [gas, micro, ...]
        # (a no-op for prefetched batches — _place_batch already folded)
        batch = _gas_fold(
            batch,
            self.config.gradient_accumulation_steps,
            self.config.train_micro_batch_size_per_gpu * self.config.dp_world_size,
        )
        if self.curriculum_scheduler is not None:
            # reference: curriculum difficulty advances per global step and
            # (for the seqlen metric) truncates the batch — each distinct
            # difficulty is one cached XLA compilation
            difficulty = self.curriculum_scheduler.update_difficulty(
                self.global_steps + 1
            )
            if self._curriculum_metric == "seqlen":
                from ..data.curriculum_scheduler import truncate_to_seqlen

                batch = truncate_to_seqlen(batch, difficulty)
        self.tput_timer.start()
        self.timers(STEP_GLOBAL_TIMER).start()
        rng = self._next_rng()
        # no host read is added to the step: the span closes at dispatch and
        # is exported unsynced (its duration is the enqueue; the step's
        # device time is the trace's, one XLA Modules event per execution)
        with self.telemetry.span(
            "train_batch", track="train", step=self.global_steps + 1,
        ) as tb_span:
            args = (self.state, batch, rng)
            self.state, metrics = self._get_train_step(batch)(*args)
            if self._step_program is not None:
                self._step_program.note(args)
            del args  # the donated state is gone; hold no dead handle
            tb_span.end(sync_obj=metrics.loss, **self._step_facts)
        self._last_metrics = metrics
        self.global_steps += 1
        async_metrics = self.config.train_data.async_metrics
        # ONE metrics path for both modes: buffer the device arrays; the
        # flush (below, after the timers — outside the measured window,
        # where the old emission also ran) does skip accounting, the
        # steps_per_print log line, and monitor emission.  Sync mode
        # flushes every step (host reads on the critical path, the
        # historical behavior); async mode defers the flush to
        # steps_per_print boundaries / get_last_loss / checkpoints so the
        # loop issues no per-step blocking host read.
        self._metrics_buffer.append(
            self.global_steps,
            metrics,
            keep_history=self.config.fp16.enabled
            or (self.monitor is not None and self.monitor.enabled)
            or metrics.counts is not None,  # every step's counts are booked
        )
        self.lr_scheduler.step()
        if self.progressive_layer_drop is not None:
            # host-side mirror of the traced theta (monitoring/get_state();
            # the traced step computes theta_at(step) itself)
            self.progressive_layer_drop.update_state(self.global_steps)
        if (
            self.eigenvalue is not None
            and self.global_steps % self.eigenvalue.gas_boundary_resolution == 0
        ):
            self._compute_block_eigenvalue(batch)
        fp = self.config.flops_profiler
        profiling_now = fp.enabled and self.global_steps == fp.profile_step
        self.timers(STEP_GLOBAL_TIMER).stop(
            # the profiler divides analytic FLOPs by this window: it must be
            # a synced device time, not async dispatch time
            sync_obj=metrics.loss
            if (self.config.wall_clock_breakdown or profiling_now)
            else None
        )
        print_boundary = self.global_steps % self.config.steps_per_print == 0
        # async mode: the throughput timer stays a dispatch-time sample
        # except at print boundaries, where the sync makes the *window*
        # total (and thus avg_samples_per_sec) exact device time
        self.tput_timer.stop(
            sync_obj=metrics.loss
            if (not async_metrics or print_boundary)
            else None
        )
        if self.config.memory_breakdown and print_boundary:
            from ..utils.memory import see_memory_usage

            see_memory_usage(f"after step {self.global_steps}", force=True)
        if not async_metrics or print_boundary:
            self._flush_step_metrics()
        if profiling_now:
            # before the wall-clock log below: log(reset=True) zeroes the
            # step timer the profiler reads its latency from
            self._run_flops_profiler(batch)
        if self.config.wall_clock_breakdown and print_boundary:
            # reference: EngineTimers groups logged per steps_per_print
            self.timers.log(
                [FORWARD_GLOBAL_TIMER, BACKWARD_GLOBAL_TIMER, STEP_GLOBAL_TIMER],
                reset=True,
            )
        return metrics.loss

    def _compute_block_eigenvalue(self, batch) -> None:
        """Power-iteration curvature estimate at the gas boundary (reference
        engine.py:1503: eigenvalue drives compression scheduling).  Results
        accumulate in ``self.block_eigenvalues`` as (step, value)."""
        micro = jax.tree_util.tree_map(lambda x: x[0], batch)
        if not hasattr(self, "_eig_loss"):
            # ONE wrapper object across steps: the estimator caches its
            # compiled HVP keyed on this identity
            def _eig_loss(p, b, r):
                cp = precision.cast_floating(p, self.compute_dtype)
                return self.loss_fn(cp, b, r)

            self._eig_loss = _eig_loss
        # fp32 primal regardless of offload mode (NVMe keeps bf16 compute
        # copies in state.params) — tangents follow the primal dtype
        masters = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32), self.state.params
        )
        ev, _ = self.eigenvalue.compute_eigenvalue(self._eig_loss, masters, micro)
        self.block_eigenvalues.append((self.global_steps, ev))
        log_dist(f"eigenvalue at step {self.global_steps}: {ev:.4e}")

    def _run_flops_profiler(self, batch) -> None:
        """Engine-integrated flops profiler firing at ``profile_step``
        (reference engine.py:1938-1955)."""
        from ..profiling.flops_profiler import FlopsProfiler

        prof = FlopsProfiler(model=self.model, engine=self)
        timer = self.timers(STEP_GLOBAL_TIMER)
        # last step's synced duration, not mean(): the mean is polluted by
        # step 1's trace+compile time (set profile_step >= 2 for a clean read)
        prof._duration = timer.last()
        prof.engine_step_hook(self, batch)

    # ------------------------------------------------------------------
    # public API — forward/backward/step parity shim
    # ------------------------------------------------------------------
    def forward(self, batch):
        """Stage a micro-batch; returns its loss (reference engine.py:1926)."""
        if self._offload_nvme:
            raise NotImplementedError(
                "offload_optimizer=nvme supports the fused train_batch() path only"
            )
        self.timers(FORWARD_GLOBAL_TIMER).start()
        state_sh = self._dev_state_shardings() if self._offload_cpu else self.state_shardings
        loco = self._loco_state is not None
        if self._grad_fn is None:
            def micro_step(state, micro, rng, loco_err=None):
                scale = (
                    state.loss_scale.scale
                    if self.config.fp16.enabled
                    else jnp.asarray(1.0, jnp.float32)
                )
                out = self._micro_value_and_grad(
                    state.params, micro, rng, scale, state.step, loco_err=loco_err
                )
                loss, grads = out[0], out[1]
                grads = zero.constrain(grads, self.master_shardings_dev)
                if loco:
                    return loss, grads, out[2]
                return loss, grads

            if loco:
                self._grad_fn = self._jit(
                    micro_step,
                    in_shardings=(
                        state_sh, self.batch_sharding(batch), None,
                        self._loco_shardings,
                    ),
                    out_shardings=(
                        self._scalar_sharding, self.master_shardings_dev,
                        self._loco_shardings,
                    ),
                )
            else:
                self._grad_fn = self._jit(
                    micro_step,
                    in_shardings=(state_sh, self.batch_sharding(batch), None),
                    out_shardings=(self._scalar_sharding, self.master_shardings_dev),
                )
        st = jax.device_put(self.state, state_sh) if self._offload_cpu else self.state
        if loco:
            # reset_T on the shim path (the fused path resets by state.step
            # inside the jitted step): zero the buffer host-side every
            # reset_T micro-grad computations
            if self._loco_calls % self._loco_reset_T == 0:
                self._loco_state = jax.tree_util.tree_map(
                    jnp.zeros_like, self._loco_state
                )
            self._loco_calls += 1
            loss, grads, self._loco_state = self._grad_fn(
                st, batch, self._next_rng(), self._loco_state
            )
        else:
            loss, grads = self._grad_fn(st, batch, self._next_rng())
        self._pending = {"grads": grads, "loss": loss}
        self.timers(FORWARD_GLOBAL_TIMER).stop()
        return loss

    def backward(self, loss=None):
        """Accumulate the staged micro-batch's gradients (engine.py:2085)."""
        assert self._pending is not None, "backward() without a prior forward()"
        self.timers(BACKWARD_GLOBAL_TIMER).start()
        grads = self._pending["grads"]
        if self._grad_buffer is None:
            self._grad_buffer = grads
        else:
            self._grad_buffer = jax.tree_util.tree_map(
                jnp.add, self._grad_buffer, grads
            )
        self._micro_steps += 1
        self._pending = None
        self.timers(BACKWARD_GLOBAL_TIMER).stop()

    def wait_pending_checkpoint(self) -> None:
        """Block until an async checkpoint save (checkpoint.async_save) has
        durably committed (reference: NebulaCheckpointEngine commit)."""
        ce = getattr(self, "_ckpt_engine", None)
        if ce is not None:
            ce.wait()

    def is_gradient_accumulation_boundary(self) -> bool:
        """reference: engine.py:2166.  Inside ``no_sync`` accumulation-step
        tracking is disabled (never a boundary), per the reference contract."""
        if self._inside_no_sync:
            return False
        return self._micro_steps % self.config.gradient_accumulation_steps == 0

    @contextmanager
    def no_sync(self):
        """Suspend gradient-reduction bookkeeping during backward
        (reference engine.py:2065).  Contract parity: (1) illegal with ZeRO
        stage >= 2 — gradient partitioning *is* the reduction; (2) ``step()``
        inside the context is illegal; (3) accumulation-boundary tracking is
        disabled.  The comm-volume effect differs by construction: per-micro
        grads here accumulate in the ZeRO-sharded master layout inside one
        jitted step, so there is no per-backward all-reduce to elide — XLA's
        schedule already defers cross-DP reduction to the boundary."""
        if self.config.zero_optimization.stage >= 2:
            raise RuntimeError(
                "no_sync is incompatible with the gradient partitioning of "
                f"ZeRO stage {self.config.zero_optimization.stage}"
            )
        if self._inside_no_sync:
            raise RuntimeError("no_sync context manager reentry is unsupported")
        self._inside_no_sync = True
        try:
            yield
        finally:
            self._inside_no_sync = False

    def step(self):
        """Apply accumulated gradients at the GAS boundary (engine.py:2282)."""
        if self._inside_no_sync:
            raise RuntimeError("it is illegal to call engine.step() within no_sync")
        if not self.is_gradient_accumulation_boundary():
            return
        state_sh = self._dev_state_shardings() if self._offload_cpu else self.state_shardings
        if self._apply_fn is None:
            fp16 = self.config.fp16.enabled
            gas = self.config.gradient_accumulation_steps

            def apply(state: TrainState, grad_sum):
                scale = state.loss_scale.scale if fp16 else jnp.asarray(1.0, jnp.float32)
                with jax.named_scope("optimizer"):
                    new_state, _, finite = self._apply_grads(state, grad_sum, scale * gas)
                return new_state, jnp.logical_not(finite)

            self._apply_fn = self._jit(
                apply,
                in_shardings=(state_sh, self.master_shardings_dev),
                out_shardings=(state_sh, self._scalar_sharding),
                donate_argnums=(0, 1),
            )
        st = jax.device_put(self.state, state_sh) if self._offload_cpu else self.state
        new_state, skipped = self._apply_fn(st, self._grad_buffer)
        if self._offload_cpu:
            new_state = new_state._replace(
                params=jax.device_put(new_state.params, self.master_shardings),
                opt_state=jax.device_put(new_state.opt_state, self.opt_shardings),
            )
        self.state = new_state
        self._grad_buffer = None
        self.global_steps += 1
        if bool(skipped):
            self.skipped_steps += 1
        self.lr_scheduler.step()

    __call__ = forward

    # ------------------------------------------------------------------
    # eval / inference
    # ------------------------------------------------------------------
    def eval_batch(self, batch):
        self.flush_nvme_pipeline()
        # an eval boundary is a natural sync point: settle deferred train
        # metrics (skip counts, monitor rows) before reporting eval numbers
        self._flush_step_metrics()
        if self._eval_step is None:
            fn = self.eval_fn or self.loss_fn

            def ev(state, b, rng):
                cp = precision.cast_floating(state.params, self.compute_dtype)
                cp = zero.constrain(cp, self.param_shardings)
                return fn(cp, b, rng)

            self._eval_step = self._jit(ev)
        st = (
            jax.device_put(self.state, self._dev_state_shardings())
            if self._offload_cpu
            else self.state
        )
        return self._eval_step(st, batch, self._next_rng())

    # ------------------------------------------------------------------
    # misc parity API
    # ------------------------------------------------------------------
    def _next_rng(self):
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def get_lr(self):
        return self.lr_scheduler.get_last_lr()

    def get_global_grad_norm(self) -> Optional[float]:
        """Synced grad norm of the newest step.  An explicit host read of
        the async-metrics contract (like ``get_last_loss``): flushes the
        deferred buffer and routes through ``host_scalar`` so the sync
        surface stays auditable."""
        if self._last_metrics is None:
            return None
        self._flush_step_metrics()
        return host_scalar(self._last_metrics.grad_norm)

    @property
    def loss_scale(self) -> float:
        return float(self.state.loss_scale.scale)

    def gradient_accumulation_steps(self) -> int:
        return self.config.gradient_accumulation_steps

    def train_micro_batch_size_per_gpu(self) -> int:
        return self.config.train_micro_batch_size_per_gpu

    @property
    def dp_world_size(self) -> int:
        return self.grid.dp_world_size

    def module_params(self):
        """Compute-dtype view of the current parameters."""
        self.flush_nvme_pipeline()  # pipelined NVMe: adopt the latest walk
        return precision.cast_floating(self.state.params, self.compute_dtype)

    def memory_breakdown(self):
        """Exact state-component byte accounting + a live device/host
        snapshot (reference: ``memory_breakdown`` config consumed by
        ``see_memory_usage`` call sites, runtime/utils.py:771)."""
        from ..utils.memory import memory_breakdown_report

        return memory_breakdown_report(self)

    # ------------------------------------------------------------------
    # latency-hiding input/step pipeline (runtime/prefetch.py)
    # ------------------------------------------------------------------
    def _flush_step_metrics(self) -> None:
        """Host accounting for buffered StepMetrics — THE single emission
        path for both metric modes: fp16 skip counts, the
        ``steps_per_print`` log line, monitor events per step in order.
        Sync mode flushes a one-item buffer every step; async mode flushes
        a whole window at once (one deferred sync instead of one per
        step)."""
        # deferred telemetry spans settle at the same boundary (one
        # block_until_ready per window, same contract as the buffer below)
        self.telemetry.flush()
        if len(self._metrics_buffer) == 0:
            return
        fp16 = self.config.fp16.enabled
        emit = self.monitor is not None and self.monitor.enabled
        events = []
        for step, m in self._metrics_buffer.flush():
            for name, n in (m.counts or {}).items():
                self.telemetry.registry.counter(name).inc(int(n))
            if fp16 and m.skipped:
                self.skipped_steps += 1
            if step % self.config.steps_per_print == 0:
                log_dist(
                    f"step={step} loss={m.loss:.4f} "
                    f"lr={m.lr:.3e} grad_norm={m.grad_norm:.3f}"
                )
            if emit:
                events.extend(
                    [
                        ("Train/Samples/train_loss", m.loss, step),
                        ("Train/Samples/lr", m.lr, step),
                        ("Train/Samples/loss_scale", m.loss_scale, step),
                    ]
                )
        if emit and self.telemetry.enabled:
            # registry aggregates ride the same monitor fan-out as the
            # per-step rows — (label, value, step) is the shared shape
            events.extend(self.telemetry.registry.snapshot(self.global_steps))
        if events:
            self.monitor.write_events(events)

    def get_last_loss(self) -> Optional[float]:
        """Synced scalar loss of the newest completed step.  THE explicit
        host read of the async-metrics contract: flushes the deferred
        buffer (skip accounting, logs, monitor) and blocks on the loss."""
        self._flush_step_metrics()
        if self._last_metrics is None:
            return None
        return host_scalar(self._last_metrics.loss)

    def _place_batch(self, batch):
        """Gas-fold host-side and ``device_put`` into the fused step's batch
        shardings.  Runs on the prefetch worker thread, so the H2D transfer
        for batch k+1 overlaps batch k's device compute instead of paying it
        at dispatch time."""
        batch = _gas_fold(
            batch,
            self.config.gradient_accumulation_steps,
            self.config.train_micro_batch_size_per_gpu * self.config.dp_world_size,
        )
        if self._prefetch_shardings is None:
            # NamedShardings depend on leaf rank only, so one plan covers
            # every step (static shapes are already a TPU requirement)
            self._prefetch_shardings = self.batch_sharding(batch, batch_dim=1)
        return jax.device_put(batch, self._prefetch_shardings)

    def train_on_loader(self, data_loader, num_steps: Optional[int] = None):
        """Iterator-driven fast path: generator over pipelined
        ``train_batch`` steps.

        A background worker (``train_data.prefetch_depth`` deep, default 2 =
        double buffering) collates, gas-folds and ``device_put``-places batches
        ahead of the step; together with ``train_data.async_metrics`` the
        loop dispatches step k+1 while step k executes on device.  Yields
        the per-step loss as a device array — call ``get_last_loss()`` for
        a synced value.

        Clean shutdown + exactness: worker exceptions re-raise here at the
        point in the stream where they occurred; on generator exit (or
        ``close()``), prefetched-but-unconsumed batches are returned to the
        loader's sampler position via ``load_state_dict``, and a checkpoint
        saved mid-iteration records that same drained position — resume
        replays without skipping or repeating samples."""
        from .dataloader import unwrap_loader_chain

        from ..data.data_analyzer import CurriculumDataSampler

        def _draws_at_live_difficulty(link) -> bool:
            sampler = getattr(link, "data_sampler", None)
            return (
                getattr(sampler, "index_filter", None) is not None
                or isinstance(sampler, CurriculumDataSampler)
                or isinstance(link, CurriculumDataSampler)
            )

        depth = self.config.train_data.prefetch_depth
        if depth > 0 and any(
            _draws_at_live_difficulty(link)
            for link in unwrap_loader_chain(data_loader)
        ):
            # difficulty-driven sampling reads (and CurriculumDataSampler
            # mutates) the LIVE scheduler at draw time; a worker running
            # ahead would evaluate it at a stale/racing difficulty —
            # exactness wins: run synchronously
            log_dist(
                "train_on_loader: curriculum-driven sampling active — "
                "prefetch disabled for this loader (the eligible pool must "
                "be built at the consuming step's difficulty)"
            )
            depth = 0
        if depth == 0:
            try:
                n = 0
                for batch in data_loader:
                    yield self.train_batch(batch)
                    n += 1
                    if num_steps is not None and n >= num_steps:
                        return
                return
            finally:
                # tail steps past the last steps_per_print boundary still
                # owe their skip accounting / monitor rows
                self._flush_step_metrics()
        if self._active_prefetcher is not None:
            raise RuntimeError(
                "train_on_loader is already active on this engine; close the "
                "previous generator first"
            )
        # each invocation may carry a different batch pytree structure;
        # _place_batch re-derives the sharding plan from its first batch
        self._prefetch_shardings = None
        # find the resumable-position owner by walking wrapper ``.loader``
        # chains (RepeatingLoader etc.) — the SAME chain save_checkpoint's
        # drain check walks, so "drain applies" and "drain can capture
        # state" never diverge
        state_owner = next(
            (
                link
                for link in unwrap_loader_chain(data_loader)
                if callable(getattr(link, "state_dict", None))
            ),
            None,
        )
        state_fn = (
            state_owner.state_dict if state_owner is not None else None
        )
        pf = DevicePrefetcher(
            iter(data_loader),
            self._place_batch,
            depth=depth,
            state_fn=state_fn,
            telemetry=self.telemetry,
        )
        self._active_prefetcher = pf
        self._prefetch_loader = data_loader
        try:
            n = 0
            for dev_batch in pf:
                yield self.train_batch(dev_batch)
                n += 1
                if num_steps is not None and n >= num_steps:
                    return
        finally:
            stopped = pf.close()
            resume = pf.resume_state()
            self._active_prefetcher = None
            self._prefetch_loader = None
            if (
                stopped
                and resume is not None
                and callable(getattr(state_owner, "load_state_dict", None))
            ):
                # return prefetched-but-unconsumed batches to the sampler
                # that owns the position (the state_dict provider above)
                state_owner.load_state_dict(resume)
            elif not stopped:
                # a worker stuck in a slow draw could advance the sampler
                # AFTER a restore here — leave the position untouched
                # rather than restore a value the zombie would clobber
                logger.warning(
                    "prefetch worker did not stop within timeout; loader "
                    "position left as-is (checkpoint it only after the "
                    "worker exits)"
                )
            # tail steps past the last steps_per_print boundary still owe
            # their skip accounting / monitor rows
            self._flush_step_metrics()

    # checkpointing is provided by deepspeed_tpu.checkpoint; engine methods
    # delegate so the reference API shape survives.
    def save_checkpoint(self, save_dir, tag=None, client_state=None):
        from ..checkpoint.saving import save_checkpoint as _save

        self.flush_nvme_pipeline()
        # deferred metrics settle inside saving.save_checkpoint (shared
        # with direct callers of the saving module)

        return _save(self, save_dir, tag=tag, client_state=client_state or {})

    def load_checkpoint(self, load_dir, tag=None, **kw):
        from ..checkpoint.saving import load_checkpoint as _load

        # a pending walk would race the swap files being restored AND its
        # result would clobber the loaded params at the next join
        self.flush_nvme_pipeline()

        return _load(self, load_dir, tag=tag, **kw)
