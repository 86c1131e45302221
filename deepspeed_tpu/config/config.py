"""Config system: one JSON/dict tree -> validated dataclasses.

TPU-native counterpart of the reference's ``runtime/config.py``
(``DeepSpeedConfig``) + ``runtime/config_utils.py:17 DeepSpeedConfigModel``.
Keeps the same user-facing JSON keys where they make sense
(``train_batch_size``, ``train_micro_batch_size_per_gpu``,
``gradient_accumulation_steps``, ``zero_optimization.stage`` ...) so a
DeepSpeed user can bring their config file, but validation is plain
dataclasses (no pydantic dependency) and the batch invariant is triangulated
against the mesh's dp world size exactly as the reference does:

    train_batch_size == micro_batch_per_device * gradient_accumulation_steps
                        * dp_world_size
(reference: runtime/config.py _configure_train_batch_size)
"""
from __future__ import annotations

import copy
import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

AUTO = "auto"


class ConfigError(ValueError):
    pass


def _coerce(cls, value):
    """Build a dataclass from a dict, recursing into nested dataclass fields
    and rejecting unknown keys (the reference's pydantic models also forbid
    extras for most sub-configs)."""
    if value is None:
        return cls()
    if dataclasses.is_dataclass(value):
        return value
    if not isinstance(value, dict):
        raise ConfigError(f"expected dict for {cls.__name__}, got {type(value)}")
    names = {f.name: f for f in dataclasses.fields(cls)}
    kwargs = {}
    for k, v in value.items():
        if k not in names:
            raise ConfigError(f"unknown config key '{k}' for {cls.__name__}")
        f = names[k]
        target = None
        if f.default_factory is not dataclasses.MISSING:  # type: ignore[misc]
            probe = f.default_factory()  # type: ignore[misc]
            if dataclasses.is_dataclass(probe):
                target = type(probe)
        if target is not None and isinstance(v, dict):
            v = _coerce(target, v)
        kwargs[k] = v
    return cls(**kwargs)


@dataclass
class ZeroConfig:
    """reference: runtime/zero/config.py:86 DeepSpeedZeroConfig."""

    stage: int = 0
    # ZeRO-3 persistence: params smaller than this stay replicated
    # (reference: stage3_param_persistence_threshold)
    param_persistence_threshold: int = 10_000
    # offload targets: None | "cpu" (host memory space) | "nvme" (local SSD
    # via the C++ AIO engine; reference runtime/zero/offload_config.py)
    offload_optimizer: Optional[str] = None
    offload_param: Optional[str] = None
    offload_nvme_path: str = "/tmp/deepspeed_tpu_nvme"
    # ZeRO++ style knobs
    zero_quantized_weights: bool = False
    zero_quantized_gradients: bool = False
    # LoCo error-feedback for the quantized gradient reduce (reference
    # zero/config.py:315 zeropp_loco_param = {"err_beta": 0.8, "reset_T": 1024})
    zeropp_loco_param: Optional[Dict[str, Any]] = None
    # hpZ: secondary partition size (hierarchical gather group)
    zero_hpz_partition_size: int = 1
    # NVMe offload pipelining (reference offload_config.py:78
    # pipeline_read/pipeline_write -> pipeline): overlap step k's host Adam
    # walk with step k+1's device grad computation (ZeRO-Offload's delayed
    # parameter update — one-step gradient staleness)
    offload_pipeline: bool = False
    # dtype of the gradient D2H transfer feeding the host optimizer walk:
    # "bf16" halves the host-link traffic (the reference's host Adam takes
    # bf16 grads, csrc/adam cpu_adam bf16 path); fp32 master math either way
    offload_grad_dtype: str = "fp32"
    # legacy keys accepted & ignored for compat with reference configs
    allgather_partitions: bool = True
    overlap_comm: bool = True
    reduce_scatter: bool = True
    contiguous_gradients: bool = True
    sub_group_size: int = 1_000_000_000
    stage3_max_live_parameters: int = 1_000_000_000
    stage3_prefetch_bucket_size: int = 50_000_000
    stage3_param_persistence_threshold: Optional[int] = None
    reduce_bucket_size: int = 500_000_000
    round_robin_gradients: bool = False
    mics_shard_size: int = -1

    def __post_init__(self):
        if not 0 <= self.stage <= 3:
            raise ConfigError(f"zero_optimization.stage must be 0-3, got {self.stage}")
        if self.stage3_param_persistence_threshold is not None:
            self.param_persistence_threshold = self.stage3_param_persistence_threshold
        for k in ("offload_optimizer", "offload_param"):
            v = getattr(self, k)
            if isinstance(v, dict):  # reference nests {"device": "cpu", ...}
                if v.get("nvme_path"):
                    self.offload_nvme_path = v["nvme_path"]
                if k == "offload_optimizer" and (
                    v.get("pipeline") or v.get("pipeline_read")
                    or v.get("pipeline_write")
                ):
                    self.offload_pipeline = True
                setattr(self, k, v.get("device"))
        if self.offload_optimizer not in (None, "none", "cpu", "nvme"):
            raise ConfigError(f"bad offload_optimizer {self.offload_optimizer}")
        if self.offload_param not in (None, "none", "cpu"):
            raise ConfigError(
                f"bad offload_param {self.offload_param!r} (supported: cpu; "
                "params-to-nvme has no TPU implementation yet)"
            )
        if self.offload_optimizer == "none":
            self.offload_optimizer = None
        if self.offload_param == "none":
            self.offload_param = None
        if self.offload_grad_dtype not in ("fp32", "bf16"):
            raise ConfigError(
                f"offload_grad_dtype must be fp32|bf16, got {self.offload_grad_dtype!r}"
            )
        if self.offload_pipeline and self.offload_optimizer != "nvme":
            raise ConfigError(
                "offload_optimizer pipeline/pipeline_read/pipeline_write is "
                "implemented for device='nvme' only (the CPU tier's step is "
                "a single fused jit with nothing to overlap)"
            )


@dataclass
class TrainDataConfig:
    """Input-pipeline knobs (runtime/prefetch.py — the latency-hiding input
    pipeline).

    ``prefetch_depth``: bounded count of global batches collated +
    ``device_put`` into the engine's batch shardings ahead of the step by a
    background worker (2 = double buffering; 0 disables prefetch so
    ``train_on_loader`` degenerates to the synchronous loop).

    ``async_metrics``: keep ``StepMetrics`` as device arrays and defer every
    host read (fp16 skip accounting, monitor emission, throughput timer
    sync) to ``steps_per_print`` boundaries or an explicit
    ``engine.get_last_loss()``.  The flops profiler and
    ``wall_clock_breakdown`` still request synced reads at their own
    boundaries regardless.
    """

    prefetch_depth: int = 2
    async_metrics: bool = True

    def __post_init__(self):
        if not 0 <= self.prefetch_depth <= 64:
            raise ConfigError(
                f"train_data.prefetch_depth must be in [0, 64] (each slot "
                f"parks one global batch in device memory), got "
                f"{self.prefetch_depth}"
            )


@dataclass
class TelemetryConfig:
    """Unified-telemetry knobs (telemetry/ — metrics registry, tick spans,
    per-request serve traces).

    ``enabled`` turns on histogram/span/trace recording; the engines'
    ``stats`` counters count either way (they are a correctness surface).
    ``jsonl_path`` appends structured events (per-request summaries) as one
    JSON object per line.  ``chrome_trace_path`` writes the span + request
    timeline as Chrome trace-event JSON on engine close/exit — load it at
    https://ui.perfetto.dev.  ``jax_profiler`` mirrors every
    ``Telemetry.span()`` into a ``jax.profiler.TraceAnnotation`` of the same
    name and ``span_id``, so a live ``jax.profiler`` capture holds the
    program's span tree on the trace's clock next to the device's events.  ``exact_quantiles`` is the raw
    sample count histograms retain before degrading to the log-bucket
    estimate; ``max_spans`` bounds the span ring buffer."""

    enabled: bool = False
    jsonl_path: Optional[str] = None
    chrome_trace_path: Optional[str] = None
    jax_profiler: bool = False
    exact_quantiles: int = 4096
    max_spans: int = 65536

    def __post_init__(self):
        if self.exact_quantiles < 0:
            raise ConfigError(
                f"telemetry.exact_quantiles must be >= 0, got {self.exact_quantiles}"
            )
        if self.max_spans < 1:
            raise ConfigError(
                f"telemetry.max_spans must be >= 1, got {self.max_spans}"
            )


@dataclass
class AdaptationConfig:
    """Online-autotuning controller knobs (``autotuning/controller.py``).

    The controller samples the live telemetry registry every
    ``epoch_s`` seconds (windowed TTFT/TBT percentiles, spec accept-rate,
    queue depth, pool headroom, ``comm/bytes_on_wire``) and retunes the
    live-tier knobs (``prefill_chunk``, ``kv_watermark``,
    ``spec_max_draft``, shed thresholds, ``decode_megastep``) through
    ``ServeScheduler.apply_knobs``.  Every retune opens ``guard_epochs``
    A/B guard epochs: if the SLO percentile the change was meant to
    improve regresses by more than ``regress_tolerance`` (ratio), the
    change rolls back and the knob enters ``cooldown_epochs`` of
    cooldown.  Rebuild-tier knobs (tp / serve_replicas / weight quant /
    ``quant_comm`` — frozen into compiled programs) are only PROPOSED,
    and only when the roofline-predicted win clears ``rebuild_hysteresis``;
    the engine's single-owner thread executes the rebuild
    (``engine.close()`` + ``build_serve_engine``), never the controller
    thread."""

    enabled: bool = False
    epoch_s: float = 0.25
    min_window: int = 4  # min windowed samples before any decision
    guard_epochs: int = 2
    regress_tolerance: float = 1.15  # guard metric ratio that triggers rollback
    cooldown_epochs: int = 4
    rebuild_hysteresis: float = 1.25  # predicted-cost ratio gating a rebuild proposal
    allow_rebuild: bool = True
    # SLO targets the retune heuristics steer toward (None = throughput-only)
    ttft_slo_ms: Optional[float] = None
    tbt_slo_ms: Optional[float] = None
    max_decode_megastep: int = 8
    max_spec_draft: int = 8

    def __post_init__(self):
        if self.epoch_s <= 0:
            raise ConfigError(
                f"adaptation.epoch_s must be positive, got {self.epoch_s}")
        for k in ("min_window", "guard_epochs", "cooldown_epochs",
                  "max_decode_megastep", "max_spec_draft"):
            if int(getattr(self, k)) < 1:
                raise ConfigError(
                    f"adaptation.{k} must be >= 1, got {getattr(self, k)}")
        for k in ("regress_tolerance", "rebuild_hysteresis"):
            if getattr(self, k) < 1.0:
                raise ConfigError(
                    f"adaptation.{k} must be >= 1.0 (a ratio), got "
                    f"{getattr(self, k)}")
        for k in ("ttft_slo_ms", "tbt_slo_ms"):
            v = getattr(self, k)
            if v is not None and v <= 0:
                raise ConfigError(
                    f"adaptation.{k} must be positive or None, got {v}")


@dataclass
class ServeConfig:
    """Fault-tolerant-serving knobs (inference/scheduler.py lifecycle layer).
    Consumed by ``InferenceEngineV2(serve=...)`` / ``ServeScheduler`` — the
    serving stack's config block, not a training-engine key.

    ``deadline_ms`` / ``ttft_deadline_ms``: default per-request end-to-end /
    first-token deadlines, checked at tick boundaries (None = none; a
    ``submit()`` may override per request).  ``max_retries``: bounded
    retries of a transiently-failing dispatch before requests are failed;
    ``retry_backoff_ms`` is the exponential-backoff base.
    ``shed_queue_depth``: waiting-queue depth that flips the scheduler into
    shed mode (new submissions get a typed RETRY_LATER rejection, and
    speculation is disabled until the queue drains; None = never shed).
    ``watchdog_tick_ms``: tick-duration watchdog — this many milliseconds
    per tick, ``watchdog_grace_ticks`` ticks in a row, also enters shed
    mode (None disables the watchdog)."""

    deadline_ms: Optional[float] = None
    ttft_deadline_ms: Optional[float] = None
    max_retries: int = 3
    retry_backoff_ms: float = 20.0
    shed_queue_depth: Optional[int] = None
    watchdog_tick_ms: Optional[float] = None
    watchdog_grace_ticks: int = 3
    # quantized-collective transport for TP serving's row-parallel partial
    # sums (comm/qcomm.py): 'none' (exact lax.psum — the default, token-
    # identical to pre-qcomm serving), 'int8' or 'fp8' (EQuARX-style
    # quantized all-reduce, lossy within documented tolerance).
    # ``comm_tiles`` > 1 splits each row-parallel matmul output into that
    # many free-dim tiles reduced independently (T3-style overlap).
    quant_comm: str = "none"
    comm_tiles: int = 1
    # megastep decode: fuse up to this many decode-only scheduler ticks
    # into ONE device-resident engine burst (one host sync for the whole
    # run of ticks; stop tokens / length caps are detected on device, so
    # the fused ticks stay token-identical to per-tick decode).  1 = off.
    # The scheduler adaptively collapses to per-tick whenever the tick has
    # non-decode work (queued admissions, running prefills, live
    # speculation proposals) and clamps the fuse count to the nearest
    # request deadline — but deadline/cancel/watchdog checks still only
    # run at megastep BOUNDARIES, so the reaction latency bound grows to
    # decode_megastep x per-tick duration.
    decode_megastep: int = 1
    # online autotuning (autotuning/controller.py): the telemetry-driven
    # controller that retunes the live-tier knobs under traffic drift.
    # Off by default — enabled=False is token-identical to no controller
    # (nothing samples, nothing retunes).
    adaptation: AdaptationConfig = field(default_factory=AdaptationConfig)

    def __post_init__(self):
        if not isinstance(self.adaptation, AdaptationConfig):
            self.adaptation = _coerce(AdaptationConfig, self.adaptation)
        if self.quant_comm not in ("none", "int8", "fp8"):
            raise ConfigError(
                f"serve.quant_comm must be one of 'none'|'int8'|'fp8', "
                f"got {self.quant_comm!r}")
        if self.comm_tiles < 1:
            raise ConfigError(
                f"serve.comm_tiles must be >= 1, got {self.comm_tiles}")
        if self.decode_megastep < 1:
            raise ConfigError(
                f"serve.decode_megastep must be >= 1, got "
                f"{self.decode_megastep}")
        for k in ("deadline_ms", "ttft_deadline_ms", "watchdog_tick_ms"):
            v = getattr(self, k)
            if v is not None and v <= 0:
                raise ConfigError(f"serve.{k} must be positive or None, got {v}")
        if self.max_retries < 0:
            raise ConfigError(
                f"serve.max_retries must be >= 0, got {self.max_retries}")
        if self.retry_backoff_ms < 0:
            raise ConfigError(
                f"serve.retry_backoff_ms must be >= 0, got "
                f"{self.retry_backoff_ms}")
        if self.shed_queue_depth is not None and self.shed_queue_depth < 1:
            raise ConfigError(
                f"serve.shed_queue_depth must be >= 1 or None, got "
                f"{self.shed_queue_depth}")
        if self.watchdog_grace_ticks < 1:
            raise ConfigError(
                f"serve.watchdog_grace_ticks must be >= 1, got "
                f"{self.watchdog_grace_ticks}")


@dataclass
class ServeEngineConfig:
    """Canonical build-an-``InferenceEngineV2``-from-config seam.

    One validated block capturing the serving-engine constructor surface
    (pool shape, scheduler knobs, quant format, parallelism), so the
    autotuner's trials and any
    front end all construct engines through ONE path
    (``inference.engine_v2.build_serve_engine``) instead of re-spelling
    keyword soup.  ``tp``/``serve_replicas``/``seq_shards`` > 1 make the
    builder bring up the batch x seq x model mesh itself."""

    max_seqs: int = 8
    num_blocks: int = 96
    block_size: int = 32
    max_seq_len: Optional[int] = None
    prefill_buckets: List[int] = field(
        default_factory=lambda: [64, 128, 256])
    prefill_budget: Optional[int] = None
    prefill_chunk: Optional[int] = None
    kv_watermark: float = 0.0625
    enable_prefix_caching: bool = False
    enable_speculation: bool = False
    spec_max_draft: int = 4
    quantize_weights: Optional[str] = None
    tp: int = 1
    serve_replicas: int = 1
    seq_shards: int = 1
    quant_comm: str = "none"
    comm_tiles: int = 1
    seed: int = 0

    def __post_init__(self):
        for k in ("max_seqs", "num_blocks", "block_size", "tp",
                  "serve_replicas", "seq_shards", "comm_tiles"):
            if int(getattr(self, k)) < 1:
                raise ConfigError(f"serve_engine.{k} must be >= 1, got "
                                  f"{getattr(self, k)}")
        if not 0.0 <= self.kv_watermark < 1.0:
            raise ConfigError(
                f"serve_engine.kv_watermark must be in [0, 1), got "
                f"{self.kv_watermark}")
        if self.quantize_weights not in (None, "int8", "fp8", "fp6"):
            raise ConfigError(
                f"serve_engine.quantize_weights must be None|int8|fp8|fp6, "
                f"got {self.quantize_weights!r}")
        if self.quant_comm not in ("none", "int8", "fp8"):
            raise ConfigError(
                f"serve_engine.quant_comm must be none|int8|fp8, got "
                f"{self.quant_comm!r}")
        if not self.prefill_buckets:
            raise ConfigError("serve_engine.prefill_buckets cannot be empty")
        if self.prefill_chunk is not None and self.prefill_chunk < 1:
            raise ConfigError(
                f"serve_engine.prefill_chunk must be >= 1 or None, got "
                f"{self.prefill_chunk}")

    def engine_kwargs(self) -> Dict[str, Any]:
        """The ``InferenceEngineV2`` constructor kwargs this block encodes
        (mesh construction is the builder's job — ``tp``/``serve_replicas``
        are not raw engine kwargs)."""
        return dict(
            max_seqs=self.max_seqs, num_blocks=self.num_blocks,
            block_size=self.block_size, max_seq_len=self.max_seq_len,
            prefill_buckets=tuple(self.prefill_buckets),
            prefill_budget=self.prefill_budget,
            prefill_chunk=self.prefill_chunk,
            kv_watermark=self.kv_watermark,
            enable_prefix_caching=self.enable_prefix_caching,
            enable_speculation=self.enable_speculation,
            spec_max_draft=max(self.spec_max_draft, 1),
            quantize_weights=self.quantize_weights,
            serve_replicas=self.serve_replicas,
            seq_shards=self.seq_shards,
            quant_comm=self.quant_comm, comm_tiles=self.comm_tiles,
            seed=self.seed,
        )


@dataclass
class RouterConfig:
    """Serve-front-end knobs (``serving/`` — the disaggregated request
    router over N engine workers).  Consumed by ``serving.Router`` /
    ``serving.build_router``; one validated block so tests and
    launchers spell the routing policy the same way.

    ``n_workers``: engine workers the pool stamps out (each via
    ``build_serve_engine`` from one ``ServeEngineConfig``);
    ``prefill_workers``: the first K workers take the PREFILL role — long
    prompts land there and migrate to a decode worker at first token via
    the paged-KV handoff (0 disables disaggregation).
    ``disagg_threshold``: prompt length (tokens) from which a request
    counts as long (None = the engine's prefill chunk).
    ``handoff_fmt``: KV-handoff wire format — 'none' ships pages in the
    cache dtype (token-exact), 'int8'/'fp8' quantize per qcomm's
    per-chunk-scale payload codec (~half/quarter the bytes, lossy within
    the same tolerance as quantized collectives).
    ``affinity``: prefix-affinity routing — chained full-block content
    hashes map a prompt's shared prefix to the worker already holding its
    blocks (fall back: least-loaded); ``affinity_max_keys`` bounds the
    router's hash->worker map (LRU).
    ``shed_queue_depth``: router-side backlog depth that sheds new
    submissions at the front door with typed RETRY_LATER (None = never).
    ``max_replays``: times a request may re-route and replay from its
    prompt after a worker death before it is failed.
    ``retry_backoff_ms``: fallback backoff when a worker rejects
    RETRY_LATER without a ``retry_after_ms`` hint.

    Out-of-process transport knobs (``serving/transport.py`` /
    ``serving/remote.py`` — ignored by in-process pools):
    ``heartbeat_interval_ms``/``lease_ms``: the monitor pings each worker's
    dedicated heartbeat channel every interval; a worker silent past the
    lease has its lease EXPIRE and is discovered dead (its requests replay
    elsewhere).  ``rpc_deadline_ms``: absolute per-RPC budget (a backstop —
    lease expiry aborts waits much earlier); ``rpc_max_attempts`` /
    ``rpc_backoff_ms`` / ``rpc_backoff_max_ms``: bounded exponential
    reconnect backoff (with deterministic jitter) on transient transport
    failures; ``connect_timeout_ms``: per-channel dial budget;
    ``max_frame_bytes``: oversized-frame guard on both sides of the wire
    (KV-handoff payloads are the big frames)."""

    n_workers: int = 2
    prefill_workers: int = 0
    disagg_threshold: Optional[int] = None
    handoff_fmt: str = "none"
    affinity: bool = True
    affinity_max_keys: int = 8192
    shed_queue_depth: Optional[int] = None
    max_replays: int = 3
    retry_backoff_ms: float = 20.0
    heartbeat_interval_ms: float = 50.0
    lease_ms: float = 1000.0
    rpc_deadline_ms: float = 120_000.0
    rpc_max_attempts: int = 5
    rpc_backoff_ms: float = 10.0
    rpc_backoff_max_ms: float = 250.0
    connect_timeout_ms: float = 30_000.0
    max_frame_bytes: int = 64 * 1024 * 1024
    # wire-level megastep: scheduler ticks batched into ONE step_burst RPC
    # per worker per router tick (1 = the classic begin/finish tick pair).
    # The worker runs up to this many ticks back to back and replies once —
    # router-side death discovery, cancel forwarding and terminal
    # collection shift to megastep boundaries (latency bound:
    # decode_megastep x worker tick duration).  Exactly-once replay is
    # unchanged: the whole burst is one rid in the reply cache.
    decode_megastep: int = 1
    # fleet observability (telemetry/fleet.py): a router-side collector
    # thread pulls each worker's mergeable registry snapshot over its own
    # metrics channel every ``metrics_pull_interval_ms`` and folds it into
    # the FleetRegistry/SloMonitor published through ``Router.signals()``.
    # Off by default — disabled is byte-identical to no collector (nothing
    # dials, nothing pulls).  ``slo_objective`` is the availability target
    # the burn rates are computed against (error budget = 1 - objective);
    # ``slo_fast_window_s``/``slo_slow_window_s`` are the two burn-rate
    # windows (fast catches a cliff, slow catches a smoulder).
    # ``pull_spans``: also drain worker span events each pull so
    # ``fleet_chrome_trace`` can stitch one cross-process timeline.
    metrics_pull_interval_ms: Optional[float] = None
    pull_spans: bool = True
    slo_objective: float = 0.999
    slo_fast_window_s: float = 5.0
    slo_slow_window_s: float = 60.0

    def __post_init__(self):
        if self.n_workers < 1:
            raise ConfigError(
                f"router.n_workers must be >= 1, got {self.n_workers}")
        if not 0 <= self.prefill_workers < self.n_workers:
            raise ConfigError(
                f"router.prefill_workers must be in [0, n_workers), got "
                f"{self.prefill_workers} of {self.n_workers} (at least one "
                "decode-capable worker must remain)")
        if self.handoff_fmt not in ("none", "int8", "fp8"):
            raise ConfigError(
                f"router.handoff_fmt must be none|int8|fp8, got "
                f"{self.handoff_fmt!r}")
        if self.disagg_threshold is not None and self.disagg_threshold < 1:
            raise ConfigError(
                f"router.disagg_threshold must be >= 1 or None, got "
                f"{self.disagg_threshold}")
        if self.affinity_max_keys < 1:
            raise ConfigError(
                f"router.affinity_max_keys must be >= 1, got "
                f"{self.affinity_max_keys}")
        if self.shed_queue_depth is not None and self.shed_queue_depth < 1:
            raise ConfigError(
                f"router.shed_queue_depth must be >= 1 or None, got "
                f"{self.shed_queue_depth}")
        if self.max_replays < 0:
            raise ConfigError(
                f"router.max_replays must be >= 0, got {self.max_replays}")
        if self.retry_backoff_ms < 0:
            raise ConfigError(
                f"router.retry_backoff_ms must be >= 0, got "
                f"{self.retry_backoff_ms}")
        if self.heartbeat_interval_ms <= 0:
            raise ConfigError(
                f"router.heartbeat_interval_ms must be > 0, got "
                f"{self.heartbeat_interval_ms}")
        if self.lease_ms <= self.heartbeat_interval_ms:
            raise ConfigError(
                f"router.lease_ms ({self.lease_ms}) must exceed "
                f"heartbeat_interval_ms ({self.heartbeat_interval_ms}) — a "
                "lease shorter than one ping interval expires every healthy "
                "worker")
        if self.rpc_deadline_ms <= 0 or self.connect_timeout_ms <= 0:
            raise ConfigError(
                "router.rpc_deadline_ms and connect_timeout_ms must be > 0")
        if self.rpc_max_attempts < 1:
            raise ConfigError(
                f"router.rpc_max_attempts must be >= 1, got "
                f"{self.rpc_max_attempts}")
        if self.rpc_backoff_ms < 0 or self.rpc_backoff_max_ms < self.rpc_backoff_ms:
            raise ConfigError(
                "router rpc backoff must satisfy 0 <= rpc_backoff_ms <= "
                f"rpc_backoff_max_ms, got {self.rpc_backoff_ms}/"
                f"{self.rpc_backoff_max_ms}")
        if self.max_frame_bytes < 4096:
            raise ConfigError(
                f"router.max_frame_bytes must be >= 4096, got "
                f"{self.max_frame_bytes}")
        if self.decode_megastep < 1:
            raise ConfigError(
                f"router.decode_megastep must be >= 1, got "
                f"{self.decode_megastep}")
        if (self.metrics_pull_interval_ms is not None
                and self.metrics_pull_interval_ms <= 0):
            raise ConfigError(
                f"router.metrics_pull_interval_ms must be > 0 or None, got "
                f"{self.metrics_pull_interval_ms}")
        if not 0.0 < self.slo_objective < 1.0:
            raise ConfigError(
                f"router.slo_objective must be in (0, 1), got "
                f"{self.slo_objective}")
        if self.slo_fast_window_s <= 0 or self.slo_slow_window_s <= 0:
            raise ConfigError(
                "router.slo_fast_window_s and slo_slow_window_s must be > 0")
        if self.slo_slow_window_s < self.slo_fast_window_s:
            raise ConfigError(
                f"router.slo_slow_window_s ({self.slo_slow_window_s}) must "
                f"be >= slo_fast_window_s ({self.slo_fast_window_s})")


@dataclass
class AutotuneConfig:
    """Autotuner knobs (``autotuning/`` — the roofline-seeded config
    search).  Consumed by the offline entrypoints
    (``autotuning.autotune_model`` / ``autotune_serving``), never by the
    runtime engine — same split as the reference's ds_autotuner.

    ``mode`` picks the workload (``training`` | ``serving``); ``rungs``
    are the successive-halving budget fractions (ascending, final must be
    1.0 = the full trial workload); ``top_k`` is the rung-0 cohort size
    taken from the roofline ranking; ``eta`` the halving divisor;
    ``max_trials`` caps total measured runs.
    ``leaderboard_path`` is where the per-trial JSON leaderboard lands."""

    enabled: bool = False
    mode: str = "serving"
    metric: str = "throughput"
    max_trials: int = 16
    top_k: int = 8
    eta: int = 2
    rungs: List[float] = field(default_factory=lambda: [0.25, 1.0])
    seed: int = 0
    leaderboard_path: Optional[str] = None

    def __post_init__(self):
        if self.mode not in ("training", "serving"):
            raise ConfigError(
                f"autotune.mode must be training|serving, got {self.mode!r}")
        if self.metric not in ("throughput", "latency"):
            raise ConfigError(
                f"autotune.metric must be throughput|latency, got "
                f"{self.metric!r}")
        if self.max_trials < 1 or self.top_k < 1:
            raise ConfigError("autotune.max_trials/top_k must be >= 1")
        if self.eta < 2:
            raise ConfigError(f"autotune.eta must be >= 2, got {self.eta}")
        if (not self.rungs or list(self.rungs) != sorted(self.rungs)
                or self.rungs[0] <= 0 or abs(self.rungs[-1] - 1.0) > 1e-9):
            raise ConfigError(
                f"autotune.rungs must ascend and end at 1.0, got {self.rungs}")


@dataclass
class PrecisionConfig:
    enabled: bool = False
    loss_scale: float = 0.0  # 0 -> dynamic
    initial_scale_power: int = 16
    loss_scale_window: int = 1000
    hysteresis: int = 2
    min_loss_scale: float = 1.0
    consecutive_hysteresis: bool = False
    auto_cast: bool = False


@dataclass
class OptimizerConfig:
    type: str = "adamw"
    params: Dict[str, Any] = field(default_factory=dict)


@dataclass
class SchedulerConfig:
    type: Optional[str] = None
    params: Dict[str, Any] = field(default_factory=dict)


def _strip_auto(obj):
    """Drop ``"auto"`` values at every nesting level.  HF-integration configs
    use nested autos (e.g. optimizer.params.lr = "auto"); integrations resolve
    them, and standalone use falls back to our defaults — matching the
    reference's behaviour where unresolved autos are an integration concern."""
    if isinstance(obj, dict):
        return {k: _strip_auto(v) for k, v in obj.items() if v != AUTO}
    if isinstance(obj, list):
        return [_strip_auto(v) for v in obj if v != AUTO]
    return obj


@dataclass
class MonitorSubConfig:
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedTpuJob"
    # wandb extras
    team: Optional[str] = None
    group: Optional[str] = None
    project: Optional[str] = None
    # comet extras (reference monitor/config.py CometConfig)
    api_key: Optional[str] = None
    workspace: Optional[str] = None
    experiment_name: Optional[str] = None
    experiment_key: Optional[str] = None
    online: Optional[bool] = None
    mode: Optional[str] = None
    samples_log_interval: int = 100


@dataclass
class CommsLoggerConfig:
    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    debug: bool = False
    prof_ops: List[str] = field(default_factory=list)


@dataclass
class FlopsProfilerConfig:
    enabled: bool = False
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None


@dataclass
class ActivationCheckpointingConfig:
    partition_activations: bool = False
    cpu_checkpointing: bool = False
    contiguous_memory_optimization: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False
    # TPU-native: remat policy name handed to jax.checkpoint
    policy: str = "nothing_saveable"


@dataclass
class MeshConfig:
    """Mesh axis sizes; 0/absent axes are inferred (leftover -> data)."""

    data: int = 0
    fsdp: int = 0
    model: int = 1
    seq: int = 1
    expert: int = 1
    stage: int = 1


@dataclass
class MoEConfig:
    enabled: bool = False
    num_experts: int = 1
    top_k: int = 2
    capacity_factor: float = 1.0
    eval_capacity_factor: float = 1.0
    min_capacity: int = 4
    drop_tokens: bool = True
    aux_loss_coef: float = 0.01


@dataclass
class TensorParallelConfig:
    enabled: bool = False
    tp_size: int = 1
    # Domino-style micro-chunked TP overlap (reference runtime/domino):
    # batch chunks per layer whose independent dataflows let XLA overlap
    # TP all-reduces with compute; 1 = off
    domino_chunks: int = 1

    def __post_init__(self):
        if self.domino_chunks < 1:
            raise ConfigError(
                f"tensor_parallel.domino_chunks must be >= 1, got "
                f"{self.domino_chunks}"
            )


@dataclass
class CheckpointConfig:
    # async checkpointing via a background committer thread
    use_node_local_storage: bool = False
    load_universal: bool = False
    async_save: bool = False


@dataclass
class CompressionConfig:
    enabled: bool = False
    weight_quantization: Dict[str, Any] = field(default_factory=dict)
    activation_quantization: Dict[str, Any] = field(default_factory=dict)
    sparse_pruning: Dict[str, Any] = field(default_factory=dict)
    # structured compression (reference compression/constants.py:137-180, :27)
    row_pruning: Dict[str, Any] = field(default_factory=dict)
    head_pruning: Dict[str, Any] = field(default_factory=dict)
    channel_pruning: Dict[str, Any] = field(default_factory=dict)
    layer_reduction: Dict[str, Any] = field(default_factory=dict)

    def as_dict(self) -> Dict[str, Any]:
        return {
            "weight_quantization": self.weight_quantization,
            "activation_quantization": self.activation_quantization,
            "sparse_pruning": self.sparse_pruning,
            "row_pruning": self.row_pruning,
            "head_pruning": self.head_pruning,
            "channel_pruning": self.channel_pruning,
            "layer_reduction": self.layer_reduction,
        }

    @property
    def any_technique(self) -> bool:
        return bool(
            self.weight_quantization or self.activation_quantization
            or self.sparse_pruning or self.row_pruning or self.head_pruning
            or self.channel_pruning
        )


@dataclass
class DataEfficiencyConfig:
    enabled: bool = False
    curriculum_learning: Dict[str, Any] = field(default_factory=dict)


@dataclass
class PLDConfig:
    """reference: runtime/config.py progressive_layer_drop + PLD post."""

    enabled: bool = False
    theta: float = 0.5
    gamma: float = 0.001


@dataclass
class EigenvalueConfig:
    """reference: runtime/config.py eigenvalue_* (engine.py:1503 hook)."""

    enabled: bool = False
    verbose: bool = False
    max_iter: int = 100
    tol: float = 1e-2
    stability: float = 1e-6
    gas_boundary_resolution: int = 1
    layer_name: str = ""
    layer_num: int = 0

    def __post_init__(self):
        if self.gas_boundary_resolution < 1:
            raise ConfigError(
                f"eigenvalue.gas_boundary_resolution must be >= 1, got "
                f"{self.gas_boundary_resolution}"
            )
        if self.max_iter < 1:
            raise ConfigError(f"eigenvalue.max_iter must be >= 1, got {self.max_iter}")


@dataclass
class SparseAttentionConfig:
    """reference: ops/sparse_attention/sparsity_config.py schemas; mode ''
    (absent key) = disabled.  Only keys relevant to the implemented layouts
    are accepted — the point is config-drives-behavior, not schema cosplay."""

    mode: str = ""
    block: int = 16
    different_layout_per_head: bool = False
    # fixed
    num_local_blocks: int = 4
    num_global_blocks: int = 1
    # bigbird
    num_random_blocks: int = 1
    num_sliding_window_blocks: int = 3
    # bsLongformer
    global_block_indices: List[int] = field(default_factory=lambda: [0])
    # variable
    local_window_blocks: List[int] = field(default_factory=lambda: [4])

    def __post_init__(self):
        if self.mode not in ("", "dense", "fixed", "bigbird", "bsLongformer",
                             "variable"):
            raise ConfigError(
                f"sparse_attention.mode '{self.mode}' not in "
                "dense|fixed|bigbird|bsLongformer|variable"
            )
        if self.different_layout_per_head:
            raise ConfigError(
                "sparse_attention.different_layout_per_head is not supported: "
                "all heads share one block layout here"
            )
        if self.block < 1:
            raise ConfigError(f"sparse_attention.block must be >= 1, got {self.block}")
        if any(w < 1 for w in self.local_window_blocks):
            raise ConfigError(
                f"sparse_attention.local_window_blocks must be positive, got "
                f"{self.local_window_blocks}"
            )

    def build(self):
        """Instantiate the ops-level SparsityConfig for this mode."""
        from ..ops.sparse_attention import (
            BigBirdSparsityConfig,
            BSLongformerSparsityConfig,
            DenseSparsityConfig,
            FixedSparsityConfig,
            VariableSparsityConfig,
        )

        if self.mode in ("", "dense"):
            return DenseSparsityConfig(block=self.block)
        if self.mode == "fixed":
            return FixedSparsityConfig(
                block=self.block,
                num_local_blocks=self.num_local_blocks,
                num_global_blocks=self.num_global_blocks,
            )
        if self.mode == "bigbird":
            return BigBirdSparsityConfig(
                block=self.block,
                num_random_blocks=self.num_random_blocks,
                num_sliding_window_blocks=self.num_sliding_window_blocks,
                num_global_blocks=self.num_global_blocks,
            )
        if self.mode == "bsLongformer":
            return BSLongformerSparsityConfig(
                block=self.block,
                num_sliding_window_blocks=self.num_sliding_window_blocks,
                global_block_indices=tuple(self.global_block_indices),
            )
        return VariableSparsityConfig(
            block=self.block,
            local_window_blocks=tuple(self.local_window_blocks),
            num_global_blocks=self.num_global_blocks,
        )


@dataclass
class CompileConfig:
    """reference: runtime/compiler.py CompileConfig (torch.compile knobs).

    On TPU, jit IS the substrate — ``enabled`` is accepted (always true in
    effect) and ``disable: true`` switches the engine's train/eval steps to
    eager per-op execution for debugging (the torch.compile-disable
    analogue).  ``backend``/``kwargs`` are validated but vestigial."""

    enabled: bool = True
    disable: bool = False
    backend: str = "xla"
    kwargs: Dict[str, Any] = field(default_factory=dict)


@dataclass
class HybridEngineConfig:
    """reference: runtime/config.py hybrid_engine (DeepSpeedHybridEngine).

    ``max_out_tokens`` caps generate() lengths.  ``inference_tp_size`` must
    stay 1: hybrid serving follows the training mesh (set mesh.model for TP).
    ``release_inference_cache``/``pin_parameters``/``tp_gather_partition_size``
    are GPU container-flipping knobs with no counterpart (the serving jits
    take live params as arguments; there is nothing to pin or flip) —
    accepted for reference-config compat only."""

    enabled: bool = False
    max_out_tokens: int = 512
    inference_tp_size: int = 1
    release_inference_cache: bool = False
    pin_parameters: bool = True
    tp_gather_partition_size: int = 8


@dataclass
class AIOConfig:
    """reference: runtime/swap_tensor/aio_config.py — thread_count and
    queue_depth reach the C++ AIO engine (csrc/aio) behind NVMe offload/
    swap.  block_size / single_submit / overlap_events are libaio
    submission-strategy knobs with no counterpart in the thread-pool design
    (whole-tensor files, always-overlapped completion thread) — accepted for
    reference-config compat only."""

    block_size: int = 1 << 20
    queue_depth: int = 32
    thread_count: int = 8
    single_submit: bool = False
    overlap_events: bool = True


@dataclass
class NebulaConfig:
    """reference: nebula/config.py — an async checkpoint service.  Mapped to
    the async checkpoint engine (checkpoint/engine.py): enabled => async_save."""

    enabled: bool = False
    persistent_storage_path: Optional[str] = None
    persistent_time_interval: int = 100
    num_of_version_in_retention: int = 2
    enable_nebula_load: bool = True
    load_path: Optional[str] = None


@dataclass
class Config:
    """Top-level validated config (reference: DeepSpeedConfig)."""

    train_batch_size: Optional[int] = None
    train_micro_batch_size_per_gpu: Optional[int] = None
    gradient_accumulation_steps: Optional[int] = None
    steps_per_print: int = 10
    gradient_clipping: float = 0.0
    prescale_gradients: bool = False
    wall_clock_breakdown: bool = False
    memory_breakdown: bool = False
    dump_state: bool = False
    seed: int = 42

    zero_optimization: ZeroConfig = field(default_factory=ZeroConfig)
    bf16: PrecisionConfig = field(default_factory=lambda: PrecisionConfig(enabled=True))
    fp16: PrecisionConfig = field(default_factory=PrecisionConfig)
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    scheduler: SchedulerConfig = field(default_factory=SchedulerConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    moe: MoEConfig = field(default_factory=MoEConfig)
    tensor_parallel: TensorParallelConfig = field(default_factory=TensorParallelConfig)
    comms_logger: CommsLoggerConfig = field(default_factory=CommsLoggerConfig)
    flops_profiler: FlopsProfilerConfig = field(default_factory=FlopsProfilerConfig)
    activation_checkpointing: ActivationCheckpointingConfig = field(
        default_factory=ActivationCheckpointingConfig
    )
    checkpoint: CheckpointConfig = field(default_factory=CheckpointConfig)
    compression_training: CompressionConfig = field(default_factory=CompressionConfig)
    data_efficiency: DataEfficiencyConfig = field(default_factory=DataEfficiencyConfig)
    tensorboard: MonitorSubConfig = field(default_factory=MonitorSubConfig)
    csv_monitor: MonitorSubConfig = field(default_factory=MonitorSubConfig)
    wandb: MonitorSubConfig = field(default_factory=MonitorSubConfig)
    comet: MonitorSubConfig = field(default_factory=MonitorSubConfig)
    elasticity: Dict[str, Any] = field(default_factory=dict)
    progressive_layer_drop: PLDConfig = field(default_factory=PLDConfig)
    eigenvalue: EigenvalueConfig = field(default_factory=EigenvalueConfig)
    sparse_attention: SparseAttentionConfig = field(default_factory=SparseAttentionConfig)
    compile: CompileConfig = field(default_factory=CompileConfig)
    hybrid_engine: HybridEngineConfig = field(default_factory=HybridEngineConfig)
    aio: AIOConfig = field(default_factory=AIOConfig)
    nebula: NebulaConfig = field(default_factory=NebulaConfig)
    train_data: TrainDataConfig = field(default_factory=TrainDataConfig)
    telemetry: TelemetryConfig = field(default_factory=TelemetryConfig)
    autotune: AutotuneConfig = field(default_factory=AutotuneConfig)

    # --- derived (filled by finalize) ---
    dp_world_size: int = 1

    @property
    def precision_dtype(self) -> str:
        if self.fp16.enabled:
            return "float16"
        if self.bf16.enabled:
            return "bfloat16"
        return "float32"

    def finalize(self, dp_world_size: int) -> "Config":
        """Triangulate the batch-size triple against dp_world_size.

        Any two of (train_batch_size, micro_batch, gas) determine the third;
        one alone assumes the others; all three must satisfy the invariant.
        Mirrors reference runtime/config.py _configure_train_batch_size.
        """
        self.dp_world_size = dp_world_size
        tb, mb, gas = (
            self.train_batch_size,
            self.train_micro_batch_size_per_gpu,
            self.gradient_accumulation_steps,
        )
        if tb is not None and mb is not None and gas is not None:
            if tb != mb * gas * dp_world_size:
                raise ConfigError(
                    f"batch invariant violated: {tb} != {mb} * {gas} * {dp_world_size}"
                )
        elif tb is not None and mb is not None:
            if tb % (mb * dp_world_size) != 0:
                raise ConfigError(
                    f"train_batch_size {tb} not divisible by micro_batch*dp {mb * dp_world_size}"
                )
            gas = tb // (mb * dp_world_size)
        elif tb is not None and gas is not None:
            if tb % (gas * dp_world_size) != 0:
                raise ConfigError(
                    f"train_batch_size {tb} not divisible by gas*dp {gas * dp_world_size}"
                )
            mb = tb // (gas * dp_world_size)
        elif mb is not None:
            gas = gas if gas is not None else 1
            tb = mb * gas * dp_world_size
        elif gas is not None:
            mb = 1
            tb = mb * gas * dp_world_size
        elif tb is not None:
            gas = 1
            if tb % dp_world_size != 0:
                raise ConfigError(f"train_batch_size {tb} not divisible by dp {dp_world_size}")
            mb = tb // dp_world_size
        else:
            mb, gas = 1, 1
            tb = dp_world_size
        self.train_batch_size, self.train_micro_batch_size_per_gpu = tb, mb
        self.gradient_accumulation_steps = gas
        if self.fp16.enabled and self.bf16.enabled:
            raise ConfigError("fp16 and bf16 cannot both be enabled")
        return self


# Keys a DeepSpeed JSON may contain that are accepted and DELIBERATELY
# ignored — each entry must be genuinely n/a on this stack, with the reason
# recorded here.  Features that exist in this repo must NOT hide in this set
# (the "accepted-and-ignored is worse than absent" rule): their keys are real
# Config fields consumed by initialize()/the engine.
_REFERENCE_PASSTHROUGH_KEYS = {
    # permission flag for unvalidated optimizers under ZeRO — this engine
    # treats every optax optimizer as first-class, so there is nothing to gate
    "zero_allow_untested_optimizer",
    # forces DeepSpeedCPUAdam over torch Adam for CPU offload — there is one
    # host Adam (csrc/adam), no alternative to force
    "zero_force_ds_cpu_optimizer",
    # wire dtype for NCCL collectives — GSPMD inserts collectives in the
    # array dtype; quantized wire formats are the zero++ knobs
    # (zero_quantized_weights/gradients), which ARE consumed
    "communication_data_type",
    # torch sparse embedding gradients — XLA has no sparse gradient type
    "sparse_gradients",
    # NVIDIA apex mixed precision — bf16/fp16 configs are the path here
    "amp",
    # consumed by the offline autotuner entrypoint (autotuning/autotuner.py),
    # never by the runtime engine — same split as the reference's ds_autotuner
    "autotuning",
    # pipeline-engine knobs (partition method, activation checkpoint
    # interval) — stage count and partitioning are constructor arguments of
    # PipelinedCausalLM/PipelineModule, chosen with the model, not the JSON
    "pipeline",
    # ZeRO-Inference post-training weight quantization schema — covered by
    # compression_training.weight_quantization (QAT) and ops/quantizer.py
    "weight_quantization",
    # pluggable checkpoint engine class selection — selection here is
    # checkpoint.async_save / nebula.enabled (checkpoint/engine.py)
    "checkpoint_engine",
}


def parse_config(source: Any, dp_world_size: Optional[int] = None) -> Config:
    """Parse a dict / JSON string / path into a ``Config``.

    ``dp_world_size=None`` leaves batch triangulation for the engine (which
    knows the mesh).
    """
    if source is None:
        raw: Dict[str, Any] = {}
    elif isinstance(source, Config):
        return source
    elif isinstance(source, dict):
        raw = copy.deepcopy(source)
    elif isinstance(source, str):
        if source.strip().startswith("{"):
            raw = json.loads(source)
        else:
            with open(source) as fh:
                raw = json.load(fh)
    else:
        raise ConfigError(f"cannot parse config from {type(source)}")

    for k in list(raw.keys()):
        if k in _REFERENCE_PASSTHROUGH_KEYS:
            raw.pop(k)
    # legacy top-level curriculum (reference runtime/config.py
    # curriculum_learning_legacy) maps onto the data_efficiency section
    if "curriculum_learning" in raw:
        legacy = raw.pop("curriculum_learning")
        if "data_efficiency" not in raw:
            raw["data_efficiency"] = {
                "enabled": bool(legacy.get("enabled", False)),
                "curriculum_learning": legacy,
            }
        # else: the modern section wins (the reference also prefers
        # data_efficiency when both are present)
    raw = _strip_auto(raw)
    cfg = _coerce(Config, raw)
    if cfg.nebula.enabled:
        # nebula IS an async checkpoint service; same engine here
        cfg.checkpoint.async_save = True
    if dp_world_size is not None:
        cfg.finalize(dp_world_size)
    return cfg
