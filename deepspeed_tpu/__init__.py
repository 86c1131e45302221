"""deepspeed_tpu — a TPU-native distributed training & inference framework.

Brand-new JAX/XLA/Pallas implementation of the capability set of the
reference framework (DeepSpeed, mounted at /root/reference): engine API
(``initialize`` mirrors ``deepspeed/__init__.py:69``), ZeRO-style sharded
training, tensor/pipeline/expert/sequence parallelism as mesh axes, a
collective façade, Pallas kernels, checkpointing, and an inference engine.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

__version__ = "0.1.0"

from . import comm  # noqa: F401
from .config.config import Config, ConfigError, parse_config
from .parallel.topology import Grid, MeshSpec, initialize_mesh
from .runtime.dataloader import DeepSpeedTpuDataLoader, RepeatingLoader
from .runtime.engine import DeepSpeedTpuEngine, TrainState
from .telemetry import MetricsRegistry, Telemetry  # noqa: F401
from .utils.logging import log_dist, logger


def _mesh_axes_from_config(cfg: Config, world: int, zero_stage: int):
    """Resolve mesh axis sizes: explicit sizes win; leftover devices go to
    ``fsdp`` when ZeRO>=1 (partitioning wants the fsdp axis) else ``data``.
    ``zero_hpz_partition_size`` / ``mics_shard_size`` factor the fsdp extent
    into (fsdp, sub) so secondary partitions ride the inner ``sub`` axis."""
    m = cfg.mesh
    fixed = {}
    for ax in ("model", "seq", "expert", "stage"):
        v = getattr(m, ax)
        if v and v > 1:
            fixed[ax] = v
    if m.data:
        fixed["data"] = m.data
    if m.fsdp:
        fixed["fsdp"] = m.fsdp
    import math

    used = math.prod(fixed.values()) if fixed else 1
    if "data" not in fixed and "fsdp" not in fixed:
        leftover = world // used
        if zero_stage >= 1:
            fixed["fsdp"] = leftover
            fixed["data"] = 1
        else:
            fixed["data"] = leftover
    elif "data" not in fixed:
        fixed["data"] = world // used
    elif "fsdp" not in fixed:
        fixed["fsdp"] = world // used
    zo = cfg.zero_optimization
    group = max(zo.zero_hpz_partition_size, zo.mics_shard_size)
    if group > 1:
        total = fixed.get("fsdp", 1)
        if total % group:
            raise ConfigError(
                f"hpZ/MiCS group size {group} does not divide the fsdp "
                f"extent {total}"
            )
        fixed["fsdp"] = total // group
        fixed["sub"] = group
    return fixed


def initialize(
    loss_fn: Optional[Callable] = None,
    params: Any = None,
    config: Any = None,
    model: Any = None,
    training_data: Any = None,
    lr_scheduler: Any = None,
    mesh: Optional[Grid] = None,
    tp_rules=None,
    eval_fn: Optional[Callable] = None,
    collate_fn: Optional[Callable] = None,
    dist_init_required: Optional[bool] = None,
    args: Any = None,
):
    """Build the engine — the ``deepspeed.initialize()`` equivalent
    (reference deepspeed/__init__.py:69).

    Returns ``(engine, optimizer, dataloader, lr_scheduler)`` like the
    reference.  ``optimizer`` is the engine itself (the optax transform is
    internal to the jitted step); ``lr_scheduler`` is the engine's scheduler
    shim.

    Two ways to describe the model:
    - ``loss_fn(params, batch, rng) -> scalar`` + initialized ``params``
    - ``model`` = a flax module adapter from ``deepspeed_tpu.models`` that
      exposes ``.loss_fn`` / ``.init_params(rng)`` / ``.tp_rules``
    """
    cfg = parse_config(config)
    if dist_init_required:
        comm.comm.init_distributed()

    hf_dir = None
    if isinstance(model, str):
        # HF checkpoint directory: config now, weights later — the streamed
        # loader needs the mesh + sharding plan so tensors land directly in
        # their shards (no full tree in host RAM)
        import json as _json
        import os as _os

        from .checkpoint.hf_import import config_from_hf
        from .models.transformer import CausalLM

        hf_dir = model
        with open(_os.path.join(hf_dir, "config.json")) as fh:
            model_cfg = config_from_hf(_json.load(fh))
        model = CausalLM(model_cfg)

    def _set_model_cfg(m, new_cfg):
        m.cfg = new_cfg
        inner = getattr(m, "_inner", None)
        if inner is not None and hasattr(inner, "cfg"):
            inner.cfg = new_cfg

    aq = (cfg.compression_training.activation_quantization or {})
    if (
        aq.get("shared_parameters", {}).get("enabled")
        and model is not None
        and hasattr(model, "cfg")
        and hasattr(model.cfg, "act_quant_bits")
    ):
        # wire activation fake-quant into the model family (the engine-side
        # CompressionManager only transforms weights — activations live
        # inside the model's forward)
        groups = aq.get("different_groups", {}) or {}
        first = next(iter(groups.values()), {})
        bits = int(first.get("params", {}).get("bits", 8))
        _set_model_cfg(model, model.cfg.replace(act_quant_bits=bits))
        log_dist(f"activation quantization: {bits}-bit STE on sublayer inputs")

    if cfg.sparse_attention.mode:
        # block-sparse attention layouts are a model-forward construct (the
        # reference swaps attention modules via SparseAttentionUtils) — the
        # config key must change behavior, never be silently dropped
        if model is None or not hasattr(model, "cfg"):
            raise ConfigError(
                "sparse_attention requires model= (a models.CausalLM); it "
                "cannot be injected into a raw loss_fn"
            )
        if getattr(model.cfg, "sequence_parallel", "none") == "ring":
            raise ConfigError(
                "sparse_attention composes with ulysses but not ring "
                "(ring attention supplies its own attention body)"
            )
        sp = cfg.sparse_attention.build()
        _set_model_cfg(model, model.cfg.replace(sparse_attention=sp))
        log_dist(
            f"sparse attention: mode={cfg.sparse_attention.mode} "
            f"block={sp.block}"
        )

    if cfg.tensor_parallel.domino_chunks > 1:
        if model is None or not hasattr(model, "cfg"):
            raise ConfigError(
                "tensor_parallel.domino_chunks requires model= (a "
                "models.CausalLM); it cannot chunk a raw loss_fn"
            )
        if getattr(model.cfg, "moe_num_experts", 0) > 0:
            raise ConfigError(
                "domino_chunks does not compose with MoE: capacity-based "
                "routing per chunk would change token dropping vs the "
                "full-batch build (not an overlap-only transformation)"
            )
        _set_model_cfg(
            model,
            model.cfg.replace(domino_chunks=cfg.tensor_parallel.domino_chunks),
        )
        log_dist(
            f"domino TP overlap: {cfg.tensor_parallel.domino_chunks} "
            "chunks per layer"
        )

    if cfg.progressive_layer_drop.enabled:
        if model is None or not hasattr(model, "cfg"):
            raise ConfigError(
                "progressive_layer_drop requires model= (a models.CausalLM) "
                "so the engine can thread the per-step layer-keep mask"
            )
        if getattr(model, "_inner", None) is not None:
            raise ConfigError(
                "progressive_layer_drop is not supported on the pipelined "
                "stack (per-stage layer-keep routing pending); use a dense "
                "CausalLM or disable PLD"
            )

    if model is not None and loss_fn is None:
        loss_fn = model.loss_fn
        if tp_rules is None:
            tp_rules = getattr(model, "tp_rules", None)

    if loss_fn is None:
        raise ValueError("initialize() needs (loss_fn, params) or model=")

    import jax

    from .utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    if mesh is None:
        axes = _mesh_axes_from_config(cfg, jax.device_count(), cfg.zero_optimization.stage)
        mesh = initialize_mesh(**axes)
    # install the ambient mesh: activation-sharding constraints and the
    # pipelined executor read it (parallel/sharding.py) — users shouldn't
    # have to call set_current_mesh by hand
    from .parallel.sharding import set_current_mesh

    set_current_mesh(mesh.mesh)
    model_cfg_ = getattr(model, "cfg", None)
    if getattr(model_cfg_, "latent", None) is not None:
        from .models.latent import refuse
        from .parallel.topology import MODEL_AXIS, STAGE_AXIS

        for axis in (MODEL_AXIS, STAGE_AXIS):
            if mesh.axis_size(axis) > 1:
                refuse(f"a mesh with {axis}={mesh.axis_size(axis)}", "its layers are per-kind "
                       "tuples of unlike trees: no tensor-parallel rule or pipeline stage "
                       "is written for them; data and ZeRO axes shard them")

    if params is None:
        if model is None:
            raise ValueError("initialize() needs (loss_fn, params) or model=")
        # zero.Init analogue (runtime/zero.py:init_sharded_params): build
        # params straight into their plan shardings inside jit — the full
        # tree never materializes on one host, so models larger than host
        # RAM can initialize (reference zero/partition_parameters.py:824)
        from .runtime import zero as zero_mod

        key = jax.random.PRNGKey(cfg.seed)
        shapes = jax.eval_shape(model.init_params, key)
        plan = zero_mod.plan_sharding(
            shapes, cfg.zero_optimization, mesh.spec, tp_rules
        )
        if hf_dir is not None:
            from .checkpoint.hf_import import load_hf_checkpoint_sharded

            params, model_cfg = load_hf_checkpoint_sharded(
                hf_dir, plan, mesh.mesh, cfg=model.cfg
            )
            model.cfg = model_cfg  # tie_embeddings may have been corrected
        else:
            params = zero_mod.init_sharded_params(
                model.init_params, key, plan, mesh.mesh
            )
    if cfg.elasticity.get("enabled"):
        # reference engine.py:594-604: adopt the elastic batch size and
        # verify this world size is in the compatible set
        from .elasticity import ElasticityConfigError, compute_elastic_config

        # v0.2 reasons in total chips and divides by model_parallel_size
        # itself; dp_world_size already excludes model parallelism
        mp = int(cfg.elasticity.get("model_parallel_size", 1))
        final_batch, valid_gpus, micro = compute_elastic_config(
            {"elasticity": cfg.elasticity},
            world_size=mesh.dp_world_size * mp,
            return_microbatch=True,
        )
        # reference semantics (engine.py:594-604): elastic values ALWAYS win;
        # user-provided batch params are a config error unless
        # ignore_non_elastic_batch_info suppresses the conflict check
        user_batch_info = any(
            v is not None for v in (
                cfg.train_batch_size,
                cfg.train_micro_batch_size_per_gpu,
                cfg.gradient_accumulation_steps,
            )
        )
        if user_batch_info and not cfg.elasticity.get(
            "ignore_non_elastic_batch_info", False
        ):
            raise ElasticityConfigError(
                "elasticity is enabled but batch sizes are also set in the "
                "config; remove train_batch_size/"
                "train_micro_batch_size_per_gpu/gradient_accumulation_steps "
                "or set elasticity.ignore_non_elastic_batch_info"
            )
        if micro is None:
            raise ElasticityConfigError(
                f"no micro batch in {cfg.elasticity.get('micro_batch_sizes')} "
                f"divides elastic batch {final_batch} at world size "
                f"{mesh.dp_world_size}"
            )
        cfg.train_batch_size = final_batch
        cfg.train_micro_batch_size_per_gpu = micro
        cfg.gradient_accumulation_steps = final_batch // (micro * mesh.dp_world_size)
        log_dist(
            f"elasticity: train_batch_size={final_batch} micro={micro} "
            f"valid world sizes={valid_gpus}"
        )
    cfg.finalize(mesh.dp_world_size)
    comm.comm.configure(cfg.comms_logger)

    trainable_mask = None
    if model is not None and hasattr(model, "trainable_mask"):
        trainable_mask = model.trainable_mask(params)
    engine = DeepSpeedTpuEngine(
        loss_fn=loss_fn,
        params=params,
        config=cfg,
        grid=mesh,
        tp_rules=tp_rules,
        eval_fn=eval_fn,
        trainable_mask=trainable_mask,
    )
    from .monitor.monitor import MonitorMaster

    engine.monitor = MonitorMaster(cfg)
    if model is not None and not isinstance(model, str):
        engine.model = model  # flops profiler reads .cfg for its module tree

    dataloader = None
    if training_data is not None:
        # curriculum from ANALYZED difficulty indices (reference
        # data_sampling: DataAnalyzer output feeding DeepSpeedDataSampler):
        # config data_efficiency.curriculum_learning.data_analysis_path
        # points at a data_analyzer save dir; the sampler then only admits
        # samples within the scheduler's current difficulty
        index_filter = None
        cl = cfg.data_efficiency.curriculum_learning or {}
        if (
            cfg.data_efficiency.enabled
            and cl.get("enabled")
            and cl.get("data_analysis_path")
            and engine.curriculum_scheduler is not None
        ):
            from .data.data_analyzer import curriculum_index_filter

            index_filter = curriculum_index_filter(
                cl["data_analysis_path"],
                cl.get("difficulty_metric", cl.get("curriculum_type", "seqlen")),
                engine.curriculum_scheduler,
            )
        dataloader = DeepSpeedTpuDataLoader(
            training_data,
            micro_batch_size=cfg.train_micro_batch_size_per_gpu,
            dp_world_size=mesh.dp_world_size,
            gradient_accumulation_steps=cfg.gradient_accumulation_steps,
            collate_fn=collate_fn,
            seed=cfg.seed,
            index_filter=index_filter,
        )
    if dataloader is not None:
        engine.training_dataloader = dataloader  # sampler state rides checkpoints
    if lr_scheduler is not None:
        log_dist("external lr_scheduler object ignored; use config['scheduler']")
    if cfg.hybrid_engine.enabled:
        # reference deepspeed/__init__.py:131: hybrid_engine.enabled swaps
        # the returned engine for the RLHF train<->generate wrapper
        from .runtime.hybrid_engine import DeepSpeedHybridEngine

        if cfg.hybrid_engine.inference_tp_size != 1:
            raise ConfigError(
                "hybrid_engine.inference_tp_size is not supported: hybrid "
                "serving follows the training mesh (set mesh.model for TP)"
            )
        engine = DeepSpeedHybridEngine(
            engine, max_out_tokens=cfg.hybrid_engine.max_out_tokens
        )
        log_dist("hybrid engine enabled: generate() serves the live weights")
    return engine, engine, dataloader, engine.lr_scheduler
