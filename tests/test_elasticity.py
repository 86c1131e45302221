"""Elasticity tests (reference: tests/unit/elasticity/test_elastic.py
semantics — v0.1/v0.2 batch math, incompatible world sizes, engine adoption,
and world-size-change restart through topology-free checkpoints)."""
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.elasticity import (
    ElasticityConfigError,
    ElasticityIncompatibleWorldSize,
    compute_elastic_config,
)

BASE = {
    "elasticity": {
        "enabled": True,
        "max_train_batch_size": 10000,
        "micro_batch_sizes": [8, 12, 16, 17],
        "min_gpus": 32,
        "max_gpus": 1500,
        "min_time": 20,
        "version": 0.1,
    }
}


def test_v01_batch_and_valid_gpus_deterministic():
    """The reference's own doc example: this config resolves to 9792 with
    a fixed valid-gpu list (tests/unit/elasticity values)."""
    batch, valid = compute_elastic_config(BASE)
    assert batch == 9792
    assert valid == sorted(valid)
    # every valid world size divides the batch through some micro batch
    for w in valid:
        assert any(
            batch % (m * w) == 0 for m in BASE["elasticity"]["micro_batch_sizes"]
        ), w
    assert 32 <= min(valid) and max(valid) <= 1500


def test_v01_world_size_check():
    valid_ws = 96
    batch, valid, micro = compute_elastic_config(
        BASE, world_size=valid_ws, return_microbatch=True
    )
    assert valid_ws in valid
    assert micro in BASE["elasticity"]["micro_batch_sizes"]
    assert batch // valid_ws % micro == 0
    with pytest.raises(ElasticityIncompatibleWorldSize):
        compute_elastic_config(BASE, world_size=53)


def test_v02_node_granular_and_model_parallel():
    cfg = {
        "elasticity": {
            **BASE["elasticity"],
            "version": 0.2,
            "num_gpus_per_node": 8,
            "model_parallel_size": 2,
            "min_gpus": 32,
            "max_gpus": 1024,
        }
    }
    batch, valid, micro = compute_elastic_config(
        cfg, world_size=64, return_microbatch=True
    )
    # dp sizes come in units of chips_per_node/mp = 4
    assert all(v % 4 == 0 for v in valid)
    # micro may be None when the chosen batch doesn't split evenly at this
    # world size (reference get_microbatch returns None then)
    assert micro is None or batch // 64 % micro == 0


def test_v02_incompatible_world_size_falls_back_to_current_dp():
    cfg = {
        "elasticity": {
            "enabled": True,
            "max_train_batch_size": 2000,
            "micro_batch_sizes": [2, 4],
            "min_gpus": 1,
            "max_gpus": 100,
            "version": 0.2,
            "num_gpus_per_node": 1,
        }
    }
    batch, valid, micro = compute_elastic_config(
        cfg, world_size=11, return_microbatch=True
    )
    # 11 incompatible with every HCN-derived candidate: the v0.2 fallback
    # pins dp=11 with the largest batch that exact size supports
    assert valid == [11]
    assert batch // 11 % micro == 0


def test_config_validation_errors():
    with pytest.raises(ElasticityConfigError):
        compute_elastic_config({"elasticity": {"enabled": False}})
    with pytest.raises(ElasticityConfigError):
        compute_elastic_config({})
    with pytest.raises(ElasticityConfigError):
        compute_elastic_config(
            {"elasticity": {"enabled": True, "micro_batch_sizes": [2]}}
        )
    with pytest.raises(ElasticityConfigError):
        # model parallel requires v0.2
        compute_elastic_config({
            "elasticity": {
                "enabled": True, "max_train_batch_size": 100,
                "micro_batch_sizes": [2], "model_parallel_size": 4,
                "version": 0.1,
            }
        })


def test_engine_adopts_elastic_batch():
    from deepspeed_tpu.models import CausalLM, get_preset

    cfg = get_preset("tiny", max_seq_len=32)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=CausalLM(cfg),
        config={
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "elasticity": {
                "enabled": True,
                "max_train_batch_size": 64,
                "micro_batch_sizes": [2, 4],
                "min_gpus": 1,
                "max_gpus": 64,
                "version": 0.1,
            },
        },
        mesh=deepspeed_tpu.initialize_mesh(data=8),
    )
    c = engine.config
    assert c.train_batch_size == c.train_micro_batch_size_per_gpu * \
        c.gradient_accumulation_steps * 8
    assert c.train_micro_batch_size_per_gpu in (2, 4)
    rng = np.random.default_rng(0)
    batch = {
        "input_ids": rng.integers(
            0, cfg.vocab_size,
            (c.gradient_accumulation_steps, c.train_micro_batch_size_per_gpu * 8, 33),
        ).astype(np.int32)
    }
    assert np.isfinite(float(engine.train_batch(batch)))


# slow: 19 s: trains, checkpoints and restarts two engines at different world sizes
@pytest.mark.slow
def test_elastic_restart_different_world_size(tmp_path):
    """Save at dp=8, resume at dp=4 with the SAME global batch (gas doubles):
    the elastic-restart contract (reference: elastic ZeRO checkpoint merge;
    here topology-free checkpoints make it direct)."""
    from deepspeed_tpu.models import CausalLM, get_preset

    mcfg = get_preset("tiny", max_seq_len=16)
    # batch resolves to 48 = 2 x HCN(24): divisors cover both dp=8 and dp=4
    elastic = {
        "enabled": True,
        "max_train_batch_size": 48,
        "micro_batch_sizes": [2],
        "min_gpus": 1,
        "max_gpus": 48,
        "version": 0.1,
    }
    conf = {
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "elasticity": elastic,
    }
    rng = np.random.default_rng(0)

    e8, _, _, _ = deepspeed_tpu.initialize(
        model=CausalLM(mcfg), config=dict(conf),
        mesh=deepspeed_tpu.initialize_mesh(data=8),
    )
    gb = e8.config.train_batch_size
    batch = {"input_ids": rng.integers(0, mcfg.vocab_size, (gb, 17)).astype(np.int32)}
    for _ in range(2):
        e8.train_batch(batch)
    e8.save_checkpoint(str(tmp_path))
    l8 = float(e8.train_batch(batch))

    # data=4 x model=2: dp world is 4 (model is not a batch axis)
    e4, _, _, _ = deepspeed_tpu.initialize(
        model=CausalLM(mcfg), config=dict(conf),
        mesh=deepspeed_tpu.initialize_mesh(data=4, model=2),
    )
    assert e4.config.train_batch_size == gb  # same global batch at dp=4
    e4.load_checkpoint(str(tmp_path))
    l4 = float(e4.train_batch(batch))
    assert abs(l8 - l4) < 2e-2, (l8, l4)
