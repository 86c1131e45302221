"""Checkpoint round-trip tests, incl. restore across a different mesh shape —
the property the reference needs universal checkpointing for
(tests/unit/checkpoint/test_universal_checkpoint.py)."""
import jax
import numpy as np
import pytest

import deepspeed_tpu
from simple_model import init_mlp, mlp_loss, random_batches

CFG = {
    "train_micro_batch_size_per_gpu": 2,
    "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
    "bf16": {"enabled": False},
    "zero_optimization": {"stage": 2, "param_persistence_threshold": 0},
    "steps_per_print": 100,
}


def _engine(stage=2, fsdp=8):
    cfg = dict(CFG)
    cfg["zero_optimization"] = {"stage": stage, "param_persistence_threshold": 0}
    params = init_mlp(jax.random.PRNGKey(0))
    mesh = deepspeed_tpu.initialize_mesh(fsdp=fsdp, data=8 // fsdp)
    e, _, _, _ = deepspeed_tpu.initialize(loss_fn=mlp_loss, params=params, config=cfg, mesh=mesh)
    return e


def test_save_load_roundtrip(tmp_path):
    e = _engine()
    for b in random_batches(3, 1, 16):
        e.train_batch(b)
    path = e.save_checkpoint(str(tmp_path), tag="tag1", client_state={"foo": 1})
    kernel_before = jax.device_get(e.state.params["layer_0"]["kernel"])
    step_before = e.global_steps

    e2 = _engine()
    load_path, client = e2.load_checkpoint(str(tmp_path), tag="tag1")
    assert load_path is not None
    assert client == {"foo": 1}
    assert e2.global_steps == step_before
    np.testing.assert_array_equal(
        jax.device_get(e2.state.params["layer_0"]["kernel"]), kernel_before
    )
    # training continues identically
    b = random_batches(1, 1, 16, seed=9)[0]
    np.testing.assert_allclose(
        float(e.train_batch(b)), float(e2.train_batch(b)), rtol=1e-6
    )


def test_latest_tag(tmp_path):
    e = _engine()
    e.save_checkpoint(str(tmp_path))  # default tag global_step0
    from deepspeed_tpu.checkpoint.saving import get_latest_tag

    assert get_latest_tag(str(tmp_path)) == "global_step0"
    path, _ = e.load_checkpoint(str(tmp_path))
    assert path.endswith("global_step0")


def test_restore_across_mesh_reshape(tmp_path):
    """Save on fsdp=8, restore on fsdp=4×data=2 — topology-free by
    construction (the reference requires ds_to_universal conversion)."""
    e = _engine(fsdp=8)
    for b in random_batches(2, 1, 16):
        e.train_batch(b)
    e.save_checkpoint(str(tmp_path), tag="reshape")
    ref_kernel = jax.device_get(e.state.params["layer_0"]["kernel"])

    e2 = _engine(fsdp=4)
    e2.load_checkpoint(str(tmp_path), tag="reshape")
    np.testing.assert_array_equal(
        jax.device_get(e2.state.params["layer_0"]["kernel"]), ref_kernel
    )
    losses = [float(e2.train_batch(b)) for b in random_batches(2, 1, 16, seed=5)]
    assert np.isfinite(losses).all()


def test_fp32_export(tmp_path):
    e = _engine()
    from deepspeed_tpu.checkpoint.saving import export_fp32_state_dict

    sd = export_fp32_state_dict(e)
    assert sd["layer_0"]["kernel"].dtype == np.float32
    assert sd["layer_0"]["kernel"].shape == (8, 16)


def test_missing_checkpoint(tmp_path):
    e = _engine()
    path, client = e.load_checkpoint(str(tmp_path))
    assert path is None


# ---------------------------------------------------------------------------
# crash-safe checkpointing: atomic tmp+rename publish, per-shard checksums,
# 'latest' only after durability, mid-write-crash + corruption fallback
# ---------------------------------------------------------------------------
def test_save_is_atomic_with_shard_checksums(tmp_path):
    import json
    import os

    from deepspeed_tpu.checkpoint.saving import _tree_checksums, verify_tag

    e = _engine()
    for b in random_batches(2, 1, 16):
        e.train_batch(b)
    e.save_checkpoint(str(tmp_path), tag="t1")
    assert not os.path.isdir(tmp_path / "t1.tmp")  # tmp dir renamed away
    with open(tmp_path / "t1" / "meta.json") as fh:
        meta = json.load(fh)
    sums = meta["shard_checksums"]
    assert sums  # every shard file carries a checksum...
    assert _tree_checksums(str(tmp_path / "t1")) == sums  # ...that matches
    assert verify_tag(str(tmp_path), "t1") is None


def test_crash_mid_write_keeps_previous_checkpoint(tmp_path):
    """The fault harness kills the save between shard write and publish:
    the torn save stays a .tmp leftover, 'latest' still names the previous
    good tag, load restores it, and a retry of the same tag succeeds."""
    import os

    from deepspeed_tpu.checkpoint.saving import get_latest_tag
    from deepspeed_tpu.inference import faults
    from deepspeed_tpu.inference.faults import CheckpointWriteCrash, FaultInjector

    e = _engine()
    for b in random_batches(2, 1, 16):
        e.train_batch(b)
    e.save_checkpoint(str(tmp_path), tag="good")
    good_steps = e.global_steps
    for b in random_batches(1, 1, 16, seed=3):
        e.train_batch(b)
    with faults.scope(FaultInjector().arm("checkpoint_crash", times=1)):
        with pytest.raises(CheckpointWriteCrash):
            e.save_checkpoint(str(tmp_path), tag="torn")
    assert get_latest_tag(str(tmp_path)) == "good"  # never repointed
    assert not os.path.isdir(tmp_path / "torn")  # only a .tmp leftover
    assert os.path.isdir(tmp_path / "torn.tmp")
    e2 = _engine()
    path, _ = e2.load_checkpoint(str(tmp_path))
    assert path is not None and path.endswith("good")
    assert e2.global_steps == good_steps
    # the retry cleans the stale .tmp and publishes normally
    e.save_checkpoint(str(tmp_path), tag="torn")
    assert get_latest_tag(str(tmp_path)) == "torn"
    assert not os.path.isdir(tmp_path / "torn.tmp")


def test_latest_published_only_after_rename_durable(tmp_path):
    """The latest-ordering fix: a crash AFTER the tag rename but BEFORE the
    'latest' rewrite leaves 'latest' on the previous tag — the fully-written
    newer directory is simply not yet committed (load follows 'latest')."""
    import os

    from deepspeed_tpu.checkpoint.saving import get_latest_tag
    from deepspeed_tpu.inference import faults
    from deepspeed_tpu.inference.faults import CheckpointWriteCrash, FaultInjector

    e = _engine()
    for b in random_batches(2, 1, 16):
        e.train_batch(b)
    e.save_checkpoint(str(tmp_path), tag="first")
    # stage targeting via the check counter: after_shards(0),
    # before_rename(1), before_latest(2)
    with faults.scope(FaultInjector().arm("checkpoint_crash", after=2, times=1)):
        with pytest.raises(CheckpointWriteCrash):
            e.save_checkpoint(str(tmp_path), tag="second")
    assert os.path.isdir(tmp_path / "second")  # rename landed...
    assert get_latest_tag(str(tmp_path)) == "first"  # ...but uncommitted
    e2 = _engine()
    path, _ = e2.load_checkpoint(str(tmp_path))
    assert path.endswith("first")


def test_corrupt_shard_falls_back_to_previous_tag(tmp_path):
    """Bitrot in the newest checkpoint: checksum verification fails, load
    warns and falls back to the newest previous tag that verifies; an
    EXPLICITLY requested corrupt tag raises instead of substituting."""
    import os

    e = _engine()
    for b in random_batches(2, 1, 16):
        e.train_batch(b)
    e.save_checkpoint(str(tmp_path), tag="older")
    older_steps = e.global_steps
    for b in random_batches(2, 1, 16, seed=5):
        e.train_batch(b)
    e.save_checkpoint(str(tmp_path), tag="newer")
    # flip bytes in one shard file of the newest tag
    victim = None
    for dirpath, _, files in os.walk(tmp_path / "newer"):
        for name in files:
            p = os.path.join(dirpath, name)
            if name != "meta.json" and os.path.getsize(p) > 0:
                victim = p
                break
        if victim:
            break
    assert victim is not None
    with open(victim, "r+b") as fh:
        raw = fh.read(16)
        fh.seek(0)
        fh.write(bytes(255 - b for b in raw))
    e2 = _engine()
    path, _ = e2.load_checkpoint(str(tmp_path))
    assert path is not None and path.endswith("older")  # fell back
    assert e2.global_steps == older_steps
    e3 = _engine()
    with pytest.raises(RuntimeError, match="failed verification"):
        e3.load_checkpoint(str(tmp_path), tag="newer")


# slow: 8-21 s: waits on orbax's background writer thread, whose time swings with the machine's load
@pytest.mark.slow
def test_async_checkpoint_save_and_resume(tmp_path):
    """checkpoint.async_save: save returns immediately, 'latest' appears only
    after commit, and the checkpoint restores exactly (reference
    NebulaCheckpointEngine semantics, checkpoint_engine.py:10)."""
    import os
    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM, get_preset

    cfg = get_preset("tiny", max_seq_len=16)
    conf = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "checkpoint": {"async_save": True},
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=CausalLM(cfg), config=conf,
        mesh=deepspeed_tpu.initialize_mesh(data=8),
    )
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (16, 17)).astype(np.int32)}
    for _ in range(2):
        engine.train_batch(batch)
    engine.save_checkpoint(str(tmp_path))
    engine.wait_pending_checkpoint()
    assert os.path.exists(os.path.join(tmp_path, "latest"))
    after = float(engine.train_batch(batch))

    e2, _, _, _ = deepspeed_tpu.initialize(
        model=CausalLM(cfg), config=conf,
        mesh=deepspeed_tpu.initialize_mesh(data=8),
    )
    e2.load_checkpoint(str(tmp_path))
    got = float(e2.train_batch(batch))
    assert abs(got - after) < 1e-4, (got, after)
