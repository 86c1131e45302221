"""Elastic agent: supervision loop, world re-formation, checkpoint resume
(reference elasticity/elastic_agent.py + bin/ds_elastic)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from deepspeed_tpu.elasticity import (
    ElasticAgent,
    ElasticityError,
    ElasticityIncompatibleWorldSize,
)

ELASTIC_CFG = {
    "elasticity": {
        "enabled": True,
        "max_train_batch_size": 64,
        "micro_batch_sizes": [2, 4],
        "min_gpus": 1,
        "max_gpus": 4,
        "version": 0.1,
    }
}


def test_compute_world_scales_down():
    agent = ElasticAgent(ELASTIC_CFG, ["true"])
    w4 = agent.compute_world(4)
    w3 = agent.compute_world(3)
    w1 = agent.compute_world(1)
    assert w4 == 4 and w3 <= 3 and w1 == 1
    with pytest.raises(ElasticityIncompatibleWorldSize):
        agent.compute_world(0)


def test_agent_requires_elasticity_enabled():
    with pytest.raises(ElasticityError):
        ElasticAgent({"elasticity": {"enabled": False}}, ["true"])


def test_render_remote_commands():
    agent = ElasticAgent(
        ELASTIC_CFG, ["python", "train.py"],
        hosts={"host-a": 4, "host-b": 4}, runner="openmpi",
    )
    cmd = agent.render_remote_commands(4)
    joined = " ".join(cmd)
    assert "mpirun" in joined and "train.py" in joined
    assert any("WORLD_SIZE" in c for c in cmd), cmd


def test_ds_elastic_cli(tmp_path, capsys):
    from deepspeed_tpu.elasticity.elastic_agent import main

    cfg = tmp_path / "cfg.json"
    cfg.write_text(json.dumps(ELASTIC_CFG))
    assert main(["-c", str(cfg), "-w", "4"]) == 0
    out = capsys.readouterr().out
    assert "final_batch_size" in out and "valid_gpus" in out
    assert "micro_batch_size" in out


WORKER = textwrap.dedent("""
    import os, sys, time, pathlib
    rank = int(os.environ["RANK"]); world = int(os.environ["WORLD_SIZE"])
    restart = int(os.environ["DS_ELASTIC_RESTART_COUNT"])
    workdir = pathlib.Path(sys.argv[1])
    done = workdir / "done"
    stepf = workdir / "step"
    if rank != 0:
        # non-zero ranks simulate compute peers; the highest rank of the
        # FIRST attempt is preempted once training passes step 3
        crash = restart == 0 and rank == world - 1
        while not done.exists():
            if crash and stepf.exists():
                try:
                    if int(stepf.read_text() or 0) >= 3:
                        os._exit(1)
                except ValueError:
                    pass
            time.sleep(0.05)
        sys.exit(0)
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import jax.numpy as jnp
    import deepspeed_tpu as ds

    def loss_fn(p, batch, rng):
        h = jnp.tanh(batch["x"] @ p["w1"])
        return jnp.mean((h @ p["w2"] - batch["y"]) ** 2)

    rngnp = np.random.default_rng(0)
    params = {
        "w1": jnp.asarray(rngnp.normal(size=(8, 16)) * 0.3, jnp.float32),
        "w2": jnp.asarray(rngnp.normal(size=(16, 4)) * 0.3, jnp.float32),
    }
    engine, _, _, _ = ds.initialize(loss_fn=loss_fn, params=params, config={
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 0.05}},
        "zero_optimization": {"stage": 1},
        "bf16": {"enabled": False},
        "steps_per_print": 1000,
    })
    ckpt = str(workdir / "ckpt")
    if os.path.isdir(ckpt):
        engine.load_checkpoint(ckpt)
    x = jnp.asarray(rngnp.normal(size=(16, 8)), jnp.float32)
    y = jnp.asarray(rngnp.normal(size=(16, 4)), jnp.float32)
    with open(workdir / "losses.csv", "a") as log:
        while engine.global_steps < 8:
            loss = float(engine.train_batch({"x": x, "y": y}))
            log.write(f"{world},{engine.global_steps},{loss}\\n")
            log.flush()
            engine.save_checkpoint(ckpt)
            stepf.write_text(str(engine.global_steps))
            time.sleep(0.3)  # widen the preemption window for the crasher
    done.write_text("ok")
""")


# slow: 26 s: the agent spawns worker processes, kills one and restarts the group at a smaller world
@pytest.mark.slow
def test_agent_resumes_at_smaller_world_with_loss_continuity(tmp_path):
    """Kill a worker mid-training: the agent must re-form a smaller valid
    world and the relaunched rank 0 must RESUME from the checkpoint (steps
    continue; loss does not reset)."""
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    env = {
        "PYTHONPATH": os.pathsep.join(sys.path),
        "JAX_PLATFORMS": "cpu",
    }
    agent = ElasticAgent(
        ELASTIC_CFG,
        [sys.executable, str(script), str(tmp_path)],
        heartbeat_interval=0.2,
        env=env,
    )
    rc = agent.run(capacity=4)
    assert rc == 0
    # two attempts: world 4, then the largest valid world fitting capacity 3
    resumed_world = agent.compute_world(3)
    assert [h["world"] for h in agent.history] == [4, resumed_world], agent.history
    rows = [
        line.split(",")
        for line in (tmp_path / "losses.csv").read_text().splitlines()
    ]
    worlds = [int(r[0]) for r in rows]
    steps = [int(r[1]) for r in rows]
    losses = [float(r[2]) for r in rows]
    assert set(worlds) == {4, resumed_world}
    # steps CONTINUE across the restart: the first resumed step is one past
    # the last checkpointed world-4 step, never back to 1
    ri = worlds.index(resumed_world)
    first_resumed = steps[ri]
    last_before = max(s for s, w in zip(steps, worlds) if w == 4)
    assert first_resumed == last_before + 1, (steps, worlds)
    # loss continuity: resumed loss continues the descent (no re-init jump)
    resumed_loss = losses[ri]
    initial_loss = losses[0]
    pre_crash_loss = losses[ri - 1]
    assert resumed_loss < initial_loss
    assert resumed_loss < pre_crash_loss * 1.5
    assert losses[-1] < losses[0] * 0.5
