"""One process for each chip: the two places that spawn JAX children refuse
one that would land on the default (TPU) backend, with an error instead of a
hang (utils/chip_owner.py)."""
import pytest

from deepspeed_tpu.utils.chip_owner import refuse_chip_children


def test_refuses_unpinned_children_and_accepts_cpu_pinned():
    with pytest.raises(RuntimeError, match="a chip belongs to one process"):
        refuse_chip_children({}, "who")
    with pytest.raises(RuntimeError, match="'tpu'"):
        refuse_chip_children({"JAX_PLATFORMS": "cpu"}, "who", platform="tpu")
    refuse_chip_children({"JAX_PLATFORMS": "cpu"}, "who")
    refuse_chip_children({}, "who", platform="cpu")  # the child pins itself


def test_spawn_worker_refuses_before_starting_a_process(monkeypatch):
    import subprocess

    from deepspeed_tpu.serving import remote

    def no_popen(*a, **kw):
        raise AssertionError("a worker process was started")

    monkeypatch.setattr(subprocess, "Popen", no_popen)
    with pytest.raises(RuntimeError, match="spawn_worker"):
        remote.spawn_worker({"preset": "tiny"}, env={"JAX_PLATFORMS": ""})


def test_elastic_agent_refuses_multi_rank_chip_children(monkeypatch):
    import subprocess

    from deepspeed_tpu.elasticity.elastic_agent import ElasticAgent

    cfg = {"elasticity": {"enabled": True, "max_train_batch_size": 64,
                          "micro_batch_sizes": [1, 2, 4], "min_gpus": 1,
                          "max_gpus": 8, "version": 0.1}}
    agent = ElasticAgent(cfg, ["true"], env={"JAX_PLATFORMS": ""})
    monkeypatch.setattr(subprocess, "Popen", lambda *a, **kw: (_ for _ in ()).throw(
        AssertionError("a rank process was started")))
    with pytest.raises(RuntimeError, match="ElasticAgent"):
        agent._start_local(2)
