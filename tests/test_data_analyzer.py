"""Offline DataAnalyzer map-reduce + curriculum consumption (r4 VERDICT
next #6; reference data_analyzer.py:22/:455)."""
import numpy as np
import pytest

from deepspeed_tpu.data.curriculum_scheduler import CurriculumScheduler
from deepspeed_tpu.data.data_analyzer import (
    SINGLE_VALUE,
    CurriculumDataSampler,
    CurriculumIndex,
    DataAnalyzer,
    curriculum_index_filter,
    seqlen_metric,
)
from deepspeed_tpu.data.indexed_dataset import (
    MMapIndexedDataset,
    MMapIndexedDatasetBuilder,
)
from deepspeed_tpu.data.sampler import DeepSpeedDataSampler


@pytest.fixture
def corpus(tmp_path):
    """64 docs with lengths 4..67 (unique per doc, shuffled)."""
    prefix = str(tmp_path / "corpus")
    lengths = np.random.default_rng(0).permutation(np.arange(4, 68))
    b = MMapIndexedDatasetBuilder(prefix, dtype=np.int32)
    for n in lengths:
        b.add_item(np.arange(n, dtype=np.int32))
    b.finalize()
    return prefix, lengths


def test_map_reduce_multiworker(corpus, tmp_path):
    prefix, lengths = corpus
    ds = MMapIndexedDataset(prefix)
    save = str(tmp_path / "analysis")
    analyzer = DataAnalyzer(
        ds, num_workers=3, metric_names=["seqlen"],
        metric_functions=[seqlen_metric], metric_types=[SINGLE_VALUE],
        save_path=save,
    )
    # multi-process map (picklable via dataset prefix) + reduce
    out = analyzer.run_map_reduce(processes=3)
    np.testing.assert_array_equal(out["seqlen"]["sample_to_metric"], lengths)
    idx = CurriculumIndex(save, "seqlen")
    # sorted index round-trips through the mmap files
    np.testing.assert_array_equal(
        np.asarray(idx.index_to_metric), np.sort(lengths)
    )
    np.testing.assert_array_equal(
        lengths[np.asarray(idx.index_to_sample)], np.sort(lengths)
    )
    assert set(idx.sample_ids_up_to(10)) == set(np.where(lengths <= 10)[0])


def test_reduce_detects_missing_worker(corpus, tmp_path):
    prefix, _ = corpus
    ds = MMapIndexedDataset(prefix)
    save = str(tmp_path / "analysis")
    a = DataAnalyzer(ds, num_workers=2, worker_id=0, save_path=save)
    a.run_map()  # worker 1 never ran
    with pytest.raises(RuntimeError, match="no mapped metric"):
        a.run_reduce()


def test_curriculum_sampler_follows_schedule(corpus, tmp_path):
    """e2e: analyze corpus by seqlen, then sample with a fixed_linear
    curriculum — every batch's max seqlen must respect the step's
    difficulty, and late batches must use samples early ones could not."""
    prefix, lengths = corpus
    ds = MMapIndexedDataset(prefix)
    save = str(tmp_path / "analysis")
    DataAnalyzer(ds, num_workers=2, save_path=save).run_map_reduce(processes=1)

    sched = CurriculumScheduler({
        "curriculum_type": "seqlen",
        "min_difficulty": 12,
        "max_difficulty": 70,
        "schedule_type": "fixed_linear",
        "schedule_config": {"total_curriculum_step": 10, "difficulty_step": 8},
    })
    sampler = CurriculumDataSampler(
        CurriculumIndex(save, "seqlen"), sched, global_batch_size=4, seed=0
    )
    max_seen = []
    for step in range(1, 13):
        batch = sampler.next_batch(step)
        difficulty = sched.get_current_difficulty()
        assert lengths[batch].max() <= difficulty, (
            step, difficulty, lengths[batch]
        )
        max_seen.append(lengths[batch].max())
    # the schedule actually opened up: late batches admit longer samples
    assert max(max_seen[-4:]) > max(max_seen[:2])
    # resumable state contract
    st = sampler.state_dict()
    assert st["consumed_samples"] == 12 * 4


def test_curriculum_sampler_resume_exact(corpus, tmp_path):
    """state_dict/load_state_dict round-trip mid-run: the restored sampler
    must continue with the exact batches the original would have drawn —
    a bare consumed_samples restore used to restart the difficulty pool at
    index 0 and repeat samples."""
    prefix, _ = corpus
    ds = MMapIndexedDataset(prefix)
    save = str(tmp_path / "analysis")
    DataAnalyzer(ds, num_workers=1, save_path=save).run_map_reduce(processes=1)

    def mk():
        sched = CurriculumScheduler({
            "curriculum_type": "seqlen",
            "min_difficulty": 12,
            "max_difficulty": 70,
            "schedule_type": "fixed_linear",
            "schedule_config": {"total_curriculum_step": 10,
                                "difficulty_step": 8},
        })
        return CurriculumDataSampler(
            CurriculumIndex(save, "seqlen"), sched, global_batch_size=4, seed=0
        )

    # checkpoint at several points, incl. mid-pool and right after a
    # difficulty change rebuilt the pool; exercise both the direct
    # pool_key/pos restore and the legacy consumed_samples-only replay
    for stop in (1, 3, 5, 8):
        ref = mk()
        for step in range(1, stop + 1):
            ref.next_batch(step)
        st = ref.state_dict()
        legacy = {"consumed_samples": st["consumed_samples"]}
        expect = [ref.next_batch(s) for s in range(stop + 1, stop + 5)]

        for snapshot in (st, legacy):
            res = mk()
            res.load_state_dict(snapshot)
            got = [res.next_batch(s) for s in range(stop + 1, stop + 5)]
            for e, g in zip(expect, got):
                np.testing.assert_array_equal(g, e)

    # rewind into a WARM sampler: the scheduler has ratcheted past the
    # checkpoint — load_state_dict must replay the original trajectory,
    # not the advanced difficulty
    warm = mk()
    for step in range(1, 13):
        warm.next_batch(step)
    ref = mk()
    for step in range(1, 4):
        ref.next_batch(step)
    st = ref.state_dict()
    expect = [ref.next_batch(s) for s in range(4, 8)]
    warm.load_state_dict(st)
    got = [warm.next_batch(s) for s in range(4, 8)]
    for e, g in zip(expect, got):
        np.testing.assert_array_equal(g, e)


def test_index_filter_plugs_into_data_sampler(corpus, tmp_path):
    prefix, lengths = corpus
    ds = MMapIndexedDataset(prefix)
    save = str(tmp_path / "analysis")
    DataAnalyzer(ds, num_workers=1, save_path=save).run_map_reduce(processes=1)
    sched = CurriculumScheduler({
        "curriculum_type": "seqlen",
        "min_difficulty": 16,
        "max_difficulty": 70,
        "schedule_type": "fixed_linear",
        "schedule_config": {"total_curriculum_step": 100, "difficulty_step": 8},
    })
    sampler = DeepSpeedDataSampler(
        one_epoch_total_samples=len(ds),
        micro_batch_size=2,
        index_filter=curriculum_index_filter(save, "seqlen", sched),
        num_epochs=1,
        seed=0,
    )
    batch = next(iter(sampler))
    assert lengths[batch].max() <= sched.get_current_difficulty()


def test_cli(corpus, tmp_path, capsys):
    prefix, lengths = corpus
    from deepspeed_tpu.data.data_analyzer import main

    save = str(tmp_path / "cli_out")
    assert main(["--data-prefix", prefix, "--save", save, "--workers", "2"]) == 0
    idx = CurriculumIndex(save, "seqlen")
    np.testing.assert_array_equal(np.asarray(idx.index_to_metric), np.sort(lengths))


def test_analysis_path_wires_into_initialize(tmp_path, monkeypatch):
    """Config-level loop closure (reference data_sampling): a
    ``data_analysis_path`` in the curriculum config makes initialize()'s
    dataloader admit only samples within the scheduler's difficulty."""
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM, get_preset

    # dataset of fixed-shape samples whose difficulty = first token value
    n = 64
    rng = np.random.default_rng(0)
    samples = []
    for i in range(n):
        row = rng.integers(1, 250, 17).astype(np.int32)
        row[0] = i % 32  # the difficulty metric
        samples.append({"input_ids": row})

    class ListDS:
        def __len__(self):
            return n

        def __getitem__(self, i):
            return samples[i]

    save = str(tmp_path / "analysis")
    DataAnalyzer(
        ListDS(), num_workers=1, metric_names=["first_token"],
        metric_functions=[lambda s: int(np.asarray(s["input_ids"])[0])],
        metric_types=[SINGLE_VALUE], save_path=save,
    ).run_map_reduce(processes=1)

    cfg = get_preset("tiny", max_seq_len=32)
    engine, _, loader, _ = deepspeed_tpu.initialize(
        model=CausalLM(cfg),
        training_data=ListDS(),
        config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 0},
            "bf16": {"enabled": True},
            "data_efficiency": {
                "enabled": True,
                "curriculum_learning": {
                    "enabled": True,
                    "curriculum_type": "first_token",
                    "data_analysis_path": save,
                    "min_difficulty": 8,
                    "max_difficulty": 32,
                    "schedule_type": "fixed_linear",
                    "schedule_config": {"total_curriculum_step": 100,
                                        "difficulty_step": 8},
                },
            },
        },
        mesh=deepspeed_tpu.initialize_mesh(data=8),
    )
    # the first epoch's batches must only contain first-token <= 8
    it = iter(loader)
    batch = next(it)
    firsts = np.asarray(batch["input_ids"]).reshape(-1, 17)[:, 0]
    assert (firsts <= 8).all(), firsts
    # and the engine still trains on them
    loss = engine.train_batch(batch)
    assert np.isfinite(float(loss))
    # train_on_loader must fall back to the synchronous path here: the
    # index_filter reads the LIVE scheduler difficulty, which a prefetch
    # worker running ahead would evaluate stale.  Probe the fallback
    # directly — constructing a prefetcher at all IS the bug.
    import deepspeed_tpu.runtime.engine as eng_mod

    def _no_prefetcher(*a, **k):
        raise AssertionError(
            "DevicePrefetcher constructed for a curriculum index_filter "
            "loader — the synchronous fallback regressed"
        )

    monkeypatch.setattr(eng_mod, "DevicePrefetcher", _no_prefetcher)
    losses = [float(l) for l in engine.train_on_loader(loader, num_steps=2)]
    assert np.isfinite(losses).all()
