"""Data pipeline tests: resumable sampler, curriculum, mmap dataset.

Reference patterns: runtime/data_pipeline/data_sampling/data_sampler.py:36
(consumed_samples resume), curriculum_scheduler.py:11 (schedule math),
indexed_dataset.py (mmap round-trip).
"""
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.data import (
    CurriculumScheduler,
    DeepSpeedDataSampler,
    MMapIndexedDataset,
    MMapIndexedDatasetBuilder,
    truncate_to_seqlen,
)
from deepspeed_tpu.runtime.dataloader import DeepSpeedTpuDataLoader


# ---------------------------------------------------------------------------
# sampler
# ---------------------------------------------------------------------------
def test_sampler_resume_mid_epoch_exact_stream():
    """Save consumed_samples mid-epoch; a fresh sampler resumes the exact
    remaining batch stream (the VERDICT item-4 'done' criterion)."""
    kw = dict(
        one_epoch_total_samples=100,
        micro_batch_size=2,
        data_parallel_size=2,
        gradient_accumulation_steps=2,
        num_epochs=3,
        seed=7,
    )
    ref = DeepSpeedDataSampler(**kw)
    full = list(ref)

    run = DeepSpeedDataSampler(**kw)
    it = iter(run)
    first = [next(it) for _ in range(5)]
    state = run.state_dict()

    resumed = DeepSpeedDataSampler(**kw)
    resumed.load_state_dict(state)
    rest = list(resumed)

    got = first + rest
    assert len(got) == len(full)
    for a, b in zip(got, full):
        np.testing.assert_array_equal(a, b)


def test_sampler_epoch_reshuffle_and_coverage():
    s = DeepSpeedDataSampler(
        one_epoch_total_samples=64, micro_batch_size=4, num_epochs=2, seed=0
    )
    batches = list(s)
    epoch0 = np.concatenate(batches[: len(batches) // 2])
    epoch1 = np.concatenate(batches[len(batches) // 2 :])
    # full coverage each epoch, different order across epochs
    assert sorted(epoch0.tolist()) == list(range(64))
    assert sorted(epoch1.tolist()) == list(range(64))
    assert epoch0.tolist() != epoch1.tolist()


def test_sampler_rank_slices_partition_batch():
    s = DeepSpeedDataSampler(
        one_epoch_total_samples=32,
        micro_batch_size=2,
        data_parallel_size=4,
        gradient_accumulation_steps=1,
        seed=1,
    )
    batch = next(iter(s))
    slices = []
    for rank in range(4):
        s.data_parallel_rank = rank
        local = s.local_slice(batch).reshape(-1)
        assert local.shape == (2,)
        slices.append(local)
    np.testing.assert_array_equal(np.concatenate(slices), batch)


# ---------------------------------------------------------------------------
# curriculum scheduler (reference schedule math)
# ---------------------------------------------------------------------------
def test_curriculum_fixed_linear_matches_reference_math():
    sched = CurriculumScheduler({
        "curriculum_type": "seqlen",
        "min_difficulty": 8,
        "max_difficulty": 128,
        "schedule_type": "fixed_linear",
        "schedule_config": {"total_curriculum_step": 100, "difficulty_step": 8},
    })
    import math as m

    for step in (1, 10, 25, 50, 75, 100, 200):
        got = sched.get_difficulty(step)
        want = m.floor((step / 100) * (128 - 8) + 8)
        want -= want % 8
        want = min(want, 128)
        assert got == want, step
    # monotone ramp reaching max
    assert sched.get_difficulty(1) == 8
    assert sched.get_difficulty(100) == 128


def test_curriculum_fixed_root_and_discrete():
    root = CurriculumScheduler({
        "min_difficulty": 16,
        "max_difficulty": 256,
        "schedule_type": "fixed_root",
        "schedule_config": {
            "total_curriculum_step": 400, "difficulty_step": 16, "root_degree": 2,
        },
    })
    assert root.get_difficulty(100) == min(
        256, (lambda d: d - d % 16)(int((100 / 400) ** 0.5 * (256 - 16) + 16))
    )
    disc = CurriculumScheduler({
        "min_difficulty": 1,
        "max_difficulty": 3,
        "schedule_type": "fixed_discrete",
        "schedule_config": {"difficulty": [1, 2, 3], "max_step": [5, 10]},
    })
    assert [disc.get_difficulty(s) for s in (1, 5, 6, 10, 11, 99)] == [1, 1, 2, 2, 3, 3]


def test_curriculum_update_difficulty_is_sticky_at_max():
    sched = CurriculumScheduler({
        "min_difficulty": 8,
        "max_difficulty": 16,
        "schedule_type": "fixed_linear",
        "schedule_config": {"total_curriculum_step": 4, "difficulty_step": 8},
    })
    out = [sched.update_difficulty(s) for s in range(1, 8)]
    assert out[-1] == 16 and sorted(out) == out


def test_truncate_to_seqlen():
    batch = {"input_ids": np.zeros((2, 4, 65), np.int32), "flag": np.zeros((4,))}
    cut = truncate_to_seqlen(batch, 16)
    assert cut["input_ids"].shape == (2, 4, 17)
    assert cut["flag"].shape == (4,)


# ---------------------------------------------------------------------------
# engine integration: seqlen curriculum ramps, loss still trains
# ---------------------------------------------------------------------------
# slow: 16 s: the sequence-length ramp recompiles the step at every length it reaches
@pytest.mark.slow
def test_engine_curriculum_seqlen_ramp():
    from deepspeed_tpu.models import CausalLM, get_preset

    cfg = get_preset("tiny", max_seq_len=64)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=CausalLM(cfg),
        config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
            "data_efficiency": {
                "enabled": True,
                "curriculum_learning": {
                    "enabled": True,
                    "curriculum_type": "seqlen",
                    "min_difficulty": 16,
                    "max_difficulty": 64,
                    "schedule_type": "fixed_linear",
                    "schedule_config": {"total_curriculum_step": 4, "difficulty_step": 16},
                },
            },
        },
        mesh=deepspeed_tpu.initialize_mesh(data=8),
    )
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (16, 65)).astype(np.int32)}
    losses = [float(engine.train_batch(batch)) for _ in range(5)]
    assert all(np.isfinite(losses))
    assert engine.curriculum_scheduler.get_current_difficulty() == 64


# ---------------------------------------------------------------------------
# dataloader resume through engine checkpoints
# ---------------------------------------------------------------------------
class _TokDataset:
    def __init__(self, n=64, seq=16, vocab=256, seed=0):
        rng = np.random.default_rng(seed)
        self.data = rng.integers(0, vocab, (n, seq + 1)).astype(np.int32)

    def __len__(self):
        return len(self.data)

    def __getitem__(self, i):
        return {"input_ids": self.data[i]}


def _make(tmpdir, ds):
    from deepspeed_tpu.models import CausalLM, get_preset

    cfg = get_preset("tiny", max_seq_len=16)
    return deepspeed_tpu.initialize(
        model=CausalLM(cfg),
        training_data=ds,
        config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        },
        mesh=deepspeed_tpu.initialize_mesh(data=8),
    )


# slow: 12 s: two engines and an orbax checkpoint round trip
@pytest.mark.slow
def test_dataloader_position_rides_checkpoint(tmp_path):
    ds = _TokDataset()
    engine, _, loader, _ = _make(tmp_path, ds)
    it = iter(loader)
    seen = []
    for _ in range(2):
        b = next(it)
        engine.train_batch(b)
        seen.append(b["input_ids"])
    engine.save_checkpoint(str(tmp_path / "ck"))
    # continue the original run: the next batch after the checkpoint
    expected_next = next(iter(loader))["input_ids"]

    engine2, _, loader2, _ = _make(tmp_path, ds)
    engine2.load_checkpoint(str(tmp_path / "ck"))
    got_next = next(iter(loader2))["input_ids"]
    np.testing.assert_array_equal(got_next, expected_next)


# ---------------------------------------------------------------------------
# mmap indexed dataset
# ---------------------------------------------------------------------------
def test_mmap_indexed_dataset_roundtrip(tmp_path):
    prefix = str(tmp_path / "corpus")
    builder = MMapIndexedDatasetBuilder(prefix, dtype=np.int32)
    seqs = [np.arange(i + 1, dtype=np.int32) * 3 for i in range(10)]
    for s in seqs:
        builder.add_item(s)
    builder.finalize()

    dataset = MMapIndexedDataset(prefix)
    assert len(dataset) == 10
    for i, s in enumerate(seqs):
        np.testing.assert_array_equal(dataset[i], s)
    np.testing.assert_array_equal(dataset.sizes, [len(s) for s in seqs])
    np.testing.assert_array_equal(dataset.get(4, offset=1, length=2), seqs[4][1:3])
    # windowed reads compose with the sampler
    sampler = DeepSpeedDataSampler(
        one_epoch_total_samples=len(dataset), micro_batch_size=2, seed=0
    )
    idx = next(iter(sampler))
    assert all(0 <= int(i) < len(dataset) for i in idx)
