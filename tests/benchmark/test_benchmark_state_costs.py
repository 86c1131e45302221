"""The yardstick of a model of single-mixer blocks: ``costs_ssm.py``'s needed
work at the published widths, the reader that divides it by a body's time, and
the serving driver's copy of how the engine splits a tick's prefill into
packs."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import costs, costs_ssm, harness  # noqa: E402
from benchmark.drivers import serve_hybrid  # noqa: E402
from benchmark.readers import state_roofline  # noqa: E402

M = harness.load_json(harness.HERE / "published" / "nemotron3_super_120b_a12b.json")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_a_step_reads_and_writes_each_live_state_once():
    fl, by = costs_ssm.ssm_step(128, M)
    state = 128 * 64 * 128 * 4  # one slot's float32 state: 4 MiB
    assert state == 4 << 20
    assert 2 * 128 * state < by < 2 * 128 * state * 1.01  # x, B, C, dt, y are the rest
    assert fl == 6.0 * 128 * 128 * 64 * 128
    # bound by memory: 1 GiB a block at 819 GB/s
    assert costs.roofline_min_s(fl, by, PEAKS) == pytest.approx(by / 819e9)
    assert costs_ssm.ssm_step(64, M)[1] == pytest.approx(by / 2)  # idle slots are not counted


def test_a_scan_counts_valid_tokens_and_one_state_hand_over_a_chunk():
    whole, part = costs_ssm.ssm_scan([128], M), costs_ssm.ssm_scan([40], M)
    assert part[0] < whole[0] and part[1] < whole[1]
    two = costs_ssm.ssm_scan([128, 128], M)
    assert two == (2 * whole[0], 2 * whole[1])
    assert whole[1] > 2 * 4 * 128 * 64 * 128  # a state in and a state out


def test_touched_experts_are_read_once_whatever_their_rows():
    few, many = costs_ssm.expert_matmul(704, 128, M), costs_ssm.expert_matmul(2816, 128, M)
    weights = 2.0 * 2 * 1024 * 2688 * 128  # two bf16 matrices an expert
    assert few[1] == pytest.approx(weights + 4 * 1024 * 704)
    assert many[1] - few[1] == pytest.approx(4 * 1024 * (2816 - 704))
    assert many[0] == 4 * few[0]


def test_reader_has_nothing_to_read_without_a_trace():
    obs = {"trace": None, "device": {"platform": "tpu", "kind": "TPU v5 lite"}, "requests": []}
    assert state_roofline.read(obs, "^jit_decode_impl$", "ssm_step", "ssm_step") is None


@pytest.mark.parametrize("entries,packs", [
    ([(0, 1024, 1400), (1, 0, 136)], [[(0, 1024, 1400)], [(1, 0, 136)]]),  # 384 + 256 > 512
    ([(0, 1024, 1400), (1, 0, 128)], [[(0, 1024, 1400), (1, 0, 128)]]),
    ([(0, 0, 512)], [[(0, 0, 512)]]),
    ([(0, 0, 300), (1, 0, 300), (2, 0, 100)], [[(0, 0, 300), (2, 0, 100)], [(1, 0, 300)]]),
])
def test_a_ticks_entries_split_into_packs_as_the_engine_splits_them(entries, packs):
    assert list(serve_hybrid._packs(entries, 128, 512)) == packs


def test_a_schedule_of_prompts_alone_then_decoding_side_by_side():
    prompts, fed = [[1] * 70, [2] * 20], [[5, 6, 7], [8]]
    assert serve_hybrid._alone(prompts, fed, 32) == [
        ([(0, 0, 32)], []), ([(0, 32, 64)], []), ([(0, 64, 70)], []), ([(1, 0, 20)], []),
        ([], [0, 1]), ([], [0]), ([], [0])]


def test_the_state_error_is_the_worst_blocks():
    import numpy as np

    rng = np.random.default_rng(0)
    again = [rng.standard_normal((8, 4, 6)) for _ in range(3)]
    kept = [a.copy() for a in again]
    assert serve_hybrid._state_error(np, kept, again) == 0.0
    kept[1] = kept[1] * 1.01
    assert serve_hybrid._state_error(np, kept, again) == pytest.approx(0.01)
    assert serve_hybrid._state_error(np, [], []) == float("inf")  # no block, no pass


@pytest.fixture(scope="module")
def replayed():
    """The driver's replay and the reference's kept state at the rehearsal
    size (float32): two prompts of 3 and 2 chunks, 6 fed tokens each."""
    import jax
    import numpy as np

    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models.transformer import init_params

    m = harness.rehearsed(harness.load_json(
        harness.HERE / "configs" / "nemotron3_super_l11_e128_serve_1chip.json"), True)
    arch = harness.module("models", m["model_type"])
    e = m["engine"]
    cfg = arch.transformer_config(m, max_seq_len=e["max_seq_len"])
    eng = InferenceEngineV2(
        init_params(jax.random.PRNGKey(3), cfg), cfg, max_seqs=e["max_seqs"],
        num_blocks=e["num_blocks"], block_size=e["block_size"], max_seq_len=e["max_seq_len"],
        prefill_buckets=(e["prefill_chunk"],), prefill_chunk=e["prefill_chunk"])
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (75, 41)]
    fed = [rng.integers(0, cfg.vocab_size, 6).tolist() for _ in prompts]
    replay = serve_hybrid._Replay(jax, np, eng, cfg)
    schedule = serve_hybrid._alone(prompts, fed, e["prefill_chunk"])
    k = m["num_experts_per_tok"]

    recur = jax.jit(lambda x, b, c, dt, a_log: arch.recurrence(
        x[None], b[None], c[None], dt[None], -jax.numpy.exp(a_log))[1][0])

    def against_reference(state_as=None):
        out = []
        for (got, probes, kept, consumed), p, f in zip(
                replay(prompts, fed, schedule, state_as=state_as), prompts, fed):
            buf = np.zeros((1, 88), np.int32)
            buf[0, :len(p) + len(f)] = p + f
            lg, _ = arch.probe(eng.params, buf, m, serve_hybrid._forced(np, probes, 88, k))
            d = np.abs(got - np.asarray(lg)[0][len(p) - 1: len(p) + len(f)]).max()
            assert all(len(c["ssm_x"]) == len(p) + len(f) for c in consumed)
            again = [np.asarray(recur(c["ssm_x"], c["ssm_b"], c["ssm_c"], c["ssm_dt"], w["a_log"]))
                     for c, w in zip(consumed, eng.params["layers"]["mamba"])]
            out.append((float(d), serve_hybrid._state_error(np, kept, again)))
        return out

    yield against_reference
    eng.close()


def test_the_replays_kept_state_is_the_one_token_recurrences(replayed):
    """Chunks, hand-overs from pack to pack and steps leave the state the
    float32 recurrence leaves on the same inputs, and the logits are the
    reference's."""
    for d, off in replayed():
        assert d < 1e-4 and off < 1e-5


def test_a_state_kept_in_bfloat16_shows_in_the_kept_state(replayed):
    """What the logits cannot tell at the real size the kept state tells at
    any: every store rounds it."""
    for _, off in replayed("bfloat16"):
        assert off > 1e-3
