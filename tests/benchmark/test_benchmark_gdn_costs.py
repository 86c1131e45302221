"""The yardstick of a model of two-norm blocks (Gated DeltaNet beside gated
attention, experts in every block): ``costs_gdn.py``'s needed work at the
published widths, the readers that divide it by a body's time or take a
pack's share of two counters, and the serving driver's replay held to the
reference's one-token delta rule."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import costs, costs_gdn, harness  # noqa: E402
from benchmark.drivers import serve_deltanet  # noqa: E402
from benchmark.readers import counter_difference_ratio, gdn_roofline  # noqa: E402

M = harness.load_json(harness.HERE / "configs" / "qwen3_next_l8_e128_serve_1chip.json")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
STATE = 32 * 128 * 128 * 4  # one slot's float32 matrix states of a block


def test_the_configuration_holds_six_delta_rule_blocks_of_eight():
    assert costs_gdn.gdn_blocks(M) == 6
    assert costs_gdn.gdn_blocks(dict(M, num_hidden_layers=48)) == 36


def test_a_step_reads_and_writes_each_live_state_once():
    fl, by = costs_gdn.gdn_step(16, M)
    assert STATE == 2 << 20
    assert 2 * 16 * STATE < by < 2 * 16 * STATE * 1.02  # q, k, v, g, beta, o are the rest
    assert fl == 8.0 * 16 * 32 * 128 * 128
    assert costs.roofline_min_s(fl, by, PEAKS) == pytest.approx(by / 819e9)  # bound by memory
    assert costs_gdn.gdn_step(8, M)[1] == pytest.approx(by / 2)  # idle slots are not counted


def test_a_scan_counts_valid_tokens_and_one_state_hand_over_a_chunk():
    whole, part = costs_gdn.gdn_scan([128], M), costs_gdn.gdn_scan([40], M)
    assert part[0] < whole[0] and part[1] < whole[1]
    assert costs_gdn.gdn_scan([128, 128], M) == (2 * whole[0], 2 * whole[1])
    assert whole[1] > 2 * STATE  # a state in and a state out
    # per value head L^2 (3 Dk + 2 Dv) + 6 L Dk Dv at L = Dk = Dv = 128: 11 x 128^3
    assert whole[0] == 32 * 11.0 * 128 ** 3


def test_touched_experts_are_read_once_whatever_their_rows():
    few, many = costs_gdn.expert_matmul(1280, 128, M), costs_gdn.expert_matmul(5120, 128, M)
    weights = 2.0 * 3 * 2048 * 512 * 128  # three bf16 matrices an expert
    assert few[1] == pytest.approx(weights + 4 * 2048 * 1280)
    assert many[1] - few[1] == pytest.approx(4 * 2048 * (5120 - 1280))
    assert many[0] == 4 * few[0]


def test_readers_have_nothing_to_read_without_a_trace_or_their_counters():
    obs = {"trace": None, "device": {"platform": "tpu", "kind": "TPU v5 lite"}, "requests": []}
    assert gdn_roofline.read(obs, "^jit_decode_impl$", "gdn_step", "gdn_step") is None
    packs = dict(num="expert_pairs_held", num_less="expert_pairs_held_decode",
                 den="experts_touched", den_less="experts_touched_decode")
    assert counter_difference_ratio.read({}, **packs) is None
    assert counter_difference_ratio.read({"counters": {"expert_pairs_held": 5}}, **packs) is None
    counters = {"expert_pairs_held": 1300, "expert_pairs_held_decode": 100,
                "experts_touched": 130, "experts_touched_decode": 10}
    assert counter_difference_ratio.read({"counters": counters}, **packs) == 10.0
    counters["experts_touched_decode"] = 130  # no pack in the window
    assert counter_difference_ratio.read({"counters": counters}, **packs) is None


def test_every_control_names_what_the_comparison_reads():
    arch = harness.module("models", M["model_type"])
    assert set(arch.DEPARTURES) < set(serve_deltanet.CONTROLS)
    assert set(serve_deltanet._STATE_AS) < set(serve_deltanet.CONTROLS)
    with pytest.raises(ValueError, match="no departure"):
        with arch.departure("no_such_thing"):
            pass


@pytest.fixture(scope="module")
def replayed():
    """The driver's replay and the reference's kept state at the rehearsal
    size (float32): two prompts of 3 and 2 chunks, 6 fed tokens each."""
    import jax
    import numpy as np

    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models.transformer import init_params

    m = harness.rehearsed(M, True)
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
    replay = serve_deltanet._Replay(jax, np, eng, cfg)
    schedule = serve_deltanet._alone(prompts, fed, e["prefill_chunk"])
    k = m["num_experts_per_tok"]
    recur = jax.jit(lambda *c: arch.recurrence(*(a[None] for a in c))[1][0])

    def against_reference(state_as=None, departing=None):
        out = []
        for (got, probes, kept, consumed), p, f in zip(
                replay(prompts, fed, schedule, state_as=state_as), prompts, fed):
            buf = np.zeros((1, 88), np.int32)
            buf[0, :len(p) + len(f)] = p + f
            forced = serve_deltanet._forced(np, probes, 88, k)
            if departing is None:
                lg, _ = arch.probe(eng.params, buf, m, forced)
            else:
                with arch.departure(departing):
                    lg, _ = arch.probe(eng.params, buf, m, forced)
            d = np.abs(got - np.asarray(lg)[0][len(p) - 1: len(p) + len(f)]).max()
            assert len(consumed) == 6
            assert all(len(c["gdn_q"]) == len(p) + len(f) for c in consumed)
            again = [np.asarray(recur(*(c[key] for key in serve_deltanet._INPUTS)))
                     for c in consumed]
            out.append((float(d), serve_deltanet._state_error(np, kept, again)))
        return out

    yield against_reference
    eng.close()


def test_the_replays_kept_state_is_the_one_token_delta_rules(replayed):
    """Chunks, hand-overs from pack to pack and steps leave the state the
    float32 delta rule leaves on the same inputs, and the logits are the
    reference's."""
    for d, off in replayed():
        assert d < 1e-4 and off < 1e-5


def test_a_state_kept_in_bfloat16_shows_in_the_kept_state(replayed):
    for _, off in replayed("bfloat16"):
        assert off > 1e-3


@pytest.mark.parametrize("name", ["no_output_gate", "rotary_on_whole_head", "no_beta",
                                  "no_decay", "routing_not_renormalised"])
def test_a_departure_of_the_mathematics_shows_in_the_logits(replayed, name):
    """Each control of the mathematics moves the reference away from the
    program by far more than rounding."""
    assert all(d > 1e-2 for d, _ in replayed(departing=name))
