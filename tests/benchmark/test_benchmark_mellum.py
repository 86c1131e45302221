"""The yardstick of a TRAINED model of window and full GQA over a held share of
experts (``benchmark/models/mellum.py``, ``costs_experts_train.py``, the two
roofline readers, the scopes' classes, the configuration's arithmetic) and the
training driver's ``correct``: sound at the rehearsal size, every control
refused.  No test here pins a position in ``BENCHMARK.json``'s lists."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import costs, costs_experts_train, harness  # noqa: E402
from benchmark.drivers import train_experts  # noqa: E402
from benchmark.readers import counter_ratio, expert_train_roofline, flash_window_roofline  # noqa: E402

MAN = harness.manifest()
CELL = "mellum2_train_8k_experts_1chip"
M = harness.load_json(harness.HERE / "configs" / "mellum2_l4_e16_train_1chip.json")
ARCH = harness.module("models", M["model_type"])
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TPU = {"platform": "tpu", "kind": "TPU v5 lite"}


def test_the_cell_trains_the_configuration_on_one_chip_and_reports_the_training_rate():
    cell = next(w for w in MAN["workloads"] if w["name"] == CELL)
    entry = next(c for c in MAN["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and cell["traffic"] == "train_fixed_8k"
    assert entry["file"].endswith("mellum2_l4_e16_train_1chip.json") and M["driver"] == "train_experts"
    rate = next(m for m in MAN["end_to_end"] if m["name"] == "train_tokens_per_s_per_chip")
    assert CELL in rate["workloads"] and rate["bound"] == 0.01
    mine = [m for m in MAN["per_layer"] if m["name"].endswith(".mellum")]
    assert 1 <= len(mine) <= 12 and len(MAN["per_layer"]) <= 128
    assert {m["moves"] for m in mine} == {"train_tokens_per_s_per_chip"}
    assert all(CELL in m["workloads"] for m in mine)   # a later cell may join one, behind it
    t = harness.traffic_of(cell["traffic"])
    assert (t["seq_len"], t["micro_batch_per_chip"], t["distinct_batches"]) == (8192, 2, 4)


def test_the_cut_is_one_whole_period_a_quarter_of_the_experts_and_of_the_vocabulary():
    dep = M["deployment"]
    assert M["layer_types"][:M["num_hidden_layers"]] == ["sliding_attention"] * 3 + ["full_attention"]
    assert len(M["layer_types"]) == dep["published"]["num_hidden_layers"] == 28
    assert M["num_experts"] * dep["group_chips"] == dep["num_experts_total"] == 64
    assert M["vocab_size"] * dep["group_chips"] == dep["published"]["vocab_size"]
    assert set(M["reduced"]) == set(M["reduced_why"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    for key in ("qk_norm", "window_edge", "yarn", "balance_term", "weights", "left_out"):
        assert M["assumed"][key]
    # the guide's floors: a whole period and >= 4 layers, >= 8 experts, >= 1/8 of the vocabulary
    assert M["num_hidden_layers"] >= 4 and M["num_experts"] >= 8 and M["vocab_size"] * 8 >= 98304


def test_the_arithmetic_of_the_configuration_file_is_the_programs_parameter_count():
    cfg = ARCH.transformer_config(harness.rehearsed(M, False))
    d, hd = M["hidden_size"], M["head_dim"]
    attn = d * hd * (2 * M["num_attention_heads"] + 2 * M["num_key_value_heads"])
    layer = attn + d * 64 + M["num_experts"] * 3 * d * M["moe_intermediate_size"] + 2 * d + 2 * hd
    assert cfg.param_count == 4 * layer + 2 * M["vocab_size"] * d + d == 595_154_176
    text = M["deployment"]["arithmetic"]
    for said in ("21.23 M", "99.09 M", "120.48 M", "113.2 M", "595.2 M", "6.65 GiB", "8.87 GiB"):
        assert said in text, said
    assert cfg.latent.router_aux_loss_coef == M["training"]["router_aux_loss_coef"] == 0.001
    assert cfg.latent.wattn.window == 1024 and cfg.latent.gattn.window == 0
    assert cfg.latent.gattn.rope_scaling.factor == 16 and cfg.latent.wattn.rope_scaling is None
    assert cfg.latent.gattn.gate == "none" and cfg.latent.n_shared == 0


def test_a_token_requires_what_the_issue_counted():
    """498 MFLOP forward: the held experts 99, attention's pairs 114 (a full
    layer 67, a sliding layer 15.7: the window skips 77% of the causal pairs),
    attention's matrices 170, the head 113."""
    pairs = ARCH.attended_pairs(M, 8192)
    per_pair = 4 * M["num_attention_heads"] * M["head_dim"]
    assert pairs["full_attention"] / 8192 * per_pair / 1e6 == pytest.approx(67.1, abs=0.1)
    assert pairs["sliding_attention"] / 3 / 8192 * per_pair / 1e6 == pytest.approx(15.7, abs=0.1)
    assert 1 - pairs["sliding_attention"] / 3 / pairs["full_attention"] == pytest.approx(0.77, abs=0.01)
    assert ARCH.allowed_pairs(8192, 1024) == sum(min(i + 1, 1024) for i in range(8192))
    d, f = M["hidden_size"], M["moe_intermediate_size"]
    experts = 4 * 2 * 3 * d * f * M["num_experts_per_tok"] * 16 / 64
    assert experts / 1e6 == pytest.approx(99.1, abs=0.1)
    forward = 2 * ARCH.matmul_params(M) + per_pair * sum(pairs.values()) / 8192
    assert forward / 1e6 == pytest.approx(498, abs=1.5)
    assert ARCH.train_flops_per_token(M, 8192) == pytest.approx(3 * forward)


def test_a_held_pair_costs_three_products_forward_and_six_backward():
    pairs, e, d, f = 32768.0, 16, 2304, 896
    fl, by = costs_experts_train.experts_fwd(pairs, e, d, f)
    assert fl == 3 * 2 * pairs * d * f and by == 2 * (3 * e * d * f + 2 * pairs * d)
    bfl, bby = costs_experts_train.experts_bwd(pairs, e, d, f)
    assert bfl == 2 * fl and bby > by
    # 2048 rows an expert: the products bound the time, not the matrices' bytes
    assert costs.roofline_min_s(fl, by, PEAKS) == pytest.approx(fl / 197e12)
    # ... a few rows an expert: the matrices' bytes do
    fl, by = costs_experts_train.experts_fwd(64.0, e, d, f)
    assert costs.roofline_min_s(fl, by, PEAKS) == pytest.approx(by / 819e9)


class _Trace:
    window_s = 3.0

    def __init__(self, seconds):
        self.seconds = seconds

    def kernel_seconds(self, pattern):
        return self.seconds[pattern], 1


def _obs(**kw):
    return {"kind": "train", "window": (10.0, 55.0), "steps": 90, "tokens_per_step": 16384,
            "chips": 1, "seq": 8192, "micro": 2, "model": M, "device": dict(TPU), **kw}


def test_the_flash_readers_need_comes_from_positions_not_from_the_implementation():
    need = 0.0
    for kind, n in (("sliding_attention", 3), ("full_attention", 1)):
        window = 1024 if kind == "sliding_attention" else 0
        pairs = 2 * ARCH.allowed_pairs(8192, window)
        need += n * (14 * 32 * 128 * pairs) / 197e12  # 4 forward + 10 backward a pair
    steps = 90 / 45.0 * 3.0
    got = flash_window_roofline.read(_obs(trace=_Trace({"F": 0.5, "B": 1.0})), "F", "B")
    assert got == pytest.approx(100 * steps * need / 1.5, rel=1e-3)
    assert got < 100
    # twice the kernels' time, half the share; nothing to read without a trace or off the chip
    assert flash_window_roofline.read(_obs(trace=_Trace({"F": 1.0, "B": 2.0})), "F", "B") \
        == pytest.approx(got / 2)
    assert flash_window_roofline.read(_obs(trace=None), "F", "B") is None
    assert flash_window_roofline.read(_obs(trace=_Trace({"F": 0.0, "B": 0.0})), "F", "B") is None
    cpu = _obs(trace=_Trace({"F": 0.5, "B": 1.0}), device={"platform": "cpu", "kind": "cpu"})
    assert flash_window_roofline.read(cpu, "F", "B") is None


def test_the_expert_reader_needs_a_trace_and_the_programs_count_of_held_pairs():
    assert expert_train_roofline.read(_obs(trace=None, counters={"expert_pairs_held": 5}),
                                      "train_step_experts", "expert_matmul") is None
    # (``_xprograms``: the capture's programs as read; without the key the reader looks for
    # the newest trace file under ``.bench_out`` and, in a checkout that has none, raises)
    assert expert_train_roofline.read(_obs(trace=_Trace({}), counters={}, _xprograms=None),
                                      "train_step_experts", "expert_matmul") is None


def test_the_counters_read_the_share_routed_here_and_the_largest_group_over_the_mean():
    c = {"expert_pairs_routed": 4 * 131072, "expert_pairs_held": 4 * 32768,
         "expert_rows_max": 4 * 2150, "expert_rows_min": 4 * 1950}
    share = harness.load_json(harness.HERE / "metrics" / "routed_here_share.mellum.json")
    skew = harness.load_json(harness.HERE / "metrics" / "expert_rows_max_over_mean.mellum.json")
    assert counter_ratio.read({"counters": c}, **share["params"]) == pytest.approx(25.0)
    assert counter_ratio.read({"counters": c}, **skew["params"]) == pytest.approx(2150 / 2048)
    assert counter_ratio.read({"counters": {}}, **skew["params"]) is None
    assert skew["params"]["scale"] == M["num_experts"]  # the held experts: what the mean is over


def test_the_window_is_made_stationary_by_the_schedule_and_the_embeddings_scale():
    """The cell's routing must neither drift over a window nor depend on the
    seed (PERF.md section 7): a linear warm-up from a learning rate that is not
    zero (step 0's update is what ``correct`` reads) and unit embedding rows."""
    tr = M["training"]
    sched = tr["lr_schedule"]["params"]
    assert tr["lr_schedule"]["type"] == "WarmupLR" and sched["warmup_type"] == "linear"
    assert 0 < sched["warmup_min_lr"] < sched["warmup_max_lr"] == tr["lr"]
    assert sched["warmup_num_steps"] >= 1000 and tr["embedding_std"] == 1.0
    assert "embedding_std" in M["assumed"]["weights"] and "lr_schedule" in M["assumed"]["training"]


@pytest.mark.parametrize("a,b,over,whole,each", [
    ([[[3.0, 4.0]], [[0.0, 0.0]]], [[[3.0, 4.0]], [[0.0, 0.0]]], None, 0.0, [0.0, 0.0]),
    ([[[3.0, 4.0]], [[1.0, 0.0]]], [[[3.0, 4.0]], [[0.0, 0.0]]], None, 1 / 26 ** 0.5, [0.0, 1.0]),
    ([[0.0, 0.0]], [[3.0, 4.0]], [[3.0, 4.0]], 1.0, None),
], ids=["equal_and_an_empty_expert", "one_stale_expert_of_two", "a_state_left_unchanged_reads_1"])
def test_the_gap_of_two_tensors_whole_and_an_expert_at_a_time(a, b, over, whole, each):
    import numpy as np

    arr = lambda x: None if x is None else np.asarray(x, np.float32)
    got_whole, got_each = train_experts._gap(arr(a), arr(b), arr(over))
    assert float(got_whole) == pytest.approx(whole, abs=1e-6)
    assert (got_each is None) if each is None else np.allclose(got_each, each, atol=1e-6)


@pytest.mark.parametrize("path,want", [
    ("jit(train_step)/grad/jvp(jit(main))/checkpoint/expert_matmul/gmm", "expert_matmul"),
    ("jit(train_step)/grad/transpose(jvp(jit(main)))/rematted_computation/expert_matmul/gmm", "expert_matmul"),
    ("jit(train_step)/grad/transpose(jvp(jit(main)))/checkpoint/expert_matmul/tgmm", "expert_matmul"),
    ("jit(train_step)/grad/jvp(jit(main))/checkpoint/expert_layout/sort", "expert_layout"),
    ("jit(train_step)/grad/jvp(jit(main))/checkpoint/router/dot_general", "router"),
    ("jit(train_step)/grad/transpose(jvp(jit(main)))/checkpoint/attn_window/flash_sparse_bwd_dq", "attention"),
    ("jit(train_step)/grad/jvp(jit(main))/checkpoint/attn_full/flash_fwd", "attention"),
    ("jit(train_step)/grad/jvp(jit(main))/loss/chunked", "loss"),
    ("jit(train_step)/optimizer/mul", "optimizer"),
    ("jit(train_step)/grad/jvp(jit(main))/checkpoint/dot_general", "other"),
])
def test_a_scopes_forward_recomputation_and_backward_fall_in_one_class(path, want):
    import re

    spec = harness.load_json(harness.HERE / "scopes" / "train_step_experts.json")
    got = next((c for c, rx in spec["classes"] if re.search(rx, path)), spec["default"])
    assert got == want


def test_every_control_names_a_fault_the_reference_can_plant():
    assert set(train_experts.CONTROLS) == set(ARCH.DEPARTURES) | {
        "weights_fp8", "stale_expert_gradient", "update_dropped"}
    with pytest.raises(ValueError, match="no departure"):
        with ARCH.departure("nothing"):
            pass
    with ARCH.departure("window_off_by_one"):
        assert ARCH.window_of(M, "sliding_attention") == 1025
    with ARCH.departure("no_window"):
        assert ARCH.window_of(M, "sliding_attention") == 0
    assert ARCH.window_of(M, "sliding_attention") == 1024 and ARCH.window_of(M, "full_attention") == 0


def test_a_rehearsal_with_every_control_is_sound_and_refuses_each():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, str(ROOT / MAN["command"][1]), "--workload", CELL, "--seed",
         str(2**31 + 11), "--rehearse", "--set", 'control="all"'],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.splitlines()
    sound = next(l for l in lines if l.startswith("correct: "))
    assert sound.endswith("-> True") and "exact" in sound
    controls = [l for l in lines if l.startswith("control ")]
    assert len(controls) == len(train_experts.CONTROLS)
    assert all(l.endswith("-> refused") for l in controls), [l for l in controls if "PASSED" in l]
    assert f"controls: all {len(controls)} refused" in out.stdout
    # the step's OWN update is what is compared: a state left unchanged reads 1 and is refused
    dropped = next(l for l in controls if l.startswith("control update_dropped"))
    assert "worst 1.000 at" in dropped
    assert any(l.startswith("pairs on the held experts: ") for l in lines)
    assert json.loads(lines[-1])["attempted"] > 0
