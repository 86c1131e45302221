"""The cell of EVA attention (``evabyte_byte_docs_closed``): its configuration's
cut and arithmetic re-reckoned from the file, the yardstick ``costs_eva.py`` and
the reader that divides it by a body's time, every new entry found by NAME, the
traffic's fixed rounds, each of the reference's readings shown to decide a
logit, and the rehearsal."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import costs, costs_eva, harness  # noqa: E402
from benchmark.readers import counter_ratio, eva_roofline  # noqa: E402

MAN = harness.manifest()
CELL = "evabyte_byte_docs_closed"
ENTRY = next(w for w in MAN["workloads"] if w["name"] == CELL)
CONFIG = next(c for c in MAN["configs"] if c["name"] == ENTRY["config"])
M = harness.load_json(ROOT / CONFIG["file"])
PUBLISHED = harness.load_json(harness.HERE / "published" / f"{M['published']}.json")
TRAFFIC = harness.traffic_of(ENTRY["traffic"])
MINE = [m for m in MAN["per_layer"] if m["name"].endswith(".evabyte")]   # a later cell may join one
# what PR 53 brought as ``<family>.evabyte`` copies of the ``.serve`` readers and PR 57 folded
# into the ``.serve`` lists (the manifest's tests keep what each stated; the three guards
# among them are ONE entry, their sum, since), and the ``.serve`` families born with the cell
SHARED = ("window_faults", "device_idle_share",
          "peak_hbm_gib", "prefill_pack_device_p50_ms", "decode_device_p50_ms",
          "decode_batch_mean", "host_slack_p50_ms", "host_device_skew_ms",
          "late_collect_lost_ms", "fetch_tail_max_ms")
OWN = ("eva_decode_attn_call_ms", "eva_decode_attn_roofline", "eva_prefill_attn_call_ms",
       "eva_prefill_attn_roofline", "eva_summarise_call_ms", "eva_summary_rows_share",
       "eva_rows_per_context_token", "eva_pages_returned_per_window_closed")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
GIB = 2.0 ** 30


def test_the_manifest_holds_the_cell_and_its_entries_under_the_cap():
    """The cell, its configuration and its entries by NAME, wherever in their lists
    they stand; the list against the driver's cap, not a count."""
    assert len(MAN["workloads"]) >= 11 and harness.find_cell(MAN, CELL) is ENTRY
    assert len(MAN["per_layer"]) <= 128, f"{len(MAN['per_layer'])} of 128 used"
    assert ENTRY["chips"] == 1 and CONFIG["file"].endswith(f"{ENTRY['config']}.json")
    rate = next(m for m in MAN["end_to_end"] if m["name"] == "serve_tokens_per_s")
    # the cell joined the rate that was there and brought no bound of its own: the value is
    # the manifest's to state (a `benchmark` PR refits it: 0.02, 0.03, 0.06 since PR 41)
    assert CELL in rate["workloads"] and 0.01 <= rate["bound"] <= 0.1
    assert sorted(m["name"] for m in MINE) == sorted(f"{name}.evabyte" for name in OWN)
    loaded = [m["name"] for m in harness.metrics_of(MAN, CELL, True)]
    assert sorted(loaded) == sorted([m["name"] for m in MINE] + [f"{f}.serve" for f in SHARED])


@pytest.mark.parametrize("family", SHARED)
def test_a_serve_family_lists_the_cell_and_no_copy_of_it_is_left(family):
    theirs = next(m for m in MAN["per_layer"] if m["name"] == f"{family}.serve")
    assert CELL in theirs["workloads"] and theirs["moves"] == "serve_tokens_per_s"
    assert not [m for m in MAN["per_layer"] if m["name"] == f"{family}.evabyte"]
    assert not (harness.HERE / "metrics" / f"{family}.evabyte.json").exists()
    spec = harness.load_json(harness.HERE / "metrics" / f"{family}.serve.json")
    assert callable(harness.module("readers", spec["reader"]).read)


@pytest.mark.parametrize("name", OWN)
def test_an_entry_of_its_own_is_found_by_its_name_with_a_file_and_a_reader(name):
    mine = next(m for m in MINE if m["name"] == f"{name}.evabyte")
    assert mine["moves"] == "serve_tokens_per_s" and CELL in mine["workloads"]
    spec = harness.load_json(harness.HERE / "metrics" / f"{mine['name']}.json")
    assert spec["unit"] == mine["unit"]
    assert callable(harness.module("readers", spec["reader"]).read)
    if name.endswith("roofline"):
        assert mine["unit"] == "%" and mine["better"] == "higher" \
            and mine["source"] == "device_trace" and spec["reader"] == "eva_roofline"
    if name.endswith("call_ms"):
        # the attention's bodies are read in one program each; the summaries run in BOTH
        # programs of a tick (a pack's 32 chunks, a step's gather a slot) and are read in both
        both = name == "eva_summarise_call_ms"
        assert spec["reader"] == ("scope_call_ms_programs" if both else "scope_call_ms")
        assert "eva_" in spec["params"]["scope"] and len(spec["params"].get("modules", "1")) == 1 + both


def test_the_configuration_cuts_the_depth_alone_and_states_its_readings():
    assert M["reduced"] == CONFIG["reduced"] == ["num_hidden_layers"]
    assert {k: M[k] for k in PUBLISHED if k != "num_hidden_layers"} == \
        {k: v for k, v in PUBLISHED.items() if k != "num_hidden_layers"}
    assert (M["num_hidden_layers"], PUBLISHED["num_hidden_layers"]) == (8, 32)
    assert set(M["assumed"]) >= {"pooling", "windows", "summaries_of", "head_layout", "weights",
                                 "torch_dtype", "left_out", "engine"}
    d = M["deployment"]
    assert (d["pipeline_stage"], d["pipeline_stages"], d["chips"]) == (0, 4, 1)
    assert d["published"]["num_hidden_layers"] == d["pipeline_stages"] * d["held"]["num_hidden_layers"]
    assert M["num_hidden_layers"] >= 4  # the guide's floor: one kind of layer, a period is one


def test_the_deployments_arithmetic_re_reckoned_from_the_file():
    d, f, h = M["hidden_size"], M["intermediate_size"], M["num_attention_heads"]
    hd = d // h
    layer = 4 * d * d + 3 * d * f + 2 * h * hd + 2 * d
    assert layer == M["deployment"]["layer_params"] == 202_391_552
    ends = M["vocab_size"] * d + d * M["num_pred_heads"] * M["vocab_size"] + d
    weights = 2 * (M["num_hidden_layers"] * layer + ends)
    assert round(2 * layer / 2**20) == 386 and round(weights / GIB, 2) == 3.04
    e = M["engine"]
    page = e["block_size"] * 2 * d * 2 * M["num_hidden_layers"]  # rows x (k, v) x bf16 x layers
    assert page == M["deployment"]["page_bytes"] == 16 * 2**20
    pool = e["num_blocks"] * page
    assert pool == 9 * GIB and 0.25 * 15.75 * GIB < weights + pool < 15.75 * GIB
    # a closed window's summaries are ONE page; the longest context's table
    assert M["window_size"] // M["chunk_size"] == e["block_size"]
    longest = e["max_seq_len"] == M["max_position_embeddings"] and e["max_seq_len"] // M["window_size"]
    assert longest - 1 + 1 + M["window_size"] // e["block_size"] == 32  # pages, against 256 of MHA
    assert e["max_seq_len"] // e["block_size"] == 256
    assert e["prefix_caching"] is False and e["prefill_chunk"] == 512
    assert M["window_size"] % e["prefill_chunk"] == 0


def test_the_traffic_fits_the_positions_and_the_sample_crosses_the_windows():
    assert TRAFFIC["kind"] == "reasoning_closed" and TRAFFIC["clients"] == M["engine"]["max_seqs"]
    assert TRAFFIC["prompt_tokens"]["max"] + TRAFFIC["answer_tokens"]["max"] \
        <= M["max_position_embeddings"]
    sample = M["correctness"]
    closed = [n // M["window_size"] for n in sample["prompts"]]
    assert closed == [4, 2, 0]
    # the shortest closes its first window DURING decode
    assert sample["prompts"][2] < M["window_size"] <= sample["prompts"][2] + sample["decode_steps"] - 1
    r = harness.rehearsed(M, True)
    assert [n // r["window_size"] for n in r["correctness"]["prompts"]] == [4, 2, 0]
    assert r["window_size"] // r["chunk_size"] == r["engine"]["block_size"]


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_the_fixed_rounds_are_the_same_tasks_for_every_seed(seed):
    build = harness.module("generators", TRAFFIC["kind"]).build
    a = build(TRAFFIC, seed=seed, seconds=45.0, vocab=M["vocab_size"])
    b = build(TRAFFIC, seed=7, seconds=45.0, vocab=M["vocab_size"])
    fixed = TRAFFIC["fixed_rounds"] * TRAFFIC["strata"]
    assert a.multiset() == b.multiset()
    assert a.lengths[:fixed] == b.lengths[:fixed] and a.answers[:fixed] == b.answers[:fixed]
    assert fixed < TRAFFIC["pool"] or a.lengths == b.lengths
    ids = a._request(0, 0).prompt
    assert 0 <= min(ids) and max(ids) < M["vocab_size"] == 320


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_every_round_fixed_deals_the_rounds_a_run_reaches_as_they_are(seed):
    """ISSUE 53's first fallback for a wide spread, "every round fixed", cannot move
    a run: the rounds that ARE fixed hold the same documents either way, and ramp +
    window reach fewer tasks than they hold (95 of 192; my chip runs, PR 53)."""
    build = harness.module("generators", TRAFFIC["kind"]).build
    every = dict(TRAFFIC, fixed_rounds=TRAFFIC["pool"] // TRAFFIC["strata"])
    a = build(TRAFFIC, seed=seed, seconds=45.0, vocab=M["vocab_size"])
    b = build(every, seed=seed, seconds=45.0, vocab=M["vocab_size"])
    fixed = TRAFFIC["fixed_rounds"] * TRAFFIC["strata"]
    assert fixed >= 1.5 * 95
    assert a.lengths[:fixed] == b.lengths[:fixed] and a.answers[:fixed] == b.answers[:fixed]
    assert [t for t, _ in a.initial()] == [t for t, _ in b.initial()]
    assert TRAFFIC["spread_s"] == 8.0 and TRAFFIC["ramp_s"] == 20.0  # as ISSUE 53 gives them


@pytest.mark.parametrize("n", [0, 1, 15, 16, 2047, 2048, 2049, 4095, 4096, 11000, 32767, 32768])
def test_rows_is_a_count_by_hand(n):
    w, c = M["window_size"], M["chunk_size"]
    closed_windows = n // w
    by_hand = sum(1 for p in range(closed_windows * w) if p % c == 0) \
        + sum(1 for p in range(closed_windows * w, n))
    assert costs_eva.rows(n, M) == by_hand
    arch = harness.module("models", M["model_type"])
    assert arch.rows(n, M) == by_hand


def test_a_pair_costs_four_flops_a_head_and_dim_and_rows_are_read_once():
    fl, by = costs_eva.attention(1000, 10, 100, M)
    assert fl == 4.0 * 32 * 128 * 1000
    assert by == 2.0 * 4096 * 2 * 10 + 16384 * 100 and costs_eva.row_bytes(M) == 16 * 1024
    # a tick of 32 slots at ~1700 live rows each, one layer: the rows' bytes bound it
    rows = 32 * 1700
    fl, by = costs_eva.attention(rows, 32, rows, M)
    assert costs.roofline_min_s(fl, by, PEAKS) == pytest.approx(by / 819e9)
    assert 8 * by == pytest.approx(7.1e9, rel=0.02)  # the issue's estimate of a tick's read
    # a pack of 512 over 3456 rows, one layer: compute bounds it
    pairs = 512 * 3456 + 512 * 513 // 2
    fl, by = costs_eva.attention(pairs, 512, 3456 + 512, M)
    assert costs.roofline_min_s(fl, by, PEAKS) == pytest.approx(fl / 197e12)
    sfl, sby = costs_eva.summarise(32, M)
    assert sby == 16384 * 17 * 32 and sfl == 6.0 * 4096 * 16 * 32


def test_the_roofline_reader_divides_the_need_by_the_bodys_time(monkeypatch):
    class Trace:
        def whole_spans(self, name, key):
            return [1, 2]

    ticks = [(0.0, 1.0), (1.0, 2.0), (2.0, 3.0), (3.0, 4.0)]
    tick_args = {"batch": 32, "rows_total": 32 * 1700, "eva_pairs": 32 * 1700}
    obs = {"trace": Trace(), "ticks": ticks, "model": M,
           "device": {"platform": "tpu", "kind": "TPU v5 lite"},
           "spans": [("decode_tick", 0.2, 0.8, tick_args), ("decode_tick", 1.2, 1.8, tick_args),
                     ("decode_tick", 2.2, 2.8, tick_args), ("prefill_pack", 1.1, 1.9, {"tokens": 5})]}
    need = 8 * costs.roofline_min_s(*costs_eva.attention(32 * 1700, 32, 32 * 1700, M), PEAKS)
    monkeypatch.setattr(eva_roofline, "per_execution", lambda o, module, scope: [2 * need, 2 * need])
    read = lambda span: eva_roofline.read(obs, "^jit_decode_impl$", "eva_attend", span)
    assert read("decode_tick") == pytest.approx(50.0)
    assert read("prefill_pack") is None          # its span carries no rows_total
    assert eva_roofline.read(dict(obs, trace=None), "m", "s", "decode_tick") is None
    monkeypatch.setattr(eva_roofline, "per_execution", lambda o, module, scope: None)
    assert read("decode_tick") is None           # a program without the scope (the parent)


def test_a_body_of_both_programs_is_read_in_each_and_summed(monkeypatch):
    from benchmark.readers import scope_call_ms, scope_call_ms_programs

    spec = harness.load_json(harness.HERE / "metrics" / "eva_summarise_call_ms.evabyte.json")["params"]
    per = {spec["modules"][0]: 0.147, spec["modules"][1]: 0.452}
    monkeypatch.setattr(scope_call_ms, "read", lambda obs, module, scope, q=50: per.get(module))
    assert scope_call_ms_programs.read({}, **spec) == pytest.approx(0.599)
    del per[spec["modules"][0]]                  # a capture that holds no pack
    assert scope_call_ms_programs.read({}, **spec) == pytest.approx(0.452)
    per.clear()                                  # a program without the scope (the parent)
    assert scope_call_ms_programs.read({}, **spec) is None


def test_the_counters_readers_read_the_drivers_names():
    spec = lambda name: harness.load_json(harness.HERE / "metrics" / f"{name}.evabyte.json")["params"]
    c = {"eva_summary_rows_read": 30, "eva_exact_rows_read": 70, "eva_rows_read": 100,
         "eva_rows_live": 16, "eva_context_tokens_live": 100, "eva_pages_returned": 32,
         "eva_windows_closed": 2}
    assert counter_ratio.read({"counters": c}, **spec("eva_summary_rows_share")) == 30.0
    assert counter_ratio.read({"counters": c}, **spec("eva_rows_per_context_token")) == 0.16
    assert counter_ratio.read({"counters": c}, **spec("eva_pages_returned_per_window_closed")) == 16.0
    assert counter_ratio.read({"counters": dict(c, eva_windows_closed=0)},
                              **spec("eva_pages_returned_per_window_closed")) is None
    assert counter_ratio.read({}, **spec("eva_summary_rows_share")) is None


@pytest.fixture(scope="module")
def small():
    import jax

    from deepspeed_tpu.models.transformer import init_params

    m = harness.rehearsed(M, True)
    arch = harness.module("models", m["model_type"])
    cfg = arch.transformer_config(m, max_seq_len=256)
    params = init_params(jax.random.PRNGKey(11), cfg)
    ids = np.random.default_rng(11).integers(0, m["vocab_size"], (1, 100)).astype(np.int32)
    return m, arch, params, ids, np.asarray(arch.logits(params, ids, m))[0]


@pytest.mark.parametrize("name,past", [
    ("mean_pooling", 32), ("no_key_offset", 32), ("own_window_summaries", 4),
    ("summaries_unroped", 32), ("window_edge_off_by_one_chunk", 32), ("row_for_position", 32),
    ("bf16_softmax", 0)])
def test_each_reading_of_the_reference_decides_a_logit(small, name, past):
    """A departure that only a context past the first window can show (``past``
    32) leaves the first window's logits as they were; every one moves a later
    logit by far more than the 1e-4 the CPU tests hold program and reference to."""
    m, arch, params, ids, want = small
    with arch.departure(name):
        got = np.asarray(arch.logits(params, ids, m))[0]
    assert np.abs(got[:past] - want[:past]).max(initial=0.0) <= 1e-5
    assert np.abs(got[past:] - want[past:]).max() > (2e-3 if name != "bf16_softmax" else 3e-4)


def test_the_drivers_controls_are_the_references_departures_and_one_precision_down():
    from benchmark.drivers import serve_eva

    arch = harness.module("models", M["model_type"])
    assert set(serve_eva.CONTROLS) == set(arch.DEPARTURES) | {"fp8_weights"}
    with pytest.raises(ValueError, match="no departure"):
        with arch.departure("no_such_reading"):
            pass


def test_the_rehearsal_serves_closes_windows_and_holds_every_comparison():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, str(ROOT / MAN["command"][1]), "--workload", CELL, "--seed",
         str(2**31 + 11), "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["metrics"] == {} and line["failed"] == 0 and line["attempted"] > 0
    checks = [l for l in out.stdout.splitlines() if l.startswith("correct: ")]
    assert len(checks) >= 1 + 3 + 1 + 3 and all("-> False" not in l for l in checks)
    assert "7 windows (7 by the positions) and gave back 28 pages (4 a window)" in checks[0]
    ran = next(l for l in out.stdout.splitlines()
               if l.startswith("rehearsal: readers that returned a value:")).split()
    for name in ("eva_summary_rows_share", "eva_rows_per_context_token",
                 "eva_pages_returned_per_window_closed", "window_faults", "serve_tokens_per_s"):
        assert any(r.startswith(name) for r in ran), name
