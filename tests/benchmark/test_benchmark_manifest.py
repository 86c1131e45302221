"""BENCHMARK.json against the contract's static rules and against the files
the harness finds by name, and the CPU rehearsal of every cell on disk."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import harness  # noqa: E402

MAN = harness.manifest()
CELLS = [w["name"] for w in MAN["workloads"]]
ALL_METRICS = MAN["end_to_end"] + MAN["per_layer"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def cells_of(metric):
    return metric.get("workloads", CELLS)


def test_top_level_keys_and_limits():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    assert isinstance(MAN["run_seconds"], int) and 1 <= MAN["run_seconds"] <= 51
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(MAN["workloads"]) <= 24 and 1 <= len(MAN["end_to_end"]) <= 16
    assert sum(w["chips"] == 4 for w in MAN["workloads"]) <= max(1, len(CELLS) // 4)
    assert all(w["chips"] in (1, 4) for w in MAN["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_command_names_only_files_under_paths():
    for word in MAN["command"]:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in MAN["paths"]) and (ROOT / word).is_file()


@pytest.mark.parametrize("entry", ALL_METRICS + MAN["workloads"] + MAN["configs"],
                         ids=lambda e: e["name"])
def test_names_units_and_keys(entry):
    assert NAME.match(entry["name"])
    if "unit" in entry:
        assert UNIT.match(entry["unit"]) and entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES
        extra = set(entry) - {"name", "unit", "better", "source", "bound", "layer",
                              "moves", "workloads"}
        assert not extra
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] and "\t" not in entry[key]


def test_no_two_entries_share_a_name():
    for group in (ALL_METRICS, MAN["workloads"], MAN["configs"]):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))


def test_end_to_end_bounds_and_setup():
    by_name = {m["name"]: m for m in MAN["end_to_end"]}
    assert "setup_s" in by_name and by_name["setup_s"]["bound"] <= 0.1
    for m in MAN["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    assert "itl_p95_ms" in by_name and "itl_p99_ms" not in by_name
    assert len(by_name) - 1 <= 4


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_end_to_end_and_a_layer_metric(cell):
    e2e = [m["name"] for m in MAN["end_to_end"] if cell in cells_of(m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(cell in cells_of(m) for m in MAN["per_layer"])


@pytest.mark.parametrize("metric", MAN["per_layer"], ids=lambda m: m["name"])
def test_moves_names_an_end_to_end_metric_its_cells_report(metric):
    target = next(m for m in MAN["end_to_end"] if m["name"] == metric["moves"])
    assert set(cells_of(metric)) <= set(cells_of(target))
    assert set(cells_of(metric)) <= set(CELLS)


@pytest.mark.parametrize("metric", ALL_METRICS, ids=lambda m: m["name"])
def test_every_metric_has_its_own_file_and_a_reader(metric):
    spec = harness.load_json(harness.HERE / "metrics" / f"{metric['name']}.json")
    assert spec["unit"] == metric["unit"]
    reader = harness.module("readers", spec["reader"])
    assert callable(reader.read)


def test_layers_of_one_module_are_spelled_alike():
    layers = {m["layer"] for m in MAN["per_layer"]}
    assert len({l.lower() for l in layers}) == len(layers)


@pytest.mark.parametrize("config", MAN["configs"], ids=lambda c: c["name"])
def test_configuration_files_state_source_reduced_assumed_and_widths(config):
    path = ROOT / config["file"]
    assert any(config["file"].startswith(p + "/") for p in MAN["paths"])
    c = harness.load_json(path)
    assert c["source"] == config["source"] and c["reduced"] == config["reduced"]
    assert c["assumed"] and c["deployment"]
    # Mistral-7B-v0.1's published widths, none changed
    published = {"hidden_size": 4096, "intermediate_size": 14336,
                 "num_attention_heads": 32, "num_key_value_heads": 8,
                 "vocab_size": 32000, "rope_theta": 10000.0, "rms_norm_eps": 1e-05,
                 "sliding_window": 4096, "max_position_embeddings": 32768,
                 "torch_dtype": "bfloat16", "hidden_act": "silu"}
    for k, v in published.items():
        assert c[k] == v, k
    assert c["num_hidden_layers"] < 32 and config["reduced"] == ["num_hidden_layers"]
    assert any(w["config"] == config["name"] for w in MAN["workloads"])
    harness.module("drivers", c["driver"])


@pytest.mark.parametrize("cell", MAN["workloads"], ids=lambda w: w["name"])
def test_traffic_is_a_data_file_with_a_generator(cell):
    t = harness.traffic_of(cell["traffic"])
    harness.module("generators", t["kind"])
    if cell["traffic"] == "chat_sessions":
        assert isinstance(t["session_starts_per_s"], (int, float))


def test_harness_holds_no_table_of_names():
    """Cells, configurations, traffic mixes and metrics are found by listing
    files: none of their names appears in the harness's code."""
    code = "".join(p.read_text() for p in harness.HERE.rglob("*.py")
                   if "tools" not in p.parts)
    names = CELLS + [c["name"] for c in MAN["configs"]] \
        + [w["traffic"] for w in MAN["workloads"]] \
        + [m["name"] for m in ALL_METRICS if m["name"] != "setup_s"]
    assert [n for n in names if re.search(rf"['\"]{re.escape(n)}['\"]", code)] == []


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_contracts_last_line(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the cell asks for its own device count
    out = subprocess.run(
        [sys.executable, str(ROOT / MAN["command"][1]), "--workload", cell,
         "--seed", str(2**31 + 5), "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is False and line["metrics"] == {}
    assert line["device"]["platform"] == "cpu"
    assert "busy_s" not in line["device"] and "breakdown" not in line
    chips = next(w["chips"] for w in MAN["workloads"] if w["name"] == cell)
    assert line["device"]["count"] == chips
    assert line["attempted"] > 0 and line["failed"] == 0
    # the readers ran: every end-to-end metric of the cell found a value
    ran = next(l for l in out.stdout.splitlines()
               if l.startswith("rehearsal: readers that returned a value:"))
    for m in MAN["end_to_end"]:
        if cell in cells_of(m):
            assert m["name"] in ran.split()
    assert "correct: " in out.stdout and "-> False" not in out.stdout


def test_real_run_refuses_the_cpu():
    """Without --rehearse the CPU is no device to measure on: non-zero exit,
    no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(ROOT / MAN["command"][1]), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert not out.stdout.strip().splitlines()[-1].startswith("{")
