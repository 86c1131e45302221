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
MODEL_TYPES = sorted(p.stem for p in (harness.HERE / "models").glob("[!_]*.py"))
CAP = 128   # entries of ``per_layer`` the driver takes


def cells_of(metric):
    return metric.get("workloads", CELLS)


def pairs_of(metrics):
    """(metric, cell) for every cell a metric lists: what a run of that cell
    loads through ``harness.metrics_of``."""
    return [pytest.param(m, c, id=f"{m['name']}-{c}") for m in metrics for c in cells_of(m)]


def test_top_level_keys_and_limits():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    assert isinstance(MAN["run_seconds"], int) and 1 <= MAN["run_seconds"] <= 51
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(MAN["workloads"]) <= 24 and 1 <= len(MAN["end_to_end"]) <= 16
    # the driver's cap, not this PR's count (benchmark/README.md, "The cap")
    assert 1 <= len(MAN["per_layer"]) <= CAP, f"{len(MAN['per_layer'])} of {CAP} used"
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


@pytest.mark.parametrize("metric,cell", pairs_of(MAN["per_layer"]))
def test_moves_names_an_end_to_end_metric_its_cells_report(metric, cell):
    target = next(m for m in MAN["end_to_end"] if m["name"] == metric["moves"])
    assert cell in CELLS and cell in cells_of(target)


@pytest.mark.parametrize("metric,cell", pairs_of(ALL_METRICS))
def test_every_metric_has_its_own_file_and_a_reader(metric, cell):
    assert metric in harness.metrics_of(MAN, cell, metric in MAN["per_layer"])
    spec = harness.load_json(harness.HERE / "metrics" / f"{metric['name']}.json")
    assert spec["unit"] == metric["unit"]
    reader = harness.module("readers", spec["reader"])
    assert callable(reader.read)


def test_no_metric_file_is_left_without_an_entry():
    on_disk = {p.stem for p in (harness.HERE / "metrics").glob("*.json")}
    assert on_disk == {m["name"] for m in ALL_METRICS}
    used = {harness.load_json(harness.HERE / "metrics" / f"{n}.json")["reader"] for n in on_disk}
    # a reader no metric names is dead code (a module without ``read`` is a helper)
    readers = {p.stem for p in (harness.HERE / "readers").glob("[!_]*.py")
               if hasattr(harness.module("readers", p.stem), "read")}
    assert used == readers


# PR 41, PR 44 and PR 52 merged the families below: one entry ``<family>.serve``
# (``.train``) whose ``workloads`` are the cells that each had an entry
# ``<family>.<suffix>`` with the same reader and parameters.  What the per-cell
# files held, kept here:
SUFFIX = {"docs": "mistral7b_docs_closed", "dots": "dots3_note_longdocs_closed",
          "nemo": "nemotron3_super_reasoning_closed", "qnext": "qwen3_next_longctx_qa_closed",
          "laguna": "laguna_xs2_mixed_len_closed", "dsv2": "deepseek_v2_doc_qa_sessions_closed",
          "mellum": "mellum2_train_8k_experts_1chip"}
FOUR = ("docs", "dots", "nemo", "qnext")
FIVE = FOUR + ("laguna",)
SIX = FIVE + ("dsv2",)   # PR 52: cell 9's fourteen copies (PR 45 brought them)
PACKS = "^jit_packed(_ctx)?_impl$"
PACK_EXPERTS = {"num": "expert_pairs_held", "num_less": "expert_pairs_held_decode",
                "den": "experts_touched", "den_less": "experts_touched_decode"}
MERGED = [
    ("kv_preemptions", SIX, "counter", {"key": "preemptions"}),
    ("kernel_fallbacks", SIX, "kernel_fallbacks", {}),
    ("compiles_in_window", SIX, "field", {"key": "compiles_in_window"}),
    ("device_idle_share", SIX, "device_idle_share", {}),
    ("peak_hbm_gib", SIX, "peak_hbm_gib", {}),
    ("prefill_pack_device_p50_ms", SIX, "module_device_percentile", {"module": PACKS, "q": 50}),
    ("pack_build_p50_ms", FOUR[:3], "span_percentile", {"span": "engine.pack_build", "q": 50}),
    ("host_device_skew_ms", SIX, "host_device_skew",
     {"span": "decode_tick", "module": "^jit_decode_impl$"}),
    ("routed_here_share", FOUR[1:] + ("dsv2",), "counter_ratio",
     {"num": "expert_pairs_held", "den": "expert_pairs_routed", "scale": 100.0}),
    ("decode_batch_mean", SIX[2:], "counter_ratio",
     {"num": "decode_emitted", "den": "decode_ticks"}),
    ("decode_device_p50_ms", SIX[2:], "module_device_percentile",
     {"module": "^jit_decode_impl$", "q": 50}),
    # PR 44: the expert layer of the cells whose PACK program runs it (cell 5's
    # files named ``jit_packed_ctx_impl`` alone: a ``cfg.latent`` runner packs
    # through no other program, so the wider pattern finds the same executions)
    ("expert_layout_call_ms", ("dots", "qnext", "laguna"), "scope_call_ms",
     {"module": PACKS, "scope": "(^|/)expert_layout(/|$)", "q": 50}),
    ("expert_matmul_call_ms", ("dots", "qnext", "laguna", "dsv2"), "scope_call_ms",
     {"module": PACKS, "scope": "(^|/)expert_matmul(/|$)", "q": 50}),
    ("expert_matmul_roofline", SIX[3:], "gdn_roofline",
     {"module": PACKS, "scope": "(^|/)expert_matmul(/|$)", "cost": "expert_matmul"}),
    ("expert_rows_mean", SIX[3:], "counter_difference_ratio", PACK_EXPERTS),
    # born a ``.serve`` entry in PR 44 (no per-cell file but cell 9's ever held it)
    ("host_slack_p50_ms", SIX, "span_sum_percentile",
     {"outer": "sched.tick", "inner": "tick_collect", "q": 50}),
]
# PR 52: cell 10's five copies of the ``.train`` readers (PR 49 brought them), the
# cell appended behind the two cells the ``.train`` entries were born with.
# ``train_mfu.mellum``'s file carried a ``note`` beside the same reader: its FLOPs
# a token are ``models/mellum.py``'s ``train_flops_per_token``, what a token
# REQUIRES at the deployment's nominal share of the experts (2 held a token), not
# the pairs a step counted (``expert_train_roofline.mellum`` takes those).
TRAIN_MERGED = [
    ("device_idle_share", ("mellum",), "device_idle_share", {}),
    ("peak_hbm_gib", ("mellum",), "peak_hbm_gib", {}),
    ("kernel_fallbacks", ("mellum",), "kernel_fallbacks", {}),
    ("compiles_in_window", ("mellum",), "field", {"key": "compiles_in_window"}),
    ("train_mfu", ("mellum",), "train_mfu", {}),
]
BORN_WITH = {"serve": [], "train": ["mistral7b_train_1chip", "mistral7b_train_fsdp4"]}
MOVES = {"serve": "serve_tokens_per_s", "train": "train_tokens_per_s_per_chip"}
ROWS = [(into, *row) for into, rows in (("serve", MERGED), ("train", TRAIN_MERGED)) for row in rows]
# a family's cells that keep an entry of their own on OTHER parameters (their
# decode program runs the expert layer, or another reader counts its need)
OTHER_PARAMETERS = {"expert_layout_call_ms": {"nemo"}, "expert_matmul_call_ms": {"nemo"},
                    "expert_matmul_roofline": {"dots", "nemo"}, "expert_rows_mean": {"nemo"}}
IDLE = ("fetch_tail", "upload", "build_rng", "bookkeeping", "enqueue", "build_rows")
RETIRED = ["tick_p50_ms.docs", "host_enqueue_ms.train", "decode_dispatch_p50_ms.chat",   # PR 41
           # PR 44: what one-ahead dispatch left nothing to read, or something else
           "tick_host_gap_p50_ms", "decode_only_tick_p50_ms", "decode_tick_p50_ms.chat",
           "prefill_pack_p50_ms.chat"] + [f"idle_{phase}_share" for phase in IDLE] + [
           # PR 52, by the ledger: each read ONE value on both sides of all seven serving
           # cells on every line since it was added (0.0 small programs a tick; 1.0 step
           # ahead), and every new cell would have copied both.  A stray program shows in
           # the line's ``breakdown`` and is held on the CPU by
           # ``test_benchmark_tick_programs.py``; a tick no longer dispatched one ahead
           # shows in ``host_slack_p50_ms`` and ``device_idle_share`` and is held on the
           # CPU by ``tests/test_dispatch_ahead.py`` (``ahead == [1] * 11``)
           "aux_programs_per_tick", "dispatch_ahead_p10"]
FOLDED = [f"{family}.{suffix}" for _, family, suffixes, _, _ in ROWS for suffix in suffixes]


def entry_named(man, name):
    """The ``per_layer`` entry of that name, wherever in the list it stands."""
    found, = [m for m in man["per_layer"] if m["name"] == name]
    return found


@pytest.mark.parametrize("into,family,suffixes,reader,params", ROWS,
                         ids=[r[1] if r[0] == "serve" else f"{r[1]}.{r[0]}" for r in ROWS])
def test_a_merged_family_reads_what_its_per_cell_files_read(into, family, suffixes, reader, params):
    entry = entry_named(MAN, f"{family}.{into}")
    assert entry["workloads"] == BORN_WITH[into] + [SUFFIX[s] for s in suffixes]
    assert entry["moves"] == MOVES[into]
    spec = harness.load_json(harness.HERE / "metrics" / f"{family}.{into}.json")
    assert spec["reader"] == reader and spec.get("params", {}) == params
    names = {m["name"] for m in MAN["per_layer"]}
    assert not names & {f"{family}.{s}" for s in suffixes}
    # the cells that move another end-to-end metric keep an entry of their own
    # on the same reader and parameters
    for other in sorted(n for n in names if n.rpartition(".")[0] == family and n != entry["name"]):
        if other.rpartition(".")[2] in OTHER_PARAMETERS.get(family, ()):
            continue
        kept = harness.load_json(harness.HERE / "metrics" / f"{other}.json")
        assert (kept["reader"], kept.get("params", {})) == (reader, params)


@pytest.mark.parametrize("into,family,suffix", [(i, f, s) for i, f, ss, _, _ in ROWS for s in ss],
                         ids=FOLDED)
def test_a_folded_cell_loads_its_familys_one_file(into, family, suffix):
    """What a traced run of the cell loads under the family's name is the
    ``.serve`` (``.train``) entry, once, and no per-cell file of that family is left."""
    loaded = [m["name"] for m in harness.metrics_of(MAN, SUFFIX[suffix], True)
              if m["name"].rpartition(".")[0] == family]
    keeps = suffix in OTHER_PARAMETERS.get(family, ())
    assert loaded == [f"{family}.{into}"] + ([f"{family}.{suffix}"] if keeps else [])
    assert not (harness.HERE / "metrics" / f"{family}.{suffix}.json").exists() or keeps


@pytest.mark.parametrize("name", RETIRED + FOLDED)
def test_the_retired_metrics_are_gone_from_the_manifest_and_the_harness(name):
    """A retired NAME is in no entry, no metric file and no line of the
    harness (a folded per-cell name: in no entry and no file)."""
    assert not [m["name"] for m in ALL_METRICS if m["name"].startswith(name)]
    assert not list((harness.HERE / "metrics").glob(f"{name}*"))
    if name in RETIRED:
        text = "".join(p.read_text() for p in harness.HERE.rglob("*")
                       if p.suffix in (".py", ".json", ".md"))
        assert name not in text


# What each copy PR 52 folded STATED in its own entry (unit, better, source, layer,
# moves), recorded from the tree it was folded on: the namesake that reports the
# cell now states the same, so the cell's ledger lines changed a name and nothing else.
_KV, _DISP = "KV cache (inference/ragged.py)", "kernel dispatchers (ops/pallas)"
_ENG = "engine and runner (inference/engine_v2.py, model_runner.py)"
_SCHED, _HELD = "scheduler (inference/scheduler.py)", "held experts (moe/layer.py)"
_S, _T = MOVES["serve"], MOVES["train"]
COPIES = {
    "kv_preemptions.dsv2": ("count", "lower", "program_counter", _KV, _S),
    "kernel_fallbacks.dsv2": ("count", "lower", "program_counter", _DISP, _S),
    "compiles_in_window.dsv2": ("count", "lower", "program_counter", "compile", _S),
    "device_idle_share.dsv2": ("%", "lower", "device_trace", "device", _S),
    "peak_hbm_gib.dsv2": ("GiB", "lower", "program_counter", "device", _S),
    "prefill_pack_device_p50_ms.dsv2": ("ms", "lower", "device_trace", _ENG, _S),
    "decode_device_p50_ms.dsv2": ("ms", "lower", "device_trace", _ENG, _S),
    "decode_batch_mean.dsv2": ("count", "higher", "program_counter", _SCHED, _S),
    "host_slack_p50_ms.dsv2": ("ms", "higher", "program_span", _SCHED, _S),
    "host_device_skew_ms.dsv2": ("ms", "lower", "device_trace", "device", _S),
    "routed_here_share.dsv2": ("%", "higher", "program_counter", _HELD, _S),
    "expert_matmul_call_ms.dsv2": ("ms", "lower", "device_trace", _HELD, _S),
    "expert_matmul_roofline.dsv2": ("%", "higher", "device_trace", _HELD, _S),
    "expert_rows_mean.dsv2": ("count", "higher", "program_counter", _HELD, _S),
    "device_idle_share.mellum": ("%", "lower", "device_trace", "device", _T),
    "peak_hbm_gib.mellum": ("GiB", "lower", "host_clock", "device", _T),
    "kernel_fallbacks.mellum": ("count", "lower", "program_counter", _DISP, _T),
    "compiles_in_window.mellum": ("count", "lower", "host_clock", "compile", _T),
    "train_mfu.mellum": ("%", "higher", "host_clock", "model (models/transformer.py)", _T),
}
# ... but for one label: PR 49 wrote ``host_clock`` as the ``source`` of a count of
# compile requests and of the allocator's peak, on the readers (``field``,
# ``peak_hbm_gib``) whose every other entry says ``program_counter``.  Same reader,
# same parameters, same number: folded, the namesake's label standing (PERF.md section 7).
MISLABELLED = {"peak_hbm_gib.mellum": ["source"], "compiles_in_window.mellum": ["source"]}
KEYS = ("unit", "better", "source", "layer", "moves")


@pytest.mark.parametrize("copy", sorted(COPIES))
def test_a_folded_copy_kept_its_contract(copy):
    family, _, suffix = copy.rpartition(".")
    into = next(i for i, f, ss, _, _ in ROWS if f == family and suffix in ss)
    namesake = entry_named(MAN, f"{family}.{into}")
    assert SUFFIX[suffix] in namesake["workloads"]
    differs = [k for k, said in zip(KEYS, COPIES[copy]) if namesake[k] != said]
    assert differs == MISLABELLED.get(copy, [])
    for key in differs:   # the label that stands is the one the family's other entries carry
        siblings = {m[key] for m in MAN["per_layer"] if m["name"].rpartition(".")[0] == family}
        assert siblings == {namesake[key]}


def test_every_folded_copy_has_its_row_and_its_record():
    assert sorted(COPIES) == sorted(n for n in FOLDED if n.rpartition(".")[2] in ("dsv2", "mellum"))
    assert len(COPIES) == 19


def test_the_readmes_count_is_one_the_list_has_reached():
    """``benchmark/README.md`` ("The cap") states what the last ``benchmark``
    PR left used and free; cells added since only add to it (they may not
    edit the README).  The running count is this test's message."""
    text = (harness.HERE / "README.md").read_text()
    stated, free = map(int, re.search(r"holds (\d+): (\d+) are free", text).groups())
    used = len(MAN["per_layer"])
    assert stated + free == CAP and stated <= used <= CAP, f"{used} of {CAP} used"


# ---------------------------------------------------------------------------
# the next cell: appended to a COPY of the manifest, it turns no lookup red
# ---------------------------------------------------------------------------
MADE_UP = "made_up_sessions_closed"
# what a new serving architecture's cell brings today: a copy of each ``.serve``
# family on its own suffix (it may not edit the lists) and readings of its own kernels
ITS_COPIES = ["kv_preemptions", "kernel_fallbacks", "compiles_in_window", "device_idle_share",
              "peak_hbm_gib", "prefill_pack_device_p50_ms", "decode_device_p50_ms",
              "decode_batch_mean", "host_slack_p50_ms", "host_device_skew_ms",
              "routed_here_share", "expert_matmul_call_ms", "expert_matmul_roofline"]
ITS_OWN = [f"{kernel}_{what}" for kernel in ("scan", "step", "attn_pack", "attn_step")
           for what in ("call_ms", "roofline")] + ["state_rows_share"]
# the statements about ONE cell's entries, each in the file of that cell's tests
LOOKUPS = {
    "test_benchmark_mla": ["test_the_cell_is_one_chip_on_the_new_configuration_and_reports_throughput",
                           "test_the_cells_why_states_the_sizes_its_traffic_file_runs",
                           "test_the_cells_per_layer_entries_fit_under_the_cap"],
    "test_benchmark_decompressed_keys": ["test_the_entry_is_found_by_its_name_and_sits_in_the_cells_layer"],
    "test_benchmark_mellum": ["test_the_cell_trains_the_configuration_on_one_chip_and_reports_the_training_rate"],
    "test_benchmark_manifest": ["test_top_level_keys_and_limits", "test_no_two_entries_share_a_name",
                                "test_end_to_end_bounds_and_setup", "test_layers_of_one_module_are_spelled_alike",
                                "test_the_readmes_count_is_one_the_list_has_reached",
                                "test_every_folded_copy_has_its_row_and_its_record"],
}


def with_a_cell_appended(man):
    """A deep copy of ``man`` with what the next ``model_config`` PR appends: a
    configuration, a one-chip serving cell on it, the cell in its end-to-end
    metric's list, and 22 entries of ``per_layer`` on a suffix of its own."""
    import copy

    man = copy.deepcopy(man)
    man["configs"].append({
        "name": "made_up_l4_serve_1chip", "source": "https://example.org/made-up/config.json",
        "file": "benchmark/configs/made_up_l4_serve_1chip.json", "reduced": ["num_hidden_layers"],
        "why": "an architecture nobody published: two mixers a block, each with a cache of its own"})
    man["workloads"].append({
        "name": MADE_UP, "config": "made_up_l4_serve_1chip", "traffic": "made_up_closed", "chips": 1,
        "why": "16 closed-loop callers on contexts of 8k-32k: both caches of a block under one pack"})
    next(m for m in man["end_to_end"] if m["name"] == MOVES["serve"])["workloads"].append(MADE_UP)
    for family in ITS_COPIES:
        man["per_layer"].append({**entry_named(man, f"{family}.serve"),
                                 "name": f"{family}.made", "workloads": [MADE_UP]})
    for name in ITS_OWN:
        man["per_layer"].append({**entry_named(man, "kernel_fallbacks.serve"), "name": f"{name}.made",
                                 "unit": "%" if name.endswith(("roofline", "share")) else "ms",
                                 "source": "device_trace", "workloads": [MADE_UP]})
    return man


def test_a_cell_appended_to_the_manifest_turns_no_lookup_by_name_red(monkeypatch):
    """Every statement ``tests/benchmark/`` makes about one cell's entries finds
    them by NAME: with a made-up cell, its configuration and 22 entries behind
    everything that is there, each still holds and the list stays under the cap."""
    import importlib.util

    grown = with_a_cell_appended(MAN)
    assert len(ITS_COPIES) + len(ITS_OWN) == 22
    assert len(grown["per_layer"]) == len(MAN["per_layer"]) + 22 <= CAP
    assert grown["workloads"][-1]["name"] == MADE_UP and grown["per_layer"][-1]["workloads"] == [MADE_UP]
    monkeypatch.setattr(harness, "manifest", lambda: grown)
    # the copies' files, as the cell would bring them: what the ``.serve`` namesake's file holds
    on_disk = harness.load_json
    monkeypatch.setattr(harness, "load_json", lambda path: on_disk(
        Path(str(path).replace(".made.json", ".serve.json"))))
    for file, tests in LOOKUPS.items():
        spec = importlib.util.spec_from_file_location(f"grown_{file}", Path(__file__).with_name(f"{file}.py"))
        again = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(again)   # its ``MAN = harness.manifest()`` is the grown copy
        assert again.MAN is grown
        for name in tests:
            getattr(again, name)()
        if file == "test_benchmark_manifest":   # ... and its parametrised statements, row by row
            for row in again.ROWS:
                again.test_a_merged_family_reads_what_its_per_cell_files_read(*row)
                for suffix in row[2]:
                    again.test_a_folded_cell_loads_its_familys_one_file(row[0], row[1], suffix)
            for copy in again.COPIES:
                again.test_a_folded_copy_kept_its_contract(copy)
            for cell in again.CELLS:
                again.test_every_cell_reports_setup_another_end_to_end_and_a_layer_metric(cell)
            assert again.CELLS[-1] == MADE_UP
            for metric in grown["per_layer"]:
                for cell in again.cells_of(metric):
                    again.test_moves_names_an_end_to_end_metric_its_cells_report(metric, cell)


def test_layers_of_one_module_are_spelled_alike():
    layers = {m["layer"] for m in MAN["per_layer"]}
    assert len({l.lower() for l in layers}) == len(layers)


# What ``reduced`` may name, by the key's form and for any model: a count of
# what is held here (layers, and for a stated deployment's share heads,
# experts, vocabulary rows).  Every other key is a width or says nothing of
# size, and experts PER TOKEN are a width however the key begins.
COUNT = re.compile(r"^(num_|n_)(?!.*(per_tok|top_?k))|^vocab_size$")


def check_configuration(entry, c, published):
    """One configuration (its ``BENCHMARK.json`` entry, its file, the
    published ``config.json`` keys its file names) against the rules; raises
    AssertionError naming the key at fault."""
    assert c["source"] == entry["source"] and c["reduced"] == entry["reduced"]
    assert c["assumed"] and c["deployment"]
    reduced = c["reduced"]
    assert len(reduced) <= 16
    for k, v in published.items():
        if k not in reduced:
            assert k in c and c[k] == v, f"{k} differs from the published file and is not in reduced"
    for k in reduced:
        assert k in published, f"{k} is reduced from nothing: the published file lacks it"
        assert c.get("reduced_why", {}).get(k), f"{k} has no reduced_why"
        assert COUNT.search(k), f"{k} is a width, or no count: it may not be reduced"
        assert 0 < c[k] <= published[k], \
            f"{k} is cut to {c[k]} from {published[k]}: a share is not larger than the whole"
    harness.module("drivers", c["driver"])
    harness.module("models", c["model_type"])


@pytest.mark.parametrize("config", MAN["configs"], ids=lambda c: c["name"])
def test_configuration_files_state_source_reduced_assumed_and_widths(config):
    assert any(config["file"].startswith(p + "/") for p in MAN["paths"])
    c = harness.load_json(ROOT / config["file"])
    check_configuration(
        config, c, harness.load_json(harness.HERE / "published" / f"{c['published']}.json"))
    assert any(w["config"] == config["name"] for w in MAN["workloads"])


@pytest.mark.parametrize("config", MAN["configs"], ids=lambda c: c["name"])
def test_a_configurations_runtime_group_sizes_the_premapped_buffer_and_says_why(config):
    """``runtime`` holds what the TPU runtime is started with, not the model:
    today only the size of its pinned host staging buffer (PR 41's refusal:
    pinning the default 4 GiB was 8.7-11.7 s of an 18-22 s set-up)."""
    runtime = harness.load_json(ROOT / config["file"]).get("runtime")
    if runtime is None:
        return
    assert set(runtime) == {"tpu_premapped_buffer_bytes", "why"} and runtime["why"]
    size = runtime["tpu_premapped_buffer_bytes"]
    assert isinstance(size, int) and size % 2**20 == 0 and 2**26 <= size <= 2**32


@pytest.mark.parametrize("rehearse,given,already,expect", [
    (False, 2**28, None, str(2**28)),      # the configuration's size reaches libtpu
    (False, 2**28, "1024", "1024"),        # an operator's value wins, as with the cache
    (False, None, None, None),             # no key: the runtime's default
    (True, 2**28, None, None),             # a rehearsal starts no TPU runtime
])
def test_prepare_environment_hands_the_runtime_its_premapped_size(
        monkeypatch, rehearse, given, already, expect):
    for name in (harness.CACHE_ENV, "JAX_PLATFORMS", "XLA_FLAGS",
                 "JAX_ENABLE_COMPILATION_CACHE"):
        monkeypatch.setenv(name, os.environ.get(name, ""))
    for name in harness.PREMAP_ENV:
        if already is None:
            monkeypatch.delenv(name, raising=False)
        else:
            monkeypatch.setenv(name, already)
    harness.prepare_environment(1, rehearse, given)
    assert [os.environ.get(name) for name in harness.PREMAP_ENV] == [expect, expect]


def _expert_configuration():
    """An in-memory configuration of another architecture: a chip's share of
    the experts and half the depth, every width as published."""
    published = {"model_type": MODEL_TYPES[0], "hidden_size": 2048, "intermediate_size": 1024,
                 "num_hidden_layers": 16, "num_attention_heads": 16, "num_experts": 64,
                 "num_experts_per_tok": 8, "vocab_size": 50304, "sliding_window": None}
    entry = {"source": "https://example.org/config.json",
             "reduced": ["num_hidden_layers", "num_experts"]}
    c = dict(published, **entry, num_hidden_layers=8, num_experts=16,
             reduced_why={"num_hidden_layers": "8 of 16", "num_experts": "one chip of four"},
             assumed={"weights": "seeded"}, deployment="a quarter of the experts",
             driver="train")
    return entry, c, published


def test_a_share_of_experts_and_of_depth_may_be_reduced():
    check_configuration(*_expert_configuration())


@pytest.mark.parametrize("key,value,says", [
    ("hidden_size", 1024, "width"),            # a width named in reduced
    ("num_experts_per_tok", 2, "width"),       # experts per token is a width
    ("sliding_window", 1024, "width"),
    ("num_experts", 128, "share"),             # more than the published count
    ("num_attention_heads", 8, "reduced_why"),  # a count cut without a reason
])
def test_what_reduced_may_not_hold(key, value, says):
    entry, c, published = _expert_configuration()
    entry["reduced"] = c["reduced"] = c["reduced"] + [key]
    c[key] = value
    if says != "reduced_why":
        c["reduced_why"][key] = "because"
    with pytest.raises(AssertionError, match=says):
        check_configuration(entry, c, published)


@pytest.mark.parametrize("key,value", [("intermediate_size", 512), ("num_experts", 8),
                                       ("vocab_size", 32000)])
def test_a_key_that_differs_from_its_published_file_must_be_in_reduced(key, value):
    entry, c, published = _expert_configuration()
    c["reduced"] = entry["reduced"] = ["num_hidden_layers"]
    c["num_experts"] = published["num_experts"]
    c[key] = value
    with pytest.raises(AssertionError, match=f"{key} differs"):
        check_configuration(entry, c, published)


@pytest.mark.parametrize("cell", MAN["workloads"], ids=lambda w: w["name"])
def test_traffic_is_a_data_file_with_a_generator(cell):
    t = harness.traffic_of(cell["traffic"])
    harness.module("generators", t["kind"])
    if t["kind"] == "open_sessions":   # offered at a rate, told by the kind and not by a file's name
        assert isinstance(t["session_starts_per_s"], (int, float))


def test_harness_holds_no_table_of_names():
    """Cells, configurations, traffic mixes and metrics are found by listing
    files: none of their names appears in the harness's code.  Nor does an
    architecture's: outside ``models/`` no ``model_type`` and no file name of
    ``models/`` or ``published/`` is quoted or imported."""
    files = [p for p in harness.HERE.rglob("*.py") if "tools" not in p.parts]
    code = "".join(p.read_text() for p in files)
    names = CELLS + [c["name"] for c in MAN["configs"]] \
        + [w["traffic"] for w in MAN["workloads"]] \
        + [m["name"] for m in ALL_METRICS if m["name"] != "setup_s"]
    assert [n for n in names if re.search(rf"['\"]{re.escape(n)}['\"]", code)] == []
    outside = "".join(p.read_text() for p in files if "models" not in p.parts)
    archs = MODEL_TYPES + [p.stem for p in (harness.HERE / "published").glob("*.json")]
    assert MODEL_TYPES and [
        n for n in archs
        if re.search(rf"['\"]{re.escape(n)}['\"]|import .*\b{re.escape(n)}\b|models\.{re.escape(n)}\b",
                     outside)] == []


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
