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


@pytest.mark.parametrize("metric", ALL_METRICS, ids=lambda m: m["name"])
def test_a_metric_files_parameters_are_the_ones_its_reader_takes(metric):
    """``read(obs, **params)`` is called on the chip alone: a file whose parameters
    its reader does not take (a reader repointed, a file left behind) fails here."""
    import inspect

    spec = harness.load_json(harness.HERE / "metrics" / f"{metric['name']}.json")
    assert set(spec) <= {"reader", "unit", "params", "note"}
    inspect.signature(harness.module("readers", spec["reader"]).read).bind(
        {}, **spec.get("params", {}))


def test_no_metric_file_is_left_without_an_entry():
    on_disk = {p.stem for p in (harness.HERE / "metrics").glob("*.json")}
    assert on_disk == {m["name"] for m in ALL_METRICS}
    used = {harness.load_json(harness.HERE / "metrics" / f"{n}.json")["reader"] for n in on_disk}
    # a reader no metric names is dead code (a module without ``read`` is a helper)
    readers = {p.stem for p in (harness.HERE / "readers").glob("[!_]*.py")
               if hasattr(harness.module("readers", p.stem), "read")}
    assert used == readers


# PR 41, PR 44, PR 52 and PR 57 merged the families below: one entry
# ``<family>.serve`` (``.train``) whose ``workloads`` are the cells that each had an
# entry ``<family>.<suffix>`` with the same reader and parameters.  What the
# per-cell files held, kept here:
SUFFIX = {"docs": "mistral7b_docs_closed", "dots": "dots3_note_longdocs_closed",
          "nemo": "nemotron3_super_reasoning_closed", "qnext": "qwen3_next_longctx_qa_closed",
          "laguna": "laguna_xs2_mixed_len_closed", "dsv2": "deepseek_v2_doc_qa_sessions_closed",
          "mellum": "mellum2_train_8k_experts_1chip", "evabyte": "evabyte_byte_docs_closed"}
FOUR = ("docs", "dots", "nemo", "qnext")
FIVE = FOUR + ("laguna",)
SIX = FIVE + ("dsv2",)   # PR 52: cell 9's fourteen copies (PR 45 brought them)
SEVEN = SIX + ("evabyte",)   # PR 57: cell 11's ten copies (PR 53 brought them)
PACKS = "^jit_packed(_ctx)?_impl$"
PACK_EXPERTS = {"num": "expert_pairs_held", "num_less": "expert_pairs_held_decode",
                "den": "experts_touched", "den_less": "experts_touched_decode"}
# PR 57: three tripwires a family (``kv_preemptions`` on the reader ``counter``,
# ``kernel_fallbacks`` on its own, ``compiles_in_window`` on ``field``) read 0.0 in every
# cell on every ledger line and are ONE entry a family now, their sum, on the reader
# ``window_faults``: family that went -> the part of the sum that reads what it read
GUARDS = {"kv_preemptions": "preemptions", "kernel_fallbacks": "fallbacks",
          "compiles_in_window": "compiles"}
MERGED = [
    ("window_faults", SEVEN, "window_faults", {"parts": list(GUARDS.values())}),
    ("device_idle_share", SEVEN, "device_idle_share", {}),
    ("peak_hbm_gib", SEVEN, "peak_hbm_gib", {}),
    ("prefill_pack_device_p50_ms", SEVEN, "module_device_percentile", {"module": PACKS, "q": 50}),
    ("pack_build_p50_ms", FOUR[:3], "span_percentile", {"span": "engine.pack_build", "q": 50}),
    # PR 57: read from the chain by ``run_id`` (``xruntime``), whichever programs a tick is
    # made of; until then it paired ``decode_tick`` with ``^jit_decode_impl$`` by timing
    ("host_device_skew_ms", SEVEN, "host_device_skew", {}),
    ("routed_here_share", FOUR[1:] + ("dsv2",), "counter_ratio",
     {"num": "expert_pairs_held", "den": "expert_pairs_routed", "scale": 100.0}),
    ("decode_batch_mean", SEVEN[2:], "counter_ratio",
     {"num": "decode_emitted", "den": "decode_ticks"}),
    # PR 57: cell 8 left this list (``LEFT`` below): every tick of it is a mixed program
    ("decode_device_p50_ms", ("nemo", "qnext", "dsv2", "evabyte"), "module_device_percentile",
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
    ("host_slack_p50_ms", SEVEN, "span_sum_percentile",
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
    ("window_faults", ("mellum",), "window_faults", {"parts": ["fallbacks", "compiles"]}),
    ("train_mfu", ("mellum",), "train_mfu", {}),
]
BORN_WITH = {"serve": [], "train": ["mistral7b_train_1chip", "mistral7b_train_fsdp4"]}
MOVES = {"serve": "serve_tokens_per_s", "train": "train_tokens_per_s_per_chip"}
ROWS = [(into, *row) for into, rows in (("serve", MERGED), ("train", TRAIN_MERGED)) for row in rows]
# a family's cells that keep an entry of their own on OTHER parameters (their
# decode program runs the expert layer, or another reader counts its need)
OTHER_PARAMETERS = {"expert_layout_call_ms": {"nemo"}, "expert_matmul_call_ms": {"nemo"},
                    "expert_matmul_roofline": {"dots", "nemo"}, "expert_rows_mean": {"nemo"},
                    "window_faults": {"train"}}   # a training step has no KV cache to preempt
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
           "aux_programs_per_tick", "dispatch_ahead_p10",
           # PR 57, by the ledger: host work of 0.39 ms a tick beside 10.1 ms of slack, three
           # entries (one now, their sum a tick: ``tick_host_p50_ms.chat``), and the call time
           # of a kernel whose roofline share stands beside it on the same cells
           "tick_sched_p50_ms", "decode_build_p50_ms", "decode_emit_p50_ms",
           "paged_decode_call_ms", "packed_ctx_call_ms", "flash_fwd_call_ms", "flash_bwd_call_ms"]
FOLDED = [f"{family}.{suffix}" for _, family, suffixes, _, _ in ROWS for suffix in suffixes]
# every name a guard ever had: per cell (PR 30-53), ``.chat``, and merged (PR 41-52)
GUARD_NAMES = [f"{guard}.{suffix}" for guard in GUARDS for suffix in SEVEN + ("chat", "serve")] + [
    f"{guard}.{suffix}" for guard in ("kernel_fallbacks", "compiles_in_window")
    for suffix in ("train", "mellum")]
# a cell that a fold brought into a list and that left it again: (family, suffix) -> why
LEFT = {("decode_device_p50_ms", "laguna"):
        "PR 56: every tick of cell 8 is a mixed program, the step has none of its own there; "
        "its time lies in prefill_pack_device_p50_ms.serve, which lists the cell"}


def entry_named(man, name):
    """The ``per_layer`` entry of that name, wherever in the list it stands."""
    found, = [m for m in man["per_layer"] if m["name"] == name]
    return found


def kind_of(man, cell):
    """``serve`` or ``train``: what the ``driver`` of the cell's configuration does."""
    driver = harness.config_of(man, harness.find_cell(man, cell)["config"])["driver"]
    return "train" if "train" in driver else "serve"


@pytest.mark.parametrize("into,family,suffixes,reader,params", ROWS,
                         ids=[r[1] if r[0] == "serve" else f"{r[1]}.{r[0]}" for r in ROWS])
def test_a_merged_family_reads_what_its_per_cell_files_read(into, family, suffixes, reader, params):
    """The list BEGINS with the cells whose per-cell files were folded into it, in the
    order they were folded (the parity with those files is history, held on them);
    every cell behind them JOINED by being appended: it is of the list's kind by its
    configuration's driver, reports the end-to-end metric the entry moves, and stands
    there once."""
    entry = entry_named(MAN, f"{family}.{into}")
    folded = BORN_WITH[into] + [SUFFIX[s] for s in suffixes]
    cells = entry["workloads"]
    assert cells[:len(folded)] == folded and len(set(cells)) == len(cells)
    assert entry["moves"] == MOVES[into]
    moved, = [m for m in MAN["end_to_end"] if m["name"] == MOVES[into]]
    for joined in cells[len(folded):]:
        assert kind_of(MAN, joined) == into and joined in cells_of(moved), joined
    spec = harness.load_json(harness.HERE / "metrics" / f"{family}.{into}.json")
    assert spec["reader"] == reader and spec.get("params", {}) == params
    names = {m["name"] for m in MAN["per_layer"]}
    assert not names & {f"{family}.{s}" for s in suffixes}
    # the cells that move another end-to-end metric keep an entry of their own
    # on the same reader and parameters
    for other in sorted(n for n in names if n.rpartition(".")[0] == family and n != entry["name"]):
        if {other.rpartition(".")[2], into} & OTHER_PARAMETERS.get(family, set()):
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


@pytest.mark.parametrize("name", RETIRED + FOLDED + GUARD_NAMES + [".".join(k) for k in LEFT])
def test_the_retired_metrics_are_gone_from_the_manifest_and_the_harness(name):
    """A retired NAME is in no entry, no metric file and no line of the
    harness (a folded per-cell name, a guard's: in no entry and no file)."""
    assert not [m["name"] for m in ALL_METRICS if m["name"].startswith(name)]
    assert not list((harness.HERE / "metrics").glob(f"{name}*"))
    if name in RETIRED:
        text = "".join(p.read_text() for p in harness.HERE.rglob("*")
                       if p.suffix in (".py", ".json", ".md"))
        assert name not in text


@pytest.mark.parametrize("family,suffix", sorted(LEFT))
def test_a_cell_that_left_a_list_reads_the_family_nowhere(family, suffix):
    assert LEFT[family, suffix]
    assert not [m["name"] for m in harness.metrics_of(MAN, SUFFIX[suffix], True)
                if m["name"].rpartition(".")[0] == family]
    assert suffix not in next(ss for _, f, ss, _, _ in ROWS if f == family)


# What each copy PR 52 and PR 57 folded STATED in its own entry (unit, better, source,
# layer, moves), recorded from the tree it was folded on: the namesake that reports the
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
    # PR 57: cell 11's ten (PR 53 brought them)
    "kv_preemptions.evabyte": ("count", "lower", "program_counter", _KV, _S),
    "kernel_fallbacks.evabyte": ("count", "lower", "program_counter", _DISP, _S),
    "compiles_in_window.evabyte": ("count", "lower", "program_counter", "compile", _S),
    "device_idle_share.evabyte": ("%", "lower", "device_trace", "device", _S),
    "peak_hbm_gib.evabyte": ("GiB", "lower", "program_counter", "device", _S),
    "prefill_pack_device_p50_ms.evabyte": ("ms", "lower", "device_trace", _ENG, _S),
    "decode_device_p50_ms.evabyte": ("ms", "lower", "device_trace", _ENG, _S),
    "decode_batch_mean.evabyte": ("count", "higher", "program_counter", _SCHED, _S),
    "host_slack_p50_ms.evabyte": ("ms", "higher", "program_span", _SCHED, _S),
    "host_device_skew_ms.evabyte": ("ms", "lower", "device_trace", "device", _S),
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
    # a guard's copy reports through the sum of the guards now, which sits in a layer
    # of its own (each guard sat in the layer it watched) and states the rest as it did
    merged = "window_faults" if family in GUARDS else family
    into = next(i for i, f, ss, _, _ in ROWS if f == merged and suffix in ss)
    namesake = entry_named(MAN, f"{merged}.{into}")
    assert SUFFIX[suffix] in namesake["workloads"]
    if family in GUARDS:
        spec = harness.load_json(harness.HERE / "metrics" / f"{namesake['name']}.json")
        assert GUARDS[family] in spec["params"]["parts"]
    differs = [k for k, said in zip(KEYS, COPIES[copy]) if namesake[k] != said]
    assert differs == MISLABELLED.get(copy, []) + ["layer"] * (family in GUARDS)
    for key in differs:   # the label that stands is the one the family's other entries carry
        siblings = {m[key] for m in MAN["per_layer"] if m["name"].rpartition(".")[0] == merged}
        assert siblings == {namesake[key]}


def test_every_folded_copy_has_its_row_and_its_record():
    """Each family's copies of cells 9, 10 and 11 have a record; a guard's copy is
    recorded under the name it had, its row is the sum's."""
    rows = {n for n in FOLDED if n.rpartition(".")[2] in ("dsv2", "mellum", "evabyte")}
    guards = {n for n in GUARD_NAMES if n.rpartition(".")[2] in ("dsv2", "mellum", "evabyte")}
    assert set(COPIES) == {n for n in rows if not n.startswith("window_faults.")} | guards
    assert {f"window_faults.{s}" for s in ("dsv2", "mellum", "evabyte")} <= rows
    assert len(COPIES) == 29


def test_the_readmes_count_is_one_the_list_has_reached():
    """``benchmark/README.md`` ("The cap") states what the last ``benchmark``
    PR left used and free; cells added since only add to it (they may not
    edit the README).  The running count is this test's message."""
    text = (harness.HERE / "README.md").read_text()
    stated, free = map(int, re.search(r"holds (\d+): (\d+) are free", text).groups())
    used = len(MAN["per_layer"])
    assert stated + free == CAP and stated <= used <= CAP, f"{used} of {CAP} used"


# ---------------------------------------------------------------------------
# the next cell: appended to a COPY of the manifest, it turns no statement red
# ---------------------------------------------------------------------------
MADE_UP = "made_up_sessions_closed"
# The made-up cell is a serving cell of a model with a Mamba-2 recurrence, as the cell it
# STANDS IN for is: its configuration and traffic files are read as that cell's (a PR would
# bring its own), and it JOINS, appended last, every list that cell's kind of cell reads:
# each ``.serve`` list that names it (the serving loop's, the two that name every serving
# cell, the expert layer's where its reader and parameters fit) and these of the
# recurrence and of a step's attention
STANDS_IN = "nemotron3_super_reasoning_closed"
ITS_KERNELS = ["ssm_step_call_ms.nemo", "ssm_step_roofline.nemo", "ssm_scan_call_ms.nemo",
               "ssm_scan_roofline.nemo", "gqa_attn_call_ms.nemo"]
# ... and BRINGS readings of its own, on a suffix of its own, at the END of ``per_layer``
ITS_OWN = [f"{kernel}_{what}" for kernel in ("scan", "step", "attn_pack", "attn_step")
           for what in ("call_ms", "roofline")] + ["state_rows_share", "experts_step_share"]
# What is NOT re-run on the copy, by what it is (the ONE list; the names each rule leaves
# out are counted in the test's message): rule -> why
NOT_ON_A_COPY = {
    "takes a fixture": "pytest hands it a recorded trace, a model built once, a temporary "
                       "directory or a monkeypatch: it cannot be called as a function",
    "starts a process": "the child reads the BENCHMARK.json on disk, not the copy, and a "
                        "made-up cell has no files a rehearsal could run",
    "test_benchmark_manifest.py::test_no_metric_file_is_left_without_an_entry":
        "compares the entries with the DIRECTORY of metric files: the made-up cell's own "
        "files are not on disk (a PR brings them with its entries)",
    "test_benchmark_models.py": "holds every architecture's reference to the program by "
                                "running both (minutes); of the manifest it reads which "
                                "configuration is an architecture's first, not an entry",
}


def joins_of(man):
    """The names of the entries a cell of ``STANDS_IN``'s kind appends itself to."""
    return [m["name"] for m in man["per_layer"]
            if m["name"].endswith(".serve") and STANDS_IN in m.get("workloads", ())] + ITS_KERNELS


def with_a_cell_appended(man):
    """A deep copy of ``man`` with what the next ``model_config`` PR appends, and
    nothing inserted: a configuration and a one-chip serving cell on it at the end of
    their lists, the cell's name at the end of its end-to-end metric's list and of the
    ``workloads`` of every entry it joins (``joins_of``), and its own entries at the end
    of ``per_layer`` (as many of ``ITS_OWN`` as the room that is free takes)."""
    import copy

    man = copy.deepcopy(man)
    stands_in = next(c for c in man["configs"]
                     if c["name"] == harness.find_cell(man, STANDS_IN)["config"])
    man["configs"].append({**stands_in, "name": "made_up_l4_serve_1chip",
                           "file": "benchmark/configs/made_up_l4_serve_1chip.json",
                           "why": "an architecture nobody published: a recurrence or attention by block"})
    man["workloads"].append({
        "name": MADE_UP, "config": "made_up_l4_serve_1chip", "traffic": "made_up_closed", "chips": 1,
        "why": "64 closed-loop sessions on contexts of 2k-16k: state and pages of a slot under one pack"})
    next(m for m in man["end_to_end"] if m["name"] == MOVES["serve"])["workloads"].append(MADE_UP)
    for name in joins_of(man):
        entry_named(man, name)["workloads"].append(MADE_UP)
    for name in ITS_OWN[:max(CAP - len(man["per_layer"]), 0)]:
        man["per_layer"].append({**entry_named(man, "window_faults.serve"), "name": f"{name}.made",
                                 "unit": "%" if name.endswith(("roofline", "share")) else "ms",
                                 "source": "device_trace", "workloads": [MADE_UP]})
    return man


def files_of_the_made_up_cell(path):
    """Where the files the cell would bring are read from: its configuration's and its
    traffic's from the cell's it stands in for, each reading's from an accepted file of
    the same unit."""
    path = str(path)
    if path.endswith(".made.json"):
        share = path.endswith(("roofline.made.json", "share.made.json"))
        return harness.HERE / "metrics" / ("device_idle_share.serve.json" if share
                                           else "decode_device_p50_ms.serve.json")
    cell = harness.find_cell(MAN, STANDS_IN)
    return Path(path.replace("made_up_l4_serve_1chip", cell["config"])
                .replace("made_up_closed", cell["traffic"]))


def statements_of(module):
    """(id, call) for every test function of ``module``, a parametrised one once a
    row of its marks as the module built them, and the names left out by a rule of
    ``NOT_ON_A_COPY``."""
    import inspect
    import itertools

    run, left_out = [], {}
    for name, fn in sorted(vars(module).items()):
        if not (name.startswith("test_") and inspect.isfunction(fn)
                and fn.__module__ == module.__name__):
            continue
        marks = [m for m in getattr(fn, "pytestmark", ()) if m.name == "parametrize"]
        columns, given = [], set()
        for mark in marks:
            names, rows = mark.args[0], mark.args[1]
            names = [n.strip() for n in names.split(",")] if isinstance(names, str) else list(names)
            rows = [row.values if isinstance(row, type(pytest.param()))
                    else row if len(names) > 1 else (row,) for row in rows]
            columns.append([dict(zip(names, row)) for row in rows])
            given.update(names)
        if f"{Path(module.__file__).name}::{name}" in NOT_ON_A_COPY:
            left_out[name] = f"{Path(module.__file__).name}::{name}"
        elif set(inspect.signature(fn).parameters) - given:
            left_out[name] = "takes a fixture"
        elif "subprocess." in inspect.getsource(fn):
            left_out[name] = "starts a process"
        else:
            for k, rows in enumerate(itertools.product(*columns)):
                kwargs = {key: value for row in rows for key, value in row.items()}
                case = "-".join(str(v.get("name", k) if isinstance(v, dict) else v)
                                for v in kwargs.values() if isinstance(v, (str, int, dict)))
                run.append((f"{name}[{case or k}]", lambda fn=fn, kwargs=kwargs: fn(**kwargs)))
    return run, left_out


def test_a_cell_appended_to_the_manifest_turns_no_lookup_by_name_red(monkeypatch):
    """Every statement ``tests/benchmark/`` makes of the manifest is made by NAME and
    by KIND, never by place or by today's membership: with a made-up cell appended the
    way the next PR appends it (JOINED to the lists its kind reads, its own entries
    behind everything that is there), every test function of every ``test_*.py`` here
    that reads ``harness.manifest()`` (found on disk, parametrised cases rebuilt from
    the grown copy) still holds, and the list stays under the cap.  A statement that
    pins a place or a membership fails here and is named."""
    import importlib.util
    import traceback

    used = len(MAN["per_layer"])
    assert used + 1 <= CAP, f"{used} of {CAP} used: no room for one reading of a new cell's own"
    grown = with_a_cell_appended(MAN)
    joined = joins_of(MAN)
    assert len(grown["per_layer"]) == min(used + len(ITS_OWN), CAP), f"{used} of {CAP} used"
    assert {"late_collect_lost_ms.serve", "fetch_tail_max_ms.serve", "window_faults.serve",
            "decode_device_p50_ms.serve", "host_slack_p50_ms.serve"} <= set(joined)
    for name in joined:   # joined: the cells that were there as they were, the cell behind them
        was, now = entry_named(MAN, name)["workloads"], entry_named(grown, name)["workloads"]
        assert now == was + [MADE_UP]
    assert [m["name"] for m in grown["per_layer"][:used]] == [m["name"] for m in MAN["per_layer"]]
    monkeypatch.setattr(harness, "manifest", lambda: grown)
    on_disk = harness.load_json
    monkeypatch.setattr(harness, "load_json", lambda path: on_disk(files_of_the_made_up_cell(path)))
    here = Path(__file__).parent
    files = [p for p in sorted(here.glob("test_*.py")) if "harness.manifest()" in p.read_text()]
    assert Path(__file__) in files and len(files) >= 9
    red, ran, left_out = [], {}, {}
    for file in files:
        if file.name in NOT_ON_A_COPY:
            left_out[file.name] = file.name
            continue
        spec = importlib.util.spec_from_file_location(f"grown_{file.stem}", file)
        again = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = again   # ``inspect`` finds a function's source by its module
        try:
            spec.loader.exec_module(again)   # its ``harness.manifest()`` is the grown copy
            run, skipped = statements_of(again)
            left_out.update({f"{file.name}::{name}": why for name, why in skipped.items()})
            ran[file.name] = len(run)
            for case, call in run:
                try:
                    call()
                except Exception as e:   # noqa: BLE001 - every red statement is named, not the first
                    at = traceback.extract_tb(e.__traceback__)[-1]   # (no assertion rewriting here)
                    red.append(f"{file.name}::{case}: {Path(at.filename).name}:{at.lineno}: "
                               f"{at.line} {type(e).__name__} {str(e)[:120]}")
        finally:
            del sys.modules[spec.name]
    assert set(left_out.values()) <= set(NOT_ON_A_COPY)
    total = sum(ran.values())
    assert not red, f"{len(red)} of {total} statements pin a place or a membership:\n" + "\n".join(red)
    # the net is not empty: every file re-run makes statements, this one its rows' too
    assert all(ran.values()) and ran[Path(__file__).name] > len(ROWS) + len(COPIES), \
        f"{ran} statements re-run, {len(left_out)} left out: {sorted(left_out)}"


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
