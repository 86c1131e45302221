"""The cell of two parallel mixers a block (``falcon_h1_assistant_turns_closed``):
its configuration's cut and arithmetic re-reckoned from the file, the accepted
entries it joined found by NAME with the cells that stood before it as they stood,
its own three shares of a program, the recurrence's accepted yardstick (``costs_ssm.py`` through
``readers/state_roofline.py``) on the keys the driver maps, the traffic's multiset
and fixed rounds, each of the reference's departures shown to decide a logit, and
the rehearsal."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import costs, costs_ssm, harness  # noqa: E402
from benchmark.drivers import serve_parallel  # noqa: E402
from benchmark.readers import state_roofline  # noqa: E402

MAN = harness.manifest()
CELL = "falcon_h1_assistant_turns_closed"
ENTRY = next(w for w in MAN["workloads"] if w["name"] == CELL)
CONFIG = next(c for c in MAN["configs"] if c["name"] == ENTRY["config"])
M = harness.load_json(ROOT / CONFIG["file"])
PUBLISHED = harness.load_json(harness.HERE / "published" / f"{M['published']}.json")
TRAFFIC = harness.traffic_of(ENTRY["traffic"])
# What a traced run of the cell reports.  It JOINED the accepted entries whose reader and
# parameters read its programs, appended behind the cells that were there (PR 58: the two
# every serving cell is in and the single-mixer cell's readings of the recurrence and of a
# step's attention; PR 61: the serving loop's eight ``.serve`` families): entry -> the
# cells that stood in its list before this one, in their order.  A later cell joins behind.
_SERVING = ["mistral7b_docs_closed", "dots3_note_longdocs_closed",
            "nemotron3_super_reasoning_closed", "qwen3_next_longctx_qa_closed",
            "laguna_xs2_mixed_len_closed", "deepseek_v2_doc_qa_sessions_closed",
            "evabyte_byte_docs_closed"]
_NEMO = ["nemotron3_super_reasoning_closed"]
# ... a step program of their own (every tick of cell 8 is a mixed program)
_OWN_STEP = [c for c in _SERVING[2:] if c != "laguna_xs2_mixed_len_closed"]
JOINED = {
    "late_collect_lost_ms.serve": _SERVING, "fetch_tail_max_ms.serve": _SERVING,
    "ssm_step_call_ms.nemo": _NEMO, "ssm_step_roofline.nemo": _NEMO,
    "ssm_scan_call_ms.nemo": _NEMO, "ssm_scan_roofline.nemo": _NEMO,
    "gqa_attn_call_ms.nemo": _NEMO,
    "window_faults.serve": _SERVING, "device_idle_share.serve": _SERVING,
    "peak_hbm_gib.serve": _SERVING, "prefill_pack_device_p50_ms.serve": _SERVING,
    "decode_device_p50_ms.serve": _OWN_STEP, "decode_batch_mean.serve": _SERVING[2:],
    "host_slack_p50_ms.serve": _SERVING, "host_device_skew_ms.serve": _SERVING,
}
LISTED = tuple(JOINED)
# ... and BRINGS three of its own (PR 61), behind everything that was there: a named body's
# share of ITS program's device time (``readers/scope_share_of_program.py``): entry -> the
# program and the bodies, all ones this family's runner has (``tests/
# test_parallel_mixers_serving.py`` finds them compiled); since PR 59 a pack carries the
# tick's step, whose bodies stand in the pack's program under ``ssm_step`` / ``gqa_attn_step``
OWN = {
    "mixers_step_share.falcon": ("^jit_decode_impl$", {"ssm_step", "gqa_attn"}),
    "mixers_pack_share.falcon": ("^jit_packed_ctx_impl$",
                                 {"ssm_scan", "gqa_attn", "ssm_step", "gqa_attn_step"}),
    "head_step_share.falcon": ("^jit_decode_impl$", {"lm_head"}),
}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
GIB = 2.0 ** 30
metric_file = lambda name: harness.load_json(harness.HERE / "metrics" / f"{name}.json")


def test_the_manifest_holds_the_cell_and_the_lists_that_name_it():
    """The cell and its configuration by NAME, wherever in their lists they stand;
    the entries a traced run of it reports are the accepted ones that list it."""
    assert harness.find_cell(MAN, CELL) is ENTRY
    assert len(MAN["per_layer"]) <= 128, f"{len(MAN['per_layer'])} of 128 used"
    assert (ENTRY["chips"], ENTRY["config"], ENTRY["traffic"]) == \
        (1, "falcon_h1_34b_l6_serve_1chip", "assistant_turns_closed")
    assert CONFIG["file"].endswith(f"{ENTRY['config']}.json") and len(ENTRY["why"]) <= 200
    rate = next(m for m in MAN["end_to_end"] if m["name"] == "serve_tokens_per_s")
    # the cell joined the rate that was there and brought no bound of its own: the value is
    # the manifest's to state (a `benchmark` PR refits it: 0.02, 0.03, 0.06 since PR 41)
    assert CELL in rate["workloads"] and 0.01 <= rate["bound"] <= 0.1
    assert [m["name"] for m in harness.metrics_of(MAN, CELL, False)] == ["serve_tokens_per_s", "setup_s"]
    # every one of these reads the cell; an entry a later PR points at it adds to them
    assert {m["name"] for m in harness.metrics_of(MAN, CELL, True)} >= set(LISTED) | set(OWN)
    assert {m["name"] for m in MAN["per_layer"] if m.get("workloads") == [CELL]} >= set(OWN)
    # one chip in four may ask for four: this cell adds none
    assert sum(w["chips"] == 4 for w in MAN["workloads"]) == 1


@pytest.mark.parametrize("name", LISTED)
def test_an_entry_that_lists_the_cell_reads_this_familys_programs(name):
    """The cell is IN the list exactly once and the cells before it stand as they
    stood (whatever joined behind it), the entry moves the cell's end-to-end metric,
    and its file's program and scope are ones this family's runner has: two programs a
    tick, ``jit_packed_ctx_impl`` and ``jit_decode_impl``, the mixers' bodies under
    ``ssm_scan`` / ``ssm_step`` / ``gqa_attn``."""
    entry, = [m for m in MAN["per_layer"] if m["name"] == name]
    cells = entry["workloads"]
    assert cells.count(CELL) == 1 and cells[:cells.index(CELL)] == JOINED[name]
    assert entry["moves"] == "serve_tokens_per_s"
    spec = metric_file(name)
    assert callable(harness.module("readers", spec["reader"]).read)
    params = spec.get("params", {})
    if "scope" in params:
        body = params["scope"].split(")")[1].split("(")[0]
        assert params["module"] == ("^jit_packed_ctx_impl$" if body == "ssm_scan"
                                    else "^jit_decode_impl$")
        assert body in ("ssm_scan", "ssm_step", "gqa_attn")
    elif "module" in params:   # a whole program's device time: the pack's, mixed or not, or the step's
        assert params["module"] in ("^jit_packed(_ctx)?_impl$", "^jit_decode_impl$")
    if name.endswith("roofline"):
        assert spec["reader"] == "state_roofline" and params["cost"] in ("ssm_step", "ssm_scan")


@pytest.mark.parametrize("name", sorted(OWN))
def test_an_entry_of_its_own_is_a_named_bodys_share_of_its_program(name):
    entry, = [m for m in MAN["per_layer"] if m["name"] == name]
    assert CELL in entry["workloads"] and entry["moves"] == "serve_tokens_per_s"
    assert (entry["unit"], entry["source"]) == ("%", "device_trace")
    spec = metric_file(name)
    assert spec["reader"] == "scope_share_of_program" and spec["unit"] == "%"
    module, bodies = OWN[name]
    assert spec["params"]["module"] == module
    named = re.fullmatch(r"\(\^\|/\)\(?([\w|]+)\)?\(/\|\$\)", spec["params"]["scope"])
    assert set(named.group(1).split("|")) == bodies   # whole path components, these and no other


def test_the_configuration_cuts_the_depth_alone_and_states_its_readings():
    assert M["reduced"] == CONFIG["reduced"] == ["num_hidden_layers"]
    assert M["source"] == CONFIG["source"] == \
        "https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json"
    assert {k: M[k] for k in PUBLISHED if k != "num_hidden_layers"} == \
        {k: v for k, v in PUBLISHED.items() if k != "num_hidden_layers"}
    assert (M["num_hidden_layers"], PUBLISHED["num_hidden_layers"]) == (6, 72)
    assert set(M["assumed"]) >= {"weights", "torch_dtype", "ssm_init", "ssm_state_dtype",
                                 "logits_dtype", "multipliers_where", "gated_norm", "d_skip",
                                 "widths_read", "rotary", "left_out", "engine"}
    d = M["deployment"]
    assert (d["pipeline_stage"], d["pipeline_stages"], d["chips"]) == (0, 12, 1)
    assert d["published"]["num_hidden_layers"] == d["pipeline_stages"] * d["held"]["num_hidden_layers"]
    assert M["num_hidden_layers"] >= 4  # the guide's floor: one kind of block, a period is one
    assert M["driver"] == "serve_parallel" and M["model_type"] == "falcon_h1"


def test_the_deployments_arithmetic_re_reckoned_from_the_file():
    d, f, v = M["hidden_size"], M["intermediate_size"], M["vocab_size"]
    h, p, g, n, k = (M["mamba_n_heads"], M["mamba_d_head"], M["mamba_n_groups"],
                     M["mamba_d_state"], M["mamba_d_conv"])
    hq, hkv, hd = costs.heads(M)
    assert (h * p, hq // hkv, n // p) == (M["mamba_d_ssm"], 5, 2) == (4096, 5, 2)
    attn = d * hd * (hq + 2 * hkv) + hq * hd * d
    conv_w = h * p + 2 * g * n
    mamba = d * (2 * h * p + 2 * g * n + h) + h * p * d + conv_w * (k + 1) + 3 * h + h * p
    block = attn + mamba + 3 * d * f + 2 * d
    assert round(attn / 1e6, 2) == 31.46 and round(3 * d * f / 1e6, 1) == 330.3
    assert round(block / 1e6, 1) == 430.1
    weights = 2 * (M["num_hidden_layers"] * block + 2 * v * d + d)
    assert round(weights / GIB, 2) == 9.79
    # what the program itself holds: the same count
    from deepspeed_tpu.models.latent import param_count
    arch = harness.module("models", M["model_type"])
    assert 2 * param_count(arch.transformer_config(M)) == weights
    e = M["engine"]
    slot = M["num_hidden_layers"] * (h * p * n * 4 + (k - 1) * conv_w * 2)
    page = M["num_hidden_layers"] * 2 * e["block_size"] * hkv * hd * 2
    assert round(slot / 2**20, 1) == 24.2 and page == 1.5 * 2**20
    held = weights + e["max_seqs"] * slot + e["num_blocks"] * page
    assert 12 * GIB < held < 15.75 * GIB  # the acceptance's floor on the peak; the chip
    # the head's share of a decode tick's weight stream, here and in a deployment of 72
    head, blocks = 2 * v * d, 2 * block
    assert round(100 * head / (head + 6 * blocks)) == 34 and round(100 * head / (head + 72 * blocks)) == 4
    assert e["block_size"] == M["mamba_chunk_size"] == 128  # a page IS the scan's chunk
    assert e["prefix_caching"] is False and e["prefill_chunk"] == 512
    assert e["max_seq_len"] == TRAFFIC["prompt_tokens"]["max"] + TRAFFIC["answer_tokens"]["max"]


def test_the_traffic_is_the_issues_and_its_multiset_is_fixed():
    t = TRAFFIC
    assert t["kind"] == "reasoning_closed" and t["clients"] == M["engine"]["max_seqs"]
    assert (t["ramp_s"], t["spread_s"], t["strata"], t["pool"], t["trace_s"]) == (20.0, 8.0, 8, 256, 4.0)
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 2048, "sigma": 0.8, "min": 256,
                                  "max": 8192, "integer": True}
    assert t["answer_tokens"] == {"dist": "lognormal", "median": 384, "sigma": 0.6, "min": 64,
                                  "max": 1536, "integer": True}
    build = harness.module("generators", t["kind"]).build
    a = build(t, seed=1, seconds=45.0, vocab=M["vocab_size"])
    b = build(t, seed=2**31 + 5, seconds=45.0, vocab=M["vocab_size"])
    assert a.multiset() == b.multiset() and a.multiset()["clients"] == t["clients"]
    prompts, answers = a.multiset()["prompts"], a.multiset()["answers"]
    assert len(prompts) == len(answers) == 256
    assert 256 == prompts[0] and prompts[-1] == 8192 and 64 <= answers[0] and answers[-1] <= 1536
    assert 2400 < np.mean(prompts) < 2800 and 400 < np.mean(answers) < 500
    fixed = t["fixed_rounds"] * t["strata"]
    assert a.lengths[:fixed] == b.lengths[:fixed] and a.answers[:fixed] == b.answers[:fixed]
    ids = a._request(0, 0).prompt
    assert 0 <= min(ids) and max(ids) < M["vocab_size"] == 261120


def test_the_driver_hands_the_recurrences_sizes_under_the_names_costs_ssm_reads():
    mapped = serve_parallel._as_single_mixer_blocks(M)
    assert costs_ssm._sizes(mapped) == (32, 128, 2, 256)
    assert {k: mapped[k] for k in M} == M  # nothing of the configuration is lost
    kinds = mapped["hybrid_override_pattern"][: mapped["num_hidden_layers"]]
    assert kinds.count("M") == M["num_hidden_layers"] == 6  # EVERY block runs the recurrence
    # the same state a block a slot as the single-mixer cell's 128 x 64 x 128
    nemo = harness.load_json(ROOT / "benchmark/configs/nemotron3_super_l11_e128_serve_1chip.json")
    assert costs_ssm.ssm_step(1, mapped)[0] == costs_ssm.ssm_step(1, nemo)[0]
    fl, by = costs_ssm.ssm_step(45, mapped)
    assert costs.roofline_min_s(fl, by, PEAKS) == pytest.approx(by / 819e9)  # the states' bytes bound it
    assert by == pytest.approx(45 * 2 * 4 * 32 * 128 * 256, rel=0.01)


def test_the_accepted_roofline_reader_counts_every_block_of_this_cell(monkeypatch):
    class Trace:
        def whole_spans(self, name, key):
            return [1, 2]

    ticks = [(0.0, 1.0, 40, 99), (1.0, 2.0, 45, 45 * 2800), (2.0, 3.0, 45, 45 * 2800),
             (3.0, 4.0, 40, 99)]
    # one prompt's chunk of 512 tokens from position 1024, inside the traced ticks
    requests = [{"prompt_len": 1536, "chunks": [(0.1, 0.2, 512), (0.3, 0.4, 512), (1.1, 1.9, 512)]}]
    mapped = serve_parallel._as_single_mixer_blocks(M)
    obs = {"trace": Trace(), "ticks": ticks, "requests": requests, "model": mapped,
           "engine": M["engine"], "device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    blocks = M["num_hidden_layers"]
    least = lambda fl_by: costs.roofline_min_s(*fl_by, PEAKS)
    needs = {"ssm_step": least(costs_ssm.ssm_step(45, mapped)),
             "ssm_scan": least(costs_ssm.ssm_scan([128] * 4, mapped))}
    for cost, need in needs.items():
        monkeypatch.setattr(state_roofline, "per_execution",
                            lambda o, module, scope, need=need: [4 * blocks * need] * 2)
        assert state_roofline.read(obs, "m", "s", cost) == pytest.approx(25.0), cost
    with pytest.raises(KeyError):  # the configuration's own names are not the reader's
        state_roofline.read(dict(obs, model=M), "m", "s", "ssm_step")


@pytest.fixture(scope="module")
def small():
    import jax

    from deepspeed_tpu.models.transformer import init_params

    m = harness.rehearsed(M, True)
    arch = harness.module("models", m["model_type"])
    cfg = arch.transformer_config(m, max_seq_len=256)
    params = init_params(jax.random.PRNGKey(11), cfg)
    ids = np.random.default_rng(11).integers(0, m["vocab_size"], (1, 60)).astype(np.int32)
    return m, arch, params, ids, np.asarray(arch.logits(params, ids, m))[0]


@pytest.mark.parametrize("name", ["key_multiplier_left_out", "ssm_c_multiplier_left_out",
                                  "attention_dropped"])
def test_each_departure_of_the_reference_decides_a_logit(small, name):
    m, arch, params, ids, want = small
    with arch.departure(name):
        got = np.asarray(arch.logits(params, ids, m))[0]
    assert np.abs(got - want).max() > 2e-3
    again = np.asarray(arch.logits(params, ids, m))[0]  # ... and is gone with its context
    assert np.array_equal(again, want)


def test_the_reference_in_blocks_is_the_reference(small):
    """The forward a block and a column block of the head at a time (what the chip
    has room for) gives the whole forward's rows, inside a departure too."""
    m, arch, params, ids, want = small
    rows = [0, 17, 59]
    got = arch.logits_in_blocks(params, ids, m, rows, cols=50)
    assert got.shape == (3, m["vocab_size"]) and np.abs(got - want[rows]).max() <= 1e-5
    with arch.departure("attention_dropped"):
        inside = arch.logits_in_blocks(params, ids, m, rows, cols=50)
        whole = np.asarray(arch.logits(params, ids, m))[0]
    assert np.abs(inside - whole[rows]).max() <= 1e-5 and np.abs(inside - got).max() > 2e-3
    assert np.abs(arch.logits_in_blocks(params, ids, m, rows) - got).max() <= 1e-5


def test_the_drivers_controls_are_the_references_departures_and_the_precisions():
    arch = harness.module("models", M["model_type"])
    assert set(serve_parallel.CONTROLS) == set(arch.DEPARTURES) | {
        "fp8_weights", "ssm_state_bf16", "bf16_logits", "served_tokens_swapped"}
    with pytest.raises(KeyError):
        with arch.departure("no_such_reading"):
            pass
    rows = np.random.default_rng(0).standard_normal((4, 64)).astype(np.float32) * 0.008
    assert serve_parallel._f32_share(np, rows) > 0.99
    import jax.numpy as jnp
    low = np.asarray(jnp.asarray(rows).astype(jnp.bfloat16).astype(jnp.float32))
    assert serve_parallel._f32_share(np, low) == 0.0
    assert serve_parallel._logits_off(np, rows + 0.0008, rows)[1] == pytest.approx(
        0.0008 / rows.std(), rel=1e-3)


def test_the_rehearsal_serves_both_caches_and_holds_every_comparison():
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
    assert len(checks) >= 1 + 3 + 1 + 2 and all("-> False" not in l for l in checks)
    assert "5 packs through the scheduler (6 if no pack were shared)" in checks[0]
    assert sum("2 blocks, off the one-token float32 recurrence" in l for l in checks) >= 5
    ran = next(l for l in out.stdout.splitlines()
               if l.startswith("rehearsal: readers that returned a value:")).split()
    for name in ("late_collect_lost_ms.serve", "serve_tokens_per_s", "setup_s"):
        assert name in ran, name
    # both kinds of cache, sampled a tick of the window (the program's two gauges)
    assert any("GiB of state and" in l and "GiB of K / V pages" in l for l in out.stdout.splitlines())
