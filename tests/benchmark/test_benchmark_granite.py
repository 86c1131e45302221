"""The cell of two-norm blocks whose mixer is chosen by block
(``granite4_h_small_rag_agents_closed``): its configuration's three cuts and the
arithmetic re-reckoned from the file, the accepted entries it joined found by NAME
with the cells that stood before it as they stood, its own six readings, the
accepted yardsticks of the recurrence and of a SwiGLU expert (``costs_ssm.py`` /
``costs_gdn.py`` through their readers) on the keys the driver maps, the traffic's
multiset and fixed rounds, the driver's comparison on made-up rows, and the
rehearsal through ``run.py``."""
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

from benchmark import costs, costs_gdn, costs_ssm, harness  # noqa: E402
from benchmark.drivers import serve_block_mixers as driver  # noqa: E402
from benchmark.readers import gdn_roofline, state_roofline  # noqa: E402

MAN = harness.manifest()
CELL = "granite4_h_small_rag_agents_closed"
ENTRY = next(w for w in MAN["workloads"] if w["name"] == CELL)
CONFIG = next(c for c in MAN["configs"] if c["name"] == ENTRY["config"])
M = harness.load_json(ROOT / CONFIG["file"])
PUBLISHED = harness.load_json(harness.HERE / "published" / f"{M['published']}.json")
TRAFFIC = harness.traffic_of(ENTRY["traffic"])
CUT = {"num_hidden_layers": (10, 40), "num_local_experts": (36, 72), "vocab_size": (50176, 100352)}
# What a traced run of the cell reports.  It JOINED the accepted entries whose reader and
# parameters read its programs, appended behind the cells that were there: entry -> the
# cells that stood in its list before this one, in their order.  A later cell joins behind.
_SERVING = ["mistral7b_docs_closed", "dots3_note_longdocs_closed",
            "nemotron3_super_reasoning_closed", "qwen3_next_longctx_qa_closed",
            "laguna_xs2_mixed_len_closed", "deepseek_v2_doc_qa_sessions_closed",
            "evabyte_byte_docs_closed", "falcon_h1_assistant_turns_closed"]
_STATES = ["nemotron3_super_reasoning_closed", "falcon_h1_assistant_turns_closed"]
_SWIGLU = ["qwen3_next_longctx_qa_closed", "laguna_xs2_mixed_len_closed",
           "deepseek_v2_doc_qa_sessions_closed"]
# ... a step program of their own (every tick of cell 8 is a mixed program)
_OWN_STEP = [c for c in _SERVING[2:] if c != "laguna_xs2_mixed_len_closed"]
JOINED = {
    "window_faults.serve": _SERVING, "device_idle_share.serve": _SERVING,
    "peak_hbm_gib.serve": _SERVING, "prefill_pack_device_p50_ms.serve": _SERVING,
    "decode_device_p50_ms.serve": _OWN_STEP, "decode_batch_mean.serve": _SERVING[2:],
    "host_slack_p50_ms.serve": _SERVING, "host_device_skew_ms.serve": _SERVING,
    "late_collect_lost_ms.serve": _SERVING, "fetch_tail_max_ms.serve": _SERVING,
    "routed_here_share.serve": [_SERVING[1], _SERVING[2], _SERVING[3], _SERVING[5]],
    "expert_matmul_call_ms.serve": [_SERVING[1]] + _SWIGLU,
    "expert_matmul_roofline.serve": _SWIGLU, "expert_rows_mean.serve": _SWIGLU,
    "expert_layout_call_ms.serve": [_SERVING[1]] + _SWIGLU[:2],
    "ssm_step_call_ms.nemo": _STATES, "ssm_step_roofline.nemo": _STATES,
    "ssm_scan_call_ms.nemo": _STATES, "ssm_scan_roofline.nemo": _STATES,
    "gqa_attn_call_ms.nemo": _STATES,
}
LISTED = tuple(JOINED)
# ... and BRINGS six of its own: five named bodies' shares of ITS two programs
# (``readers/scope_share_of_program.py``; the bodies are ones this family's runner has:
# ``tests/test_block_mixers_serving.py`` finds them compiled) and the two gauges' ratio
_EXPERTS = {"router", "expert_layout", "expert_matmul", "shared_expert"}
OWN_SHARES = {
    "mixers_pack_share.granite": ("^jit_packed_ctx_impl$", {"ssm_scan", "gqa_attn"}),
    "mixers_step_share.granite": ("^jit_decode_impl$", {"ssm_step", "gqa_attn"}),
    "experts_pack_share.granite": ("^jit_packed_ctx_impl$", _EXPERTS),
    "experts_step_share.granite": ("^jit_decode_impl$", _EXPERTS),
    "head_step_share.granite": ("^jit_decode_impl$", {"lm_head"}),
}
OWN = tuple(OWN_SHARES) + ("kv_bytes_per_state_byte.granite",)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
GIB = 2.0 ** 30
metric_file = lambda name: harness.load_json(harness.HERE / "metrics" / f"{name}.json")
entry_named = lambda name: next(m for m in MAN["per_layer"] if m["name"] == name)


def test_the_manifest_holds_the_cell_and_the_lists_that_name_it():
    """The cell and its configuration by NAME, wherever in their lists they stand;
    the entries a traced run of it reports are the accepted ones that list it and
    its own six."""
    assert harness.find_cell(MAN, CELL) is ENTRY
    assert len(MAN["per_layer"]) <= 128, f"{len(MAN['per_layer'])} of 128 used"
    assert (ENTRY["chips"], ENTRY["config"], ENTRY["traffic"]) == \
        (1, "granite4_h_small_l10_e36_serve_1chip", "rag_agents_closed")
    assert CONFIG["file"].endswith(f"{ENTRY['config']}.json")
    assert len(ENTRY["why"]) <= 200 and len(CONFIG["why"]) <= 200
    rate = next(m for m in MAN["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert rate["workloads"].count(CELL) == 1 and 0.01 <= rate["bound"] <= 0.1
    assert [m["name"] for m in harness.metrics_of(MAN, CELL, False)] == ["serve_tokens_per_s", "setup_s"]
    assert {m["name"] for m in harness.metrics_of(MAN, CELL, True)} >= set(LISTED) | set(OWN)
    assert {m["name"] for m in MAN["per_layer"] if m.get("workloads") == [CELL]} >= set(OWN)
    # its own readings stand BEHIND every entry that was there before the cell
    names = [m["name"] for m in MAN["per_layer"]]
    assert min(names.index(n) for n in OWN) > max(names.index(n) for n in LISTED)
    # one cell in four may ask for four chips: this cell adds none
    assert sum(w["chips"] == 4 for w in MAN["workloads"]) == 1


@pytest.mark.parametrize("name", LISTED)
def test_an_entry_that_lists_the_cell_reads_this_familys_programs(name):
    """The cell is IN the list exactly once and the cells before it stand as they
    stood (whatever joins behind it), the entry moves the cell's end-to-end metric,
    and its file's program and scope are ones this family's runner has: two programs
    a tick, ``jit_packed_ctx_impl`` and ``jit_decode_impl``, the blocks' bodies under
    ``ssm_scan`` / ``ssm_step`` / ``gqa_attn`` / ``expert_layout`` / ``expert_matmul``."""
    entry = entry_named(name)
    cells = entry["workloads"]
    assert cells.count(CELL) == 1 and cells[:cells.index(CELL)] == JOINED[name]
    assert entry["moves"] == "serve_tokens_per_s"
    spec = metric_file(name)
    assert callable(harness.module("readers", spec["reader"]).read)
    params = spec.get("params", {})
    if "scope" in params:
        body = params["scope"].split(")")[1].split("(")[0]
        assert body in ("ssm_scan", "ssm_step", "gqa_attn", "expert_matmul", "expert_layout")
        assert re.fullmatch(params["module"], "jit_packed_ctx_impl" if body in (
            "ssm_scan", "expert_matmul", "expert_layout") else "jit_decode_impl")
    elif "module" in params:   # a whole program's device time: the pack's, or the step's
        assert params["module"] in ("^jit_packed(_ctx)?_impl$", "^jit_decode_impl$")
    if name.endswith("roofline.nemo"):
        assert spec["reader"] == "state_roofline" and params["cost"] in ("ssm_step", "ssm_scan")
    if name == "expert_matmul_roofline.serve":  # a SwiGLU expert's three products, the packs'
        assert (spec["reader"], params["cost"]) == ("gdn_roofline", "expert_matmul")


@pytest.mark.parametrize("name", sorted(OWN_SHARES))
def test_an_entry_of_its_own_is_a_named_bodys_share_of_its_program(name):
    entry = entry_named(name)
    assert entry["workloads"] == [CELL] and entry["moves"] == "serve_tokens_per_s"
    assert (entry["unit"], entry["source"]) == ("%", "device_trace")
    spec = metric_file(name)
    assert spec["reader"] == "scope_share_of_program" and spec["unit"] == "%"
    module, bodies = OWN_SHARES[name]
    assert spec["params"]["module"] == module
    named = re.fullmatch(r"\(\^\|/\)\(?([\w|]+)\)?\(/\|\$\)", spec["params"]["scope"])
    assert set(named.group(1).split("|")) == bodies   # whole path components, these and no other


def test_the_gauges_ratio_is_read_from_what_the_driver_sums_a_tick():
    entry, spec = entry_named(OWN[-1]), metric_file(OWN[-1])
    assert (entry["source"], entry["unit"], entry["workloads"]) == ("program_counter", "ratio", [CELL])
    assert spec["reader"] == "counter_ratio"
    from deepspeed_tpu.inference.latent_runner import CACHE_GAUGES
    assert (spec["params"]["den"], spec["params"]["num"]) == CACHE_GAUGES
    read = harness.module("readers", spec["reader"]).read
    # a tick of 30 live requests of ~9.5k tokens: 75 pages of 512 KiB beside 36.4 MiB of state each
    counters = {"kv_page_bytes_in_use": 30 * 75 * 2**19, "state_bytes_live": 30 * 38204928}
    assert read({"counters": counters}, **spec["params"]) == pytest.approx(1.03, abs=0.01)
    assert read({"counters": {}}, **spec["params"]) is None


def test_the_configuration_cuts_depth_experts_and_vocabulary_and_states_its_readings():
    assert M["reduced"] == CONFIG["reduced"] == list(CUT)
    assert M["source"] == CONFIG["source"] == \
        "https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json"
    assert {k: M[k] for k in PUBLISHED if k not in CUT} == \
        {k: v for k, v in PUBLISHED.items() if k not in CUT}
    assert {k: (M[k], PUBLISHED[k]) for k in CUT} == CUT and set(M["reduced_why"]) == set(CUT)
    # every published width and every constant
    assert (M["hidden_size"], M["intermediate_size"], M["shared_intermediate_size"]) == (4096, 768, 1536)
    assert (M["mamba_n_heads"], M["mamba_d_head"], M["mamba_d_state"], M["mamba_n_groups"],
            M["mamba_d_conv"]) == (128, 64, 128, 1, 4)
    assert (M["num_attention_heads"], M["num_key_value_heads"], M["num_experts_per_tok"]) == (32, 8, 10)
    assert (M["embedding_multiplier"], M["residual_multiplier"], M["attention_multiplier"],
            M["logits_scaling"], M["tie_word_embeddings"]) == (12, 0.22, 0.0078125, 16, True)
    assert set(M["assumed"]) >= {"weights", "torch_dtype", "head_dim", "expert_width", "ssm_init",
                                 "ssm_state_dtype", "logits_dtype", "constants_where",
                                 "attention_positions", "gated_norm", "d_skip", "routing",
                                 "left_out", "engine"}
    d = M["deployment"]
    assert (d["chips"], d["pipeline_stage"], d["pipeline_stages"]) == (2, 0, 4)
    assert (d["n_routed_experts_total"], d["expert_offset"]) == (72, 0)
    assert d["published"] == {k: v[1] for k, v in CUT.items()} and d["held"] == {k: v[0] for k, v in CUT.items()}
    assert d["published"]["num_hidden_layers"] == d["pipeline_stages"] * d["held"]["num_hidden_layers"]
    assert d["published"]["num_local_experts"] == d["chips"] * d["held"]["num_local_experts"]
    assert d["published"]["vocab_size"] == d["chips"] * d["held"]["vocab_size"]
    # the guide's floors: ONE whole period at the published ratio, >= 8 experts, >= 1/8 vocabulary
    held = M["layer_types"][: M["num_hidden_layers"]]
    assert len(M["layer_types"]) == 40 and M["layer_types"] == PUBLISHED["layer_types"]
    assert (held.count("mamba"), held.count("attention")) == (9, 1)
    assert M["layer_types"].count("mamba") == 4 * 9 and M["layer_types"] == held * 4
    assert M["num_local_experts"] >= 8 and 8 * M["vocab_size"] >= PUBLISHED["vocab_size"]
    assert M["driver"] == "serve_block_mixers" and "train" not in M["driver"]
    assert harness.module("models", M["model_type"]).KINDS == {"mamba": "mamba", "attention": "gqa"}
    # the rehearsal's constants are its own: all distinct and none 1
    toy = [M["rehearsal"][k] for k in ("embedding_multiplier", "attention_multiplier",
                                       "residual_multiplier", "logits_scaling")]
    assert len(set(toy)) == 4 and 1 not in toy and M["rehearsal"]["multipliers_why"]


def test_the_deployments_arithmetic_re_reckoned_from_the_file():
    d, f, fs, v = M["hidden_size"], M["intermediate_size"], M["shared_intermediate_size"], M["vocab_size"]
    h, p, g, n, k = (M["mamba_n_heads"], M["mamba_d_head"], M["mamba_n_groups"],
                     M["mamba_d_state"], M["mamba_d_conv"])
    hq, hkv, hd = M["num_attention_heads"], M["num_key_value_heads"], d // M["num_attention_heads"]
    assert (h * p, hd) == (M["mamba_expand"] * d, 128)
    conv_w = h * p + 2 * g * n
    mamba = d * (2 * h * p + 2 * g * n + h) + h * p * d + conv_w * (k + 1) + 3 * h + h * p
    attn = d * hd * (hq + 2 * hkv) + hq * hd * d
    ffn = d * M["deployment"]["n_routed_experts_total"] + 3 * d * fs + M["num_local_experts"] * 3 * d * f
    assert [round(x / 1e6, 1) for x in (mamba, attn, ffn)] == [102.3, 41.9, 358.9]
    assert round((mamba + ffn + 2 * d) / 1e6, 1) == 461.2 and round((attn + ffn + 2 * d) / 1e6, 1) == 400.9
    kinds = M["layer_types"][: M["num_hidden_layers"]]
    held = sum((mamba if t == "mamba" else attn) + ffn + 2 * d for t in kinds) + v * d + d
    assert round(held / 1e6) == 4757 and round(2 * held / GIB, 2) == 8.86
    # all 72 experts at one period leave no room for the slots' states, let alone a page: why 36
    whole = held + M["num_hidden_layers"] * M["num_local_experts"] * 3 * d * f
    assert round(2 * whole / 1e9, 1) == 16.3 and 2 * whole + 32 * 36.4 * 2**20 > 15.75 * GIB
    # what the program itself holds: the same count, ONE array for embedding and head
    from deepspeed_tpu.models.latent import param_count
    arch = harness.module("models", M["model_type"])
    assert param_count(arch.transformer_config(M)) == held
    e = M["engine"]
    slot = kinds.count("mamba") * (h * p * n * 4 + (k - 1) * conv_w * 2)
    page = kinds.count("attention") * 2 * e["block_size"] * hkv * hd * 2
    assert round(slot / 2**20, 1) == 36.4 and page == 512 * 2**10
    total = 2 * held + e["max_seqs"] * slot + e["num_blocks"] * page
    assert 0.25 * 16e9 < 12 * GIB < total < 15.75 * GIB  # the floor on the peak; the chip
    assert e["max_seq_len"] == TRAFFIC["prompt_tokens"]["max"] + TRAFFIC["answer_tokens"]["max"]
    pages_a_seq = e["max_seq_len"] // e["block_size"]
    assert e["num_blocks"] <= e["max_seqs"] * pages_a_seq + 128  # never more than every slot at full length
    assert e["block_size"] == 128 and e["prefill_chunk"] == 512 and e["prefix_caching"] is False
    # the head's share of a decode tick's weight stream, here and in a deployment of 40
    head, blocks = 2 * v * d, 2 * (held - v * d)
    assert round(100 * head / (head + blocks)) == 4 and round(100 * head / (head + 4 * blocks)) == 1


def test_the_traffic_is_the_issues_and_its_multiset_is_fixed():
    t = TRAFFIC
    assert t["kind"] == "reasoning_closed" and t["clients"] == M["engine"]["max_seqs"] == 32
    assert (t["ramp_s"], t["spread_s"], t["strata"], t["pool"], t["trace_s"]) == (20.0, 8.0, 8, 256, 4.0)
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 8192, "sigma": 0.7, "min": 2048,
                                  "max": 32768, "integer": True}
    assert t["answer_tokens"] == {"dist": "lognormal", "median": 512, "sigma": 0.6, "min": 128,
                                  "max": 2048, "integer": True}
    build = harness.module("generators", t["kind"]).build
    a = build(t, seed=1, seconds=45.0, vocab=M["vocab_size"])
    b = build(t, seed=2**31 + 5, seconds=45.0, vocab=M["vocab_size"])
    assert a.multiset() == b.multiset() and a.multiset()["clients"] == t["clients"]
    prompts, answers = a.multiset()["prompts"], a.multiset()["answers"]
    assert len(prompts) == len(answers) == 256
    assert prompts[0] == 2048 and prompts[-1] == 32768 and answers[0] >= 128 and answers[-1] <= 2048
    assert 9500 < np.mean(prompts) < 11000 and 550 < np.mean(answers) < 650
    assert 1 <= t["fixed_rounds"] <= t["pool"] // t["strata"] and t["why"] and t["why_rounds"]
    fixed = t["fixed_rounds"] * t["strata"]
    assert a.lengths[:fixed] == b.lengths[:fixed] and a.answers[:fixed] == b.answers[:fixed]
    ids = a._request(0, 0).prompt
    assert 0 <= min(ids) and max(ids) < M["vocab_size"] == 50176  # drawn from the slice


def test_the_driver_hands_the_sizes_under_the_names_the_accepted_costs_read():
    mapped = driver._as_the_readers_look_it_up(M)
    assert costs_ssm._sizes(mapped) == (128, 64, 1, 128)       # ONE group where cell 6 has 8
    assert {k: mapped[k] for k in M} == M  # nothing of the configuration is lost
    kinds = mapped["hybrid_override_pattern"][: mapped["num_hidden_layers"]]
    assert (kinds.count("M"), kinds.count("*"), kinds.count("E")) == (9, 1, 0)
    # the same state a block a slot as the single-mixer cell's 128 x 64 x 128
    nemo = harness.load_json(ROOT / "benchmark/configs/nemotron3_super_l11_e128_serve_1chip.json")
    assert costs_ssm.ssm_step(1, mapped)[0] == costs_ssm.ssm_step(1, nemo)[0]
    fl, by = costs_ssm.ssm_step(30, mapped)
    assert costs.roofline_min_s(fl, by, PEAKS) == pytest.approx(by / 819e9)  # the states' bytes bound it
    assert by == pytest.approx(30 * 2 * 4 * 128 * 64 * 128, rel=0.01)
    # a SwiGLU expert's three products at the narrowest width the benchmark has
    fl, by = costs_gdn.expert_matmul(5120, 36, mapped)
    assert fl == 6.0 * 4096 * 768 * 5120
    assert by == 36 * 3 * 4096 * 768 * 2 + 5120 * 2 * 4096 * 2  # the touched experts once, a row in and out a pair


def test_the_accepted_roofline_readers_count_this_cells_blocks(monkeypatch):
    class Trace:
        def whole_spans(self, name, key):
            return [1, 2]

    ticks = [(0.0, 1.0, 28, 99), (1.0, 2.0, 30, 30 * 9000), (2.0, 3.0, 30, 30 * 9000),
             (3.0, 4.0, 28, 99)]
    # one prompt's chunk of 512 tokens from position 1024, inside the traced ticks
    requests = [{"prompt_len": 4096, "chunks": [(0.1, 0.2, 512), (0.3, 0.4, 512), (1.1, 1.9, 512)]}]
    mapped = driver._as_the_readers_look_it_up(M)
    counters = {"prefill_dispatches": 100, "experts_touched": 100 * 10 * 36 + 7, "experts_touched_decode": 7,
                "expert_pairs_held": 100 * 10 * 2560 + 11, "expert_pairs_held_decode": 11}
    obs = {"trace": Trace(), "ticks": ticks, "requests": requests, "model": mapped,
           "engine": M["engine"], "counters": counters,
           "device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    least = lambda fl_by: costs.roofline_min_s(*fl_by, PEAKS)
    needs = {"ssm_step": 9 * least(costs_ssm.ssm_step(30, mapped)),          # nine blocks run it
             "ssm_scan": 9 * least(costs_ssm.ssm_scan([128] * 4, mapped))}
    for cost, need in needs.items():
        monkeypatch.setattr(state_roofline, "per_execution",
                            lambda o, module, scope, need=need: [4 * need] * 2)
        assert state_roofline.read(obs, "m", "s", cost) == pytest.approx(25.0), cost
    with pytest.raises(KeyError):  # the configuration's own names are not the reader's
        state_roofline.read(dict(obs, model=M), "m", "s", "ssm_step")
    # every one of the ten blocks holds experts: a pack's 2560 pairs on 36 touched experts a block
    need = 10 * least(costs_gdn.expert_matmul(2560, 36, mapped))
    monkeypatch.setattr(gdn_roofline, "per_execution", lambda o, module, scope: [2 * need] * 3)
    assert gdn_roofline.read(obs, "m", "s", "expert_matmul") == pytest.approx(50.0)


def test_the_drivers_controls_are_the_references_departures_and_the_precisions():
    arch = harness.module("models", M["model_type"])
    assert set(driver.CONTROLS) == set(arch.DEPARTURES) | {
        "fp8_weights", "ssm_state_bf16", "bf16_logits", "served_tokens_swapped"}
    assert {"ssm_state_bf16", "bf16_logits", "softmax_scale_rsqrt",
            "residual_multiplier_one"} <= set(driver.CONTROLS)   # the four ISSUE 62 names
    with pytest.raises(KeyError):
        with arch.departure("no_such_reading"):
            pass


def _sample(rng, rows=6, vocab=64, experts=8, k=3, tokens=20):
    """Made-up rows a sound run would hand ``_check_sample``: (got, picks, kept,
    reference logits, reference scores, the recurrence again, tokens)."""
    ref = rng.standard_normal((rows, vocab)).astype(np.float32) * 0.06
    got = ref + rng.standard_normal((rows, vocab)).astype(np.float32) * 1e-4
    scores = rng.standard_normal((tokens, experts)).astype(np.float32)
    picks = np.argsort(-scores, axis=1)[:, :k]
    seen = {"router_biased": scores, "router_cutoff": np.sort(scores, axis=1)[:, -k]}
    kept = [rng.standard_normal((4, 2, 3)).astype(np.float32)]
    return got, [picks], kept, ref, [seen], [kept[0] * (1 + 1e-5)], got.argmax(-1)


@pytest.mark.parametrize("fault,caught_by", [
    (None, None), ("logits", "logits max"), ("bf16", "no bfloat16's"), ("pick", "under the cut-off"),
    ("repeat", "repeated expert"), ("state", "recurrence"), ("token", "under the replay's")])
def test_the_comparison_refuses_each_kind_of_fault_by_its_own_limit(fault, caught_by):
    import jax.numpy as jnp

    got, picks, kept, ref, seen, again, tokens = _sample(np.random.default_rng(3))
    if fault == "logits":
        got = got + 0.06 * 2 * driver.LOGIT_TOL_MAX * (np.arange(got.shape[1]) == 5)
        tokens = got.argmax(-1)
    elif fault == "bf16":
        got = np.asarray(jnp.asarray(got).astype(jnp.bfloat16).astype(jnp.float32))
        tokens = got.argmax(-1)
    elif fault == "pick":  # an expert two logits under the cut-off
        scores = seen[0]["router_biased"]
        worst = np.argmin(scores[0])
        scores[0, worst] = seen[0]["router_cutoff"][0] - 2.0
        picks[0][0, -1] = worst
    elif fault == "repeat":
        picks[0][3, 1] = picks[0][3, 0]
    elif fault == "state":
        again = [kept[0] * (1 + 10 * driver.STATE_TOL)]
    elif fault == "token":
        tokens = np.where(np.arange(len(tokens)) == 2, got.argmin(-1), tokens)
    notes: list = []
    ok = driver._check_sample(np, got, picks, kept, ref, seen, again, 15, list(tokens), notes, "made up")
    assert ok == (fault is None) and notes[0].endswith(f"-> {ok}")
    assert caught_by is None or caught_by in notes[0]
    # the reference takes the program's picks a block at a time, padded to its length
    forced = driver._forced(np, picks, 32)
    assert forced[0].shape == (1, 32, 3) and np.array_equal(forced[0][0, :20], picks[0])
    joined = [{"ssm_x": 1}, {"experts_picked": picks[0]}, {"experts_picked": picks[0]}]
    assert [len(part) for part in driver._split(joined)] == [2, 1]


def test_the_rehearsal_serves_both_caches_and_holds_every_comparison():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, str(ROOT / MAN["command"][1]), "--workload", CELL, "--seed",
         str(2**31 + 11), "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["metrics"] == {} and line["failed"] == 0 and line["attempted"] > 0
    checks = [l for l in out.stdout.splitlines() if l.startswith("correct: ")]
    assert len(checks) >= 1 + 3 + 1 + 2 and all("-> False" not in l for l in checks)
    assert "5 packs through the scheduler (6 if no pack were shared)" in checks[0]
    # nine kept states a request, ten blocks' picks
    assert sum("9 blocks, off the one-token float32 recurrence" in l for l in checks) >= 5
    assert sum("expert picks in 10 blocks" in l for l in checks) >= 5
    ran = next(l for l in out.stdout.splitlines()
               if l.startswith("rehearsal: readers that returned a value:")).split()
    for name in ("late_collect_lost_ms.serve", "serve_tokens_per_s", "setup_s",
                 "routed_here_share.serve", "expert_rows_mean.serve", OWN[-1]):
        assert name in ran, name
    # both kinds of cache, sampled a tick of the window (the program's two gauges)
    assert any("GiB of state and" in l and "GiB of K / V pages" in l for l in out.stdout.splitlines())
