"""What the cell of latent attention over every cached row brings to the
benchmark: the costs of its two bodies (``costs_mla``), the reader of their
rooflines, the generator of document sessions, and its entries in the manifest."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import costs_mla, harness  # noqa: E402
from benchmark.costs import causal_pairs  # noqa: E402

MAN = harness.manifest()
CELL = "deepseek_v2_doc_qa_sessions_closed"
M = harness.load_json(ROOT / "benchmark/configs/deepseek_v2_l5_e40_serve_1chip.json")
H = 128


# ---------------------------------------------------------------------------
# costs
# ---------------------------------------------------------------------------
def test_the_two_forms_cost_what_the_issue_counts_and_cross_at_171():
    assert costs_mla.absorbed_flops(1, M) == H * 2176          # 2 x (2 x 512 + 64) a pair and head
    assert costs_mla.decompressed_flops(1, 0, M) == H * 640    # 2 x 192 + 2 x 128
    assert costs_mla.decompressed_flops(0, 1, M) == H * 262144  # 2 x 512 x 256 a key and head
    assert costs_mla.crossing(M) == 171


@pytest.mark.parametrize("start,end,form", [
    (0, 2048, "decompressed"),        # a document's first pack
    (24576, 26624, "decompressed"),   # ... a later one, over 24k cached rows
    (24576, 24704, "absorbed"),       # a question of 128 behind a hit
    (30000, 30001, "absorbed"),       # a decode tick's row
], ids=["pack_cold", "pack_over_24k", "question_128", "one_row"])
def test_a_segments_need_by_hand_and_never_above_either_form(start, end, form):
    n, pairs = end - start, causal_pairs(end - start, start)
    flops, by = costs_mla.segment(start, end, M)
    absorbed = 2176.0 * H * pairs
    decompressed = 640.0 * H * pairs + 262144.0 * H * end
    assert flops == pytest.approx(min(absorbed, decompressed))
    assert flops == pytest.approx(absorbed if form == "absorbed" else decompressed)
    assert flops <= absorbed and flops <= decompressed
    # the segment's rows once (576 wide, bf16), its queries in and its values out
    assert by == pytest.approx(1152.0 * end + 2.0 * n * H * (192 + 128))
    assert costs_mla.mla_prefill([(start, end)], M) == (flops, by)


def test_a_segment_of_171_queries_is_where_decompressing_starts_to_pay():
    """Over a LONG context: at 170 queries the absorbed form is the cheaper, at
    171 the decompressed one (near a sequence's start the causal triangle moves
    the crossing up, and the need follows it)."""
    ctx = 1 << 20
    for n, form in ((170, "absorbed"), (171, "decompressed")):
        pairs = causal_pairs(n, ctx)
        a, d = costs_mla.absorbed_flops(pairs, M), costs_mla.decompressed_flops(pairs, ctx + n, M)
        assert (a < d) == (form == "absorbed")
        assert costs_mla.segment(ctx, ctx + n, M)[0] == min(a, d)


def test_a_tick_is_absorbed_and_sits_on_the_ridge():
    """15 slots over 26k rows each: 2176 FLOPs a key and head for 1152 B a key
    is 242 FLOP/B against the v5e's 197 T / 819 G = 240."""
    from benchmark.peaks import peaks_for

    ctx, batch = 15 * 26000, 15
    flops, by = costs_mla.mla_decode(ctx, batch, M)
    assert flops == 2176.0 * H * ctx
    assert by == 1152.0 * ctx + 2.0 * batch * H * 320
    peaks = peaks_for("TPU v5 lite")
    ridge = peaks["bf16_flops_per_s"] / peaks["hbm_bytes_per_s"]
    assert abs(flops / by / ridge - 1.0) < 0.02


def _obs(spans, requests, secs_by_module):
    """The least an observation bag needs for ``mla_roofline``: traced ticks 0
    and 1, the program's spans, the requests' chunks."""
    class Trace:
        def whole_spans(self, name, stat):
            return [0, 1]

    return {"trace": Trace(), "device": {"platform": "tpu", "kind": "TPU v5 lite"},
            "model": M, "ticks": [(0.0, 1.0), (1.0, 2.0)], "spans": spans, "requests": requests,
            "_secs": secs_by_module}


def test_the_roofline_reader_takes_its_need_from_the_traced_dispatches(monkeypatch):
    from benchmark.costs import roofline_min_s
    from benchmark.peaks import peaks_for
    from benchmark.readers import mla_roofline

    monkeypatch.setattr(mla_roofline, "per_execution",
                        lambda obs, module, scope: obs["_secs"].get(module))
    peaks = peaks_for("TPU v5 lite")
    # one request behind a hit of 24 576 tokens: its one chunk of 200 lies in tick 0;
    # another's first two packs of 2048 in ticks 0 and 1
    requests = [{"prompt_len": 24776, "chunks": [(0.1, 0.2, 200)]},
                {"prompt_len": 6144, "chunks": [(0.3, 0.9, 2048), (1.1, 1.9, 2048), (2.5, 3.0, 2048)]}]
    spans = [("decode_tick", 0.5, 0.6, {"batch": 3, "ctx_tokens": 70000}),
             ("decode_tick", 1.5, 1.6, {"batch": 2, "ctx_tokens": 50000}),
             ("decode_tick", 2.5, 2.6, {"batch": 9, "ctx_tokens": 1})]  # outside the capture
    obs = _obs(spans, requests, {"PACK": [0.5, 0.7, 0.6], "TICK": [0.01, 0.03]})
    packs = [[(24576, 24776)], [(0, 2048)], [(2048, 4096)]]
    need = sum(5 * roofline_min_s(*costs_mla.mla_prefill(e, M), peaks) for e in packs) / 3
    assert mla_roofline.read(obs, "PACK", "x", "mla_prefill") == pytest.approx(100 * need / 0.6)
    need = sum(5 * roofline_min_s(*costs_mla.mla_decode(c, b, M), peaks)
               for b, c in ((3, 70000), (2, 50000))) / 2
    assert mla_roofline.read(obs, "TICK", "x", "mla_decode") == pytest.approx(100 * need / 0.02)
    # nothing to read: no such scope in the program, an older program's spans, another model
    assert mla_roofline.read(obs, "NONE", "x", "mla_prefill") is None
    bare = dict(obs, spans=[("decode_tick", 0.5, 0.6, {"batch": 3})])
    assert mla_roofline.read(bare, "TICK", "x", "mla_decode") is None
    assert mla_roofline.read(dict(obs, model={"hidden_size": 8}), "PACK", "x", "mla_prefill") is None
    assert mla_roofline.read(dict(obs, trace=None), "PACK", "x", "mla_prefill") is None


# ---------------------------------------------------------------------------
# the generator
# ---------------------------------------------------------------------------
def plan(seed, rehearse=False):
    t = harness.rehearsed(harness.traffic_of("doc_qa_sessions_closed"), rehearse)
    return t, harness.module("generators", t["kind"]).build(
        t, seed=seed, seconds=45.0, vocab=M["vocab_size"])


def test_the_traffic_file_states_the_issues_table_with_its_fallback_a():
    """ISSUE 45's table, the documents by its fallback (a) (median 16 384,
    clipped 8192-49 152: at 24 576 a window finished 5 requests, the file's
    ``why_documents``), everything else letter for letter."""
    t, p = plan(1)
    assert (t["clients"], t["questions"], t["ramp_s"], t["spread_s"]) == (16, 4, 30.0, 10.0)
    assert (t["pool"], t["strata"], t["fixed_rounds"], t["trace_s"]) == (64, 8, 8, 4.0)
    for key, median, lo, hi in (("document_tokens", 16384, 8192, 49152),
                                ("question_tokens", 128, 32, 512), ("answer_tokens", 128, 32, 512)):
        spec = t[key]
        assert (spec["dist"], spec["median"], spec["min"], spec["max"]) == ("lognormal", median, lo, hi)
        assert spec["sigma"] == (0.4 if key == "document_tokens" else 0.5)
    assert "why_documents" in t
    ms = p.multiset()
    assert len(ms["documents"]) == 64 and len(ms["questions"]) == len(ms["answers"]) == 256
    assert ms["documents"][0] == 8192 and 40000 < ms["documents"][-1] <= 49152
    # a prompt and its answer fit the engine's sequence, whatever meets whatever
    assert 49152 + 512 + 512 <= M["engine"]["max_seq_len"]
    # the 16 documents live at once and their questions and answers fit the pool under
    # its watermark, whichever two rounds meet
    e, rows = M["engine"], [sum(p.lengths[r:r + 16]) for r in range(0, 56, 8)]
    pages = (max(rows) + 16 * (512 + 512)) / e["block_size"]
    assert pages <= e["num_blocks"] * (1 - 1 / 16)


def test_every_document_question_and_answer_is_the_same_for_every_seed():
    (_, a), (_, b) = plan(1), plan(2**31 + 77)
    assert a.multiset() == b.multiset()
    assert a.lengths == b.lengths and a.asked == b.asked and a.answers == b.answers
    assert sorted(a.lengths) != a.lengths  # dealt, not ascending
    # every round of 8 sessions holds one document of each octile
    octile = {v: i // 8 for i, v in enumerate(sorted(a.lengths))}
    for r in range(0, 64, 8):
        assert sorted(octile[v] for v in a.lengths[r:r + 8]) == list(range(8)) or \
            len(set(a.lengths)) < 64  # (clipped lengths repeat: 16384 fills the first octile)
    # the seed draws the ids, and nothing else
    pa = [r.prompt for _, r in a.initial()]
    pb = [r.prompt for _, r in b.initial()]
    assert [len(x) for x in pa] == [len(x) for x in pb] and pa != pb


def test_a_session_asks_its_document_four_times_one_after_the_other():
    """Question k + 1 is never sent before answer k returns (it is what
    ``on_finish`` hands back, due at that moment), carries the same document and
    nothing of the questions before it; the fourth answer opens the caller's
    next session on a new document; first requests are spread over ``spread_s``
    of the ramp and share nothing."""
    t, p = plan(3, rehearse=True)
    first = p.initial()
    assert [due for due, _ in first] == pytest.approx(
        [-t["ramp_s"] + c * t["spread_s"] / t["clients"] for c in range(t["clients"])])
    assert len({tuple(r.prompt[:20]) for _, r in first}) == t["clients"]
    reqs, req, now = [], first[1][1], 0.0
    for turn in range(9):
        assert (req.session, req.turn) == (1, turn)
        reqs.append(req)
        now += 1.5
        (due, req), = p.on_finish(req, now, [0] * req.max_new)
        assert due == now  # sent the moment the answer returns, never before
    # turns 0-3 are one session, 4-7 the next, 8 opens a third: caller 1 took the
    # plan's 2nd session first, then (every other caller still in its first) the 4th and 5th
    for group, session in ((reqs[0:4], 1), (reqs[4:8], 3), (reqs[8:9], 4)):
        n_doc = p.lengths[session % len(p.lengths)]
        assert all(x.prompt[:n_doc] == group[0].prompt[:n_doc] for x in group)
        tails = [tuple(x.prompt[n_doc:]) for x in group]
        assert len(set(tails)) == len(tails)  # each question is its own, none carried on
        for k, x in enumerate(group):
            j = (session * 4 + k) % len(p.asked)
            assert (len(x.prompt) - n_doc, x.max_new) == (p.asked[j], p.answers[j])
    assert reqs[0].prompt[:8] != reqs[4].prompt[:8] != reqs[8].prompt[:8]  # between sessions nothing


# ---------------------------------------------------------------------------
# the cell's entries in the manifest, each found by NAME (a cell added later
# moves every position; ``test_benchmark_manifest.py`` appends one and comes back here)
# ---------------------------------------------------------------------------
OWN = ["mla_prefill_call_ms", "mla_prefill_roofline", "mla_decode_call_ms", "mla_decode_roofline",
       "prefix_hit_share", "decompressed_keys_share"]   # the last is PR 47's, with a file of tests of its own
# what PR 45 brought as ``<family>.dsv2`` copies of the ``.serve`` readers and PR 52
# folded into the ``.serve`` lists (``MERGED`` of the manifest's tests keeps what each file
# held; its three guards are ONE entry, their sum, since PR 57) ...
FOLDED = ["window_faults", "device_idle_share",
          "peak_hbm_gib", "prefill_pack_device_p50_ms", "decode_device_p50_ms", "decode_batch_mean",
          "host_slack_p50_ms", "host_device_skew_ms", "routed_here_share", "expert_matmul_roofline",
          "expert_matmul_call_ms", "expert_rows_mean"]
# ... and the ``.serve`` families born with the cell in their lists (PR 55)
BORN_SHARED = ["late_collect_lost_ms", "fetch_tail_max_ms"]


def test_the_cell_is_one_chip_on_the_new_configuration_and_reports_throughput():
    cell = harness.find_cell(MAN, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("deepseek_v2_l5_e40_serve_1chip", "doc_qa_sessions_closed", 1)
    e2e = {m["name"] for m in harness.metrics_of(MAN, CELL, False)}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    config, = [c for c in MAN["configs"] if c["name"] == cell["config"]]
    assert harness.load_json(ROOT / config["file"]) == M == harness.config_of(MAN, cell["config"])
    assert M["reduced"] == config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert set(M["reduced_why"]) == set(M["reduced"])


def test_the_cells_why_states_the_sizes_its_traffic_file_runs():
    """The manifest's line for the cell is read beside the traffic file: callers,
    the documents' clip and median in k (1024), the questions a session."""
    import re

    cell = harness.find_cell(MAN, CELL)
    t = harness.load_json(ROOT / "benchmark/traffic" / f"{cell['traffic']}.json")
    d, k = t["document_tokens"], 1024
    lo, hi, median = re.search(r"(\d+)k-(\d+)k document \(median (\d+)k", cell["why"]).groups()
    assert (int(lo) * k, int(hi) * k, int(median) * k) == (d["min"], d["max"], d["median"])
    assert cell["why"].startswith(f"{t['clients']} closed-loop callers")
    assert f"asked {t['questions']} questions" in cell["why"]


def test_the_cells_per_layer_entries_fit_under_the_cap():
    """The cell's OWN entries by name, what it reads through the ``.serve`` lists,
    and the driver's cap; wherever in the list they stand."""
    assert len(MAN["per_layer"]) <= 128, f"{len(MAN['per_layer'])} of 128 used"
    mine = {m["name"]: m for m in MAN["per_layer"] if m["name"].endswith(".dsv2")}
    assert sorted(mine) == sorted(f"{name}.dsv2" for name in OWN)
    for m in mine.values():
        assert CELL in m["workloads"] and m["moves"] == "serve_tokens_per_s"
        assert (harness.HERE / "metrics" / f"{m['name']}.json").is_file()
    shared = {f"{name}.serve" for name in FOLDED + BORN_SHARED}
    loaded = [m["name"] for m in harness.metrics_of(MAN, CELL, True)]
    assert sorted(loaded) == sorted(set(mine) | shared)


def test_the_new_readings_name_the_two_scopes_and_the_counters():
    spec = lambda name: harness.load_json(harness.HERE / "metrics" / f"{name}.dsv2.json")
    for body, module in (("mla_prefill", "^jit_packed(_ctx)?_impl$"), ("mla_decode", "^jit_decode_impl$")):
        for kind, reader in (("call_ms", "scope_call_ms"), ("roofline", "mla_roofline")):
            s = spec(f"{body}_{kind}")
            assert s["reader"] == reader and s["params"]["module"] == module
            assert s["params"]["scope"] == f"(^|/){body}(/|$)"
        assert spec(f"{body}_roofline")["params"]["cost"] == body
    assert spec("prefix_hit_share") == harness.load_json(
        harness.HERE / "metrics" / "prefix_hit_share.chat.json")
    # the scopes are the program's: the two bodies carry these names
    import inspect

    from deepspeed_tpu.ops import latent_attention as la

    assert 'named_scope("mla_prefill")' in inspect.getsource(la.dense_attention_pack)
    assert 'named_scope("mla_decode")' in inspect.getsource(la.dense_attention_step)
