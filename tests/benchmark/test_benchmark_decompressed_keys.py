"""``decompressed_keys_share.dsv2``: the median, over the window's
``prefill_pack`` spans, of the argument ``LatentRunner._every_dispatched``
hands each (``mla_keys_decompressed_pct``: the share of the pack's causal keys
in runs the program's rule sends through the decompressed form), on the reader
``span_arg_percentile``; nothing, and no error, from a program older than the
argument, whose spans carry ``mla_keys`` alone.  A file of its own: the
manifest's and the cell's test files are the accepted benchmark's."""
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import costs_mla, harness  # noqa: E402

MAN = harness.manifest()
NAME, CELL = "decompressed_keys_share.dsv2", "deepseek_v2_doc_qa_sessions_closed"
M = harness.load_json(ROOT / "benchmark/configs/deepseek_v2_l5_e40_serve_1chip.json")
H = 128


def _entry():
    return [m for m in harness.metrics_of(MAN, CELL, True) if m["name"] == NAME]


def test_the_entry_is_found_by_its_name_and_sits_in_the_cells_layer():
    entry, = [m for m in MAN["per_layer"] if m["name"] == NAME]   # wherever in the list it stands
    assert [entry] == _entry()
    assert entry["source"] == "program_span" and CELL in entry["workloads"]
    assert (entry["unit"], entry["better"], entry["moves"]) == ("%", "higher", "serve_tokens_per_s")
    roofline = next(m for m in MAN["per_layer"] if m["name"] == "mla_prefill_roofline.dsv2")
    assert entry["layer"] == roofline["layer"]
    assert len(MAN["per_layer"]) <= 128, f"{len(MAN['per_layer'])} of 128 used"
    spec = harness.load_json(harness.HERE / "metrics" / f"{NAME}.json")
    assert spec["reader"] == "span_arg_percentile" and spec["unit"] == entry["unit"]
    assert spec["params"] == {"span": "prefill_pack", "arg": "mla_keys_decompressed_pct", "q": 50}


def test_the_share_reads_what_the_runner_writes_on_a_packs_span():
    from deepspeed_tpu.inference import latent_runner as lr
    from deepspeed_tpu.models.latent import LatentAttn
    from deepspeed_tpu.ops import latent_attention as la

    entry = _entry()
    spec = harness.load_json(harness.HERE / "metrics" / f"{NAME}.json")

    class Counter:
        def inc(self, by=1):
            pass

    a = LatentAttn(H, M["q_lora_rank"], M["kv_lora_rank"], M["qk_nope_head_dim"],
                   M["qk_rope_head_dim"], M["v_head_dim"], 1e4, gate=False)
    runner = lr.LatentRunner(SimpleNamespace(latent=SimpleNamespace(
        every=a, stateful=False, count=lambda kind: 5 if kind == "every" else 0)))
    runner._block = 128
    counters = {k: Counter() for k in lr.MLA_COUNTERS}
    packs = [[(0, 4096, 6144)],                  # a document's chunk: one run of 16 pages
             [(1, 16384, 16500)],                # a question behind a hit: one page
             [(2, 8192, 8500), (3, 0, 128)]]     # a document's last 3 pages beside a first page
    spans = [("prefill_pack", float(i), i + 0.5, runner.dispatched(counters, work, pack=True))
             for i, work in enumerate(packs)]
    assert spec["params"]["arg"] in spans[0][3]
    shares = [args[spec["params"]["arg"]] for *_, args in spans]
    keys = lambda lo, hi: (hi * (hi + 1) - lo * (lo + 1)) // 2
    assert shares == [100.0, 0.0, pytest.approx(100.0 * keys(8192, 8500) / (keys(8192, 8500) + keys(0, 128)))]
    obs = {"window": (0.0, 10.0), "spans": spans}
    got = harness.read_metrics(entry, obs)
    assert got == {NAME: {"value": pytest.approx(shares[2]), "unit": "%"}}
    older = [(n, a0, b0, {"mla_keys": args["mla_keys"]}) for n, a0, b0, args in spans]
    assert harness.read_metrics(entry, dict(obs, spans=older)) == {}
    # the rule's threshold is the yardstick's own crossing, in whole pages
    assert la.crossing(a) == costs_mla.crossing(M) == 171


def test_the_cells_rehearsal_reports_the_share():
    """A share the program writes on its packs' spans needs no device: the
    rehearsal's readers find it on the CPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the cell asks for its own device count
    out = subprocess.run(
        [sys.executable, str(ROOT / MAN["command"][1]), "--workload", CELL,
         "--seed", str(2**31 + 7), "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["attempted"] > 0 and line["failed"] == 0
    ran = next(l for l in out.stdout.splitlines()
               if l.startswith("rehearsal: readers that returned a value:"))
    assert NAME in ran.split()
