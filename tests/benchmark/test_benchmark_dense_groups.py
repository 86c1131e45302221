"""``dense_groups_share.dots``: the median over the window's ``prefill_pack``
spans of the share of a pack's groups that walked their pages instead of
gathering rows (the argument ``selected_groups_dense_pct`` that
``LatentRunner.dispatched()`` writes), on the reader ``span_arg_percentile``;
nothing, and no error, from a program older than the argument."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import harness  # noqa: E402

NAME, CELL = "dense_groups_share.dots", "dots3_note_longdocs_closed"


def _entry():
    man = harness.load_json(ROOT / "BENCHMARK.json")
    return [m for m in harness.metrics_of(man, CELL, True) if m["name"] == NAME]


def _pack(t, **args):
    return ("prefill_pack", t, t + 0.2, {"tokens": 2048, **args})


@pytest.mark.parametrize("shares,want", [
    ([100.0, 100.0, 87.5, 100.0, 0.0], 100.0),  # a pack of short groups only reads 100
    ([50.0, 25.0], 37.5),
    ([], None),                                 # the parent's spans: no such argument
])
def test_the_metric_reads_the_packs_share_off_the_recorded_spans(shares, want):
    entry = _entry()
    assert len(entry) == 1 and entry[0]["moves"] == "serve_tokens_per_s"
    spans = [_pack(1.0 + i, selected_groups_dense_pct=s) for i, s in enumerate(shares)]
    spans += [_pack(0.2, selected_groups_dense_pct=12.5),         # before the window
              _pack(3.5), ("decode_tick", 2.0, 2.1, {"batch": 8})]  # no argument: left out
    got = harness.read_metrics(entry, {"spans": spans, "window": (1.0, 20.0)})
    if want is None:
        assert got == {}
    else:
        assert got == {NAME: {"value": want, "unit": "%"}}
