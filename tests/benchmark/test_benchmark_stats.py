"""Percentile, gap and reader arithmetic on hand-made stamps."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark.readers import (counter, counter_ratio, itl_percentile,  # noqa: E402
                               request_percentile, serve_rate, setup_seconds,
                               span_percentile, stall_share, train_rate)
from benchmark.stats import all_gaps_ms, percentile, token_gaps_ms  # noqa: E402


@pytest.mark.parametrize("values,q,expect", [
    ([], 50, None),
    ([5.0], 95, 5.0),
    ([1, 2, 3, 4], 50, 2.5),
    ([4, 1, 3, 2], 0, 1),
    ([1, 2, 3, 4], 100, 4),
    (list(range(101)), 95, 95),
    ([10, 20], 25, 12.5),
])
def test_percentile(values, q, expect):
    assert percentile(values, q) == expect


def test_first_token_has_no_gap_and_window_is_half_open():
    # tokens at 0.9 (first), 1.0, 1.1, 2.0; window [1.0, 2.0)
    gaps = token_gaps_ms([0.9, 1.0, 1.1, 2.0], (1.0, 2.0))
    assert gaps == pytest.approx([100.0, 100.0])  # 0.9->1.0 counts, ->2.0 does not
    assert token_gaps_ms([1.5], (1.0, 2.0)) == []


def request(**kw):
    base = {"token_times": [], "state": "finished", "end": None, "due": 0.0,
            "submit": 0.0, "admit": None, "prompt_len": 0, "got": 0, "chunks": []}
    base.update(kw)
    return base


OBS = {
    "window": (10.0, 20.0), "t_process": 1.5,
    "requests": [
        # in flight from the ramp: gaps inside the window count
        request(due=8.0, submit=8.001, admit=8.002, token_times=[9.0, 10.5, 10.52],
                end=10.52, prompt_len=100, got=3),
        request(due=11.0, submit=11.01, admit=11.03,
                token_times=[11.2, 11.22, 11.36, 11.38], end=11.38, prompt_len=50, got=4),
        # failed inside the window: one window-long gap, counted, not dropped
        request(due=12.0, submit=12.0, token_times=[12.3], state="failed", end=12.4),
        # finished after the window: its late gap and its tokens do not count
        request(due=19.0, submit=19.0, admit=19.0, token_times=[19.5, 20.5],
                end=20.5, prompt_len=10, got=2),
    ],
    "spans": [("prefill_pack", 11.23, 11.35, {}), ("decode_tick", 11.2, 11.22, {}),
              ("decode_tick", 11.36, 11.38, {}), ("decode_tick", 25.0, 25.5, {})],
    "counters": {"decode_emitted": 30, "decode_ticks": 10, "preemptions": 0,
                 "cached_prompt_tokens": 3, "prompt_tokens_total": 4},
}


def test_all_gaps_counts_the_failed_request():
    gaps = sorted(all_gaps_ms(OBS["requests"], OBS["window"]))
    assert gaps == pytest.approx([20.0, 20.0, 20.0, 140.0, 1500.0, 10000.0])


def test_itl_percentile_reader():
    assert itl_percentile.read(OBS, q=0) == pytest.approx(20.0)
    assert itl_percentile.read(OBS, q=100) == pytest.approx(10000.0)
    assert itl_percentile.read({"window": (0, 1)}, q=50) is None


def test_serve_rate_follows_the_curve_of_completed_prefill():
    # window [10, 20).  Output stamps inside: 2 + 4 + 1 + 1 = 8.
    # Completed prefill: 0 at 8.0 (first submit), 100 at 9.0, 150 at 11.2,
    # 150 at 12.3 (the failed request had no prompt), 160 at 19.5; flat after.
    # At 10.0: 100 + 50 * (1.0 / 2.2); at 20.0: 160.
    rise = 160 - (100 + 50 * 1.0 / 2.2)
    assert serve_rate.read(OBS) == pytest.approx((8 + rise) / 10.0)
    # a prompt whose prefill straddles the window's start counts in part, and
    # the curve between two completions is a straight line
    half = {"window": (10.0, 20.0), "requests": [
        request(submit=9.0, token_times=[11.0], prompt_len=300),
        request(submit=9.5, token_times=[21.0], prompt_len=500)]}
    assert serve_rate.read(half) == pytest.approx((1 + 150 + 500 * 9.0 / 10.0) / 10.0)
    assert serve_rate.read({"window": (0, 1), "requests": [request()]}) is None


def test_in_flight_is_the_mean_over_the_ticks_of_one_part_of_the_window():
    """The sweep tool's queue reading, from the stamps a serving driver keeps
    for every tick: (begin, end, decoding, context tokens, in flight, waiting)."""
    from benchmark.tools import sweep_traffic

    obs = {"window": (0.0, 10.0), "ticks": [(1.0, 1.1, 0, 0, 4, 0), (1.5, 1.9, 0, 0, 6, 1),
                                            (2.0, 2.3, 0, 0, 9, 0), (9.9, 10.2, 0, 0, 30, 0),
                                            (8.5, 8.6, 0, 0, 12, 3)]}
    assert sweep_traffic.in_flight(obs, 0) == pytest.approx(5.0)   # ticks ending in [0, 2)
    assert sweep_traffic.in_flight(obs, 1) == pytest.approx(9.0)
    assert sweep_traffic.in_flight(obs, 4) == pytest.approx(12.0)  # the tick past the end is out
    assert sweep_traffic.in_flight(obs, 2) is None
    assert sweep_traffic.in_flight({"window": (0, 1)}, 0) is None


def test_stall_notes_name_the_longest_tick_its_spans_and_the_longest_pause():
    """``harness.observe`` adds them to a serving run's notes: a window that
    lost seconds says whether ONE tick held them (and which span) or the host
    outside the loop.  Ticks and spans outside the window do not count."""
    from benchmark import harness

    obs = {"window": (10.0, 20.0),
           "ticks": [(9.0, 9.9, 0, 0, 1, 0), (10.0, 10.1, 0, 0, 1, 0), (10.1, 10.2, 0, 0, 1, 0),
                     (10.5, 13.0, 0, 0, 1, 0), (13.0, 13.1, 0, 0, 1, 0), (19.0, 25.0, 0, 0, 1, 0)],
           "spans": [("sched.tick", 10.5, 13.0, {}), ("prefill_pack", 10.6, 12.9, {}),
                     ("engine.pack_build", 10.5, 10.6, {}), ("decode_tick", 10.0, 10.1, {})]}
    tick, pause = harness.stall_notes(obs)
    assert tick.startswith("load: longest tick 2500.0 ms (median 100.0) at 0.5 s of the window")
    assert tick.endswith("sched.tick 2500.0, prefill_pack 2300.0, engine.pack_build 100.0")
    assert pause == "load: longest pause between two ticks 300.0 ms at 0.2 s of the window"
    assert harness.stall_notes({"window": (0.0, 1.0), "ticks": [(0.1, 0.2)]}) == []
    assert harness.stall_notes({"kind": "train"}) == []


@pytest.mark.parametrize("quantity,q,expect", [
    ("ttft", 50, 250.0),        # due 11.0 -> 11.2 = 200; 12.0 -> 12.3 = 300; 19.0 -> 19.5 = 500
    ("gen_lag", 100, 10.0),
    ("queue_wait", 0, 0.0),
])
def test_request_percentile_times_from_due(quantity, q, expect):
    got = request_percentile.read(OBS, quantity=quantity, q=q)
    if quantity == "ttft":
        assert request_percentile.read(OBS, quantity="ttft", q=0) == pytest.approx(200.0)
        assert got == pytest.approx(300.0)  # median of 200, 300, 500
    else:
        assert got == pytest.approx(expect, abs=1e-6)


def test_span_percentile_only_spans_ending_inside():
    assert span_percentile.read(OBS, span="decode_tick", q=50) == pytest.approx(20.0)
    assert span_percentile.read(OBS, span="prefill_pack", q=50) == pytest.approx(120.0)
    assert span_percentile.read(OBS, span="absent", q=50) is None


def test_stall_share_is_gaps_holding_a_pack():
    # five real gaps in the window, one of them (11.22 -> 11.36) holds the pack
    assert stall_share.read(OBS, span="prefill_pack") == pytest.approx(100.0 / 5)


def test_counter_readers():
    assert counter.read(OBS, key="preemptions") == 0
    assert counter_ratio.read(OBS, num="decode_emitted", den="decode_ticks") == 3.0
    assert counter_ratio.read(OBS, num="cached_prompt_tokens",
                              den="prompt_tokens_total", scale=100.0) == 75.0
    assert counter_ratio.read({"counters": {"a": 1, "b": 0}}, num="a", den="b") is None


def test_setup_and_train_rate():
    assert setup_seconds.read(OBS) == pytest.approx(8.5)
    obs = {"kind": "train", "window": (2.0, 4.0), "steps": 10,
           "tokens_per_step": 4 * 4096, "chips": 4}
    assert train_rate.read(obs) == pytest.approx(10 * 4096 / 2.0)
    assert train_rate.read(dict(obs, steps=0)) is None
