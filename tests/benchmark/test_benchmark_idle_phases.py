"""The device's idle time charged to the program's phases instant by instant
(``readers/idle_by_phase``), the causality interval's width with and without
the ``upload`` mark (``idle_by_phase.causality``) and the small programs
a tick runs beside the engine's own (``tools/describe_idle.started_inside``,
the tool's since the metric that read it was retired in PR 52): on
three hand-made scheduler ticks whose every idle interval is known."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import one_ahead_scenes as scenes  # noqa: E402
from benchmark import xprograms  # noqa: E402
from benchmark.readers import idle_by_phase  # noqa: E402
from benchmark.tools import describe_idle  # noqa: E402
from benchmark.xplane import HostEvent  # noqa: E402
from benchmark.xprograms import Execution, Programs, RawOp  # noqa: E402

T = 100.0          # the capture opens here on the trace's host clock
RECORDER = -50.0   # recorder clock minus trace clock
D = 0.002          # what to ADD to a device stamp: the pinned pair makes it the lower edge
AUX = "^jit_(packed|packed_ctx|decode|decode_burst|spec|cow)_impl$"

# (name, start, end, id, parent, args) in seconds of the trace's host clock after T
SPANS = [
    # tick 1, decode only; its execution has a bubble between its two ops
    ("sched.tick", 0.010, 0.110, 1, None, {}),
    ("sched.decode", 0.015, 0.105, 2, 1, {}),
    ("engine.decode_build", 0.015, 0.025, 3, 2, {"rows_ms": 6.0}),
    ("decode_tick", 0.025, 0.095, 4, 2, {"upload_ms": 5.0, "dispatch_ms": 10.0}),
    ("engine.decode_emit", 0.095, 0.100, 5, 2, {}),
    # tick 2: a pack closed unsynced, then a decode step queued behind it
    ("sched.tick", 0.200, 0.400, 11, None, {}),
    ("sched.prefill", 0.205, 0.250, 12, 11, {}),
    ("engine.pack_build", 0.205, 0.215, 13, 12, {"rows_ms": 8.0}),
    ("prefill_pack", 0.215, 0.225, 14, 12,
     {"upload_ms": 4.0, "dispatch_ms": 10.0, "synced": False}),
    ("engine.pack_emit", 0.225, 0.235, 15, 12, {}),
    ("sched.decode", 0.250, 0.390, 16, 11, {}),
    ("engine.decode_build", 0.250, 0.260, 17, 16, {"rows_ms": 10.0}),
    ("decode_tick", 0.260, 0.380, 18, 16, {"upload_ms": 5.0, "dispatch_ms": 10.0}),
    ("engine.decode_emit", 0.380, 0.385, 19, 16, {}),
    # tick 3: its execution starts AT its upload mark, which pins the shift to D
    ("sched.tick", 0.500, 0.600, 21, None, {}),
    ("sched.decode", 0.502, 0.598, 22, 21, {}),
    ("engine.decode_build", 0.502, 0.510, 23, 22, {"rows_ms": 3.0}),
    ("decode_tick", 0.510, 0.590, 24, 22, {"upload_ms": 5.0, "dispatch_ms": 10.0}),
    ("engine.decode_emit", 0.590, 0.595, 25, 22, {}),
    # a span of no phase (a shed episode) changes nothing
    ("shed_mode", 0.050, 0.700, 31, 1, {}),
]
# (module, [op intervals]) in seconds of the HOST clock after T
RUNS = [
    ("jit__threefry_split", [(0.0220, 0.0225)]), ("jit__unstack", [(0.0230, 0.0235)]),
    ("jit_decode_impl", [(0.040, 0.055), (0.060, 0.080)]),
    ("jit__threefry_split", [(0.2135, 0.2140)]), ("jit__unstack", [(0.2145, 0.2150)]),
    ("jit_packed_ctx_impl", [(0.230, 0.300)]),
    ("jit__threefry_split", [(0.300, 0.302)]), ("jit__unstack", [(0.302, 0.304)]),
    ("jit_decode_impl", [(0.310, 0.350)]),
    ("jit__threefry_split", [(0.5055, 0.5060)]), ("jit__unstack", [(0.5065, 0.5070)]),
    ("jit_decode_impl", [(0.515, 0.560)]),
]
EXPECTED = {
    "in_program": 0.005,
    "launch": 0.005 + 0.005 + 0.006,      # the unsynced pack's falls into engine.pack_emit
    "fetch_tail": 0.015 + 0.030 + 0.030,
    "upload": 0.005 + 0.004 + 0.005,
    "enqueue": 0.005 + 0.006,             # tick 3's: its program already ran
    "build_rows": 0.006 + 0.008 + 0.003,
    "build_rng": 0.003 + 0.001 + 0.004,
    "emit": 0.015,
    "sched": 0.015 + 0.020 + 0.007,
    "outside": 0.008 + 0.090 + 0.100 + 0.402,
}


def scene(unmirrored=(2,), strip=(), dropped=0):
    """(Programs, the recorder's spans) of the three ticks."""
    spans, mirrors = [], {}
    for name, a, b, i, parent, args in SPANS:
        args = {k: v for k, v in args.items() if k not in strip}
        args.setdefault("dispatch_ms", round(1e3 * (b - a), 3))
        args["span_id"] = i
        if parent is not None:
            args["parent_id"] = parent
        spans.append((name, T + a + RECORDER, T + b + RECORDER, args))
        if i not in unmirrored:
            mirrors[i] = HostEvent(name, T + a, T + b, {"span_id": str(i)})
    if dropped:
        spans[0][3]["spans_dropped"] = dropped
    runs, ops = [], []
    for n, (module, parts) in enumerate(RUNS):
        runs.append(Execution(module, n, T + parts[0][0] - D, T + parts[-1][1] - D))
        ops += [RawOp(f"fusion.{n}", T + a - D, T + b - D) for a, b in parts]
    return Programs((T, T + 1.0), {0: runs}, {0: ops}, mirrors), spans


def test_every_idle_instant_goes_to_the_phase_open_then():
    progs, spans = scene()
    secs = idle_by_phase.seconds(progs, spans)
    assert secs["shift"] == pytest.approx(D, abs=1e-12)
    for phase, want in EXPECTED.items():
        assert secs[phase] == pytest.approx(want, abs=1e-9), phase
    # the parts add up to what device_idle_share reads of the same ops
    busy = sum(b - a for _, parts in RUNS for a, b in parts)
    assert sum(secs[p] for p in idle_by_phase.PHASES) == pytest.approx(1.0 - busy, abs=1e-9)
    # ``of`` computes the same once a run
    obs = {"trace": object(), "_xprograms": progs, "spans": spans}
    assert idle_by_phase.of(obs) == secs and obs["_idle_by_phase"] is idle_by_phase.of(obs)


def test_an_unsynced_packs_launch_is_launch_and_a_fetch_splits_at_its_execution():
    progs, spans = scene()
    secs = idle_by_phase.seconds(progs, spans)
    # with the pack's execution under a name that pairs it with no span, the
    # same 5 ms are what the host was doing then: engine.pack_emit
    next(e for e in progs.executions[0] if "packed" in e.module).module = "jit_other"
    blind = idle_by_phase.seconds(progs, spans)
    assert blind["launch"] == pytest.approx(secs["launch"] - 0.005, abs=1e-9)
    assert blind["emit"] == pytest.approx(secs["emit"] + 0.005, abs=1e-9)
    # tick 2's decode step: idle after its dispatch mark is launch BEFORE its
    # execution (0.304-0.310, queued behind the pack) and fetch_tail AFTER it
    hosts = idle_by_phase.on_trace_clock(*scene())
    step = next(h for h in hosts if h.stats["span_id"] == 18)
    pairs = dict((h.stats["span_id"], e) for h, e, _ in xprograms.dispatched(
        *scene(), idle_by_phase.DISPATCH))
    assert pairs[18].start + D == pytest.approx(T + 0.310)
    assert idle_by_phase.mark_at(step, "dispatch_ms") == pytest.approx(T + 0.270)
    assert pairs[14].module == "jit_packed_ctx_impl" and set(pairs) == {4, 14, 18, 24}
    assert secs["fetch_tail"] == pytest.approx(0.075, abs=1e-9)


def test_nothing_to_read_without_marks_with_dropped_spans_or_without_a_shift():
    progs, spans = scene(strip=("upload_ms", "rows_ms"))
    assert idle_by_phase.seconds(progs, spans) is None      # the parent's spans
    obs = {"trace": object(), "_xprograms": progs, "spans": spans}
    assert idle_by_phase.of(obs) is None
    assert idle_by_phase.seconds(*scene(dropped=7)) is None
    assert idle_by_phase.seconds(None, spans) is None        # a run with no trace
    # an execution that ends after its fetch returned: no shift satisfies both
    progs, spans = scene()
    late = next(e for e in progs.executions[0] if e.run_id == 2)
    late.end += 0.020
    assert idle_by_phase.seconds(progs, spans) is None


def test_a_span_the_session_did_not_mirror_is_placed_by_the_clocks_offset():
    whole = idle_by_phase.seconds(*scene(unmirrored=()))
    some = idle_by_phase.seconds(*scene(unmirrored=(1, 2, 3, 4, 5)))
    for phase in idle_by_phase.PHASES:
        assert some[phase] == pytest.approx(whole[phase], abs=1e-9)


def test_skew_width_with_and_without_the_tightened_bound():
    progs, spans = scene()
    pairs = xprograms.dispatched(progs, spans, idle_by_phase.DISPATCH)

    def width(mark):
        lo, hi = idle_by_phase.causality(pairs, mark)
        return hi - lo

    # upper edge: tick 1's fetch returned 15 ms after its execution ended;
    # lower edge: tick 3's execution started at its upload mark, 5 ms after
    # its span opened
    assert width("upload") == pytest.approx(0.015, abs=1e-9)
    assert width(None) == pytest.approx(0.020, abs=1e-9)
    assert idle_by_phase.seconds(progs, spans)["width"] == pytest.approx(0.015, abs=1e-9)


def test_small_programs_a_tick_are_counted_by_the_span_they_start_in():
    progs, spans = scene()
    per_tick = describe_idle.started_inside(progs, "sched.tick", AUX, D)
    assert [len(r) for r in per_tick] == [2, 4, 2]
    assert {e.module for r in per_tick for e in r} == {"jit__threefry_split", "jit__unstack"}
    # cut at the looser shift the decode ticks' pairing alone allows (no upload
    # mark: the span opened 5 ms before its execution), every tick counts the same
    loose = xprograms.tight_edge(xprograms.skew(progs, "decode_tick", "^jit_decode_impl$", spans=spans))
    assert loose == pytest.approx(D - 0.005, abs=1e-9)
    assert [len(r) for r in describe_idle.started_inside(progs, "sched.tick", AUX, loose)] == [2, 4, 2]
    assert describe_idle.started_inside(progs, "sched.tick", ".", D) == [[], [], []]


def test_the_tool_prints_its_tables_of_the_same_scene():
    """``tools/describe_idle.py``'s tables on the three ticks: the phases in
    ms a tick, the marks' medians, the small programs by module, and a
    runtime TraceMe charged to the phase the scheduler's thread was in."""
    progs, spans = scene()
    secs = idle_by_phase.seconds(progs, spans)
    rows = describe_idle.phase_table(secs, 1.0, 3)
    assert any(r.split()[:2] == ["launch", "0.0160"] for r in rows)
    assert rows[-2].split()[:2] == ["all", "0.8030"] and "width 15.0000 ms" in rows[-1]
    window = (T + RECORDER, T + RECORDER + 1.0)
    marks = describe_idle.mark_table(spans, window)
    assert any(r.startswith("decode_tick") and "= 5.000 + 5.000 + 70.000" in r for r in marks)
    assert any(r.startswith("engine.pack_build") and "= 8.000 + 2.000" in r for r in marks)
    aux = describe_idle.aux_table(progs, AUX, D)
    assert aux[-1] == "median a tick: 2.0" and sum("jit__" in r for r in aux) == 2
    # an allocation of 4 ms that begins 2 ms before tick 1's upload mark
    trace = type("T", (), {"host": [HostEvent("Allocate", T + 0.028, T + 0.032, {})]})()
    phases = idle_by_phase.host_phases(idle_by_phase.on_trace_clock(progs, spans))
    (row,) = describe_idle.traceme_table(trace, phases, ["^Allocate$"])
    assert "(1 events, 0.0040 s): upload 0.0020, enqueue 0.0020" in row


@pytest.mark.parametrize("name", sorted(scenes.SCENES))
def test_the_phases_add_up_whatever_the_order_of_dispatch(name):
    """One ahead (PR 43) a dispatch span closes at the enqueue and the wait is
    a ``tick_collect``: the cut still charges every idle instant once, at a
    shift within a launch or a fetch of the truth."""
    s = scenes.SCENES[name]()
    progs, spans = s.programs()
    secs = idle_by_phase.seconds(progs, spans)
    assert secs is not None and secs["shift"] == pytest.approx(s.shift, abs=1e-3)
    w0, w1 = progs.window
    busy = sum(o.end - o.start for o in progs.ops[0])
    assert sum(secs[p] for p in idle_by_phase.PHASES) == pytest.approx(w1 - w0 - busy, abs=1e-9)
    assert secs["in_program"] == pytest.approx(1e-5 * len(s.runs), abs=1e-9)   # the bubbles
    assert secs["outside"] >= 0.019            # the capture's two margins, give or take the shift
    if name == "host_bound":
        # the device waits while the scheduler works: its idle time is the host's
        assert secs["sched"] > 0.5 * (w1 - w0 - busy - secs["outside"])
    elif name != "old_order":
        # the device sets the pace: next to nothing of a tick is idle
        host = sum(secs[p] for p in idle_by_phase.PHASES if p not in ("in_program", "outside"))
        assert host < 0.05 * busy


def test_the_tool_prints_its_tables_of_a_one_ahead_scene():
    from benchmark.tools import describe_idle

    s = scenes.pack_and_step()
    progs, spans = s.programs()
    secs = idle_by_phase.seconds(progs, spans)
    ticks = len(progs.mirrored("sched.tick"))
    rows = describe_idle.phase_table(secs, progs.window[1] - progs.window[0], ticks)
    assert rows[-2].split()[0] == "all" and f"({ticks} ticks" in rows[-2]
    assert f"shift {1e3 * secs['shift']:+.4f} ms" in rows[-1]
    t0 = min(a for _, a, _, _ in spans)
    marks = describe_idle.mark_table(spans, (t0, t0 + 10.0))
    # a dispatch span closed at the enqueue: upload + enqueue, and no fetch left in it
    assert any(r.startswith("decode_tick") and r.rstrip().endswith("(sum 0.600)") for r in marks)
    assert describe_idle.aux_table(progs, AUX, secs["shift"])[-1] == "median a tick: 0.0"


def test_the_manifest_holds_no_more_per_layer_metrics_than_the_driver_takes():
    """PR 39's first 28 entries made 146 and the driver refused the file:
    ``per_layer`` is a list of 1 to 128."""
    import json

    man = json.loads((Path(__file__).resolve().parents[2] / "BENCHMARK.json").read_text())
    assert 1 <= len(man["per_layer"]) <= 128
