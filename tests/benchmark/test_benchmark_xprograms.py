"""The second reduction (``xprograms``): program executions, the span tree,
the two clock differences and the scope classes - on hand-made events and on
a small trace recorded on the chip (``tools/record_programs_trace.py``: three
ticks, two tracked programs, one Pallas call named ``toy_double``, every span
mirrored into the trace)."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import harness, xplane, xprograms  # noqa: E402
from benchmark.xplane import HostEvent  # noqa: E402
from benchmark.xprograms import Execution, Programs, RawOp  # noqa: E402

DATA = harness.HERE / "testdata"
CLASSES = harness.load_json(harness.HERE / "scopes" / "train_step.json")


def span(name, a, b, i, parent=None, **args):
    args["span_id"] = i
    if parent is not None:
        args["parent_id"] = parent
    return (name, a, b, args)


# -- hand-made: the span tree ------------------------------------------------
def tick_tree():
    return [span("sched.tick", 0.0, 10.0, 1, tick=1),
            span("sched.admit", 0.5, 1.0, 2, 1),
            span("sched.decode", 2.0, 9.0, 3, 1),
            span("engine.decode_build", 2.0, 3.0, 4, 3),
            span("decode_tick", 3.0, 8.0, 5, 3, dispatch_ms=1.5),
            span("engine.decode_emit", 8.0, 8.5, 6, 3),
            span("shed_mode", 9.5, 30.0, 7, 1)]   # detached: outlives its tick


def test_self_time_is_duration_minus_direct_children():
    own = xprograms.self_times(tick_tree())
    # tick 10 - admit 0.5 - decode 7 - the half second of shed_mode inside it
    assert own[1] == pytest.approx(2.0)
    assert own[3] == pytest.approx(7.0 - 1.0 - 5.0 - 0.5)
    assert own[5] == pytest.approx(5.0)
    assert min(own.values()) >= 0


def test_descendants_reach_every_depth():
    below = xprograms.descendants(tick_tree())
    assert {s[0] for s in below[1]} == {
        "sched.admit", "sched.decode", "engine.decode_build", "decode_tick",
        "engine.decode_emit", "shed_mode"}
    assert {s[0] for s in below[3]} == {
        "engine.decode_build", "decode_tick", "engine.decode_emit"}
    assert 5 not in below


def test_span_readers_read_the_tree():
    from benchmark.readers import span_arg_percentile, span_self_percentile

    obs = {"spans": tick_tree(), "window": (0.0, 20.0)}
    assert span_self_percentile.read(obs, "sched.tick", 50) == pytest.approx(2000.0)
    assert span_arg_percentile.read(obs, "decode_tick", "dispatch_ms", 50) == 1.5
    # outside the window, or a program without the span: nothing to read
    assert span_self_percentile.read({"spans": tick_tree(), "window": (11.0, 20.0)},
                                     "sched.tick", 50) is None
    assert span_arg_percentile.read({"spans": [], "window": (0.0, 1.0)},
                                    "decode_tick", "dispatch_ms", 50) is None
    # a parent's spans carry no id: no self time, no error
    old = [("sched.tick", 0.0, 1.0, {})]
    assert span_self_percentile.read({"spans": old, "window": (0.0, 2.0)},
                                     "sched.tick", 50) is None


@pytest.mark.parametrize("reader", ["span_self", "span_arg", "tick_host_gap"])
def test_readers_refuse_a_span_set_the_recorder_dropped_from(reader, monkeypatch):
    """The recorder's ring drops its oldest spans; the oldest one kept then
    carries ``spans_dropped``.  A whole-window median of such a set is of the
    window's end, and a parent in it may have lost children."""
    from benchmark.readers import (span_arg_percentile, span_self_percentile,
                                   tick_host_gap)

    def read(spans):
        obs = {"spans": spans, "window": (0.0, 20.0), "trace": object(),
               "_xprograms": programs()}
        if reader == "span_self":
            return span_self_percentile.read(obs, "sched.tick", 50)
        if reader == "span_arg":
            return span_arg_percentile.read(obs, "decode_tick", "dispatch_ms", 50)
        return tick_host_gap.read(
            obs, tick="sched.tick", holding=["decode_tick"], lacking=["prefill_pack"],
            module="^jit_decode_impl$", q=50, what="span")

    # the toy programs' clocks differ by seconds: pair them with as much slack
    monkeypatch.setattr(xprograms, "skew", lambda progs, s, m: xprograms.skew_interval(
        (h.start, h.end, e.start, e.end) for h, e in xprograms.pair(
            progs.mirrored(s), progs.of_module(m), 3.0)))
    whole = tick_tree()
    assert xprograms.spans_dropped(whole) == 0 and xprograms.spans_dropped([]) == 0
    assert read(whole) is not None
    name, a, b, args = whole[0]
    short = [(name, a, b, dict(args, spans_dropped=3))] + whole[1:]
    assert xprograms.spans_dropped(short) == 3
    assert read(short) is None


def test_collective_gib_passes_an_uncountable_program_through(monkeypatch):
    from benchmark.readers import collective_gib
    from deepspeed_tpu import telemetry

    counts = {"jit_train_step": 3 * 2**30, "jit_eval_step": None}
    monkeypatch.setattr(telemetry, "collective_bytes_per_step", lambda: dict(counts))
    obs = {"trace": object()}
    assert collective_gib.read(obs, "^jit_train_step$") == 3.0
    # a loop whose trip count the counter could not find: no number, not a low one
    assert collective_gib.read(obs, "^jit_eval_step$") is None
    assert collective_gib.read(obs, "^jit_") is None
    assert collective_gib.read(obs, "^no_such_program$") is None


# -- hand-made: the clocks ---------------------------------------------------
def test_skew_interval_by_causality():
    # host: span opened at 10.0, fetch returned at 10.5; device stamps run 2.0
    # early: started 8.1 (0.1 after the open), ended 8.4 (0.1 before the fetch)
    pairs = [(10.0, 10.5, 8.1, 8.4), (20.0, 20.5, 18.05, 18.45)]
    lo, hi = xprograms.skew_interval(pairs)
    assert lo == pytest.approx(1.95) and hi == pytest.approx(2.05)
    # one pair that no shift can satisfy together with the others: empty
    assert xprograms.skew_interval(pairs + [(30.0, 30.5, 29.0, 29.4)]) is None
    assert xprograms.skew_interval([]) is None


def programs():
    runs = [Execution("jit_decode_impl", 7, 8.1, 8.4),
            Execution("jit__threefry_split", 8, 8.45, 8.46),
            Execution("jit_packed_ctx_impl", 9, 12.0, 14.0),
            Execution("jit_decode_impl", 10, 18.05, 18.45)]
    mirrors = {5: HostEvent("decode_tick", 10.0, 10.5, {"span_id": "5"}),
               1: HostEvent("sched.tick", 9.8, 10.6, {"span_id": "1"}),
               15: HostEvent("decode_tick", 20.0, 20.5, {"span_id": "15"}),
               11: HostEvent("sched.tick", 13.9, 20.6, {"span_id": "11"}),
               25: HostEvent("decode_tick", 99.0, 99.5, {"span_id": "25"})}
    return Programs((9.0, 50.0), {0: runs}, {0: []}, mirrors)


def test_pairing_and_skew_on_programs():
    p = programs()
    assert [e.run_id for e in p.of_module("^jit_decode_impl$")] == [7, 10]
    assert [e.run_id for e in p.of_module("packed")] == [9]
    assert [h.stats["span_id"] for h in p.mirrored("decode_tick")] == ["5", "15"]
    pairs = xprograms.pair(p.mirrored("decode_tick"),
                           p.of_module("^jit_decode_impl$"), slack_s=3.0)
    assert [(h.stats["span_id"], e.run_id) for h, e in pairs] == [("5", 7), ("15", 10)]
    # a span with two candidate executions is left out, not guessed
    assert xprograms.pair(p.mirrored("decode_tick"), p.of_module(""), 3.0)[0][1].run_id == 10
    lo, hi = xprograms.skew(p, "decode_tick", "^jit_decode_impl$", slack_s=3.0)
    assert (lo, hi) == (pytest.approx(1.95), pytest.approx(2.05))
    assert xprograms.skew(p, "decode_tick", "no_such_module") is None


def test_busy_inside_shifts_and_clips():
    runs = programs().of_module("")
    # tick [9.8, 10.6] with device times +1.95: decode [10.05, 10.35], split [10.40, 10.41]
    assert xprograms.busy_inside(runs, 9.8, 10.6, 1.95) == pytest.approx(0.31)
    # the pack [13.95, 15.95] clipped at the span's start 14.0... and its end
    assert xprograms.busy_inside(runs, 14.0, 15.0, 1.95) == pytest.approx(1.0)
    assert xprograms.busy_inside(runs, 30.0, 31.0, 1.95) == 0.0


def test_tick_host_gap_takes_decode_only_ticks(monkeypatch):
    from benchmark.readers import host_device_skew, tick_host_gap

    spans = [span("sched.tick", 0.0, 0.8, 1), span("decode_tick", 0.2, 0.7, 5, 1),
             span("sched.tick", 4.1, 10.8, 11), span("prefill_pack", 4.2, 6.2, 12, 11),
             span("decode_tick", 10.2, 10.7, 15, 11)]
    obs = {"spans": spans, "trace": object(), "_xprograms": programs()}
    params = dict(tick="sched.tick", holding=["decode_tick"], lacking=["prefill_pack"],
                  module="^jit_decode_impl$", q=50)
    # only tick 1 qualifies: 0.8 s of span minus 0.31 s of device inside it
    monkeypatch.setattr(xprograms, "skew",
                        lambda progs, s, m, slack_s=3.0: xprograms.skew_interval(
                            (h.start, h.end, e.start, e.end) for h, e in xprograms.pair(
                                progs.mirrored(s), progs.of_module(m), 3.0)))
    assert tick_host_gap.read(obs, **params) == pytest.approx(490.0)
    assert host_device_skew.read(obs, "decode_tick", "^jit_decode_impl$") == pytest.approx(1950.0)
    # no trace, no device plane: nothing to read
    assert tick_host_gap.read({"spans": spans, "trace": None}, **params) is None
    assert host_device_skew.read({"_xprograms": None}, "decode_tick", "x") is None


# -- hand-made: scope classes ------------------------------------------------
@pytest.mark.parametrize("op_name,cls", [
    ("jit(train_step)/optimizer/mul", "optimizer"),
    ("jit(train_step)/optimizer/jit(_where)/select_n", "optimizer"),
    ("jit(train_step)/grad/transpose(jvp())/while/body/checkpoint/rematted_computation/attn/dot_general", "remat"),
    ("jit(train_step)/grad/jvp(loss)/while/body/dot_general", "loss"),
    ("jit(train_step)/grad/transpose(jvp(loss))/while/body/dot_general", "loss"),
    ("jit(train_step)/grad/transpose(jvp())/while/body/checkpoint/mlp/dot_general", "bwd"),
    ("jit(train_step)/grad/transpose(jvp(zero/gather))/convert_element_type", "bwd"),
    ("jit(train_step)/grad/jvp()/while/body/checkpoint/attn/flash_fwd/pallas_call", "fwd"),
    ("jit(train_step)/grad/jvp(embed)/gather", "fwd"),
    ("jit(train_step)/zero/reduce/sharding_constraint", "unscoped"),
    ("jit(train_step)/add", "unscoped"),
    ("", "unscoped"),
    (None, "unscoped"),
])
def test_scope_classes_of_the_train_step(op_name, cls):
    assert xprograms.classify(op_name, CLASSES["classes"], CLASSES["default"]) == cls


def test_class_seconds_looks_an_op_up_in_the_module_it_ran_in():
    runs = [Execution("jit_a", 1, 0.0, 1.0), Execution("jit_b", 2, 2.0, 3.0)]
    ops = [RawOp("fusion.1", 0.1, 0.5), RawOp("fusion.1", 2.1, 2.3),
           RawOp("copy.2", 2.5, 2.6), RawOp("fusion.9", 5.0, 5.1)]
    xplane._self_times(ops)
    scopes = {"jit_a": {"fusion.1": "jit(a)/optimizer/mul"},
              "jit_b": {"fusion.1": "jit(b)/grad/jvp(mlp)/dot_general"}}
    secs = xprograms.class_seconds(Programs((0, 9), {0: runs}, {0: ops}, {}), scopes,
                                   CLASSES["classes"], CLASSES["default"])
    assert secs == {"optimizer": pytest.approx(0.4), "fwd": pytest.approx(0.2),
                    "unscoped": pytest.approx(0.2)}  # copy.2, and fusion.9 outside any run


def test_instruction_name_keeps_the_number():
    assert xprograms.instruction_name(
        "%fusion.12 = bf16[64,14336]{1,0:T(8,128)(2,1)} fusion(%p), kind=kLoop") == "fusion.12"
    assert xprograms.instruction_name(
        "%flash_fwd.1 = (bf16[8,512,128]{2,1,0}, f32[8,512,1]{2,1,0}) custom-call(%a)") == "flash_fwd.1"
    assert xprograms.instruction_name("%copy-start = (f32[2]{0}) copy-start(%w.1)") == "copy-start"
    assert xprograms.instruction_name("jit_step") == "jit_step"


# -- the recorded trace ------------------------------------------------------
@pytest.fixture(scope="module")
def recorded():
    progs = xprograms.reduce(xplane.load(str(DATA / "small_programs_tpu_v5e.xplane.pb")))
    side = json.loads((DATA / "small_programs_tpu_v5e.json").read_text())
    return progs, [tuple(s) for s in side["spans"]], side["scopes"]


def test_recorded_files_are_small():
    assert (DATA / "small_programs_tpu_v5e.xplane.pb").stat().st_size < 100_000
    assert (DATA / "small_programs_tpu_v5e.json").stat().st_size < 100_000


def test_recorded_module_executions(recorded):
    progs, _, _ = recorded
    serve = progs.of_module("^jit_serve_step$")
    train = progs.of_module("^jit_train_step$")
    assert len(serve) == len(train) == 3
    runs = progs.executions[0]
    assert [e.run_id for e in runs] == sorted(e.run_id for e in runs)
    assert all(0 < e.end - e.start < 1e-3 for e in runs)   # tens of microseconds
    # the capture's window is the reduction's own
    tr = xplane.reduce_trace(xplane.load(str(DATA / "small_programs_tpu_v5e.xplane.pb")))
    assert progs.window == tuple(tr.window)
    # the program's phase names are in the host plane: idle gaps are named by them
    assert set(tr.idle_gaps()) & {"tick", "build", "decode_tick", "train_tick"}


def test_recorded_mirrors_pair_with_the_recorder_by_id(recorded):
    progs, spans, _ = recorded
    assert len(spans) == 12 and all(s[3]["span_id"] in progs.mirrors for s in spans)
    for name, a, b, args in spans:
        h = progs.mirrors[args["span_id"]]
        assert h.name == name
        assert h.end - h.start == pytest.approx(b - a, abs=50e-6)
    off = xprograms.recorder_offset(progs, spans)
    # one offset puts every recorder span on the trace's clock within 50 us
    assert all(abs(progs.mirrors[s[3]["span_id"]].start - s[1] - off) < 50e-6
               for s in spans)
    own = xprograms.self_times(spans)
    ticks = [s for s in spans if s[0] == "tick"]
    assert len(ticks) == 3 and all(0 <= own[s[3]["span_id"]] < s[2] - s[1] for s in ticks)


def test_recorded_skew_interval_is_not_empty(recorded):
    progs, _, _ = recorded
    # the toy's ticks are ~6 ms apart, closer than a serving cell's: 3 ms of slack
    pairs = xprograms.pair(progs.mirrored("decode_tick"),
                           progs.of_module("^jit_serve_step$"), 0.003)
    assert len(pairs) == 3
    # at the default 10 ms every span sees two executions and none is guessed
    assert xprograms.skew(progs, "decode_tick", "^jit_serve_step$") is None
    lo, hi = xprograms.skew(progs, "decode_tick", "^jit_serve_step$", slack_s=0.003)
    assert lo <= hi and hi - lo < 3e-3 and abs(lo) < 10e-3
    # shifted, every execution lies inside the span that dispatched it
    for h, e in pairs:
        assert h.start <= e.start + lo and e.end + lo <= h.end + 1e-9
        busy = xprograms.busy_inside([e], h.start, h.end, lo)
        assert busy == pytest.approx(e.end - e.start)
    # the other program, against its own spans, allows an overlapping interval
    lo2, hi2 = xprograms.skew(progs, "train_tick", "^jit_train_step$", slack_s=0.003)
    assert max(lo, lo2) <= min(hi, hi2)


def test_recorded_ops_classify_by_scope_and_name_the_kernel(recorded):
    progs, _, scopes = recorded
    ops = progs.ops[0]
    kernel = [o for o in ops if o.name.split(".")[0] == "toy_double"]
    assert len(kernel) == 3  # the Pallas call's name= is its instruction's name
    assert scopes["jit_serve_step"][kernel[0].name] == \
        "jit(serve_step)/attn/toy_double/pallas_call"
    secs = xprograms.class_seconds(progs, scopes, CLASSES["classes"], CLASSES["default"])
    assert set(secs) <= {"optimizer", "remat", "loss", "bwd", "fwd", "unscoped"}
    # the train toy's forward, backward and clipped update are ops of their own;
    # the serve program is under no grad or optimizer scope
    assert secs["optimizer"] > 0 and secs["bwd"] > 0 and secs["fwd"] > 0
    assert secs["unscoped"] >= sum(o.self_s for o in kernel)
    assert sum(secs.values()) == pytest.approx(sum(o.self_s for o in ops))
    from benchmark.readers import kernel_call_ms, module_device_percentile, scope_share

    obs = {"_xprograms": progs, "_scopes": scopes, "trace": object()}
    call = kernel_call_ms.read(obs, [r"^toy_double(\.\d+)?$"])
    assert 0 < call < module_device_percentile.read(obs, "^jit_serve_step$", 50)
    assert kernel_call_ms.read(obs, [r"^toy_double(\.\d+)?$", "^no_such_kernel$"]) is None
    shares = [scope_share.read(obs, "train_step", c) for c in
              ("fwd", "bwd", "remat", "loss", "optimizer", "unscoped")]
    assert sum(shares) == pytest.approx(100.0)


def test_readers_have_nothing_to_read_without_a_trace_or_a_device_plane(monkeypatch):
    from benchmark.readers import (collective_gib, kernel_call_ms,
                                   module_device_percentile, scope_share)
    from deepspeed_tpu import telemetry

    def never(*a, **kw):  # an untraced run must not pay for compiled text
        raise AssertionError("compiled text read in a run with no trace")

    monkeypatch.setattr(telemetry, "program_scopes", never)
    monkeypatch.setattr(telemetry, "collective_bytes_per_step", never)
    for obs in ({"trace": None}, {"trace": object(), "_xprograms": None}):
        assert module_device_percentile.read(dict(obs), "x", 50) is None
        assert kernel_call_ms.read(dict(obs), ["x"]) is None
        assert scope_share.read(dict(obs), "train_step", "fwd") is None
    assert collective_gib.read({"trace": None}, "x") is None
    # the first small trace holds no mirrored span and is still reduced
    old = xprograms.reduce(xplane.load(str(DATA / "small_tpu_v5e.xplane.pb")))
    assert old.mirrors == {} and len(old.of_module("^jit_tick$")) == 4
    assert xprograms.skew(old, "decode_tick", "^jit_tick$") is None


def test_another_runs_trace_file_is_refused(tmp_path, monkeypatch):
    prof_dir = tmp_path / "trace_x" / "plugins" / "profile" / "t"
    prof_dir.mkdir(parents=True)
    (prof_dir / "h.xplane.pb").write_bytes(
        (DATA / "small_programs_tpu_v5e.xplane.pb").read_bytes())
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path)
    tr = xplane.reduce_trace(xplane.load(str(prof_dir / "h.xplane.pb")))
    assert xprograms.of({"trace": tr}).window == tuple(tr.window)
    tr.window = (tr.window[0], tr.window[1] + 1.0)
    with pytest.raises(RuntimeError, match="another capture"):
        xprograms.of({"trace": tr})
    assert xprograms.of({"trace": None}) is None
