"""The second reduction (``xprograms``): program executions, the span tree,
the two clock differences and the scope classes - on hand-made events and on
a small trace recorded on the chip (``tools/record_programs_trace.py``: three
ticks, two tracked programs, one Pallas call named ``toy_double``, every span
mirrored into the trace)."""
import json
import math
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import one_ahead_scenes as scenes  # noqa: E402
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
    from benchmark.readers import span_arg_percentile, span_sum_percentile

    obs = {"spans": tick_tree(), "window": (0.0, 20.0)}
    build_emit = ["engine.decode_build", "engine.decode_emit"]
    # the tick's own 2.0 s and, two levels down, a build of 1.0 and an emit of 0.5
    assert span_sum_percentile.read(obs, "sched.tick", build_emit, 50) == pytest.approx(1500.0)
    assert span_sum_percentile.read(obs, "sched.tick", build_emit, 50, own=True) == pytest.approx(3500.0)
    assert span_sum_percentile.read(obs, "sched.tick", "engine.decode_emit", 50) == pytest.approx(500.0)
    assert span_arg_percentile.read(obs, "decode_tick", "dispatch_ms", 50) == 1.5
    # outside the window, or a program without the span: nothing to read
    assert span_sum_percentile.read({"spans": tick_tree(), "window": (11.0, 20.0)},
                                    "sched.tick", build_emit, 50, own=True) is None
    assert span_sum_percentile.read(obs, "sched.tick", "absent", 50, own=True) is None
    assert span_arg_percentile.read({"spans": [], "window": (0.0, 1.0)},
                                    "decode_tick", "dispatch_ms", 50) is None
    # a parent's spans carry no id: no tree, no error
    old = [("sched.tick", 0.0, 1.0, {}), ("engine.decode_build", 0.1, 0.2, {})]
    assert span_sum_percentile.read({"spans": old, "window": (0.0, 2.0)},
                                    "sched.tick", build_emit, 50, own=True) is None


@pytest.mark.parametrize("reader", ["span_own_sum", "span_arg", "span_sum"])
def test_readers_refuse_a_span_set_the_recorder_dropped_from(reader):
    """The recorder's ring drops its oldest spans; the oldest one kept then
    carries ``spans_dropped``.  A whole-window median of such a set is of the
    window's end, and a parent in it may have lost children."""
    from benchmark.readers import span_arg_percentile, span_sum_percentile

    def read(spans):
        obs = {"spans": spans, "window": (0.0, 20.0)}
        if reader == "span_own_sum":
            return span_sum_percentile.read(obs, "sched.tick", ["engine.decode_emit"], 50, own=True)
        if reader == "span_arg":
            return span_arg_percentile.read(obs, "decode_tick", "dispatch_ms", 50)
        return span_sum_percentile.read(obs, "sched.tick", "decode_tick", 50)

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
    lo, hi = scenes.skew(p, "decode_tick", "^jit_decode_impl$", slack_s=3.0)
    assert (lo, hi) == (pytest.approx(1.95), pytest.approx(2.05))
    assert scenes.skew(p, "decode_tick", "no_such_module") is None


def test_the_edge_nearer_to_zero_is_the_tight_one_and_no_capture_reads_no_skew():
    from benchmark.readers import host_device_skew

    assert xprograms.tight_edge((-0.0073, 0.0016)) == 0.0016   # one ahead: the upper edge
    assert xprograms.tight_edge((0.0008, 0.0051)) == 0.0008    # the host sets the pace
    assert xprograms.tight_edge((-0.0002, math.inf)) == -0.0002  # nothing was fetched
    # no trace, no device plane, a capture without the runtime's events (these toy
    # programs'): nothing to read.  What it reads is ``test_benchmark_collects.py``'s
    assert host_device_skew.read({"_xprograms": None}) is None
    assert host_device_skew.read({"spans": [], "trace": None, "_xprograms": programs()}) is None
    empty = xplane.Trace((9.0, 50.0), {}, [])
    assert host_device_skew.read({"spans": [], "trace": empty, "_xprograms": programs()}) is None


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
    assert scenes.skew(progs, "decode_tick", "^jit_serve_step$") is None
    lo, hi = scenes.skew(progs, "decode_tick", "^jit_serve_step$", slack_s=0.003)
    assert lo <= hi and hi - lo < 3e-3 and abs(lo) < 10e-3
    # shifted, every execution lies inside the span that dispatched it
    for h, e in pairs:
        assert h.start <= e.start + lo and e.end + lo <= h.end + 1e-9
    # the other program, against its own spans, allows an overlapping interval
    lo2, hi2 = scenes.skew(progs, "train_tick", "^jit_train_step$", slack_s=0.003)
    assert max(lo, lo2) <= min(hi, hi2)


@pytest.mark.parametrize("span,module", [("decode_tick", "^jit_serve_step$"),
                                         ("train_tick", "^jit_train_step$")])
def test_recorded_spans_pair_as_their_mirrors_do(recorded, span, module):
    """The recorder's spans of the recorded run (back to back: no ``ahead``, no
    collect, every fetch inside its span) laid on the trace's clock give the
    interval their mirrors give, and it is not empty."""
    progs, spans, _ = recorded
    iv = scenes.skew(progs, span, module, slack_s=0.003, spans=spans)
    assert iv is not None and iv[0] <= iv[1] < math.inf
    assert iv == pytest.approx(scenes.skew(progs, span, module, slack_s=0.003), abs=1e-9)
    lo, hi = xprograms.skew_interval(
        (h.start, h.end, e.start, e.end) for h, e in xprograms.pair(
            progs.mirrored(span), progs.of_module(module), 0.003))   # all three spans
    assert iv[0] <= lo <= hi <= iv[1]
    assert abs(xprograms.tight_edge(iv)) < 10e-3
    pairs = xprograms.dispatched(progs, spans, {span: module}, 0.003)
    # (a span whose execution lies within 3 ms of the capture's edge is left out)
    assert 2 <= len(pairs) <= 3 and all(at == h.end for h, _, at in pairs)
    assert xprograms.returned(xprograms.on_trace_clock(progs, spans)) == {}   # nothing is booked


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


def test_a_bodys_share_of_its_program_on_the_recorded_trace(recorded):
    """``readers/scope_share_of_program.py``: a scope's self time over the device time
    of ITS program's executions that the capture holds whole, by hand from the ops."""
    from benchmark.readers import scope_share_of_program

    progs, _, scopes = recorded
    obs = {"_xprograms": progs, "_scopes": scopes, "trace": object()}
    read = lambda scope, module="^jit_serve_step$": scope_share_of_program.read(obs, module, scope)
    runs = progs.of_module("^jit_serve_step$")
    # the first execution starts 0.4 ms into the capture: the session's edge, left out
    assert runs[0].start - progs.window[0] < xprograms.COLLECT_SLACK_S < runs[1].start - progs.window[0]
    whole = runs[1:]
    under = lambda word: sum(
        o.self_s for o in progs.ops[0] for e in whole if e.start <= o.start < e.end
        and f"/{word}/" in scopes["jit_serve_step"].get(o.name, ""))
    took = sum(e.end - e.start for e in whole)
    mlp, attn = read("(^|/)mlp(/|$)"), read("(^|/)attn(/|$)")
    assert mlp == pytest.approx(100 * under("mlp") / took) and attn == pytest.approx(100 * under("attn") / took)
    assert 0 < attn < mlp < 100   # the toy's matmul is most of its step, the Pallas call a little
    assert read("(^|/)(mlp|attn)(/|$)") == pytest.approx(mlp + attn)   # an alternation: several bodies
    # the other program's ``mlp`` is its own: a share is of ONE program's time
    assert read("(^|/)jvp\\(mlp\\)(/|$)", "^jit_train_step$") != pytest.approx(mlp)
    # nothing to read: a body the program has not, a program the capture has not
    assert read("(^|/)no_such_body(/|$)") is None and read("(^|/)mlp(/|$)", "^jit_absent$") is None


@pytest.mark.parametrize("runs,want", [
    # four executions of 10 ms, the body 2 ms of each: the two at the capture's edges left out
    ([(0.001, 0.011), (0.020, 0.030), (0.040, 0.050), (0.0895, 0.0995)], 20.0),
    ([(0.001, 0.011), (0.0895, 0.0995)], None),    # none held whole: nothing, never a 0
    ([(0.020, 0.030), (0.040, 0.060)], 100 * 4 / 30),   # a longer execution weighs by its time
])
def test_a_bodys_share_leaves_out_the_executions_at_the_captures_edges(runs, want):
    from benchmark.readers import scope_share_of_program

    ops = [RawOp(name, a + at, a + at + 0.002, 0.002) for a, _ in runs
           for name, at in (("body.1", 0.001), ("other.2", 0.004))]
    progs = Programs((0.0, 0.1), {0: [Execution("jit_step", i, a, b) for i, (a, b) in enumerate(runs)]},
                     {0: ops}, {})
    obs = {"_xprograms": progs, "trace": object(),
           "_scopes": {"jit_step": {"body.1": "jit(step)/body/dot_general", "other.2": "jit(step)/other/add"}}}
    got = scope_share_of_program.read(obs, "^jit_step$", "(^|/)body(/|$)")
    assert got == (None if want is None else pytest.approx(want))


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
    assert scenes.skew(old, "decode_tick", "^jit_tick$") is None


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


# -- hand-made: one ahead (PR 43) ---------------------------------------------
ENGINE = {"decode_tick": r"^jit_decode_impl$", "prefill_pack": r"^jit_packed(_ctx)?_impl$"}
SCENES = sorted(scenes.SCENES)


@pytest.fixture(scope="module", params=SCENES)
def scene(request):
    s = scenes.SCENES[request.param]()
    return (s,) + s.programs()


def test_a_dispatch_pairs_with_the_execution_it_enqueued(scene):
    """Whatever the order (the pipeline full, a drain, a pack beside a step,
    pack-only and step-only executions by turns, back to back, the host
    setting the pace): a pair is the TRUE one, and all but the scene's edges
    pair (the call that enqueues two, what is never collected)."""
    s, progs, spans = scene
    pairs = xprograms.dispatched(progs, spans, ENGINE)
    got = {h.stats["span_id"]: e.run_id for h, e, _ in pairs}
    assert got and all(s.truth[i] == run for i, run in got.items())
    fetched = xprograms.returned(xprograms.on_trace_clock(progs, spans))
    assert len(got) >= len(fetched) - 2
    assert len(set(got.values())) == len(got)


@pytest.mark.parametrize("span", sorted(ENGINE))
def test_the_causality_interval_holds_the_true_shift(scene, span):
    s, progs, spans = scene
    iv = scenes.skew(progs, span, ENGINE[span], spans=spans)
    if iv is None:   # a scene without this kind of dispatch
        assert not any(n == span for n, _, _, _ in spans)
        return
    assert iv[0] <= s.shift <= iv[1]
    # the edge taken is within a launch or a fetch of the truth
    assert xprograms.tight_edge(iv) == pytest.approx(s.shift, abs=1e-3)


def test_a_collect_returns_one_dispatchs_result_oldest_first(scene):
    s, progs, spans = scene
    hosts = xprograms.on_trace_clock(progs, spans)
    at = xprograms.returned(hosts)
    by_id = {h.stats["span_id"]: h for h in hosts}
    collects = sorted(h.end for h in hosts if h.name == xprograms.COLLECT)
    assert set(at) <= set(s.truth) and sorted(at.values()) == collects
    for i, t in at.items():   # a result fetched after its span closed, by a collect of its kind
        assert not by_id[i].stats.get("synced", True) and t > by_id[i].end
    for kind in ENGINE:
        ends = [at[h.stats["span_id"]] for h in hosts if h.name == kind and h.stats["span_id"] in at]
        assert ends == sorted(ends)
    # what the pairs carry: a fetch inside the span, its collect, or never
    for h, _, held in xprograms.dispatched(progs, spans, ENGINE):
        i = h.stats["span_id"]
        assert held == (h.end if h.stats.get("synced", True) else at.get(i, math.inf))


def test_mirrors_alone_pair_only_what_was_dispatched_back_to_back(scene):
    """Without the recorder's spans no collect is known: a span dispatched one
    ahead is left out, not paired by the old rule with the execution that
    happens to start while it is open."""
    s, progs, _ = scene
    pairs = xprograms.dispatched(progs, (), ENGINE)
    assert all(int(h.stats.get("ahead", 0)) == 0 for h, _, _ in pairs)
    assert all(s.truth[int(h.stats["span_id"])] == e.run_id for h, e, _ in pairs)


def test_a_record_that_lost_its_oldest_spans_pairs_nothing(scene):
    """Bookings are COUNTED against dispatches from the start of the record:
    one that begins in the middle has lost the count."""
    _, progs, spans = scene
    name, a, b, args = spans[0]
    short = [(name, a, b, dict(args, spans_dropped=5))] + list(spans[1:])
    assert xprograms.dispatched(progs, short, ENGINE) == []
    assert scenes.skew(progs, "decode_tick", ENGINE["decode_tick"], spans=short) is None


def test_a_span_whose_mirror_is_missing_is_placed_by_the_clocks_offset(scene):
    s, progs, spans = scene
    some = s.programs(unmirrored=set(range(1, 40)))[0]
    whole = scenes.skew(progs, "decode_tick", ENGINE["decode_tick"], spans=spans)
    assert scenes.skew(some, "decode_tick", ENGINE["decode_tick"], spans=spans) \
        == pytest.approx(whole, abs=1e-9)


def test_two_spans_that_claim_one_execution_are_left_out():
    """A pause of the host between an enqueue and the collect of the execution
    before it: by the time that collect returns BOTH have ended, and the last
    one ended is not the collected one.  Two spans then claim it; neither is
    paired."""
    runs = [Execution("jit_decode_impl", n, 10.0 + 0.01 * n, 10.009 + 0.01 * n) for n in range(4)]
    hosts = [HostEvent("decode_tick", 9.99 + 0.01 * n, 9.991 + 0.01 * n,
                       {"span_id": n, "ahead": 1}) for n in range(4)]
    at = {0: 10.0095, 1: 10.0295, 2: 10.0296, 3: 10.0395}   # span 1's collect came 10 ms late
    pairs = xprograms.pair(hosts, runs, 0.010, at)
    assert [(h.stats["span_id"], e.run_id) for h, e in pairs] == [(0, 0), (3, 3)]
    # a span never fetched, and one whose collect nothing had ended by, pair with nothing
    assert xprograms.pair(hosts[:1], runs, 0.010, {}) == []
    assert xprograms.pair(hosts[:1], runs, 0.010, {0: 9.995}) == []


def test_the_hosts_slack_is_the_time_inside_a_ticks_collects():
    from benchmark.readers import span_sum_percentile

    s = scenes.pack_and_step()
    _, spans = s.programs()
    t0, t1 = min(a for _, a, _, _ in spans), max(b for _, _, b, _ in spans) + 1.0
    obs = {"spans": spans, "window": (t0, t1)}
    ticks = [t for t in spans if t[0] == "sched.tick"]
    below = xprograms.descendants(spans)
    want = [sum(b - a for n, a, b, _ in below[t[3]["span_id"]] if n == "tick_collect")
            for t in ticks]
    assert sum(want) == pytest.approx(sum(s.waits)) and all(w > 0 for w in want)
    got = span_sum_percentile.read(obs, "sched.tick", "tick_collect", 50)
    assert got == pytest.approx(1e3 * sorted(want)[len(want) // 2], rel=0.2)
    assert 0 < got < 1e3 * (scenes.STEP + scenes.PACK)   # a wait is shorter than what it waits for
    # the device sets the pace: the slack is most of a tick; the host: next to nothing
    tick_ms = 1e3 * (ticks[-1][2] - ticks[-1][1])
    assert got > 0.8 * tick_ms
    bound = scenes.host_bound()
    _, spans = bound.programs()
    obs = {"spans": spans, "window": (t0, t1)}
    assert span_sum_percentile.read(obs, "sched.tick", "tick_collect", 50) < 0.1
    # a program that never waits apart from its dispatch: nothing to read
    _, spans = scenes.old_order().programs()
    assert span_sum_percentile.read({"spans": spans, "window": (t0, t1)},
                                    "sched.tick", "tick_collect", 50) is None


def test_a_stalled_gap_is_the_one_its_packs_execution_made_longer():
    """One ahead a ``prefill_pack`` span opens one call BEFORE the call that
    returns its execution's tokens; the booking of its result lies in that
    call.  Tokens are stamped where a call returns."""
    from benchmark.readers import stall_share

    s = scenes.Scene()
    step, pack = [("decode_tick", scenes.STEP, True)], ("prefill_pack", scenes.PACK, False)
    stamps, held = [], []
    plan = [step, step, [pack] + step, step, step, [pack] + step, step, step]
    s.call(plan[1], first=plan[0])
    collected = 0
    stamps.append(s.t)
    for ex in plan[2:] + [None, None]:
        s.call(ex)
        collected += 1
        stamps.append(s.t)
        if collected < len(plan) and len(plan[collected]) == 2:
            held.append(len(stamps) - 1)      # the gap that ENDS at this stamp
    _, spans = s.programs()
    stamps = [t + scenes.RECORDER for t in stamps]
    obs = {"spans": spans, "window": (stamps[0], stamps[-1] + 1.0),
           "requests": [{"token_times": stamps}]}
    gaps = [b - a for a, b in zip(stamps, stamps[1:])]
    long = [i + 1 for i, g in enumerate(gaps) if g > 0.015]
    assert long == held and len(held) == 2            # the gaps a pack made longer
    assert stall_share.read(obs, "engine.pack_emit") == pytest.approx(100 * 2 / len(gaps))
    # the dispatch span's start marks as many gaps, each one too early
    starts = sorted(a for n, a, _, _ in spans if n == "prefill_pack")
    marked = [next(i + 1 for i, (a, b) in enumerate(zip(stamps, stamps[1:])) if a <= t < b)
              for t in starts]
    assert marked == [i - 1 for i in held]
    assert stall_share.read(obs, "prefill_pack") == pytest.approx(100 * 2 / len(gaps))
    # back to back the two coincide
    old = scenes.old_order()
    _, spans = old.programs()
    ticks = [t[2] for t in spans if t[0] == "sched.tick"]
    obs = {"spans": spans, "window": (ticks[0], ticks[-1] + 1.0),
           "requests": [{"token_times": ticks}]}
    assert stall_share.read(obs, "engine.pack_emit") == stall_share.read(obs, "prefill_pack") == 100.0


def test_small_programs_are_counted_in_the_tick_they_start_in_one_ahead():
    """What ``tools/describe_idle.py`` prints of a capture dispatched one ahead
    (the metric that read the same count was retired in PR 52: 0 on every line)."""
    from benchmark.tools.describe_idle import ENGINE, started_inside

    s = scenes.Scene()
    step = [("decode_tick", scenes.STEP, True)]
    s.call(step, first=step)
    for k in range(6):
        if k % 2:
            s.aux("jit__threefry_split")
        s.call(step)
    progs, spans = s.programs()
    iv = scenes.skew(progs, "decode_tick", "^jit_decode_impl$", spans=spans)
    per_tick = [len(r) for r in started_inside(progs, "sched.tick", ENGINE, xprograms.tight_edge(iv))]
    assert sorted(per_tick)[len(per_tick) // 2] == 0 and max(per_tick) == 1 and sum(per_tick) == 3
    # with the record the parent's pairing saw (no collect known): no shift to cut at
    assert scenes.skew(progs, "decode_tick", "^jit_decode_impl$", spans=[]) is None
