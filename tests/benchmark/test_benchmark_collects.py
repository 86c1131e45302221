"""The chain of one execution (``xruntime``) and its two readers (PR 55): on a
small trace recorded on the chip (``tools/record_programs_trace.py``), whose
runtime events tie every enqueue to its execution by ``run_id``, and on
hand-made scenes (``one_ahead_scenes.Scene`` with the runtime's events laid
beside the spans) in which ONE collect returns 100 ms late while the next
execution runs - the case ``xprograms.pair`` leaves out."""
import statistics
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import one_ahead_scenes as scenes  # noqa: E402
from benchmark import harness, xplane, xprograms, xruntime  # noqa: E402
from benchmark.readers import fetch_tail, host_device_skew, idle_by_phase, late_collects  # noqa: E402
from benchmark.xplane import HostEvent  # noqa: E402

DATA = harness.HERE / "testdata"
NOTICE, COPY, WAKE = 0.0003, 0.00025, 0.00005
LATE = 0.100
LINKS = ("notice", "transfer", "wake")


# -- the recorded capture ------------------------------------------------------
@pytest.fixture(scope="module")
def recorded():
    profile = xplane.load(str(DATA / "small_programs_tpu_v5e.xplane.pb"))
    return xplane.reduce_trace(profile), xprograms.reduce(profile)


def test_every_run_id_of_the_recorded_capture_has_its_enqueue(recorded):
    trace, progs = recorded
    rt = xruntime.runtime(trace.host, progs)
    runs = progs.executions[0]
    assert len(runs) >= 5 and [e.run_id for e in runs] == sorted(e.run_id for e in runs)
    # ... one enqueue a run_id, each paired with ITS ``XLA Modules`` event
    assert set(rt.enqueued) == {e.run_id for e in runs}
    assert set(rt.done) == set(rt.enqueued)
    for e in runs:
        on_host = e.start + rt.shift, e.end + rt.shift
        assert rt.enqueued[e.run_id] <= on_host[0] < on_host[1] <= rt.done[e.run_id]
    # serve_step and train_step by turns, as the tool ran them
    assert {e.module for e in runs} == {"jit_serve_step", "jit_train_step"}


def test_the_recorded_captures_shift_is_a_third_of_a_ms_wide(recorded):
    trace, progs = recorded
    rt = xruntime.runtime(trace.host, progs)
    lo, hi = rt.interval
    assert lo == pytest.approx(1.47e-3, abs=2e-5) and hi == pytest.approx(1.74e-3, abs=2e-5)
    assert 0.0 < rt.width < 1e-3 and rt.shift == hi
    # the device idles between that file's runs: the old pairing's interval,
    # bound by the span's open and its fetch, holds the new one
    old = scenes.skew(progs, "decode_tick", r"^jit_serve_step$", slack_s=0.003)
    assert old[0] <= lo and hi <= old[1]


def test_the_recorded_capture_has_no_collect_to_chain(recorded):
    """Its program fetched inside its dispatch span: no ``tick_collect``."""
    trace, progs = recorded
    rt = xruntime.runtime(trace.host, progs)
    assert xruntime.chained(rt, progs, ()) is None


# -- hand-made: the runtime's events beside a scene's spans --------------------
class RuntimeScene(scenes.Scene):
    """``Scene`` whose device also tells the host what the TPU runtime does:
    a ``DoEnqueueProgram`` with the execution's ``run_id`` when it is
    dispatched, an ``Execute=>Done`` ``NOTICE`` after it ended, for a fetched
    one a transfer's ``=>Done`` ``COPY`` later; a collect returns ``WAKE``
    after that, marks ``ready`` at the notice and names its dispatch span.
    ``late``: {run_id: (link, seconds)} makes ONE link of one fetch late."""

    def __init__(self, late=None, **kw):
        super().__init__(**kw)
        self.late = dict(late or {})
        self.host, self.ends, self.landed = [], {}, 0.0

    def _lateness(self, run_id, link):
        made, seconds = self.late.get(run_id, (None, 0.0))
        return seconds if made == link else 0.0

    def _run(self, kind, seconds, dispatched_at):
        run_id = super()._run(kind, seconds, dispatched_at)
        # (one thread tells of every program, in order: a late notice holds
        # up the next)
        done = max(self.free + NOTICE + self._lateness(run_id, "notice"),
                   max(self.ends.values(), default=0.0) + 4e-5)
        self.ends[run_id] = done
        self.host += [
            HostEvent(xruntime.ENQUEUE, dispatched_at, dispatched_at + 4e-5,
                      {"run_id": str(run_id), "device_ordinal": "0"}),
            HostEvent(xruntime.DONE, done, done + 1e-4, {"core_id": "0"})]
        return run_id

    def collect(self):
        for kind, i, _end, fetched in self.inflight.pop(0):
            self._begin(scenes.PARENT[kind])
            if fetched:
                r = self.truth[i]
                landed = max(self.ends[r] + COPY + self._lateness(r, "transfer"),
                             self.landed + 4e-5)   # (transfers land in order too)
                self.landed = landed
                self.host.append(HostEvent(xruntime.LANDED, landed - 2e-5, landed, {"size": "16"}))
                opened = self.t
                ready = max(opened + 1e-5, self.ends[r] + 1e-5)
                back = max(ready + 1e-5, landed + WAKE + self._lateness(r, "wake"))
                self._leaf("tick_collect", back - opened, what=kind, of=i,
                           ready_ms=1e3 * (ready - opened))
                self.waits.append(back - opened)
            self._leaf(scenes.BOOKING[kind], scenes.EMIT)
            self._end()

    def obs(self):
        progs, spans = self.programs()
        trace = xplane.Trace(progs.window, {}, sorted(self.host, key=lambda h: h.start))
        return {"spans": spans, "trace": trace, "_xprograms": progs,
                "window": (self.t0 + scenes.RECORDER, self.t + scenes.RECORDER)}


def steady(late=None, calls=10):
    s = RuntimeScene(late)
    step = [("decode_tick", scenes.STEP, True)]
    s.call(step, first=step)
    for _ in range(calls):
        s.call(step)
    return s


def pack_and_step(late=None, calls=9):
    """A pack and a step an execution; every third pack completes a prompt."""
    s = RuntimeScene(late)
    ex = lambda k: [("prefill_pack", scenes.PACK, k % 3 == 0),  # noqa: E731
                    ("decode_tick", scenes.STEP, True)]
    s.call(ex(1), first=ex(0))
    for k in range(calls):
        s.call(ex(k + 2))
    return s


def mixed_only(late=None, calls=9):
    """Every tick ONE program, a pack that carries the step's rows (PR 54, PR 56):
    no ``decode_tick`` span and no ``jit_decode_impl`` anywhere in the capture."""
    s = RuntimeScene(late)
    ex = [("prefill_pack", scenes.PACK, True)]
    s.call(ex, first=ex)
    for _ in range(calls):
        s.call(ex)
    return s


@pytest.mark.parametrize("make", [steady, pack_and_step, mixed_only], ids=lambda f: f.__name__)
def test_the_skew_is_the_chains_shift_whatever_programs_a_tick_is_made_of(make):
    """``host_device_skew_ms.*`` reads the shift tied by ``run_id``: the scene's
    true one, over by less than its smallest completion notice.  The pairing by
    timing it read until PR 57 found nothing to pair where no tick is a
    ``decode_tick`` (docs since PR 54, cell 8 since PR 56)."""
    s = make()
    obs = s.obs()
    got = host_device_skew.read(obs)
    assert 1e3 * s.shift <= got <= 1e3 * (s.shift + NOTICE) + 1e-6
    assert got == pytest.approx(1e3 * xruntime.of(obs).shift)
    old = scenes.skew(obs["_xprograms"], "decode_tick", "^jit_decode_impl$", spans=obs["spans"])
    assert (old is None) == (make is mixed_only)
    if old is not None:   # ... and where it paired, its interval held the chain's shift
        assert old[0] - 1e-9 <= 1e-3 * got <= old[1] + 1e-9


def test_a_scene_with_nothing_late_chains_every_fetched_span_in_order():
    for make in (steady, pack_and_step):
        s = make()
        found = xruntime.chain(s.obs())
        assert found.left_out == {} and found.disordered == 0
        assert found.fetched == len(found.links) == len(s.waits)
        assert {l.span_id: l.run_id for l in found.links} == {
            i: s.truth[i] for i in (l.span_id for l in found.links)}
        # the shift's upper edge is off by the smallest notice, its lower by
        # what an execution waits in the queue
        assert found.runtime.shift == pytest.approx(s.shift + NOTICE, abs=1e-6)
        assert found.runtime.interval[0] <= s.shift + 1e-9
        for l in found.links:
            assert l.done - l.end == pytest.approx(0.0, abs=1e-6)  # (the notice is in the shift)
            assert l.landed - l.done == pytest.approx(COPY, abs=1e-6)
            assert l.returned - l.landed == pytest.approx(WAKE, abs=2e-5)


@pytest.mark.parametrize("link", LINKS)
def test_a_collect_100_ms_late_keeps_both_pairs_and_names_its_link(link):
    victim = 5   # the sixth step's fetch is late; the seventh runs meanwhile
    s = steady({victim: (link, LATE)})
    obs = s.obs()
    progs, spans = obs["_xprograms"], obs["spans"]
    late_span = next(i for i, r in s.truth.items() if r == victim)
    after = next(i for i, r in s.truth.items() if r == victim + 1)
    # the timing pairing loses the pair that holds the answer ...
    old = {int(h.stats["span_id"]) for h, _, _ in xprograms.dispatched(
        progs, spans, idle_by_phase.DISPATCH)}
    assert late_span not in old or after not in old
    # ... the chain keeps both, each with ITS execution
    found = xruntime.chain(obs)
    by_span = {l.span_id: l for l in found.links}
    assert by_span[late_span].run_id == victim and by_span[after].run_id == victim + 1
    assert found.left_out == {} and len(found.links) == len(s.waits)
    # the execution after it ended while the late collect was still out
    assert by_span[after].end < by_span[late_span].returned
    assert fetch_tail.read(obs, "all", 100) == pytest.approx(1e3 * LATE, abs=1.0)
    assert fetch_tail.read(obs, "all", 50) < 2.0
    for other in LINKS:
        worst = fetch_tail.read(obs, other, 100)
        if other == link:
            assert worst == pytest.approx(1e3 * LATE, abs=1.0)
        else:
            assert worst < 1.0
    # a late notice is a late ``ready``; a late copy or wake-up comes after it
    l = by_span[late_span]
    assert (l.ready - l.end > 0.9 * LATE) == (link == "notice")
    assert found.disordered == 0


@pytest.mark.parametrize("link", LINKS)
def test_the_window_lost_a_late_collects_excess_less_one_tick(link):
    s = steady({5: (link, LATE)}, calls=14)
    obs = s.obs()
    tick = 1e3 * statistics.median(b - a for n, a, b, _ in obs["spans"] if n == "sched.tick")
    assert 9.0 < tick < 12.0
    lost = late_collects.read(obs, "lost_ms")
    assert lost == pytest.approx(1e3 * LATE - tick, abs=0.5)
    # ... all of it before ``ready`` where the notice was late, none where
    # the copy or the wake-up was
    unready = late_collects.read(obs, "unready_ms")
    assert unready == pytest.approx(lost if link == "notice" else 0.0, abs=0.5)
    (span, over, _), = late_collects.late(obs)
    assert span[3]["of"] == next(i for i, r in s.truth.items() if r == 5)
    # the same window without it: nothing is late, and that reads 0.0
    calm = steady(calls=14).obs()
    assert late_collects.read(calm, "lost_ms") == 0.0
    assert late_collects.read(calm, "unready_ms") == 0.0


def test_a_collect_behind_a_long_execution_is_not_late():
    """One step of 60 ms among steps of 10: its collect outlasts the usual
    one by five ticks, but the device was busy and the tick after waits for
    its own step as long as any: nothing was lost."""
    s = RuntimeScene()
    step = lambda ms: [("decode_tick", 1e-3 * ms, True)]  # noqa: E731
    s.call(step(10), first=step(10))
    for k in range(14):
        s.call(step(60 if k == 6 else 10))
    obs = s.obs()
    longest = max(b - a for n, a, b, _ in obs["spans"] if n == "tick_collect")
    assert longest > 0.055
    assert late_collects.read(obs, "lost_ms") == 0.0 and late_collects.late(obs) == []
    # ... and the chain's tail of that execution is as short as any other's
    assert fetch_tail.read(obs, "all", 100) < 1.0


def test_a_pack_that_is_never_fetched_is_not_a_link():
    s = pack_and_step({6: ("transfer", LATE)})
    found = xruntime.chain(s.obs())
    kinds = [l.kind for l in found.links]
    assert kinds.count("decode_tick") > kinds.count("prefill_pack") > 0
    assert len(found.links) == found.fetched == len(s.waits)
    assert fetch_tail.read(s.obs(), "transfer", 100) == pytest.approx(1e3 * LATE, abs=1.0)


# -- nothing to read is None, never an error -----------------------------------
@pytest.mark.parametrize("missing", [(xruntime.ENQUEUE,), (xruntime.DONE,),
                                     (xruntime.ENQUEUE, xruntime.DONE, xruntime.LANDED)])
def test_a_capture_without_the_runtimes_events_reads_none(missing):
    obs = steady().obs()
    obs["trace"] = xplane.Trace(obs["trace"].window, {},
                                [h for h in obs["trace"].host if h.name not in missing])
    assert xruntime.of(obs) is None and xruntime.chain(obs) is None
    assert fetch_tail.read(obs, "all", 100) is None
    # the whole-window reader needs no capture
    assert late_collects.read(obs, "lost_ms") == 0.0


def test_a_capture_without_transfers_chains_nothing_and_counts_it():
    obs = steady().obs()
    obs["trace"] = xplane.Trace(obs["trace"].window, {},
                                [h for h in obs["trace"].host if h.name != xruntime.LANDED])
    found = xruntime.chain(obs)
    assert found.links == [] and found.left_out == {"no transfer": found.fetched}
    assert fetch_tail.read(obs, "all", 100) is None


def test_no_capture_a_cpu_rehearsal_and_a_parents_spans_read_none():
    obs = steady({5: ("wake", LATE)}, calls=14).obs()
    untraced = {"spans": obs["spans"], "window": obs["window"], "trace": None}
    assert xruntime.chain(untraced) is None and fetch_tail.read(untraced, "all", 100) is None
    assert late_collects.read(untraced, "lost_ms") > 80.0
    # a parent's program: collects that name no dispatch and carry no mark
    old = [(n, a, b, {k: v for k, v in args.items() if k not in ("of", "ready_ms")})
           for n, a, b, args in obs["spans"]]
    parent = {k: v for k, v in dict(obs, spans=old).items() if k != "_xchain"}
    assert xruntime.chain(parent) is None and fetch_tail.read(parent, "all", 100) is None
    assert late_collects.read(parent, "lost_ms") > 80.0
    assert late_collects.read(parent, "unready_ms") is None
    # no spans at all
    assert late_collects.read({"spans": [], "window": (0.0, 1.0)}, "lost_ms") is None


@pytest.mark.parametrize("reader", ["fetch_tail", "lost_ms", "unready_ms"])
def test_spans_the_recorder_dropped_from_read_none(reader):
    obs = steady({5: ("notice", LATE)}).obs()
    name, a, b, args = obs["spans"][0]
    obs["spans"] = [(name, a, b, dict(args, spans_dropped=2))] + obs["spans"][1:]
    if reader == "fetch_tail":
        assert xruntime.chain(obs) is None and fetch_tail.read(obs, "all", 100) is None
    else:
        assert late_collects.read(obs, reader) is None


def test_under_three_executions_chained_reads_none():
    obs = steady(calls=1).obs()
    assert 0 < len(xruntime.chain(obs).links) < 3
    assert fetch_tail.read(obs, "all", 100) is None


def test_two_device_planes_are_refused():
    """A ``Done`` names no chip: with several the order is nobody's."""
    obs = steady().obs()
    progs = obs["_xprograms"]
    progs.executions[1] = list(progs.executions[0])
    assert xruntime.of(obs) is None and fetch_tail.read(obs, "all", 100) is None


def test_an_order_that_does_not_hold_is_counted_not_repaired():
    s = steady()
    obs = s.obs()
    # every transfer's notice 2 ms early: a link's falls before its program's end
    # and the next one's after its collect returned
    obs["trace"] = xplane.Trace(obs["trace"].window, {}, [
        HostEvent(h.name, h.start - 0.002, h.end - 0.002, h.stats)
        if h.name == xruntime.LANDED else h for h in obs["trace"].host])
    found = xruntime.chain(obs)
    assert found.links and found.disordered == sum(1 for l in found.links if l.disordered())
    assert found.disordered > 0
    assert {pair for l in found.links for pair in l.disordered()} == {"landed>returned"}


# -- the manifest's entries ------------------------------------------------------
NEW = ["late_collect_lost_ms.chat", "late_collect_lost_ms.serve",
       "fetch_tail_max_ms.chat", "fetch_tail_max_ms.serve"]


@pytest.mark.parametrize("name", NEW)
def test_the_new_entries_are_found_by_name_and_list_the_serving_cells(name):
    """Wherever in ``per_layer`` the entry stands (entries a later cell brings stand
    behind it): the ``.serve`` two list EVERY serving cell, by the kind of its
    configuration's driver, so a new serving cell joins both; the ``.chat`` two the
    chat cell."""
    man = harness.manifest()
    entry, = [m for m in man["per_layer"] if m["name"] == name]
    serving = [w["name"] for w in man["workloads"]
               if "train" not in harness.config_of(man, w["config"])["driver"]]
    chat = ["mistral7b_chat_rate"]
    assert entry["workloads"] == (chat if name.endswith(".chat")
                                  else [c for c in serving if c not in chat])
    assert (entry["moves"] == "itl_p95_ms") == name.endswith(".chat")
    spec = harness.load_json(harness.HERE / "metrics" / f"{name}.json")
    source = {"late_collects": "program_span", "fetch_tail": "device_trace"}
    assert entry["source"] == source[spec["reader"]] and entry["unit"] == spec["unit"] == "ms"
    # the reader takes the file's parameters and finds nothing in an empty run
    assert harness.module("readers", spec["reader"]).read(
        {"spans": [], "window": (0.0, 1.0), "trace": None}, **spec["params"]) is None
