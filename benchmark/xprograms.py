"""The part of a ``jax.profiler`` trace that ``xplane.Trace`` drops, and the
program's own span tree laid over it on one clock.

``xplane.reduce_trace`` keeps device ops by a renumbering-proof key and host
events by name.  Three things it drops say which PROGRAM ran when, and for
which of the program's spans:

- the ``XLA Modules`` line of a device plane: one event per program execution
  (``jit_decode_impl(<fingerprint>)``, stat ``run_id``) - the device time of a
  dispatch, which no host span can give (an un-fetched dispatch returns at
  once);
- an ``XLA Ops`` event's instruction name WITH its number (``fusion.12``,
  ``flash_fwd.1``): the key into ``telemetry.program_scopes()``, which maps a
  program's instructions to the ``jax.named_scope`` path they came from;
- the program's spans mirrored into the host plane (``Telemetry.span`` with
  ``jax_profiler`` on): ``TraceAnnotation``s that carry ``span_id``, so each
  recorder span (``time.perf_counter``) is paired with itself on the trace's
  clock.

Three clocks meet here: the recorder's (``obs["spans"]``), the trace's host
side, and the device's, which runs a few ms off the host's inside one trace.
``recorder_offset`` is the first difference, ``skew_interval`` bounds the
second by causality over the dispatch spans paired BY TIMING with the
executions they enqueued (``dispatched``: back to back the execution starts
inside its span; one ahead, since PR 43, it ends before the span's
``tick_collect`` returns).  Everything is in seconds; a device time is on the
device's clock until a shift is added: ``tools/describe_idle.py`` adds the
``tight_edge`` of that interval, every metric the shift of ``xruntime``, which
ties an execution to its enqueue by ``run_id`` and guesses nothing (PR 55;
``host_device_skew_ms.*`` since PR 57).

One process runs one cell and ``harness.Capture`` clears that cell's
directory before it records, so the newest ``.xplane.pb`` under
``harness.OUT_DIR`` is this run's; ``of(obs)`` still refuses a file whose
``bench.capture`` span is not ``obs["trace"].window``.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import math
import re
import statistics
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from . import harness, xplane

MODULES_LINE = "XLA Modules"
SPAN_ID = "span_id"
_INSTRUCTION = re.compile(r"^%?([\w.\-]+) = ")
_MODULE = re.compile(r"^(.*?)\((-?\d+)\)$")

Interval = Tuple[float, float]


@dataclasses.dataclass
class Execution:
    module: str     # ``jit_decode_impl``: the HloModule's name
    run_id: int
    start: float    # device clock
    end: float


@dataclasses.dataclass
class RawOp:
    name: str       # the instruction's own name, number kept
    start: float
    end: float
    self_s: float = 0.0


@dataclasses.dataclass
class Programs:
    window: Interval                       # the bench.capture span, host side
    executions: Dict[int, List[Execution]]  # device -> executions, by start
    ops: Dict[int, List[RawOp]]            # device -> ops, instruction names kept
    mirrors: Dict[int, xplane.HostEvent]   # span_id -> the span on the trace's clock

    def mirrored(self, name: str) -> List[xplane.HostEvent]:
        """The mirrored spans of one name that lie wholly inside the capture."""
        w0, w1 = self.window
        return sorted((h for h in self.mirrors.values()
                       if h.name == name and h.start >= w0 and h.end <= w1),
                      key=lambda h: h.start)

    def holds_whole(self, e: "Execution") -> bool:
        """Not at either edge of the capture: the profiler's session begins and
        ends in the middle of a program, whose stamp is cut."""
        w0, w1 = self.window
        return e.start >= w0 + COLLECT_SLACK_S and e.end <= w1 - COLLECT_SLACK_S

    def of_module(self, pattern: str, device: Optional[int] = None) -> List[Execution]:
        rx = re.compile(pattern)
        if device is None:
            device = min(self.executions, default=None)
        return [e for e in self.executions.get(device, ()) if rx.search(e.module)]


def instruction_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``; a name that is
    no HLO text is kept."""
    m = _INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name.lstrip("%")


def reduce(profile) -> Optional[Programs]:
    """``ProfileData`` -> ``Programs``; None without a capture annotation or
    without a device plane that lists program executions (a CPU rehearsal)."""
    executions: Dict[int, List[Execution]] = {}
    raw: Dict[int, List[RawOp]] = {}
    host: List[xplane.HostEvent] = []
    for plane in profile.planes:
        m = xplane.DEVICE_PLANE.match(plane.name)
        if m:
            d = int(m.group(1))
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    for e in line.events:
                        mod = _MODULE.match(e.name)
                        a = e.start_ns * 1e-9
                        executions.setdefault(d, []).append(Execution(
                            mod.group(1) if mod else e.name,
                            int(dict(e.stats).get("run_id", -1)),
                            a, a + e.duration_ns * 1e-9))
                elif line.name == xplane.OPS_LINE:
                    for e in line.events:
                        a = e.start_ns * 1e-9
                        raw.setdefault(d, []).append(RawOp(
                            instruction_name(e.name), a, a + e.duration_ns * 1e-9))
        elif plane.name == xplane.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name == xplane.CAPTURE or SPAN_ID in dict(e.stats):
                        a = e.start_ns * 1e-9
                        host.append(xplane.HostEvent(
                            e.name, a, a + e.duration_ns * 1e-9, dict(e.stats)))
    cap = [h for h in host if h.name == xplane.CAPTURE]
    if not cap or not executions:
        return None
    # every execution and op the profiler session recorded is kept: the
    # session starts just before the capture's span, and the device's stamps
    # may run a millisecond ahead of the host's, so clipping device times to
    # the host-side window would drop the first execution
    w0, w1 = cap[0].start, cap[-1].end
    for runs in executions.values():
        runs.sort(key=lambda e: e.start)
    for ops in raw.values():
        xplane._self_times(ops)  # same nesting rule as the reduction's own ops
    mirrors = {int(h.stats[SPAN_ID]): h for h in host if SPAN_ID in h.stats}
    return Programs((w0, w1), executions, raw, mirrors)


def of(obs) -> Optional[Programs]:
    """This run's ``Programs``, read once; None in a run with no trace.
    Raises where the newest trace file is not the one ``obs["trace"]`` was
    reduced from."""
    if "_xprograms" in obs:
        return obs["_xprograms"]
    progs = None
    if obs.get("trace") is not None:
        files = sorted(harness.OUT_DIR.glob("**/*.xplane.pb"),
                       key=lambda p: p.stat().st_mtime)
        if not files:
            raise RuntimeError(f"the run has a trace and {harness.OUT_DIR} no .xplane.pb")
        progs = reduce(xplane.load(str(files[-1])))
        if progs is not None and progs.window != tuple(obs["trace"].window):
            raise RuntimeError(
                f"{files[-1]} is another capture: its window {progs.window} "
                f"is not the run's {tuple(obs['trace'].window)}")
    obs["_xprograms"] = progs
    return progs


# ---------------------------------------------------------------------------
# the program's span tree (obs["spans"]: (name, t0, t1, args), recorder clock)
# ---------------------------------------------------------------------------
def spans_dropped(spans: Sequence[tuple]) -> int:
    """How many spans the recorder's ring let go before these (the oldest one
    kept says so, ``TraceRecorder.chrome_events``).  Medians over the whole
    window and the span tree are then biased towards the window's end and
    short of children: a reader of either returns None."""
    return max((int(s[3].get("spans_dropped", 0)) for s in spans), default=0)


def self_times(spans: Sequence[tuple]) -> Dict[int, float]:
    """span_id -> duration minus what its direct children cover of it (a
    child that outlives its parent, as a shed episode does its tick, takes
    only the part inside)."""
    ends = {s[3][SPAN_ID]: (s[1], s[2]) for s in spans if SPAN_ID in s[3]}
    out = {i: b - a for i, (a, b) in ends.items()}
    for _, a, b, args in spans:
        parent = args.get("parent_id")
        if parent in out:
            pa, pb = ends[parent]
            out[parent] -= max(min(b, pb) - max(a, pa), 0.0)
    return out


def descendants(spans: Sequence[tuple]) -> Dict[int, List[tuple]]:
    """span_id -> every span below it, any depth."""
    by_id = {s[3][SPAN_ID]: s for s in spans if SPAN_ID in s[3]}
    out: Dict[int, List[tuple]] = {}
    for s in by_id.values():
        p = s[3].get("parent_id")
        while p in by_id:
            out.setdefault(p, []).append(s)
            p = by_id[p][3].get("parent_id")
    return out


def recorder_offset(progs: Programs, spans: Sequence[tuple]) -> Optional[float]:
    """Trace clock minus recorder clock: the median of (annotation start -
    span start) over the spans both sides hold."""
    diffs = [progs.mirrors[s[3][SPAN_ID]].start - s[1] for s in spans
             if s[3].get(SPAN_ID) in progs.mirrors]
    return statistics.median(diffs) if diffs else None


# ---------------------------------------------------------------------------
# device clock against host clock
# ---------------------------------------------------------------------------
COLLECT = "tick_collect"
# dispatch span -> the span that BOOKS its result.  Every dispatch is followed
# by exactly one booking, the oldest dispatch first (``ServeScheduler._collect``;
# back to back it follows at once), whether or not the result was fetched: a
# pack that completes no prompt never is
BOOKED_BY = {"prefill_pack": "engine.pack_emit", "decode_tick": "engine.decode_emit",
             "spec_tick": "engine.decode_emit", "decode_burst": "engine.decode_emit"}
# a collect returns a transfer (a ms or so) after its program ended and the
# clocks differ by -2.0 to +1.2 ms (PERF.md section 3); a program is longer
COLLECT_SLACK_S = 0.003


def tight_edge(iv: Interval) -> float:
    """The edge of a causality interval to take as THE shift: the one nearer
    to zero.  The two clocks of one machine differ by a ms or two; an edge is
    loose by what lies between cause and effect.  Back to back a program
    starts a launch after its dispatch (the lower edge is tight) and its fetch
    returns a transfer after its end; one ahead it starts a whole step after
    its dispatch, when the one before it ends (the lower edge is off by a
    program's length), while its collect returns a transfer after its end (the
    upper edge is tight) - unless the host sets the pace, and then the lower
    edge is tight again.  Either way the loose edge is the far one, and the
    edge taken is off by a launch or a transfer at most (0.3-2 ms)."""
    lo, hi = iv
    return lo if abs(lo) <= abs(hi) else hi


def on_trace_clock(progs: Programs, spans: Sequence[tuple],
                   names: Optional[Iterable[str]] = None) -> List[xplane.HostEvent]:
    """The recorder's spans (those of ``names``) as host events on the trace's
    clock, by their mirrors (a span the session did not see: by
    ``recorder_offset``), start order, outer first; ``stats`` are the
    recorder's arguments (marks, ``ahead``, ``synced``, ``what``)."""
    off = recorder_offset(progs, spans)
    out = []
    for name, a, b, args in spans:
        if names is not None and name not in names:
            continue
        m = progs.mirrors.get(args.get(SPAN_ID))
        if m is not None:
            a, b = m.start, m.end
        elif off is not None:
            a, b = a + off, b + off
        else:
            continue
        out.append(xplane.HostEvent(name, a, b, args))
    return sorted(out, key=lambda h: (h.start, -h.end))


def returned(hosts: Iterable[xplane.HostEvent]) -> Dict[int, float]:
    """span_id of each dispatch span whose result was fetched AFTER it closed
    -> the end of the ``tick_collect`` that fetched it, on the clock of
    ``hosts`` (every span of the run, start order).  A dispatch and its
    collect are matched through the span that BOOKS the result
    (``BOOKED_BY``): bookings come one a dispatch and oldest first, collects
    do not (only a pack that completes a prompt is fetched)."""
    queued: Dict[str, collections.deque] = {
        booking: collections.deque() for booking in BOOKED_BY.values()}
    fetched: Dict[str, float] = {}
    out: Dict[int, float] = {}
    for h in hosts:
        if h.name in BOOKED_BY:
            queued[BOOKED_BY[h.name]].append(h)
        elif h.name == COLLECT:
            fetched[BOOKED_BY.get(h.stats.get("what"))] = h.end
        elif h.name in queued and queued[h.name]:
            d = queued[h.name].popleft()
            if h.name in fetched:
                out[int(d.stats[SPAN_ID])] = fetched.pop(h.name)
    return out


def pair(hosts: Sequence[xplane.HostEvent], runs: Sequence[Execution], slack_s: float,
         returned_at: Optional[Dict[int, float]] = None,
         ) -> List[Tuple[xplane.HostEvent, Execution]]:
    """Each dispatch span with THE execution it enqueued (``runs``: one
    program's, by start; the clocks disagree by a few ms).  A span dispatched
    back to back (``ahead`` 0 or absent): the execution that starts inside it,
    give or take ``slack_s`` (dispatches of one program are a tick apart).  A
    span dispatched one ahead: the last execution that ended by the time its
    collect returned (``returned_at``, give or take ``COLLECT_SLACK_S``) and
    started after it opened; the one before it is still running while it is
    dispatched and starts, by the clocks, about when it opens.  A span with no
    candidate or several, one never fetched, and two spans that claim one
    execution are left out, not guessed."""
    starts = [e.start for e in runs]
    ends = [e.end for e in runs]
    found = []
    for h in hosts:
        if int(h.stats.get("ahead", 0)):
            at = (returned_at or {}).get(int(h.stats[SPAN_ID]), math.inf)
            j = bisect.bisect_right(ends, at + min(slack_s, COLLECT_SLACK_S)) - 1
            if at < math.inf and j >= 0 and runs[j].start >= h.start - slack_s:
                found.append((h, j))
        else:
            i = bisect.bisect_left(starts, h.start - slack_s)
            j = bisect.bisect_right(starts, h.end + slack_s)
            if j - i == 1:
                found.append((h, i))
    claims = collections.Counter(j for _, j in found)
    return [(h, runs[j]) for h, j in found if claims[j] == 1]


def skew_interval(pairs: Iterable[Tuple[float, float, float, float]]) -> Optional[Interval]:
    """``pairs`` of (span open, fetch returned, device start, device end): a
    program cannot start before the span that dispatches it opened, nor end
    after the fetch of its result returned, so the shift to ADD to device
    times lies in [max(open - start), min(returned - end)].  None where no
    shift satisfies every pair (or there is none)."""
    lo, hi = float("-inf"), float("inf")
    for opened, returned_at, start, end in pairs:
        lo, hi = max(lo, opened - start), min(hi, returned_at - end)
    return (lo, hi) if lo <= hi and lo > float("-inf") else None


def dispatched(progs: Programs, spans: Sequence[tuple], names: Dict[str, str],
               slack_s: float = 0.010,
               ) -> List[Tuple[xplane.HostEvent, Execution, float]]:
    """(dispatch span, the execution it enqueued, when the host held its
    result) for every span of ``names`` (span name -> its program's module
    regex) that lies inside the capture and pairs (``pair``), on the trace's
    clock: the span's own end where it fetched inside (synced), its collect's
    end, ``inf`` for a result never fetched (a pack that completes no prompt).
    With the recorder's ``spans`` a span carries their arguments and
    its collect is found (``returned``); without them (a program that mirrors
    its spans and keeps no record) the mirrors alone pair back to back.  An
    execution at either edge of the capture is left out: the profiler's
    session begins and ends in the middle of a program, whose stamp is cut."""
    w0, w1 = progs.window
    if spans_dropped(spans):
        return []   # bookings are counted from the record's start: the count is lost
    if spans:
        hosts = on_trace_clock(progs, spans, {COLLECT, *names, *BOOKED_BY, *BOOKED_BY.values()})
        at = returned(hosts)
    else:
        hosts, at = sorted(progs.mirrors.values(), key=lambda h: h.start), {}
    out = []
    for name, module in names.items():
        inside = [h for h in hosts if h.name == name and h.start >= w0 and h.end <= w1]
        for h, e in pair(inside, progs.of_module(module), slack_s, at):
            if not progs.holds_whole(e):
                continue
            synced = h.stats.get("synced", True)
            out.append((h, e, h.end if synced else at.get(int(h.stats[SPAN_ID]), math.inf)))
    return out


# ---------------------------------------------------------------------------
# scopes
# ---------------------------------------------------------------------------
def classify(op_name: Optional[str], classes: Sequence[Sequence[str]], default: str) -> str:
    """The first class whose regex finds ``op_name``; ``default`` for none and
    for an op with no recorded scope."""
    if op_name:
        for name, pattern in classes:
            if re.search(pattern, op_name):
                return name
    return default


def classified_ops(progs: Programs, scopes: Dict[str, Dict[str, str]],
                   classes: Sequence[Sequence[str]], default: str):
    """Every device op as (device, module, op, op_name, class).  An op belongs
    to the execution it starts in; its instruction name is looked up in that
    module's scopes."""
    for d, ops in progs.ops.items():
        runs = progs.executions.get(d, [])
        starts = [e.start for e in runs]
        for o in ops:
            i = bisect.bisect_right(starts, o.start) - 1
            module = runs[i].module if i >= 0 and o.start < runs[i].end else None
            op_name = scopes.get(module, {}).get(o.name)
            yield d, module, o, op_name, classify(op_name, classes, default)


def class_seconds(progs: Programs, scopes: Dict[str, Dict[str, str]],
                  classes: Sequence[Sequence[str]], default: str) -> Dict[str, float]:
    """Self time of device ops by scope class, averaged over devices."""
    out: Dict[str, float] = {}
    for _, _, o, _, cls in classified_ops(progs, scopes, classes, default):
        out[cls] = out.get(cls, 0.0) + o.self_s
    n = max(len(progs.ops), 1)
    return {k: v / n for k, v in out.items()}
