"""The chain of ONE execution, tied by identity: from the host handing a
program to the device's queue to the thread holding its tokens.

``xprograms`` pairs a dispatch span with its execution by TIMING and finds a
span's collect by COUNTING bookings; a collect that returns late is the case
both lose (PERF.md section 7, S12).  Every capture already holds what ties the
two sides without a guess, on the host plane and so on the clock of the
spans' mirrors (``obs["trace"].host`` keeps them with their stats):

- ``DoEnqueueProgram`` (stat ``run_id``): the host handed the program to the
  device's queue; the ``XLA Modules`` event of the same ``run_id``
  (``xprograms.Execution.run_id``) is its execution, which cannot start
  before it;
- ``tpu::System::Execute=>Done``: the runtime learned that a program ended,
  one an execution and in the device's order; no execution ends after its
  own BEGINS (the event runs the completion callbacks: on the chip a
  collect's ``ready`` falls inside it, 0.05-0.3 ms before its end);
- ``tpu::System::TransferFromDevice=>IssueEvent=>Done``: a result's copy
  landed on the host;
- the program's ``tick_collect`` names its dispatch span (``of``) and marks
  where the runtime called the result ready (``ready_ms``).

``Done`` events carry no ``run_id``: they are laid against the executions IN
ORDER, the one offset between the two lists being the smallest under which
no notice comes before its program's end by the ``run_id``-tied bound (a
capture begins in the middle of a program whose notice it holds).

Everything is in seconds on the trace's host clock; device 0 only, and only
where the capture holds one device plane (a ``Done`` names no chip).  Every
function returns None where the capture lacks these events (a CPU rehearsal,
another runtime), where the recorder dropped spans, or where no shift
satisfies every execution: it never raises.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import math
import re
from typing import Dict, List, Optional, Sequence, Tuple

from . import xprograms
from .readers import idle_by_phase
from .xprograms import COLLECT, SPAN_ID, Execution

ENQUEUE = "DoEnqueueProgram"
DONE = "tpu::System::Execute=>Done"
LANDED = "tpu::System::TransferFromDevice=>IssueEvent=>Done"
RUN_ID = "run_id"
# how far the two lists' offset is searched: a capture's edge cuts a program
# or two, not more
_OFFSETS = 4
# the instants of a link in the order they must come
ORDER = (("enqueued", "start"), ("start", "end"), ("end", "done"), ("done", "ready"),
         ("done", "landed"), ("ready", "returned"), ("landed", "returned"))


@dataclasses.dataclass
class Runtime:
    """What the runtime said of device 0's executions, host clock."""
    runs: List[Execution]          # by start, DEVICE clock (``shift`` not added)
    enqueued: Dict[int, float]     # run_id -> start of its DoEnqueueProgram
    done: Dict[int, float]         # run_id -> start of its Execute=>Done
    landed: List[float]            # ends of the transfers' =>Done, in order
    interval: Tuple[float, float]  # the shifts causality allows

    @property
    def shift(self) -> float:
        """What to add to a device stamp: the interval's UPPER edge, bound by
        the smallest completion notice of the capture (tens of us); the lower
        is loose by a program wherever the queue never ran dry."""
        return self.interval[1]

    @property
    def width(self) -> float:
        return self.interval[1] - self.interval[0]


@dataclasses.dataclass
class Link:
    """One dispatch span's execution from enqueue to fetch, host clock."""
    kind: str            # the dispatch span's name
    span_id: int
    run_id: int
    enqueued: float      # DoEnqueueProgram began
    start: float         # the execution, device stamps shifted
    end: float
    done: float          # the runtime learned it ended
    collect: float       # the tick_collect opened
    ready: float         # ... was told the result is defined
    landed: float        # the result's copy landed
    returned: float      # ... returned: the thread holds the tokens

    def disordered(self) -> List[str]:
        """The pairs of ``ORDER`` that do not hold, as ``a>b``."""
        return [f"{a}>{b}" for a, b in ORDER
                if getattr(self, a) > getattr(self, b) + 1e-9]


@dataclasses.dataclass
class Chain:
    runtime: Runtime
    links: List[Link]
    fetched: int                 # dispatch spans inside the capture that a collect names
    left_out: Dict[str, int]     # reason -> spans not chained

    @property
    def disordered(self) -> int:
        """Links with an order that does not hold: kept, counted."""
        return sum(1 for l in self.links if l.disordered())


def _notices(runs: Sequence[Execution], done: Sequence[float], lo: float,
             ) -> Optional[Dict[int, float]]:
    """run_id -> its ``Done``, the two lists laid in order under the smallest
    offset that puts no notice before its program's end (device end + ``lo``,
    the least the shift can be)."""
    for j in range(-_OFFSETS, _OFFSETS + 1):
        pairs = [(e, done[i + j]) for i, e in enumerate(runs) if 0 <= i + j < len(done)]
        if len(pairs) + _OFFSETS < min(len(runs), len(done)) or not pairs:
            continue
        if all(d - e.end >= lo - 1e-9 for e, d in pairs):
            return {e.run_id: d for e, d in pairs}
    return None


def runtime(host, progs) -> Optional[Runtime]:
    """``host``: the capture's host events (``xplane.Trace.host``); ``progs``:
    its ``xprograms.Programs``."""
    if progs is None or len(progs.executions) != 1:
        return None
    (device, runs), = progs.executions.items()
    enqueued: Dict[int, float] = {}
    done, landed = [], []
    for h in host:
        if h.name == ENQUEUE and RUN_ID in h.stats:
            if int(h.stats.get("device_ordinal", device)) == device:
                enqueued.setdefault(int(h.stats[RUN_ID]), h.start)
        elif h.name == DONE:
            done.append(h.start)
        elif h.name == LANDED:
            landed.append(h.end)
    tied = [e for e in runs if e.run_id in enqueued]
    if not tied or not done:
        return None
    done.sort()
    landed.sort()
    lo = max(enqueued[e.run_id] - e.start for e in tied)
    notices = _notices(runs, done, lo)
    if notices is None:
        return None
    hi = min(notices[e.run_id] - e.end for e in runs if e.run_id in notices)
    if hi < lo:
        return None
    return Runtime(list(runs), enqueued, notices, landed, (lo, hi))


def of(obs) -> Optional[Runtime]:
    """This run's ``Runtime``, read once."""
    if "_xruntime" not in obs:
        rt = None
        try:
            trace, progs = obs.get("trace"), xprograms.of(obs)
            if trace is not None:
                rt = runtime(trace.host, progs)
        except (RuntimeError, ValueError, KeyError):
            pass   # another run's capture, a stat of another form: nothing to read
        obs["_xruntime"] = rt
    return obs["_xruntime"]


def chained(rt: Runtime, progs, spans: Sequence[tuple]) -> Optional[Chain]:
    """Every dispatch span wholly inside the capture that a ``tick_collect``
    names (``of``), with ITS execution: the one whose ``DoEnqueueProgram``
    lies between the span's ``upload`` mark and the ``upload`` mark of the
    next dispatch span of the same program, of that program's module; exactly
    one, else the span is left out and counted."""
    if rt is None or xprograms.spans_dropped(spans):
        return None
    hosts = xprograms.on_trace_clock(progs, spans, {COLLECT, *idle_by_phase.DISPATCH})
    collects = {int(h.stats["of"]): h for h in hosts
                if h.name == COLLECT and "of" in h.stats}
    if not collects:
        return None
    w0, w1 = progs.window
    by_run = {e.run_id: e for e in rt.runs}
    links, left, fetched = [], collections.Counter(), 0
    for name, pattern in idle_by_phase.DISPATCH.items():
        rx = re.compile(pattern)
        mine = sorted((at, r) for r, at in rt.enqueued.items()
                      if r in by_run and rx.search(by_run[r].module))
        times = [at for at, _ in mine]
        same = [h for h in hosts if h.name == name]
        uploads = [idle_by_phase.mark_at(h, "upload_ms") for h in same] + [math.inf]
        for k, h in enumerate(same):
            c = collects.get(int(h.stats[SPAN_ID]))
            if c is None or h.start < w0 or c.end > w1:
                continue
            fetched += 1
            i, j = bisect.bisect_left(times, uploads[k]), bisect.bisect_left(times, uploads[k + 1])
            if j - i != 1:
                left["no enqueue" if j == i else "several enqueues"] += 1
                continue
            e = by_run[mine[i][1]]
            if e.run_id not in rt.done:
                left["no notice"] += 1
                continue
            links.append(Link(
                name, int(h.stats[SPAN_ID]), e.run_id, rt.enqueued[e.run_id],
                e.start + rt.shift, e.end + rt.shift, rt.done[e.run_id], c.start,
                idle_by_phase.mark_at(c, "ready_ms"), math.nan, c.end))
    links.sort(key=lambda l: l.start)
    # a transfer names no program: they land in the device's order, so a
    # link's is the first that landed after its execution ended and after
    # the transfer of the link before it
    kept, k = [], 0
    for l in links:
        k = max(k, bisect.bisect_left(rt.landed, l.end))
        if k == len(rt.landed):
            left["no transfer"] += 1
            continue
        l.landed = rt.landed[k]
        kept.append(l)
        k += 1
    return Chain(rt, kept, fetched, dict(left))


def chain(obs) -> Optional[Chain]:
    """This run's ``Chain``, computed once; None where there is none."""
    if "_xchain" not in obs:
        rt = of(obs)
        obs["_xchain"] = None if rt is None else chained(
            rt, xprograms.of(obs), obs.get("spans") or ())
    return obs["_xchain"]


def _waited(a: str, b: str):
    """The part of a link's [``a``, ``b``] in which the thread was waiting in
    its collect (a copy that landed before anybody asked cost nobody)."""
    return lambda l: max(getattr(l, b) - max(getattr(l, a), l.collect), 0.0)


# a fetch's tail from its execution's END, and the three links that add up
# to it (where the order holds): the runtime learned the program ended, the
# result's copy landed, the thread took it
LINKS = {"all": _waited("end", "returned"), "notice": _waited("end", "done"),
         "transfer": _waited("done", "landed"), "wake": _waited("landed", "returned")}
