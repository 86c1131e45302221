"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` and nothing else.  Every time is in
seconds on the trace's own clock, which host threads and device planes share.

- a device plane is one named ``/device:TPU:<n>``; its ``XLA Ops`` line holds
  one event per executed HLO op (nested: a ``while`` covers its body's ops);
- the host plane ``/host:CPU`` holds one line per thread; ``TraceAnnotation``s
  of the benchmark (``bench.*``) and of the program (``decode_tick``,
  ``prefill_pack``, ...) and the runtime's own TraceMes are events there.

``reduce_trace`` clips everything to the span of the ``bench.capture``
annotation, the window the benchmark traced on purpose.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
CAPTURE = "bench.capture"
NO_HOST = "_no_host_annotation_"

Interval = Tuple[float, float]


@dataclasses.dataclass
class Op:
    name: str      # stable key: op kind + result shapes (see ``op_key``)
    start: float
    end: float
    self_s: float = 0.0  # duration minus the part its child ops cover


@dataclasses.dataclass
class HostEvent:
    name: str
    start: float
    end: float
    stats: dict


@dataclasses.dataclass
class Trace:
    window: Interval                 # the bench.capture span
    devices: Dict[int, List[Op]]     # device ordinal -> ops inside the window
    host: List[HostEvent]            # host events overlapping the window

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices seen."""
        if not self.devices:
            return 0.0
        return sum(union_len(merge([(o.start, o.end) for o in ops]))
                   for ops in self.devices.values()) / len(self.devices)

    def idle_share(self) -> Optional[float]:
        return None if self.window_s <= 0 else 1.0 - self.busy_s() / self.window_s

    def op_seconds(self) -> Dict[str, float]:
        """Self time by op key, averaged over devices."""
        out: Dict[str, float] = {}
        for ops in self.devices.values():
            for o in ops:
                out[o.name] = out.get(o.name, 0.0) + o.self_s
        n = max(len(self.devices), 1)
        return {k: v / n for k, v in out.items()}

    def kernel_events(self, pattern: str) -> Dict[int, List[Op]]:
        """Ops whose key matches ``pattern`` (regex, ``re.search``)."""
        rx = re.compile(pattern)
        return {d: [o for o in ops if rx.search(o.name)]
                for d, ops in self.devices.items()}

    def kernel_seconds(self, pattern: str) -> Tuple[float, int]:
        """(seconds, events) of the matching ops, averaged over devices.
        Seconds are self times, so an event nested in a matching one is not
        counted twice."""
        ev = self.kernel_events(pattern)
        n = max(len(ev), 1)
        return (sum(o.self_s for ops in ev.values() for o in ops) / n,
                round(sum(len(ops) for ops in ev.values()) / n))

    def idle_gaps(self) -> Dict[str, float]:
        """Idle seconds of device 0's timeline by what the host was doing
        when the gap began: the innermost host annotation open at that
        moment (``NO_HOST`` when none was)."""
        if not self.devices:
            return {}
        ops = self.devices[min(self.devices)]
        busy = merge([(o.start, o.end) for o in ops])
        gaps: List[Interval] = []
        cur = self.window[0]
        for a, b in busy:
            if a > cur:
                gaps.append((cur, a))
            cur = max(cur, b)
        if self.window[1] > cur:
            gaps.append((cur, self.window[1]))
        # sweep: gaps and host events both in start order, so the events open
        # at a gap's start are found without scanning every event per gap
        events = sorted((h for h in self.host if h.name != CAPTURE),
                        key=lambda h: h.start)
        out: Dict[str, float] = {}
        active: List[HostEvent] = []
        i = 0
        for a, b in gaps:
            while i < len(events) and events[i].start <= a:
                active.append(events[i])
                i += 1
            active = [h for h in active if h.end > a]
            name = min(active, key=lambda h: h.end - h.start).name if active else NO_HOST
            out[name] = out.get(name, 0.0) + (b - a)
        return out

    def host_spans(self, name: str) -> List[HostEvent]:
        return [h for h in self.host if h.name == name]

    def whole_spans(self, name: str, stat: str) -> List[int]:
        """The ``stat`` value (an index the benchmark gave its annotation) of
        every span of that name that lies wholly inside the window."""
        return sorted({int(h.stats[stat]) for h in self.host_spans(name)
                       if stat in h.stats and h.start >= self.window[0]
                       and h.end <= self.window[1]})


def merge(iv: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def union_len(iv: Sequence[Interval]) -> float:
    return sum(b - a for a, b in iv)


_LAYOUT = re.compile(r"\{[^{}]*\}")
_COMMENT = re.compile(r"/\*.*?\*/")
_HEAD = re.compile(r"^%?([\w\-.]+?)(?:\.\d+)? = ")
_OPCODE = re.compile(r"^ ([a-z][\w\-]*)\(")


def op_key(name: str) -> str:
    """A key that survives renumbering.  On the TPU an ``XLA Ops`` event is
    named by its HLO text, ``%fusion.12 = bf16[64,14336]{1,0:T(8,128)}
    fusion(...)``: the key is the opcode, the instruction's base name where
    it says more than the opcode (a Pallas kernel is a ``custom-call`` named
    after the jitted function around it), and the result shapes without
    layouts - ``fusion bf16[64,14336]``, ``custom-call packed_ctx_impl
    (f32[256,32,128],f32[256,32],f32[256,32])``.  Kernels are told apart by
    their shapes.  A name that is no HLO text is kept, minus its number."""
    flat = _COMMENT.sub("", _LAYOUT.sub("", _LAYOUT.sub("", name)))
    head = _HEAD.match(flat)
    if not head:
        return re.sub(r"\.\d+$", "", name.lstrip("%"))[:120]
    rest = flat[head.end():]
    if rest.startswith("("):  # a tuple of results, possibly nested
        depth = end = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        end += 1
    else:
        end = rest.find(" ") if " " in rest else len(rest)
    op = _OPCODE.match(rest[end:])
    if not op:
        return re.sub(r"\.\d+$", "", name.lstrip("%"))[:120]
    base, shapes, opcode = head.group(1), rest[:end].replace(" ", ""), op.group(1)
    if len(shapes) > 96:
        shapes = shapes[:93] + "..."
    return f"{opcode} {shapes}" if base == opcode else f"{opcode} {base} {shapes}"


def _self_times(ops: List[Op]) -> None:
    """Fill ``self_s``: events on one line nest properly, so a stack walk in
    start order charges each child's duration to its direct parent."""
    ops.sort(key=lambda o: (o.start, -(o.end - o.start)))
    stack: List[Op] = []
    for o in ops:
        o.self_s = o.end - o.start
        while stack and stack[-1].end <= o.start:
            stack.pop()
        if stack:
            stack[-1].self_s -= o.end - o.start
        stack.append(o)
    for o in ops:
        o.self_s = max(o.self_s, 0.0)


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def reduce_trace(profile) -> Optional[Trace]:
    """``ProfileData`` -> ``Trace``; None when the capture annotation is
    missing (nothing to read is not an error here: the harness then leaves
    the trace metrics out)."""
    host: List[HostEvent] = []
    raw_dev: Dict[int, List[Op]] = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops = raw_dev.setdefault(int(m.group(1)), [])
                for e in line.events:
                    a = e.start_ns * 1e-9
                    ops.append(Op(op_key(e.name), a, a + e.duration_ns * 1e-9))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns <= 0:
                        continue
                    a = e.start_ns * 1e-9
                    host.append(HostEvent(e.name, a, a + e.duration_ns * 1e-9,
                                          dict(e.stats)))
    cap = [h for h in host if h.name == CAPTURE]
    if not cap:
        return None
    w0, w1 = cap[0].start, cap[-1].end
    devices: Dict[int, List[Op]] = {}
    for d, ops in raw_dev.items():
        _self_times(ops)
        kept = []
        for o in ops:
            if o.end <= w0 or o.start >= w1:
                continue
            # clip to the window; self time shrinks in proportion
            a, b = max(o.start, w0), min(o.end, w1)
            frac = (b - a) / (o.end - o.start) if o.end > o.start else 0.0
            kept.append(Op(o.name, a, b, o.self_s * frac))
        if kept:
            devices[d] = kept
    host = [h for h in host if h.end > w0 and h.start < w1]
    return Trace((w0, w1), devices, host)


def breakdown(trace: Trace, top: int = 10) -> dict:
    ops = sorted(trace.op_seconds().items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(trace.idle_gaps().items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


def describe(profile, max_events: int = 12) -> str:
    """Human-readable dump of planes, lines and a few events with their
    stats: what a builder looks at before writing a reader against a trace."""
    rows = []
    for plane in profile.planes:
        lines = list(plane.lines)
        rows.append(f"PLANE {plane.name} ({len(lines)} lines)")
        for line in lines:
            evs = list(line.events)
            rows.append(f"  LINE {line.name} ({len(evs)} events)")
            for e in evs[:max_events]:
                stats = {k: (str(v)[:160]) for k, v in dict(e.stats).items()}
                rows.append(f"    {e.name} start={e.start_ns} dur={e.duration_ns} {stats}")
    return "\n".join(rows)
