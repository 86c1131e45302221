"""Device 0's idle time of the capture charged INSTANT BY INSTANT to what the
scheduler's thread was doing then, in the program's own terms.  A helper, not
a reader (no ``read``): ``tools/describe_idle.py`` prints it (PR 39's
instrument; its classes were manifest metrics until PR 44, when one-ahead
dispatch had left them nothing to cut).  The idle time is
``device_idle_share``'s (the window less the union of the ``XLA Ops``
intervals), so the phases add up to it.

The program's spans are laid on the trace's clock by their mirrors
(``xprograms.on_trace_clock``), a mark as ``start + <mark>_ms``.  Device times
are shifted by the tight edge of the causality interval (``xprograms.tight_edge``)
over every dispatch span paired with the execution it dispatched
(``xprograms.dispatched``), the lower bound tightened from "the span opened"
to "its ``upload`` mark": a program cannot start before its enqueue began.

- ``in_program``: between two ops of ONE execution (the device's own bubbles;
  cut on the device's clock, so the shift does not touch it);
- ``launch``: from a dispatch span's ``dispatch`` mark (the jitted call has
  returned) to the start of ITS execution, whatever span is open by then (one
  ahead that is the rest of the execution before it, so what falls here is the
  device's own gap between two programs);
- otherwise the innermost span open: a dispatch span before ``upload_ms`` is
  ``upload``, from there to ``dispatch_ms`` ``enqueue``, after it
  ``fetch_tail`` (its execution has ended, the host is not back from the
  fetch), as is a ``tick_collect`` (the wait for a program enqueued one
  ahead); a build span before ``rows_ms`` is ``build_rows``, after it
  ``build_rng``; ``emit``; ``sched`` (the scheduler's spans' self time);
  ``outside`` where none is open (the driver's loop, the load generator).

``launch`` and ``fetch_tail`` trade against each other by exactly the shift:
the interval's width (``seconds()["width"]``, which the tool prints) is their
error bar.  None where the recorder dropped spans, where no dispatch span
carries an ``upload_ms`` (a program without the marks), or where no shift
satisfies every pair."""
import math

from .. import xplane, xprograms

_TICK = (("upload_ms", "upload"), ("dispatch_ms", "enqueue"), (None, "fetch_tail"))
_BUILD = (("rows_ms", "build_rows"), (None, "build_rng"))
_EMIT, _SCHED = ((None, "emit"),), ((None, "sched"),)
# span -> its phases in order: (the mark that ends the phase, the phase)
PHASES_OF = {
    "decode_tick": _TICK, "prefill_pack": _TICK, "spec_tick": _TICK, "decode_burst": _TICK,
    "engine.decode_build": _BUILD, "engine.pack_build": _BUILD,
    "engine.decode_emit": _EMIT, "engine.pack_emit": _EMIT,
    xprograms.COLLECT: _TICK[-1:],
    "sched.tick": _SCHED, "sched.expire": _SCHED, "sched.admit": _SCHED,
    "sched.prefill": _SCHED, "sched.decode": _SCHED,
}
# dispatch span -> the program it dispatches
DISPATCH = {
    "decode_tick": r"^jit_decode_impl$", "prefill_pack": r"^jit_packed(_ctx)?_impl$",
    "spec_tick": r"^jit_spec_impl$", "decode_burst": r"^jit_decode_burst_impl$",
}
PHASES = ("in_program", "launch", "fetch_tail", "upload", "enqueue", "build_rows",
          "build_rng", "emit", "sched", "outside")


def on_trace_clock(progs, spans):
    """The spans of ``PHASES_OF`` that touch the capture, as host events on the
    trace's clock (``xprograms.on_trace_clock``)."""
    w0, w1 = progs.window
    return [h for h in xprograms.on_trace_clock(progs, spans, PHASES_OF)
            if h.end > w0 and h.start < w1]


def mark_at(h, arg):
    """A mark of ``h`` on the trace's clock; the span's end where it has none."""
    ms = h.stats.get(arg)
    return h.end if ms is None else min(h.start + 1e-3 * ms, h.end)


def causality(pairs, mark=None):
    """The shifts causality allows (``xprograms.skew_interval``) over ``pairs``
    of ``xprograms.dispatched``: an execution starts after its span opened, or
    after the span's ``mark`` where one is named, and ends before the host held
    its result (a result never fetched bounds no end)."""
    return xprograms.skew_interval(
        (mark_at(h, f"{mark}_ms") if mark else h.start, at, e.start, e.end)
        for h, e, at in pairs)


def host_phases(hosts):
    """The host's timeline as disjoint (a, b, phase), in order: each instant
    belongs to the innermost span open (``hosts`` as ``on_trace_clock`` gives
    them), cut by that span's marks."""
    out = []

    def emit(h, a, b):
        for arg, phase in PHASES_OF[h.name]:
            upto = b if arg is None else min(max(mark_at(h, arg), a), b)
            if upto > a:
                out.append((a, upto, phase))
                a = upto

    stack, cur = [], -math.inf
    for h in list(hosts) + [None]:
        start = math.inf if h is None else h.start
        while stack and stack[-1].end <= start:
            top = stack.pop()
            emit(top, cur, top.end)
            cur = max(cur, top.end)
        if h is None:
            break
        if stack:
            emit(stack[-1], cur, start)
        cur = max(cur, start)
        stack.append(h)
    return out


def cut(pieces, cover):
    """``pieces`` against ``cover`` (both sorted, disjoint; a cover may carry
    more than its two ends): (the parts inside as (a, b, index of the cover),
    the parts outside as (a, b))."""
    inside, outside, j = [], [], 0
    for a, b in pieces:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            lo, hi = max(cover[k][0], a), min(cover[k][1], b)
            if lo > a:
                outside.append((a, lo))
            inside.append((lo, hi, k))
            a = hi
            k += 1
        if b > a:
            outside.append((a, b))
    return inside, outside


def idle_intervals(progs, device):
    """Where ``device`` ran no op inside the capture: ``xplane.Trace``'s
    clipping and union, so the total is ``device_idle_share``'s."""
    w0, w1 = progs.window
    busy = xplane.merge([(max(o.start, w0), min(o.end, w1))
                         for o in progs.ops.get(device, ()) if o.end > w0 and o.start < w1])
    return cut([(w0, w1)], busy)[1]


def seconds(progs, spans):
    """phase -> idle seconds of device 0 in the capture, or None (see above);
    beside them ``shift`` and ``width``, the causality interval's lower edge
    and width in seconds."""
    if progs is None or not progs.ops or xprograms.spans_dropped(spans):
        return None
    hosts = on_trace_clock(progs, spans)
    if not any("upload_ms" in h.stats for h in hosts if h.name in DISPATCH):
        return None
    pairs = xprograms.dispatched(progs, spans, DISPATCH)
    iv = causality(pairs, "upload")
    if iv is None:
        return None
    shift, device = xprograms.tight_edge(iv), min(progs.ops)
    out = dict.fromkeys(PHASES, 0.0)
    runs = [(e.start, e.end) for e in progs.executions.get(device, ())]
    inside, rest = cut(idle_intervals(progs, device), xplane.merge(runs))
    out["in_program"] = sum(b - a for a, b, _ in inside)
    rest = [(a + shift, b + shift) for a, b in rest]
    launches = [(mark_at(h, "dispatch_ms"), e.start + shift) for h, e, _ in pairs]
    inside, rest = cut(rest, xplane.merge([(a, b) for a, b in launches if b > a]))
    out["launch"] = sum(b - a for a, b, _ in inside)
    phases = host_phases(hosts)
    inside, rest = cut(rest, phases)
    for a, b, k in inside:
        out[phases[k][2]] += b - a
    out["outside"] = sum(b - a for a, b in rest)
    return dict(out, shift=shift, width=iv[1] - iv[0])


def of(obs):
    """This run's ``seconds``, computed once."""
    if "_idle_by_phase" not in obs:
        obs["_idle_by_phase"] = seconds(xprograms.of(obs), obs.get("spans") or ())
    return obs["_idle_by_phase"]
