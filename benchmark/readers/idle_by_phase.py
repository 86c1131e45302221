"""Share (%) of the capture in which device 0 ran no op, charged INSTANT BY
INSTANT to what the scheduler's thread was doing then, in the program's own
terms.  The idle time is ``device_idle_share``'s (the window less the union of
the ``XLA Ops`` intervals), so the phases add up to it.

The program's spans are laid on the trace's clock by their mirrors (a span the
session did not see: by ``xprograms.recorder_offset``), a mark as ``start +
<mark>_ms``.  Device times are shifted by the lower edge of the causality
interval (``xprograms.skew_interval``) over every dispatch span paired with
the execution it dispatched, the lower bound tightened from "the span opened"
to "its ``upload`` mark": a program cannot start before its enqueue began.

- ``in_program``: between two ops of ONE execution (the device's own bubbles;
  cut on the device's clock, so the shift does not touch it);
- ``launch``: from a dispatch span's ``dispatch`` mark (the jitted call has
  returned) to the start of ITS execution, whatever span is open by then;
- otherwise the innermost span open: a dispatch span before ``upload_ms`` is
  ``upload``, from there to ``dispatch_ms`` ``enqueue``, after it
  ``fetch_tail`` (its execution has ended, the host is not back from the
  fetch); a build span before ``rows_ms`` is ``build_rows``, after it
  ``build_rng``; ``emit``; ``sched`` (the scheduler's spans' self time);
  ``outside`` where none is open (the driver's loop, the load generator).

``launch`` and ``fetch_tail`` trade against each other by exactly the shift:
the interval's width (``seconds()["width"]``, which ``tools/describe_idle.py``
prints) is their error bar.  None
where the recorder dropped spans, where no dispatch span carries an
``upload_ms`` (a program without the marks), or where no shift satisfies
every pair."""
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
SUMS = {"bookkeeping": ("emit", "sched")}
PAIR_SLACK_S = 0.010  # as ``xprograms.skew``


def on_trace_clock(progs, spans):
    """The spans of ``PHASES_OF`` that touch the capture, as host events on the
    trace's clock, start order, outer first; ``stats`` are the recorder's
    arguments (marks included)."""
    off = xprograms.recorder_offset(progs, spans)
    w0, w1 = progs.window
    out = []
    for name, a, b, args in spans:
        if name not in PHASES_OF:
            continue
        m = progs.mirrors.get(args.get(xprograms.SPAN_ID))
        if m is not None:
            a, b = m.start, m.end
        elif off is not None:
            a, b = a + off, b + off
        else:
            continue
        if b > w0 and a < w1:
            out.append(xplane.HostEvent(name, a, b, args))
    return sorted(out, key=lambda h: (h.start, -h.end))


def mark_at(h, arg):
    """A mark of ``h`` on the trace's clock; the span's end where it has none."""
    ms = h.stats.get(arg)
    return h.end if ms is None else min(h.start + 1e-3 * ms, h.end)


def dispatch_pairs(progs, hosts):
    """Each dispatch span with THE execution of its program that it dispatched
    (``xprograms.pair``); a span with none or several is left out."""
    out = []
    for name, module in DISPATCH.items():
        out += xprograms.pair([h for h in hosts if h.name == name],
                              progs.of_module(module), PAIR_SLACK_S)
    return out


def causality(pairs, mark=None):
    """The shifts causality allows (``xprograms.skew_interval``): an execution
    starts after its span opened, or after the span's ``mark`` where one is
    named, and ends before a fetch returned (a span closed unsynced fetched
    nothing and bounds no end)."""
    return xprograms.skew_interval(
        (mark_at(h, f"{mark}_ms") if mark else h.start,
         h.end if h.stats.get("synced", True) else math.inf, e.start, e.end)
        for h, e in pairs)


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
    pairs = dispatch_pairs(progs, hosts)
    iv = causality(pairs, "upload")
    if iv is None:
        return None
    shift, device = iv[0], min(progs.ops)
    out = dict.fromkeys(PHASES, 0.0)
    runs = [(e.start, e.end) for e in progs.executions.get(device, ())]
    inside, rest = cut(idle_intervals(progs, device), xplane.merge(runs))
    out["in_program"] = sum(b - a for a, b, _ in inside)
    rest = [(a + shift, b + shift) for a, b in rest]
    launches = [(mark_at(h, "dispatch_ms"), e.start + shift) for h, e in pairs]
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


def read(obs, phase):
    secs = of(obs)
    if secs is None:
        return None
    w0, w1 = xprograms.of(obs).window
    return 100.0 * sum(secs[p] for p in SUMS.get(phase, (phase,))) / (w1 - w0)
