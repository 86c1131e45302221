"""Percentile of the host's share (ms) of one scheduler tick: the mirrored
``tick`` span minus the time the device ran a program inside it, device times
shifted onto the host clock (``xprograms.skew``).  Over the ticks wholly
inside the capture whose span tree holds a span named in ``holding`` and none
named in ``lacking`` (a decode step and no prefill pack).  ``what="span"``
reads the same ticks' whole spans instead, the number the gap and the
device time must add up to.  None where the recorder dropped spans: a tick
that lost its children could no longer be told from one that had none."""
from .. import xprograms
from ..stats import percentile


def read(obs, tick, holding, lacking, module, q, what="gap"):
    progs = xprograms.of(obs)
    spans = obs.get("spans") or ()
    if progs is None or xprograms.spans_dropped(spans):
        return None
    iv = xprograms.skew(progs, holding[0], module)
    if iv is None:
        return None
    runs = progs.of_module("")
    below = xprograms.descendants(spans)
    gaps = []
    for h in progs.mirrored(tick):
        names = {s[0] for s in below.get(int(h.stats[xprograms.SPAN_ID]), ())}
        if names & set(holding) and not names & set(lacking):
            busy = xprograms.busy_inside(runs, h.start, h.end, iv[0]) if what == "gap" else 0.0
            gaps.append(1e3 * (h.end - h.start - busy))
    return percentile(gaps, q)
