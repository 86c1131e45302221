"""Percentile, over the ``outer`` spans that ended inside the window and hold
an ``inner`` span at any depth, of the time (ms) their ``inner`` spans took
together.  With ``sched.tick`` and ``tick_collect`` it is the host's SLACK a
tick: how long the scheduler's thread waited for the programs it had enqueued
one ahead (a pack's collect and a step's), with nothing left to do.  While it
is most of the tick the device sets the pace; where it nears 0 the host does
again.  None where the recorder dropped spans (a tick may have lost its
collects), and in a program that never waits apart from its dispatch."""
from ..stats import percentile
from ..xprograms import SPAN_ID, descendants, spans_dropped


def read(obs, outer, inner, q):
    spans = obs.get("spans") or ()
    if spans_dropped(spans):
        return None
    below = descendants(spans)
    t0, t1 = obs["window"]
    sums = []
    for name, a, b, args in spans:
        if name == outer and t0 <= b < t1:
            waits = [e - s for n, s, e, _ in below.get(args.get(SPAN_ID), ()) if n == inner]
            if waits:
                sums.append(1e3 * sum(waits))
    return percentile(sums, q)
