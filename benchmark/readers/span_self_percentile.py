"""Percentile of the SELF time (ms) of one of the program's spans: its
duration minus the durations of its direct child spans (``sched.tick`` minus
expire / admit / prefill / decode is the scheduler's own bookkeeping), over
the spans that ended inside the window.  None where the recorder dropped
spans: a parent would keep time that belonged to children it lost."""
from ..stats import percentile
from ..xprograms import SPAN_ID, self_times, spans_dropped


def read(obs, span, q):
    spans = obs.get("spans") or ()
    if spans_dropped(spans):
        return None
    own = self_times(spans)
    t0, t1 = obs["window"]
    return percentile([1e3 * own[args[SPAN_ID]] for name, a, b, args in spans
                       if name == span and t0 <= b < t1 and SPAN_ID in args], q)
