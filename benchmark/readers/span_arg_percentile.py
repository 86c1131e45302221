"""Percentile of a number the program wrote on its spans (``decode_tick``'s
``dispatch_ms``: how long the jitted call took to return), over the spans of
that name that ended inside the window.  None where the recorder dropped
spans: the median would be of the window's end."""
from ..stats import percentile
from ..xprograms import spans_dropped


def read(obs, span, arg, q):
    spans = obs.get("spans") or ()
    if spans_dropped(spans):
        return None
    t0, t1 = obs["window"]
    return percentile([float(args[arg]) for name, a, b, args in spans
                       if name == span and t0 <= b < t1 and arg in args], q)
