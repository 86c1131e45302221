"""Percentile of the durations (ms) of one of the program's host-synced spans
(``decode_tick``, ``prefill_pack``) that ended inside the window."""
from ..stats import percentile


def read(obs, span, q):
    t0, t1 = obs["window"]
    return percentile([1e3 * (b - a) for name, a, b, _ in obs.get("spans", ())
                       if name == span and t0 <= b < t1], q)
