"""Percentile of the harness's own span around ``scheduler.tick()`` (ms), over
the ticks that ended inside the window.  Every tick ends on the decode step's
host fetch, so it is host-synced whatever it dispatched: where a prefill
chunk's own span is not (a chunk that does not finish a prompt fetches
nothing), the tick is what holds its time."""
from ..stats import percentile


def read(obs, q):
    t0, t1 = obs["window"]
    return percentile([1e3 * (t[1] - t[0]) for t in obs.get("ticks", ())
                       if t0 <= t[1] < t1], q)
