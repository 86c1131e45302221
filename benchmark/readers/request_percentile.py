"""Percentile over requests of one per-request time, in ms, for requests whose
clock starts inside the window.

- ``ttft``: first token minus when the request was DUE (by the schedule for a
  session's first turn, by the think time for a later one) - not minus submit.
- ``gen_lag``: submit minus due - how late the load generator ran.
- ``queue_wait``: the scheduler's admission minus submit.
"""
from ..stats import percentile

QUANTITY = {
    "ttft": lambda r: r["token_times"][0] - r["due"] if r["token_times"] else None,
    "gen_lag": lambda r: r["submit"] - r["due"],
    "queue_wait": lambda r: r["admit"] - r["submit"] if r.get("admit") else None,
}


def read(obs, quantity, q):
    if "requests" not in obs:
        return None
    t0, t1 = obs["window"]
    f = QUANTITY[quantity]
    xs = [f(r) for r in obs["requests"] if t0 <= r["due"] < t1]
    return percentile([1e3 * x for x in xs if x is not None], q)
