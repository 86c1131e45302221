"""Percentile and token-gap arithmetic on raw host stamps."""
from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence, Tuple


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """``q``-th percentile (0..100) by linear interpolation between the two
    nearest ranks of the sorted sample; None on an empty sample."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def token_gaps_ms(token_times: Sequence[float], window: Tuple[float, float]) -> List[float]:
    """Gaps between consecutive output tokens of ONE request, in ms.  The
    first token has no gap (it is the time to first token).  A gap counts when
    its LATER stamp falls inside ``window`` = [t0, t1)."""
    t0, t1 = window
    return [1e3 * (b - a) for a, b in zip(token_times, token_times[1:])
            if t0 <= b < t1]


def all_gaps_ms(requests: Iterable[dict], window: Tuple[float, float]) -> List[float]:
    """Every request's in-window gaps.  A request that reached a terminal
    state other than ``finished`` inside the window (or was refused there) is
    a miss: it adds one gap as long as the window, so it lands in the tail
    instead of vanishing."""
    t0, t1 = window
    out: List[float] = []
    for r in requests:
        out += token_gaps_ms(r["token_times"], window)
        if r["state"] != "finished" and r.get("end") is not None \
                and t0 <= r["end"] < t1:
            out.append(1e3 * (t1 - t0))
    return out
