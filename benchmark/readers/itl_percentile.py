"""Percentile of the gaps between consecutive output tokens, first token
excluded, over every gap whose later stamp falls inside the window (requests
in flight from the ramp included; a failed request adds a window-long gap)."""
from ..stats import all_gaps_ms, percentile


def read(obs, q):
    if "requests" not in obs:
        return None
    return percentile(all_gaps_ms(obs["requests"], obs["window"]), q)
