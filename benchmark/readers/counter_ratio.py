"""Ratio of two program counters' increases over the window, times ``scale``."""


def read(obs, num, den, scale=1.0):
    c = obs.get("counters") or {}
    if not c.get(den):
        return None
    return scale * c[num] / c[den]
