"""Device time (ms) of one call of a named kernel: the median duration of
the device ops whose INSTRUCTION name matches (a Pallas kernel's ``name=``),
summed over ``kernels`` (a backward pass that is two kernels).  None unless
every kernel was seen."""
import re

from .. import xprograms
from ..stats import percentile


def read(obs, kernels, q=50):
    progs = xprograms.of(obs)
    if progs is None or not progs.ops:
        return None
    ops = progs.ops[min(progs.ops)]
    total = 0.0
    for pattern in kernels:
        rx = re.compile(pattern)
        one = percentile([1e3 * (o.end - o.start) for o in ops if rx.search(o.name)], q)
        if one is None:
            return None
        total += one
    return total
