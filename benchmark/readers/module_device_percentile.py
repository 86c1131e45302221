"""Percentile of the device time (ms) of ONE execution of a program, from
the trace's ``XLA Modules`` line: what a dispatch took on the device whether
or not the host waited for it.  ``module`` is a regex over HloModule names."""
from .. import xprograms
from ..stats import percentile


def read(obs, module, q):
    progs = xprograms.of(obs)
    if progs is None:
        return None
    return percentile([1e3 * (e.end - e.start) for e in progs.of_module(module)], q)
