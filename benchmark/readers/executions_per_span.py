"""Percentile, over the mirrored ``span``s wholly inside the capture, of how
many executions on device 0's ``XLA Modules`` line started inside one whose
module does NOT match ``excluding``: the small programs a tick runs beside
the engine's own (the rng's split, a tuple's unstacking), each of which costs
a launch and cuts an idle gap in two.  Device times are shifted onto the host
clock by ``xprograms.skew`` over ``dispatch`` spans and the ``module`` they
run (its tight edge); None where that interval is empty."""
import bisect
import re

from .. import xprograms
from ..stats import percentile


def inside(progs, span, excluding, shift):
    """For each mirrored ``span`` in the capture, the executions that started
    inside it and are not ``excluding``'s."""
    rx = re.compile(excluding)
    aux = [e for e in progs.of_module("") if not rx.search(e.module)]
    starts = [e.start + shift for e in aux]
    return [aux[bisect.bisect_left(starts, h.start):bisect.bisect_left(starts, h.end)]
            for h in progs.mirrored(span)]


def read(obs, span, excluding, dispatch, module, q):
    progs = xprograms.of(obs)
    if progs is None:
        return None
    iv = xprograms.skew(progs, dispatch, module, spans=obs.get("spans") or ())
    if iv is None:
        return None
    shift = xprograms.tight_edge(iv)
    return percentile([len(runs) for runs in inside(progs, span, excluding, shift)], q)
