"""Share (%) of ONE program's device time that a named XLA body takes: the self
time of the ops under ``scope`` (regex over the ``op_name`` path; an alternation
names several bodies) over the device time of the executions of ``module`` that
the trace holds WHOLE, both summed over those executions.  ``scope_share`` divides
by every op of the capture, whichever program ran it; this one says what part of
a step, or of a pack, a mechanism is.  The profiler's session begins and ends in
the middle of a program whose stamp is cut: an execution at either edge of the
capture is left out (``Programs.holds_whole``), as ``xprograms.dispatched`` leaves it out.  None where the program, the body or a
whole execution is missing."""
from .. import xprograms
from .scope_ops import per_execution


def read(obs, module, scope):
    secs = per_execution(obs, module, scope)
    if not secs:
        return None
    progs = xprograms.of(obs)
    whole = [(s, e.end - e.start) for s, e in zip(secs, progs.of_module(module, min(progs.ops)))
             if progs.holds_whole(e)]
    took = sum(t for _, t in whole)
    return 100.0 * sum(s for s, _ in whole) / took if took else None
