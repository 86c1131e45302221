"""What to add to the trace's device times to put them on its host clock
(ms): the tight edge (``xprograms.tight_edge``) of the interval causality
allows over every ``span`` in the capture paired with the execution of
``module`` it dispatched.  None where the interval is empty or nothing pairs:
the instrument's own check."""
from .. import xprograms


def read(obs, span, module):
    progs = xprograms.of(obs)
    if progs is None:
        return None
    iv = xprograms.skew(progs, span, module, spans=obs.get("spans") or ())
    return None if iv is None else 1e3 * xprograms.tight_edge(iv)
