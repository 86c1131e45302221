"""What to add to the trace's device times to put them on its host clock
(ms): the lower edge of the interval causality allows over every mirrored
``span`` in the capture paired with the execution of ``module`` it
dispatched (``xprograms.skew_interval``).  None where the interval is empty."""
from .. import xprograms


def read(obs, span, module):
    progs = xprograms.of(obs)
    if progs is None:
        return None
    iv = xprograms.skew(progs, span, module)
    return None if iv is None else 1e3 * iv[0]
