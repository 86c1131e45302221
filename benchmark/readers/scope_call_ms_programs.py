"""Device time (ms) a named XLA body takes in ONE execution of EACH of several
programs, summed: ``scope_call_ms`` a program of ``modules`` (that reader takes
one program: two programs' instruction names collide and their executions
interleave).  For a body that a tick runs in both of its programs, a pack's and
a step's; a program with nothing to read adds nothing, None where none has."""
from . import scope_call_ms


def read(obs, modules, scope, q=50):
    parts = [scope_call_ms.read(obs, module, scope, q) for module in modules]
    parts = [p for p in parts if p is not None]
    return sum(parts) if parts else None
