"""Shared by the readers of a NAMED XLA BODY (``jax.named_scope`` inside the
program; not a Pallas kernel, so no instruction carries its name): the device
ops of one program's executions whose scope path matches.  None where the
program tracks no such program (``telemetry.program_scopes`` of a program
older than the scopes) or the trace holds none of its executions."""
import bisect
import re

from .. import xprograms


def per_execution(obs, module, scope):
    """Self seconds of the ops under ``scope`` (regex over the ``op_name``
    path), one number per execution of ``module`` (regex over HloModule names)
    that lies in the trace; None where nothing can be read."""
    progs = xprograms.of(obs)
    if progs is None or not progs.ops:
        return None
    from deepspeed_tpu import telemetry

    scopes_of = getattr(telemetry, "program_scopes", None)
    if scopes_of is None:
        return None
    if "_scopes" not in obs:
        obs["_scopes"] = scopes_of()
    d = min(progs.ops)
    runs = progs.of_module(module, d)
    if not runs:
        return None
    mod_rx, rx = re.compile(module), re.compile(scope)
    names = {instr for mod, table in obs["_scopes"].items() if mod_rx.search(mod)
             for instr, op_name in table.items() if rx.search(op_name)}
    if not names:
        return None
    starts = [e.start for e in runs]
    secs = [0.0] * len(runs)
    for o in progs.ops[d]:
        i = bisect.bisect_right(starts, o.start) - 1
        if i >= 0 and o.start < runs[i].end and o.name in names:
            secs[i] += o.self_s
    return secs
