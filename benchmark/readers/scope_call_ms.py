"""Device time (ms) a named XLA body takes in ONE execution of a program:
percentile ``q`` over the program's executions in the trace of the summed self
time of the ops under ``scope`` (all layers' calls of the body together)."""
from ..stats import percentile
from .scope_ops import per_execution


def read(obs, module, scope, q=50):
    secs = per_execution(obs, module, scope)
    return None if not secs else percentile([1e3 * s for s in secs], q)
