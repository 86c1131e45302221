"""Share (%) of the device's busy time spent in one class of scopes: self
time of the device ops whose ``jax.named_scope`` path (``op_name``, through
``telemetry.program_scopes()``) falls in class ``want`` over the self time
of all ops.  ``classes`` names a file under ``benchmark/scopes``: an ordered
list of [class, regex], first match wins, and the class of everything else."""
from .. import harness, xprograms


def read(obs, classes, want):
    progs = xprograms.of(obs)
    if progs is None:
        return None
    from deepspeed_tpu import telemetry

    scopes_of = getattr(telemetry, "program_scopes", None)
    if scopes_of is None:  # a program older than its scopes
        return None
    if "_scopes" not in obs:
        obs["_scopes"] = scopes_of()
    spec = harness.load_json(harness.HERE / "scopes" / f"{classes}.json")
    secs = xprograms.class_seconds(progs, obs["_scopes"], spec["classes"], spec["default"])
    total = sum(secs.values())
    return 100.0 * secs.get(want, 0.0) / total if total else None
