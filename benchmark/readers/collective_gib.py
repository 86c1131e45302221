"""GiB one device's collectives move in one execution of a program: result
bytes of every all-gather / reduce-scatter / all-reduce / collective-permute
in the compiled text, loop bodies times their trip count
(``telemetry.collective_bytes_per_step()``).  Read after a traced run only:
the untraced run must not pay for reading compiled text."""
import re


def read(obs, module):
    if obs.get("trace") is None:
        return None
    from deepspeed_tpu import telemetry

    count = getattr(telemetry, "collective_bytes_per_step", None)
    if count is None:  # a program older than the counter
        return None
    rx = re.compile(module)
    found = [b for name, b in count().items() if rx.search(name)]
    if not found or None in found:  # no such program, or a loop it could not count
        return None
    return max(found) / 2**30
