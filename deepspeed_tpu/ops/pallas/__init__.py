"""Pallas TPU kernels — the ``csrc/`` of this framework.

Each kernel module follows the reference's op-builder contract
(op_builder/builder.py:117 OpBuilder): a ``supports()`` predicate that gates
usage by shape, and a functional entry point whose dispatcher takes the jnp
body off-TPU, so every caller works on CPU test meshes.

Every dispatcher reports its choice through ``note_dispatch`` at trace time:
a caller that wants to know which bodies a run compiled (``chip_smoke.py``;
later the benchmark's fallback counters) wraps the run in
``record_dispatch()`` and reads the list.
"""
import contextlib
import contextvars
from typing import Iterator, List, Optional

# the recorder of the calling context (None = nobody is listening); a
# ContextVar, so one caller's recording never leaks into another thread's
_RECORDER: contextvars.ContextVar[Optional[List[dict]]] = (
    contextvars.ContextVar("pallas_dispatch_recorder", default=None))


def on_tpu() -> bool:
    """Whether the default backend is a TPU.  A backend that cannot
    initialise raises — a chip that fails to come up must not turn into a
    slow run on the jnp bodies."""
    import jax

    return jax.devices()[0].platform == "tpu"


@contextlib.contextmanager
def record_dispatch() -> Iterator[List[dict]]:
    """Collect every kernel-vs-jnp dispatch decision traced inside the
    block (in this thread).  Entries: ``{"kernel", "ran", "mosaic",
    "shape", "reason", "tile_keys"}`` — ``mosaic`` is True only when the Pallas body was
    chosen outside interpret mode, i.e. it lowers to a Mosaic custom call."""
    log: List[dict] = []
    token = _RECORDER.set(log)
    try:
        yield log
    finally:
        _RECORDER.reset(token)


def note_dispatch(kernel: str, ran: bool, shape, *, interpret: bool = False,
                  reason: str = "", tile_keys: Optional[int] = None) -> None:
    """Trace-time note from a dispatcher gate (no-op with no recorder).
    ``tile_keys``: the keys a tile of the kernel holds, where its rule chose
    them from the call's shapes (``paged_decode``)."""
    log = _RECORDER.get()
    if log is not None:
        log.append({
            "kernel": kernel, "ran": bool(ran),
            "mosaic": bool(ran) and not interpret,
            "shape": tuple(int(d) for d in shape), "reason": reason,
            "tile_keys": tile_keys,
        })
