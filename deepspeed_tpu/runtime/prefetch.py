"""Latency-hiding input pipeline: async prefetch + device-put double buffering.

The training loop's non-compute latency lives at the host↔device boundary:

1. **Input latency** — ``DeepSpeedTpuDataLoader.__iter__`` gathers samples in
   a Python loop, collates and gas-folds *between* device steps, and hands
   host numpy to the jitted step so the H2D transfer happens at dispatch
   time, serialized against the step.
2. **Metrics latency** — reading ``metrics.loss`` / ``metrics.skipped``
   host-side after every step forces a device sync that defeats JAX's async
   dispatch (the device drains before step k+1 is even dispatched).

This module hides both, applying the same overlap principle the collective
schedulers use (T3, arxiv 2401.16677: hide non-compute latency under
compute) at the input boundary ("The Big Send-off", arxiv 2504.18658 — keep
the accelerator never-waiting):

- :class:`DevicePrefetcher` — a background worker that pulls batches from
  any loader, collates (the loader's own ``__next__`` work runs on the
  worker thread), ``jax.device_put``-places them into the engine's batch
  shardings ahead of time, and parks them in a bounded queue
  (``train_data.prefetch_depth``, default 2 = double buffering).  H2D for
  batch k+1 overlaps batch k's device compute.
- :class:`MetricsBuffer` — keeps ``StepMetrics`` as device arrays and defers
  every ``.item()``/``bool()`` read to a flush at ``steps_per_print``
  boundaries (or an explicit ``engine.get_last_loss()``), so the steady-state
  loop issues no blocking host read.
- Checkpoint-safe drain: each queued batch carries the loader-state snapshot
  taken *before* it was drawn, so ``resume_state()`` returns the sampler
  position as if no prefetched-but-unconsumed batch existed —
  ``state_dict()`` resume stays exact.

Engine integration: ``DeepSpeedTpuEngine.train_on_loader()``.
"""
from __future__ import annotations

import queue
import threading
from collections import deque
from typing import Any, Callable, Iterable, List, Optional, Tuple

# Diagnostic counter: every deferred-metrics host read lands here.  Tests
# monkeypatch/inspect this to assert the training loop stays async (the
# acceptance criterion "no per-step blocking host read").
HOST_READS = {"count": 0}


def host_scalar(x) -> float:
    """THE host↔device sync point for deferred step metrics.

    All host conversions of buffered ``StepMetrics`` route through here so
    the sync surface is one auditable (and monkeypatchable) function.
    """
    HOST_READS["count"] += 1
    item = getattr(x, "item", None)
    return float(item()) if item is not None else float(x)


class PrefetchStopped(RuntimeError):
    """Raised when a consumer touches a prefetcher after ``close()``."""


_END = "end"
_ERR = "err"
_BATCH = "batch"


class DevicePrefetcher:
    """Bounded background prefetcher over any batch iterator.

    ``place_fn(host_batch) -> device_batch`` runs on the worker thread —
    collation (inside the iterator's ``__next__``) and the H2D transfer both
    leave the consumer's critical path.  ``depth`` bounds device memory to
    ``depth`` in-flight global batches (double buffering at the default 2).

    ``state_fn`` (e.g. ``loader.state_dict``) is snapshotted under the
    prefetcher lock immediately *before* each ``next()`` on the source, so
    :meth:`resume_state` can hand back the exact sampler position of the
    oldest batch not yet delivered to the consumer.

    Worker exceptions are re-raised in the consumer thread at the point in
    the stream where they occurred.
    """

    def __init__(
        self,
        iterator: Iterable,
        place_fn: Callable[[Any], Any],
        depth: int = 2,
        state_fn: Optional[Callable[[], Any]] = None,
        telemetry=None,
    ):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        # pipeline-health telemetry: consumer wait on an empty queue is the
        # input latency the prefetcher failed to hide (0 in steady state);
        # queue depth gauges how much headroom double-buffering has left.
        # All no-ops unless an enabled Telemetry is passed.
        from ..telemetry import Telemetry

        tel = Telemetry.ensure(telemetry)
        self._tel_enabled = tel.enabled
        self._clock = tel.clock
        self._c_batches = tel.registry.counter("input/batches_prefetched")
        self._h_wait = tel.registry.histogram("input/consumer_wait_ms")
        self._g_depth = tel.registry.gauge("input/queue_depth")
        self._it = iter(iterator)
        self._place = place_fn
        self._state_fn = state_fn
        self.depth = depth
        self._queue: "queue.Queue[Tuple[str, Any]]" = queue.Queue(maxsize=depth)
        # state snapshots of batches drawn from the source but not yet
        # delivered to the consumer (includes the one mid-device_put)
        self._pending_states: "deque[Any]" = deque()
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._closed = False
        self._thread = threading.Thread(
            target=self._run, name="dstpu-input-prefetch", daemon=True
        )
        self._thread.start()

    # -- worker side --------------------------------------------------------
    def _run(self) -> None:
        try:
            while not self._stop.is_set():
                # snapshot BEFORE the draw and append speculatively: if
                # resume_state() runs while the draw is in flight it sees
                # this batch as pending and rewinds to its pre-draw
                # position (replaying it — never skipping it).  The lock
                # covers only the snapshot/deque bookkeeping, NOT the
                # collate itself: holding it across next() would stall the
                # consumer's popleft for a full collate, putting the host
                # work this pipeline exists to hide back on the critical
                # path.
                with self._lock:
                    snap = self._state_fn() if self._state_fn is not None else None
                    self._pending_states.append(snap)
                try:
                    batch = next(self._it)
                except StopIteration:
                    with self._lock:
                        self._pending_states.pop()  # nothing was drawn
                    self._offer((_END, None))
                    return
                dev = self._place(batch)
                if not self._offer((_BATCH, dev)):
                    return  # closed while blocked on a full queue
        except BaseException as e:  # noqa: BLE001 — propagated to consumer
            # the failed batch's snapshot (if any) stays pending: resuming
            # from resume_state() replays the batch that errored
            self._offer((_ERR, e))

    def _offer(self, item) -> bool:
        """put() that stays responsive to close() instead of deadlocking on
        a full queue nobody drains."""
        while not self._stop.is_set():
            try:
                self._queue.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    # -- consumer side ------------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        if self._closed:
            raise PrefetchStopped("prefetcher is closed")
        t_wait = self._clock() if self._tel_enabled else 0.0
        while True:
            try:
                kind, payload = self._queue.get(timeout=0.05)
                break
            except queue.Empty:
                if not self._thread.is_alive():
                    # worker died without posting a terminal item (should
                    # not happen; defensive against hard thread kills)
                    raise StopIteration
        if self._tel_enabled:
            self._h_wait.observe((self._clock() - t_wait) * 1e3)
            self._g_depth.set(self._queue.qsize())
        if kind == _END:
            raise StopIteration
        if kind == _ERR:
            raise payload
        with self._lock:
            self._pending_states.popleft()
        self._c_batches.inc()
        return payload

    def qsize(self) -> int:
        """Batches currently parked device-side (tests: backpressure bound)."""
        return self._queue.qsize()

    def resume_state(self) -> Any:
        """Loader state as if no prefetched-but-unconsumed batch was drawn.

        The oldest pending snapshot when batches are in flight; the loader's
        live state otherwise.  None when the prefetcher has no ``state_fn``.
        """
        with self._lock:
            if self._pending_states:
                return self._pending_states[0]
            return self._state_fn() if self._state_fn is not None else None

    def close(self) -> bool:
        """Stop the worker and release queued batches.  Idempotent.
        Returns True when the worker has actually exited — callers must
        not restore loader state while a zombie worker (stuck in a slow
        draw) could still advance it."""
        if not self._closed:
            self._closed = True
            self._stop.set()
            # drain so a worker blocked in put() observes the stop promptly
            while True:
                try:
                    self._queue.get_nowait()
                except queue.Empty:
                    break
            self._thread.join(timeout=5.0)
        return not self._thread.is_alive()


class MetricsBuffer:
    """Deferred host accounting for ``StepMetrics``.

    ``append()`` keeps the per-step metrics as device arrays (zero host
    reads); ``flush()`` performs the one deferred sync and returns
    ``[(global_step, host_metrics_namedtuple)]`` in step order.  The engine
    flushes at ``steps_per_print`` boundaries, before checkpoints (exact
    ``skipped_steps``), and on explicit ``get_last_loss()``.
    """

    def __init__(self):
        self._items: List[Tuple[int, Any]] = []

    def __len__(self) -> int:
        return len(self._items)

    def append(self, global_step: int, metrics, keep_history: bool = True) -> None:
        """``keep_history=False`` retains only the newest step — the right
        mode when nothing consumes per-step history (no fp16 skip accounting,
        no monitor): the buffer stays O(1) across arbitrarily long print
        windows instead of parking one StepMetrics per step."""
        if not keep_history and self._items:
            self._items.clear()
        self._items.append((global_step, metrics))

    def flush(self) -> List[Tuple[int, Any]]:
        items, self._items = self._items, []
        if not items:
            return []
        import jax

        out = []
        for step, m in items:
            # one dispatch-ordered read per scalar; the first conversion
            # blocks until the step that produced it has executed, the rest
            # are already resident
            # (tree_map: ``StepMetrics.counts`` is a dict of scalars, or None)
            out.append((step, jax.tree_util.tree_map(host_scalar, m)))
        return out
