"""Wall-clock + throughput timers.

TPU-native counterpart of the reference's ``deepspeed/utils/timer.py``
(``SynchronizedWallClockTimer`` at timer.py:44, ``ThroughputTimer`` at
timer.py:199).  Device synchronization is expressed with
``jax.block_until_ready`` instead of CUDA events.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from .logging import log_dist

FORWARD_MICRO_TIMER = "fwd_microstep"
FORWARD_GLOBAL_TIMER = "fwd"
BACKWARD_MICRO_TIMER = "bwd_microstep"
BACKWARD_GLOBAL_TIMER = "bwd"
STEP_MICRO_TIMER = "step_microstep"
STEP_GLOBAL_TIMER = "step"
TRAIN_BATCH_TIMER = "train_batch"


class _Timer:
    def __init__(self, name: str):
        self.name = name
        self.started = False
        self._start = 0.0
        self._elapsed = 0.0  # seconds
        self._last = 0.0
        self._count = 0

    def start(self, sync_obj=None):
        if self.started:
            return
        if sync_obj is not None:
            _block(sync_obj)
        self._start = time.perf_counter()
        self.started = True

    def stop(self, sync_obj=None, record: bool = True):
        if not self.started:
            return
        if sync_obj is not None:
            _block(sync_obj)
        if record:
            duration = time.perf_counter() - self._start
            self._elapsed += duration
            self._last = duration
            self._count += 1
        self.started = False

    def last(self) -> float:
        """Most recent recorded duration in seconds (0 since last reset)."""
        return self._last

    def reset(self):
        self.started = False
        self._elapsed = 0.0
        self._last = 0.0  # a stale _last would leak pre-reset durations
        self._count = 0

    def elapsed(self, reset: bool = True) -> float:
        """Elapsed milliseconds since last reset."""
        value = self._elapsed * 1000.0
        if reset:
            self.reset()
        return value

    def mean(self) -> float:
        return (self._elapsed / self._count * 1000.0) if self._count else 0.0


# Diagnostic: every device fence a timer issues lands here.  The async-
# metrics tests read it to assert the steady-state training loop stays
# sync-free between steps_per_print boundaries.
TIMER_SYNCS = {"count": 0}


def _block(obj):
    """Device fence: ``jax.block_until_ready`` is a real barrier (the chip
    smoke prints enqueue vs blocked time for one step to show it).  Device
    errors propagate — a failed step must not read as a fast one.  With
    ``train_data.async_metrics`` the engine requests the throughput fence
    only at ``steps_per_print`` boundaries, so the window total stays exact
    device time while per-step stops are dispatch-only samples."""
    import jax

    TIMER_SYNCS["count"] += 1
    jax.block_until_ready(obj)


class SynchronizedWallClockTimer:
    """Named timer registry; ``log()`` prints one line with selected timers."""

    def __init__(self):
        self.timers: Dict[str, _Timer] = {}

    def __call__(self, name: str) -> _Timer:
        if name not in self.timers:
            self.timers[name] = _Timer(name)
        return self.timers[name]

    def has(self, name: str) -> bool:
        return name in self.timers

    def log(self, names: List[str], normalizer: float = 1.0, reset: bool = True, ranks=None):
        parts = []
        for name in names:
            if name in self.timers:
                elapsed = self.timers[name].elapsed(reset=reset) / normalizer
                parts.append(f"{name}: {elapsed:.2f}")
        if parts:
            log_dist("time (ms) | " + " | ".join(parts), ranks=ranks)

    def get_mean(self, names: List[str]) -> Dict[str, float]:
        return {n: self.timers[n].mean() for n in names if n in self.timers}


@dataclass
class ThroughputTimer:
    """Samples/sec + TFLOPS reporting (reference: utils/timer.py:199).

    ``batch_size`` is the *global* train batch size per step.
    """

    batch_size: int = 1
    start_step: int = 2
    steps_per_output: int = 50
    monitor_memory: bool = False
    logging_fn=None
    global_steps: int = 0
    total_elapsed: float = 0.0
    step_elapsed: float = 0.0
    _start: float = 0.0
    started: bool = False
    flops_per_sample: Optional[float] = None
    history: List[float] = field(default_factory=list)

    def start(self):
        self.started = True
        self._start = time.perf_counter()

    def stop(self, global_step: bool = True, report_speed: bool = True, sync_obj=None):
        if not self.started:
            return
        self.started = False
        if sync_obj is not None:
            _block(sync_obj)
        duration = time.perf_counter() - self._start
        self.step_elapsed += duration
        if global_step:
            self.global_steps += 1
            if self.global_steps >= self.start_step:
                self.total_elapsed += self.step_elapsed
                self.history.append(self.step_elapsed)
            if report_speed and self.global_steps % self.steps_per_output == 0:
                # window-average, not the boundary step alone: with the
                # engine's async metrics only the boundary stop carries a
                # device fence, so its raw step_elapsed absorbs the whole
                # window's drained device time (~steps_per_output x one
                # step).  The window mean is the true per-step figure in
                # both sync and async modes.
                window = self.history[-self.steps_per_output:]
                avg_ms = (
                    sum(window) / len(window) * 1000.0
                    if window
                    else self.step_elapsed * 1000.0
                )
                log_dist(
                    f"step={self.global_steps}, samples/sec={self.avg_samples_per_sec():.2f}, "
                    f"step time (window avg)={avg_ms:.1f} ms"
                )
            self.step_elapsed = 0.0

    def avg_samples_per_sec(self) -> float:
        steps = max(self.global_steps - self.start_step + 1, 0)
        if steps <= 0 or self.total_elapsed == 0:
            return 0.0
        return self.batch_size / (self.total_elapsed / steps)
