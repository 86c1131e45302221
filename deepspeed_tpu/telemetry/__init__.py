"""Unified telemetry: metrics registry, tick spans, per-request traces.

- ``registry.py`` — thread-safe counters/gauges/histograms (log-spaced
  buckets + exact small-count quantiles), ``snapshot()`` ->
  ``(label, value, step)`` events for the monitor fan-out, JSONL sink,
  ``StatsView`` compat mapping backing the engines' ``stats`` dicts.
- ``tracing.py`` — ``TraceRecorder``'s span tree (ids, parents),
  ``RequestTrace`` serve-request lifecycles (TTFT / TBT / queue wait /
  accept rate), Chrome trace-event export (Perfetto-loadable), ``Telemetry``
  facade whose ``span()`` also mirrors each span into a live
  ``jax.profiler`` capture when the ``jax_profiler`` knob is on.
- ``programs.py`` — the engines' jitted programs, tracked by shape;
  ``program_scopes()`` / ``collective_bytes_per_step()`` read their compiled
  text lazily (instruction -> ``jax.named_scope`` path, collective bytes).
- ``fleet.py`` — the fleet observability plane: ``FleetRegistry`` merges
  per-worker registry snapshots (counter rollups + histogram merges with
  the documented quantile bound), ``SloMonitor`` computes availability
  and multi-window burn rates over the router's terminal counters,
  ``FleetCollector`` pulls workers on a paced thread, and
  ``fleet_chrome_trace`` stitches every process's spans onto one
  clock-aligned Perfetto timeline.
"""
from .registry import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    RateView,
    StatsView,
    format_percentile_table,
    percentile_summary,
    window_percentile_summary,
)
from .tracing import (  # noqa: F401
    NULL_REQUEST_TRACE,
    NULL_SPAN,
    RequestTrace,
    Span,
    Telemetry,
    TraceRecorder,
)
from .programs import (  # noqa: F401
    collective_bytes_per_step,
    count_in_step,
    note_step_fact,
    program_scopes,
    step_counts,
    track as track_program,
)
from .fleet import (  # noqa: F401
    FleetCollector,
    FleetRegistry,
    SloMonitor,
    attach_fleet_collector,
    fleet_chrome_trace,
)
