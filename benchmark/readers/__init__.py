"""Readers: one module per way of taking a number from a run's observations.

``read(obs, **params) -> float | None``.  ``obs`` is the bag a driver fills
(raw stamps, spans, counters, the reduced trace, shapes); ``params`` come from
the metric's own ``metrics/<name>.json``.  A reader that finds nothing to read
returns None and the harness leaves the metric out of the line.  A new way of
reading is a new module here; a new metric that reads in an existing way is
only a new ``metrics/*.json``.
"""
