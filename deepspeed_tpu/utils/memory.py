"""Memory reporting: the ``see_memory_usage`` analogue.

Reference: ``runtime/utils.py:771 see_memory_usage`` prints
allocated/max-allocated/cached device memory plus host VM stats and is
sprinkled through the engine behind ``memory_breakdown``.  The TPU-native
version reads the device allocator's live stats
(``Device.memory_stats()`` — HBM bytes in use / peak / limit) and the host
RSS from ``/proc/self/status``.
"""
from __future__ import annotations

import gc
import os
from typing import Any, Dict, Optional

from .logging import log_dist

_GiB = 1024**3


def _host_memory() -> Dict[str, float]:
    """VmRSS / VmHWM (peak RSS) in GiB from procfs; zeros off-Linux."""
    out = {"host_rss_gb": 0.0, "host_peak_rss_gb": 0.0}
    try:
        with open("/proc/self/status") as fh:
            for line in fh:
                if line.startswith("VmRSS:"):
                    out["host_rss_gb"] = int(line.split()[1]) / 1024**2
                elif line.startswith("VmHWM:"):
                    out["host_peak_rss_gb"] = int(line.split()[1]) / 1024**2
    except OSError:
        pass
    return out


def memory_stats(device=None) -> Dict[str, Any]:
    """Device + host memory snapshot.

    Device figures come from ``memory_stats()`` of the first local device
    (or the given one).  A backend without an instrumented allocator (the
    CPU test platform) returns ``None`` there and reports zeros; an error
    from a backend that has one (TPU) propagates.
    """
    import jax

    stats = {
        "device_bytes_in_use": 0,
        "device_peak_bytes": 0,
        "device_bytes_limit": 0,
    }
    dev = device
    if dev is None:
        local = jax.local_devices()
        dev = local[0] if local else None
    if dev is not None:
        raw = dev.memory_stats() or {}
        stats["device_bytes_in_use"] = int(raw.get("bytes_in_use", 0))
        stats["device_peak_bytes"] = int(
            raw.get("peak_bytes_in_use", raw.get("bytes_in_use", 0))
        )
        stats["device_bytes_limit"] = int(raw.get("bytes_limit", 0))
    stats.update(_host_memory())
    return stats


def see_memory_usage(
    message: str, force: bool = False, collect: bool = False
) -> Optional[Dict[str, Any]]:
    """Log a one-line memory breakdown; returns the snapshot dict.

    ``force`` mirrors the reference's signature (``runtime/utils.py:771``):
    without it the call is a no-op so call sites can stay in the code
    unconditionally and be switched on by ``memory_breakdown`` config.
    ``collect`` additionally runs the host GC first (the reference calls
    ``gc.collect`` + ``empty_cache``; XLA owns the device cache here).
    """
    if not force:
        return None
    if collect:
        gc.collect()
    s = memory_stats()
    log_dist(
        f"MEMSTATS {message} | "
        f"HBM in-use {s['device_bytes_in_use'] / _GiB:.2f} GB "
        f"(peak {s['device_peak_bytes'] / _GiB:.2f} GB, "
        f"limit {s['device_bytes_limit'] / _GiB:.2f} GB) | "
        f"host RSS {s['host_rss_gb']:.2f} GB (peak {s['host_peak_rss_gb']:.2f} GB)"
    )
    return s


def memory_breakdown_report(engine) -> Dict[str, Any]:
    """Engine-level breakdown: bytes by state component (params / optimizer
    state / loss-scale bookkeeping), the analogue of the reference's
    per-phase ``see_memory_usage`` sprinkling, computed from the state
    pytree itself so it is exact rather than sampled."""
    import jax

    def tree_bytes(t) -> int:
        return sum(
            x.size * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves(t)
            if hasattr(x, "dtype")
        )

    st = engine.state
    report = {
        "master_params_bytes": tree_bytes(st.params),
        "opt_state_bytes": tree_bytes(st.opt_state),
        "snapshot": memory_stats(),
    }
    report["state_total_bytes"] = (
        report["master_params_bytes"] + report["opt_state_bytes"]
    )
    return report
