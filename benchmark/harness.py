"""What every cell shares: finding its files by name, the device gate, the
compile cache, the observation bag readers take numbers from, and the result
line.  Nothing here names a cell, a configuration, a traffic mix or a metric:
those are files, found by the names ``BENCHMARK.json`` gives.
"""
from __future__ import annotations

import importlib
import json
import os
import shutil
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent
OUT_DIR = ROOT / ".bench_out"          # traces and scratch, inside the checkout
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# libtpu pins a host staging buffer of 4 GiB a chip when it starts, and
# without transparent hugepages that takes 8.7-11.7 s by the machine; a
# configuration's ``runtime.tpu_premapped_buffer_bytes`` sets its size
PREMAP_ENV = ("TPU_PREMAPPED_BUFFER_SIZE", "TPU_PREMAPPED_BUFFER_TRANSFER_THRESHOLD_BYTES")


class BenchError(SystemExit):
    """Exit non-zero with a message and no result line."""

    def __init__(self, msg: str, code: int = 2):
        print(f"benchmark: {msg}", flush=True)
        super().__init__(code)


def say(msg: str) -> None:
    print(msg, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def find_cell(man: dict, workload: str) -> dict:
    for w in man["workloads"]:
        if w["name"] == workload:
            return w
    raise BenchError(f"no workload {workload!r} in BENCHMARK.json")


def config_of(man: dict, name: str) -> dict:
    for c in man["configs"]:
        if c["name"] == name:
            return load_json(ROOT / c["file"])
    raise BenchError(f"no configuration {name!r} in BENCHMARK.json")


def traffic_of(name: str) -> dict:
    return load_json(HERE / "traffic" / f"{name}.json")


def metrics_of(man: dict, workload: str, trace: bool) -> List[dict]:
    """The manifest entries this run reports: the cell's end-to-end metrics
    without a trace, its per-layer metrics with one.  A metric with no
    ``workloads`` key belongs to every cell."""
    group = man["per_layer"] if trace else man["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def rehearsed(d: dict, rehearse: bool) -> dict:
    """A configuration or traffic file with its ``rehearsal`` overrides
    applied (CPU pre-flight at toy size) or dropped (the real run)."""
    d = dict(d)
    over = d.pop("rehearsal", {})
    if rehearse:
        for k, v in over.items():
            d[k] = {**d[k], **v} if isinstance(v, dict) and isinstance(d.get(k), dict) else v
    return d


def module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``, found by name."""
    if not (HERE / kind / f"{name}.py").is_file():
        raise BenchError(f"no benchmark/{kind}/{name}.py")
    return importlib.import_module(f"benchmark.{kind}.{name}")


# ---------------------------------------------------------------------------
# device, cache, compile accounting
# ---------------------------------------------------------------------------
def prepare_environment(chips: int, rehearse: bool,
                        premapped_bytes: Optional[int] = None) -> None:
    """Before JAX is imported.  A rehearsal pins the CPU backend with as many
    virtual devices as the cell asks chips; a real run leaves the platform to
    JAX, which fails at start-up where it finds no accelerator, and gives the
    TPU runtime the configuration's size of its premapped host buffer."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   f" --xla_force_host_platform_device_count={chips}")
        os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    else:
        # the program's rule (utils/compile_cache.py): an operator's directory
        # wins; otherwise one fixed path inside this checkout
        os.environ.setdefault(CACHE_ENV, str(ROOT / ".jax_cache"))
        if premapped_bytes is not None:
            for name in PREMAP_ENV:
                os.environ.setdefault(name, str(int(premapped_bytes)))


def device_gate(chips: int, rehearse: bool) -> dict:
    """The devices as JAX reports them; exits non-zero without a TPU, with
    fewer chips than the cell asks, or on a device the peaks table lacks."""
    import jax

    from .peaks import UnknownDevice, peaks_for

    devs = jax.devices()
    d0 = devs[0]
    info = {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs)}
    if rehearse:
        return info
    if d0.platform != "tpu":
        raise BenchError(f"JAX reports platform {d0.platform!r}, not a TPU; "
                         "use --rehearse for the CPU pre-flight")
    if len(devs) < chips:
        raise BenchError(f"the cell asks {chips} chip(s), JAX reports {len(devs)}")
    try:
        peaks_for(d0.device_kind)
    except UnknownDevice as e:
        raise BenchError(str(e.args[0])) from None
    if not os.environ.get(CACHE_ENV):
        raise BenchError(f"{CACHE_ENV} is empty")
    # cache every program, also the sub-second ones: a warm run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return info


class CompileWatch:
    """``jax.monitoring``: one backend-compile event per XLA compile request,
    answered by the persistent cache or not."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.times: List[float] = []   # host clock of each compile request
        self.seconds = 0.0

    def install(self) -> "CompileWatch":
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event: str, secs: float, **kw) -> None:
        if event == self._COMPILE:
            self.times.append(time.perf_counter())
            self.seconds += secs

    def within(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t < t1)


class Laps:
    """Where the set-up's seconds go, as notes printed before the result."""

    def __init__(self, notes: List[str]):
        self.notes, self.mark = notes, time.perf_counter()

    def __call__(self, what: str) -> None:
        now = time.perf_counter()
        self.notes.append(f"setup: {what} {now - self.mark:.2f} s")
        self.mark = now


def peak_bytes() -> int:
    """Peak bytes in use on the fullest device."""
    import jax

    peaks = []
    for d in jax.devices():
        s = d.memory_stats() or {}
        peaks.append(int(s.get("peak_bytes_in_use", 0)))
    return max(peaks)


def observe(*, workload: str, seed: int, seconds: Optional[float], trace: bool,
            rehearse: bool, overrides: Dict[str, Any], t_process: float):
    """Run ONE cell ONCE through its driver: what ``run.py`` and the builder's
    tools share.  ``overrides`` replace keys of the traffic file (a builder's
    sweep; the driver's command never passes any).  Returns the manifest, the
    cell's entry and the driver's observations, ``obs["device"]`` filled in."""
    man = manifest()
    cell = find_cell(man, workload)
    config = rehearsed(config_of(man, cell["config"]), rehearse)
    traffic = {**rehearsed(traffic_of(cell["traffic"]), rehearse), **overrides}
    if seconds is None:
        seconds = float(traffic.get("rehearsal_seconds", 2.0) if rehearse
                        else man["run_seconds"])
    prepare_environment(cell["chips"], rehearse,
                        config.get("runtime", {}).get("tpu_premapped_buffer_bytes"))
    device = device_gate(cell["chips"], rehearse)
    t_started = time.perf_counter()
    watch = CompileWatch().install()
    obs = module("drivers", config["driver"]).run(
        config=config, traffic=traffic, chips=cell["chips"], seed=seed,
        seconds=seconds, trace=trace, rehearse=rehearse, workload=cell["name"],
        t_process=t_process, watch=watch, device=device)
    device["memory_peak_bytes"] = 0 if rehearse else peak_bytes()
    obs["device"] = device
    obs.setdefault("notes", []).insert(
        0, f"setup: process start to the driver (imports, the runtime's start) "
           f"{t_started - t_process:.2f} s")
    obs["notes"].extend(stall_notes(obs))
    return man, cell, obs


def stall_notes(obs: Dict[str, Any]) -> List[str]:
    """Where a serving window lost its time, if it lost any: its longest tick
    with the program's spans inside it, longest first, and the longest pause
    between two ticks (the host outside the loop).  From the drivers' tick
    stamps ``(begin, end, ...)`` and the span tree, both on one clock."""
    t0, t1 = obs.get("window", (0.0, 0.0))
    ticks = [t for t in obs.get("ticks", ()) if t0 <= t[1] < t1]
    if len(ticks) < 2:
        return []
    tb, te = max(((t[0], t[1]) for t in ticks), key=lambda t: t[1] - t[0])
    inside = sorted(((b - a, name) for name, a, b, _ in obs.get("spans", ())
                     if tb <= a and b <= te), reverse=True)[:4]
    median = sorted(t[1] - t[0] for t in ticks)[len(ticks) // 2]
    pause, at = max((b[0] - a[1], a[1]) for a, b in zip(ticks, ticks[1:]))
    return [f"load: longest tick {1e3 * (te - tb):.1f} ms (median {1e3 * median:.1f}) "
            f"at {tb - t0:.1f} s of the window; inside it: "
            + (", ".join(f"{name} {1e3 * d:.1f}" for d, name in inside) or "no span kept"),
            f"load: longest pause between two ticks {1e3 * pause:.1f} ms "
            f"at {at - t0:.1f} s of the window"]


# ---------------------------------------------------------------------------
# tracing a few seconds of the window
# ---------------------------------------------------------------------------
class Capture:
    """``jax.profiler`` around the LAST ``seconds`` of the window, so that
    writing the trace out falls after the window and stalls nothing in it.
    ``poll(now)`` is called from the measuring loop."""

    def __init__(self, enabled: bool, workload: str, t_end: float, seconds: float):
        self.enabled = enabled
        self.dir = OUT_DIR / f"trace_{workload}"
        self.t_begin = t_end - seconds
        self.active = False
        self._span = None

    def poll(self, now: float) -> None:
        if self.enabled and not self.active and now >= self.t_begin:
            import jax

            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir.mkdir(parents=True, exist_ok=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation("bench.capture")
            self._span.__enter__()
            self.active = True

    def annotate(self, name: str, **kw):
        """A host span in the trace while capturing, nothing otherwise."""
        if self.active:
            import jax

            return jax.profiler.TraceAnnotation(name, **kw)
        import contextlib

        return contextlib.nullcontext()

    def finish(self):
        """Stop, reduce; returns a ``xplane.Trace`` or None."""
        if not self.active:
            return None
        import jax

        from . import xplane

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False
        files = sorted(self.dir.glob("plugins/profile/*/*.xplane.pb"))
        if not files:
            return None
        return xplane.reduce_trace(xplane.load(str(files[-1])))


# ---------------------------------------------------------------------------
# result line
# ---------------------------------------------------------------------------
def read_metrics(entries: List[dict], obs: Dict[str, Any]) -> Dict[str, dict]:
    """Each manifest entry -> its ``metrics/<name>.json`` -> the reader that
    file names -> a number.  A reader with nothing to read returns None and
    the metric is left out of the line."""
    out: Dict[str, dict] = {}
    for m in entries:
        spec = load_json(HERE / "metrics" / f"{m['name']}.json")
        value = module("readers", spec["reader"]).read(obs, **spec.get("params", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, dict], device: dict,
                breakdown: Optional[dict] = None) -> str:
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    return json.dumps(line)
