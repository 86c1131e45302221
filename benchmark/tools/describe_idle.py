#!/usr/bin/env python3
"""Run ONE cell traced, as ``run.py --trace 1`` does, and print, beside the
result line, where the device's idle time of the capture went:

    python3 benchmark/tools/describe_idle.py --workload <cell> --seed <n> [--seconds <s>]

- the phases of ``readers/idle_by_phase`` (``emit`` and ``sched`` apart), in
  ms a scheduler tick and % of the capture, the shift they were cut at and
  the causality interval's width (the error bar of ``launch`` / ``fetch_tail``);
- the spans' own marks over the whole window: the median dispatch span as
  upload + enqueue + fetch, the median build span as rows + rng;
- the programs a tick runs beside the engine's own, by module: executions,
  executions a tick, device time;
- the runtime's own TraceMes (``--traceme`` regexes; any thread) by the phase
  the scheduler's thread was in while they ran: whether
  ``DeferredTpuAllocator::Allocate`` is the upload's or the enqueue's.

The tables also go to ``chiprun_out/describe_idle_<cell>_<seed>.txt``.  The
marks live on the recorder's spans, which no file keeps: hence a run, not a
trace file, is what this tool reads."""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import harness, xplane, xprograms  # noqa: E402
from benchmark.readers import idle_by_phase  # noqa: E402
from benchmark.stats import percentile  # noqa: E402

ENGINE = r"^jit_(packed|packed_ctx|decode|decode_burst|spec|cow)_impl$"  # the engine's own programs
TRACEMES = (r"^DeferredTpuAllocator::Allocate$", r"^np\.asarray", r"LinearizeIntoImpl",
            r"^PjitFunction", r"AllocateOutputBuffers", r"^DoEnqueueProgram$")


def phase_table(secs, window_s, ticks):
    rows = [f"{'phase':<12} {'s':>9} {'ms/tick':>9} {'% capture':>10}"]
    for p in idle_by_phase.PHASES:
        rows.append(f"{p:<12} {secs[p]:9.4f} {1e3 * secs[p] / max(ticks, 1):9.3f} "
                    f"{100 * secs[p] / window_s:10.3f}")
    total = sum(secs[p] for p in idle_by_phase.PHASES)
    rows.append(f"{'all':<12} {total:9.4f} {1e3 * total / max(ticks, 1):9.3f} "
                f"{100 * total / window_s:10.3f}   ({ticks} ticks in {window_s:.3f} s)")
    rows.append(f"shift {1e3 * secs['shift']:+.4f} ms (lower edge), interval width "
                f"{1e3 * secs['width']:.4f} ms")
    return rows


def mark_table(spans, window):
    """Medians over the window's spans: each dispatch span as its three
    stretches, each build span as its two."""
    t0, t1 = window
    rows = [f"{'span':<22} {'n':>6}  median ms: whole = parts"]
    for name, cuts in (("decode_tick", ("upload_ms", "dispatch_ms")),
                       ("prefill_pack", ("upload_ms", "dispatch_ms")),
                       ("engine.decode_build", ("rows_ms",)),
                       ("engine.pack_build", ("rows_ms",))):
        got = [(1e3 * (b - a), args) for n, a, b, args in spans
               if n == name and t0 <= b < t1 and all(c in args for c in cuts)]
        if not got:
            continue
        edges = [[0.0] + [args[c] for c in cuts] + [dur] for dur, args in got]
        parts = [percentile([e[i + 1] - e[i] for e in edges], 50) for i in range(len(cuts) + 1)]
        rows.append(f"{name:<22} {len(got):6d}  {percentile([d for d, _ in got], 50):.3f} = "
                    + " + ".join(f"{p:.3f}" for p in parts) + f"  (sum {sum(parts):.3f})")
    return rows


def started_inside(progs, span, excluding, shift):
    """For each mirrored ``span`` in the capture, the executions on device 0's
    ``XLA Modules`` line that started inside it and whose module does NOT
    match ``excluding`` (device times shifted onto the host clock by
    ``shift``): the small programs a tick runs beside the engine's own, each
    of which costs a launch and cuts an idle gap in two."""
    rx = re.compile(excluding)
    aux = [e for e in progs.of_module("") if not rx.search(e.module)]
    starts = [e.start + shift for e in aux]
    return [aux[bisect.bisect_left(starts, h.start):bisect.bisect_left(starts, h.end)]
            for h in progs.mirrored(span)]


def aux_table(progs, excluding, shift):
    ticks = started_inside(progs, "sched.tick", excluding, shift)
    by = {}
    for e in (e for runs in ticks for e in runs):
        n, s = by.get(e.module, (0, 0.0))
        by[e.module] = (n + 1, s + e.end - e.start)
    rows = [f"{'program beside the engine':<44} {'n':>6} {'a tick':>7} {'device ms each':>15}"]
    for module, (n, s) in sorted(by.items(), key=lambda kv: -kv[1][0]):
        rows.append(f"{module:<44} {n:6d} {n / max(len(ticks), 1):7.2f} {1e3 * s / n:15.4f}")
    rows.append(f"median a tick: {percentile([len(r) for r in ticks], 50)}")
    return rows


def traceme_table(trace, phases, patterns):
    """Seconds in which a matching host event ran (any thread; the union, so
    that nested events of one name count once) by the phase the scheduler's
    thread was in meanwhile; ``fetch_tail`` here is the whole stretch after a
    dispatch span's ``dispatch`` mark."""
    rows = []
    for pattern in patterns:
        rx = re.compile(pattern)
        events = [(h.start, h.end) for h in trace.host
                  if rx.search(h.name) and xprograms.SPAN_ID not in h.stats]
        inside, rest = idle_by_phase.cut(xplane.merge(events), phases)
        by = {"outside": sum(b - a for a, b in rest)} if rest else {}
        for a, b, k in inside:
            by[phases[k][2]] = by.get(phases[k][2], 0.0) + b - a
        rows.append(f"{pattern}  ({len(events)} events, {sum(by.values()):.4f} s): " + ", ".join(
            f"{p} {s:.4f}" for p, s in sorted(by.items(), key=lambda kv: -kv[1])))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--traceme", action="append", default=[])
    ap.add_argument("--rehearse", action="store_true",
                    help="the CPU pre-flight of run.py: the flow, and nothing to read")
    args = ap.parse_args(argv)

    man, cell, obs = harness.observe(
        workload=args.workload, seed=args.seed, seconds=args.seconds, trace=True,
        rehearse=args.rehearse, overrides={}, t_process=T_PROCESS)
    device = obs["device"]

    progs, spans = xprograms.of(obs), obs.get("spans") or ()
    secs = idle_by_phase.of(obs)
    rows = [f"describe_idle: {cell['name']} seed {args.seed}"]
    if secs is None:
        rows.append("nothing to read: no trace, spans dropped, spans without marks, "
                    "or an empty causality interval")
    else:
        w0, w1 = progs.window
        rows += phase_table(secs, w1 - w0, len(progs.mirrored("sched.tick")))
        rows.append(f"device idle share of the trace: {100 * obs['trace'].idle_share():.3f} %")
        rows += [""] + mark_table(spans, obs["window"])
        rows += [""] + aux_table(progs, ENGINE, secs["shift"])
        rows += [""] + traceme_table(
            obs["trace"], idle_by_phase.host_phases(idle_by_phase.on_trace_clock(progs, spans)),
            args.traceme or TRACEMES)
    text = "\n".join(rows)
    out = harness.ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / f"describe_idle_{cell['name']}_{args.seed}.txt").write_text(text + "\n")
    print(text, flush=True)
    # the line ``run.py --trace 1`` prints, so that one traced run serves both
    breakdown = None
    if obs.get("trace") is not None and not args.rehearse:
        device["busy_s"], device["window_s"] = obs["trace"].busy_s(), obs["trace"].window_s
        breakdown = xplane.breakdown(obs["trace"])
    metrics = {} if args.rehearse else harness.read_metrics(
        harness.metrics_of(man, cell["name"], True), obs)
    for note in obs.get("notes", []):
        harness.say(note)
    print(harness.result_line(correct=bool(obs["correct"]) and not args.rehearse, attempted=obs["attempted"],
                              failed=obs["failed"], metrics=metrics, device=device,
                              breakdown=breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
