#!/usr/bin/env python3
"""Run ONE cell ONCE and print the contract's JSON object as the last line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python3 benchmark/run.py --workload <name> --rehearse     # CPU pre-flight, toy size

A new process per run; JAX is touched only inside ``main``; no child process
is started.  Without a TPU, with fewer chips than the cell asks, or on a
device the peaks table lacks, it exits non-zero and prints no result line.
``--rehearse`` runs the same control flow on the CPU at the files' rehearsal
sizes: its line says ``"correct": false``, names the CPU under ``device`` and
carries no metric at all, because no number of a CPU run is a device metric.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                    help="builder's sweeps only: override one key of the "
                         "traffic file for this run (the driver never passes it)")
    args = ap.parse_args(argv)

    overrides = {}
    for item in args.set:
        key, _, value = item.partition("=")
        overrides[key] = json.loads(value)
    man, cell, obs = harness.observe(
        workload=args.workload, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), rehearse=args.rehearse, overrides=overrides,
        t_process=T_PROCESS)
    device = obs["device"]
    trace = obs.get("trace")
    breakdown = None
    if trace is not None and not args.rehearse:
        from benchmark import xplane

        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s
        breakdown = xplane.breakdown(trace)
    if args.rehearse:
        # the readers run (their control flow is what is rehearsed) but no
        # value leaves the process under a metric's name
        entries = (harness.metrics_of(man, cell["name"], False)
                   + harness.metrics_of(man, cell["name"], True))
        got = harness.read_metrics(entries, obs)
        harness.say("rehearsal: readers that returned a value: "
                    + " ".join(sorted(got)))
        harness.say("rehearsal: readers with nothing to read here: "
                    + " ".join(sorted(m["name"] for m in entries if m["name"] not in got)))
        metrics, correct = {}, False
    else:
        metrics = harness.read_metrics(
            harness.metrics_of(man, cell["name"], bool(args.trace)), obs)
        correct = bool(obs["correct"])
    for note in obs.get("notes", []):
        harness.say(note)
    print(harness.result_line(
        correct=correct, attempted=obs["attempted"], failed=obs["failed"],
        metrics=metrics, device=device, breakdown=breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
