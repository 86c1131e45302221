#!/usr/bin/env python3
"""Sweep ONE key of a serving cell's traffic file (a builder's tool; the knee
is read from its lines by the builder, it gives no verdict):

    python3 benchmark/tools/sweep_traffic.py --workload <cell> --key <key> \\
        --values '[v1, v2, ...]' --seeds '[s1, s2]' [--seconds 45]

Every (value, seed) is one run in a process of its own (a child of this file
given one value and one seed with ``--one``; the parent never touches JAX),
untraced, as ``run.py`` makes it, with the key overridden as ``run.py --set``
does.  A run's line holds the cell's end-to-end metrics AND every per-layer
metric that needs no trace (an untraced ``run.py`` prints the first only), and
the requests in flight (submitted, not yet ended: waiting or running) as the
mean over the ticks of the window's first and last fifth.  At a fixed rate the
ratio of the two follows the seed's arrivals (the same seed reads it to +-0.04
in two calls, 0.87 to 1.43 over six seeds at 16-20 requests in flight: PERF.md
section 6, PR 41): it shows a queue only where it grows at every seed.  Lines
go to ``chiprun_out/sweep_<cell>_<key>.jsonl`` as they come, and to stdout.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import harness  # noqa: E402


def in_flight(obs, k: int, of: int = 5):
    """Mean over the ticks of the window's ``k``-th of ``of`` parts of the
    requests submitted and not yet ended; None where the driver keeps none."""
    t0, t1 = obs["window"]
    lo, hi = t0 + k * (t1 - t0) / of, t0 + (k + 1) * (t1 - t0) / of
    xs = [t[4] for t in obs.get("ticks", ()) if len(t) > 4 and lo <= t[1] < hi]
    return sum(xs) / len(xs) if xs else None


def one(args, value, seed: int) -> int:
    man, cell, obs = harness.observe(
        workload=args.workload, seed=seed, seconds=args.seconds, trace=False,
        rehearse=args.rehearse, overrides={args.key: value}, t_process=T_PROCESS)
    entries = harness.metrics_of(man, cell["name"], False) + harness.metrics_of(man, cell["name"], True)
    first, last = in_flight(obs, 0), in_flight(obs, 4)
    line = {"value": value, "seed": seed, "correct": bool(obs["correct"]),
            "attempted": obs["attempted"], "failed": obs["failed"],
            "in_flight_first_fifth": first, "in_flight_last_fifth": last,
            "growth": last / first if first and last is not None else None,
            "metrics": {k: v["value"] for k, v in harness.read_metrics(entries, obs).items()}}
    for note in obs.get("notes", []):
        harness.say(note)
    print("sweep: " + json.dumps(line), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--key", required=True)
    ap.add_argument("--values", required=True, help="JSON list, in the order to run")
    ap.add_argument("--seeds", default="[0]", help="JSON list")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--one", action="store_true", help="(the child) run the one value and seed here")
    args = ap.parse_args(argv)
    values, seeds = json.loads(args.values), json.loads(args.seeds)
    if args.one:
        return one(args, values[0], seeds[0])

    out = harness.ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    log = out / f"sweep_{args.workload}_{args.key}.jsonl"
    for v in values:
        for seed in seeds:
            cmd = [sys.executable, __file__, "--one", "--workload", args.workload, "--key", args.key,
                   "--values", json.dumps([v]), "--seeds", json.dumps([seed])]
            cmd += ["--seconds", str(args.seconds)] if args.seconds is not None else []
            cmd += ["--rehearse"] if args.rehearse else []
            done = subprocess.run(cmd, cwd=harness.ROOT, capture_output=True, text=True)
            got = [l for l in done.stdout.splitlines() if l.startswith("sweep: ")]
            if done.returncode or not got:
                print(f"{v!r} seed {seed}: exit {done.returncode}\n{done.stdout[-1500:]}\n"
                      f"{done.stderr[-1500:]}", flush=True)
                continue
            with open(log, "a") as f:
                f.write(got[-1][len("sweep: "):] + "\n")
            print(got[-1], flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
