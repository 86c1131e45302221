#!/usr/bin/env python3
"""Run ONE traced run of a cell and print where its device time went by scope
class, with the heaviest instructions of each class and the ``op_name`` the
program recorded for them: what a builder looks at before trusting (or
editing) a ``scopes/*.json`` file.  On the chip:

    chiprun -- python3 benchmark/tools/describe_scopes.py <classes> --workload <cell> --seed <n> [--seconds <s>]

``<classes>`` names a file under ``benchmark/scopes``; the rest goes to
``benchmark/run.py`` with ``--trace 1`` added.  The run prints its result
line as always; the listing follows it."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import harness, run, xprograms  # noqa: E402


def main(argv) -> int:
    classes = harness.load_json(harness.HERE / "scopes" / f"{argv[1]}.json")
    kept = {}
    read_metrics = harness.read_metrics

    def keeping(entries, obs):
        kept["obs"] = obs
        return read_metrics(entries, obs)

    harness.read_metrics = keeping
    rc = run.main(argv[2:] + ["--trace", "1"])
    obs = kept.get("obs") or {}
    progs = xprograms.of(obs)
    if rc or progs is None:
        print("nothing to describe: no traced device plane")
        return rc or 1
    if "_scopes" not in obs:
        from deepspeed_tpu import telemetry

        obs["_scopes"] = telemetry.program_scopes()
    scopes = obs["_scopes"]
    first = min(progs.ops)
    runs = progs.executions[first]
    by_class = {}
    for d, module, o, op_name, cls in xprograms.classified_ops(
            progs, scopes, classes["classes"], classes["default"]):
        if d == first:
            row = by_class.setdefault(cls, {}).setdefault((module, o.name, op_name), [0.0, 0])
            row[0] += o.self_s
            row[1] += 1
    total = sum(r[0] for rows in by_class.values() for r in rows.values())
    print(f"DEVICE SELF TIME {total:.4f} s over {len(runs)} executions; modules "
          f"{sorted({e.module for e in runs})}; scoped modules {sorted(scopes)}")
    for cls, rows in sorted(by_class.items(), key=lambda kv: -sum(r[0] for r in kv[1].values())):
        secs = sum(r[0] for r in rows.values())
        print(f"CLASS {cls}: {secs:.4f} s = {100 * secs / total:.2f}%")
        for (module, name, op_name), (s, n) in sorted(rows.items(), key=lambda kv: -kv[1][0])[:12]:
            print(f"    {s:9.5f} s  x{n:<5d} {module} {name}  <- {op_name}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
