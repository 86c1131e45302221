#!/usr/bin/env python3
"""Print what a recorded ``.xplane.pb`` holds (planes, lines, a few events
with their stats) and its reduction: look at a trace before writing a reader
against it.

    python3 benchmark/tools/describe_trace.py <dir-or-file> [max_events] [op-key-regex]

With a regex, also lists the first 60 device-0 ops whose key matches it."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import xplane  # noqa: E402


def main(argv) -> int:
    target = Path(argv[1])
    files = [target] if target.is_file() else sorted(target.glob("**/*.xplane.pb"))
    if not files:
        print(f"no .xplane.pb under {target}")
        return 1
    prof = xplane.load(str(files[-1]))
    print(f"FILE {files[-1]} ({files[-1].stat().st_size} bytes)")
    print(xplane.describe(prof, int(argv[2]) if len(argv) > 2 else 12))
    tr = xplane.reduce_trace(prof)
    if tr is None:
        print("no bench.capture annotation: nothing to reduce")
        return 0
    print(f"WINDOW {tr.window_s:.4f} s, devices {sorted(tr.devices)}, busy "
          f"{tr.busy_s():.4f} s, idle share {tr.idle_share():.4f}")
    for name, secs in sorted(tr.op_seconds().items(), key=lambda kv: -kv[1])[:40]:
        print(f"  OP {secs:10.6f} s  {name}")
    for name, secs in sorted(tr.idle_gaps().items(), key=lambda kv: -kv[1])[:15]:
        print(f"  GAP {secs:10.6f} s  {name}")
    if len(argv) > 3:
        for o in sorted(tr.kernel_events(argv[3])[min(tr.devices)], key=lambda o: o.start)[:60]:
            print(f"  MATCH start {o.start:.6f} dur {1e3 * (o.end - o.start):.4f} ms "
                  f"self {1e3 * o.self_s:.4f} ms  {o.name}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
