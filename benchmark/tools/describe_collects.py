#!/usr/bin/env python3
"""Run ONE serving cell, as ``run.py`` does, and print, beside the result
line, where its fetches waited (ROADMAP S12):

    python3 benchmark/tools/describe_collects.py --workload <cell> --seed <n> [--trace 0]
        [--set trace_s=12]      (a longer capture holds more collects; the result line's
                                 per-layer metrics are then not the cell's own)

Traced (the default), from the capture (``xruntime.chain``):

- the shift added to device stamps (the upper edge of the interval the
  runtime's events allow, tied by ``run_id``) and the interval's width, beside
  the old pairing's ``host_device_skew_ms`` of the same run;
- dispatch spans a collect fetched inside the capture: chained, left out (by
  reason), chained with an order that does not hold;
- per kind of dispatch the p50 and the max of each link of a fetch:
  ``notice`` (the program ended -> the runtime learned it), ``transfer`` (->
  the copy landed), ``wake`` (-> the thread holds the tokens);
- for the ``--worst`` executions by their whole tail, and for every late
  collect of the window that the capture holds, the execution's device time
  beside its kind's median, the chain in ms from the execution's end, and
  every host event of ANY thread (name, thread, ms) open between the
  collect's start (or the execution's end, if earlier; half a second at
  most) and its return;
- the capture's longest dispatch span, and where it outlasts ten of its kind
  what every thread did meanwhile: the same wait on the ENQUEUE side, which
  neither ``late_collect_lost_ms`` nor ``fetch_tail_max_ms`` sees.

Traced or not, from the spans of the whole window (``readers/late_collects``):
what the window lost to late collects and the part of it before their
``ready`` marks, the late collects themselves, ``ready_ms`` beside total, and
the host's slack a tick (``host_slack_p50_ms``, which ``run.py`` prints only
traced); a traced run's end-to-end metrics (which it prints only untraced).

The tables also go to ``chiprun_out/describe_collects_<cell>_<seed>_t<trace>.txt``."""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import harness, xplane, xprograms, xruntime  # noqa: E402
from benchmark.readers import (host_device_skew, idle_by_phase, late_collects,  # noqa: E402
                               span_sum_percentile)
from benchmark.stats import percentile  # noqa: E402

PARTS = ("notice", "transfer", "wake", "all")
INSTANTS = ("enqueued", "start", "end", "done", "collect", "ready", "landed", "returned")


def by_thread(profile):
    """The host plane's events as (thread, ``HostEvent``): what
    ``xplane.Trace.host`` keeps, with the line's name."""
    out = []
    for plane in profile.planes:
        if plane.name == xplane.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    a = e.start_ns * 1e-9
                    out.append((line.name, xplane.HostEvent(
                        e.name, a, a + e.duration_ns * 1e-9, dict(e.stats))))
    return out


def newest_capture():
    files = sorted(harness.OUT_DIR.glob("**/*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    return by_thread(xplane.load(str(files[-1]))) if files else []


def chain_rows(obs, threads, worst=5, old_skew=None):
    found = xruntime.chain(obs)
    if found is None:
        return ["no chain: no capture, no runtime events in it (a CPU rehearsal, another "
                "runtime), collects that name no dispatch (a parent's program), or spans dropped"]
    rt = found.runtime
    rows = [f"shift {1e3 * rt.shift:+.4f} ms (upper edge: the smallest completion notice off), "
            f"interval [{1e3 * rt.interval[0]:+.4f}, {1e3 * rt.interval[1]:+.4f}], width "
            f"{1e3 * rt.width:.4f} ms; {len(rt.runs)} executions, {len(rt.enqueued)} enqueues, "
            f"{len(rt.done)} notices, {len(rt.landed)} transfers"]
    if old_skew is not None:
        rows.append(f"host_device_skew_ms of the same run (the old pairing's tight edge): "
                    f"{old_skew:+.4f}; the new shift is {old_skew - 1e3 * rt.shift:+.4f} under it")
    out = ", ".join(f"{n} {why}" for why, n in sorted(found.left_out.items())) or "none"
    share = 100.0 * len(found.links) / max(found.fetched, 1)
    rows.append(f"dispatch spans a collect fetched inside the capture: {found.fetched}; chained "
                f"{len(found.links)} ({share:.1f} %); left out: {out}; with an order that does "
                f"not hold: {found.disordered}")
    pairs = sorted({p for l in found.links for p in l.disordered()})
    if pairs:
        rows.append("orders that do not hold: " + ", ".join(
            f"{p} x{sum(1 for l in found.links if p in l.disordered())}" for p in pairs))
    rows += ["", f"{'kind':<14} {'n':>5} " + " ".join(f"{p + ' p50':>13} {'max':>9}" for p in PARTS)]
    for kind in sorted({l.kind for l in found.links}):
        mine = [l for l in found.links if l.kind == kind]
        cells = []
        for p in PARTS:
            ms = [1e3 * xruntime.LINKS[p](l) for l in mine]
            cells.append(f"{percentile(ms, 50):13.3f} {max(ms):9.3f}")
        rows.append(f"{kind:<14} {len(mine):5d} " + " ".join(cells))
    late = {s[3].get("of") for s, _, _ in late_collects.late(obs) or ()}
    picked = sorted(found.links, key=lambda l: -xruntime.LINKS["all"](l))[:worst]
    picked += [l for l in found.links if l.span_id in late and l not in picked]
    usual = {k: percentile([l.end - l.start for l in found.links if l.kind == k], 50)
             for k in {l.kind for l in found.links}}
    rows += ["", f"the {worst} longest tails and the window's late collects inside the capture "
                 "(ms from the execution's end, host clock):"]
    for l in picked:
        rows.append(f"{l.kind} span {l.span_id} run_id {l.run_id}"
                    + (" LATE" if l.span_id in late else "")
                    + f": ran {1e3 * (l.end - l.start):.3f} on the device (median "
                    f"{1e3 * usual[l.kind]:.3f}), tail {1e3 * xruntime.LINKS['all'](l):.3f} = "
                    + " + ".join(f"{p} {1e3 * xruntime.LINKS[p](l):.3f}" for p in PARTS[:3])
                    + (f"  [{' '.join(l.disordered())}]" if l.disordered() else ""))
        rows.append("   " + "  ".join(f"{n} {1e3 * (getattr(l, n) - l.end):+.3f}" for n in INSTANTS))
        rows += open_between(threads, min(l.end, l.collect), l.returned, l.end)
    # a late collect whose dispatch lies before the capture is no link: what
    # the threads did while it waited is still in the capture
    progs = xprograms.of(obs)
    w0, w1 = progs.window
    chained = {l.span_id for l in found.links}
    for h in xprograms.on_trace_clock(progs, obs.get("spans") or (), {xprograms.COLLECT}):
        if h.stats.get("of") in late - chained and h.end > w0 and h.start < w1:
            rows.append(f"{h.stats.get('what')} span {h.stats['of']} LATE, not chained (its "
                        f"dispatch or its return lies outside the capture): the collect took "
                        f"{1e3 * (h.end - h.start):.3f}; ms from its return:")
            rows += open_between(threads, max(h.start, w0), min(h.end, w1), h.end)
    # the same wait can fall on the ENQUEUE side (a dispatch span that blocks:
    # no collect is open, neither metric sees it): the capture's longest one
    spans = [h for h in xprograms.on_trace_clock(progs, obs.get("spans") or (),
                                                 idle_by_phase.DISPATCH)
             if h.start >= w0 and h.end <= w1]
    if spans:
        h = max(spans, key=lambda h: h.end - h.start)
        usual = percentile([x.end - x.start for x in spans if x.name == h.name], 50)
        rows.append(f"longest dispatch span inside the capture: {h.name} span "
                    f"{h.stats.get(xprograms.SPAN_ID)} {1e3 * (h.end - h.start):.3f} ms (median of "
                    f"its kind {1e3 * usual:.3f}), upload_ms {h.stats.get('upload_ms')} dispatch_ms "
                    f"{h.stats.get('dispatch_ms')}" + ("; ms from its start:" if
                    h.end - h.start > 10 * usual else ""))
        if h.end - h.start > 10 * usual:
            rows += open_between(threads, h.start, h.end, h.start)
    return rows


def open_between(threads, lo, hi, zero):
    """Every host event of any thread open between ``lo`` and ``hi`` (the
    last half second of it), in ms from ``zero``."""
    lo = max(lo, hi - 0.5)
    return [f"     {1e3 * (h.start - zero):+9.3f} .. {1e3 * (h.end - zero):+9.3f}  "
            f"{h.name[:64]}  [{thread}]"
            for thread, h in sorted(((t, h) for t, h in threads if h.end > lo and h.start < hi
                                     and h.name != xplane.CAPTURE), key=lambda th: th[1].start)]


def late_rows(obs, top=10):
    found = late_collects.late(obs)
    if found is None:
        return ["no collect, no tick, or spans dropped: nothing to read in the window"]
    t0 = (obs.get("window") or (0.0, 0.0))[0]
    slack = [span_sum_percentile.read(obs, "sched.tick", late_collects.COLLECT, q)
             for q in (50, 99, 100)]
    rows = [f"host slack a tick (sched.tick's tick_collects), ms: p50 {slack[0]:.4f} "
            f"p99 {slack[1]:.4f} max {slack[2]:.4f}",
            f"late collects of the whole window: {len(found)}; lost_ms "
            f"{late_collects.read(obs, 'lost_ms'):.3f}, of it before ready (unready_ms) "
            f"{late_collects.read(obs, 'unready_ms')}"]
    for (name, a, b, args), over, unready in found[:top]:
        rows.append(f"  at {a - t0:8.3f} s  {args.get('what')} #{args.get('of')}: "
                    f"{1e3 * (b - a):.3f} ms, ready after {args.get('ready_ms')} ms; past the "
                    f"usual + a tick by {over:.3f}, before ready {unready}")
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--worst", type=int, default=5)
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                    help="override one key of the traffic file, as run.py's")
    ap.add_argument("--rehearse", action="store_true",
                    help="the CPU pre-flight of run.py: the flow, and nothing to chain")
    args = ap.parse_args(argv)

    overrides = {}
    for item in args.set:
        key, _, value = item.partition("=")
        overrides[key] = json.loads(value)
    man, cell, obs = harness.observe(
        workload=args.workload, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        rehearse=args.rehearse, overrides=overrides, t_process=T_PROCESS)
    device = obs["device"]
    rows = [f"describe_collects: {cell['name']} seed {args.seed} trace {args.trace}"]
    if args.trace:
        old = None if args.rehearse else host_device_skew.read(
            obs, "decode_tick", r"^jit_decode_impl$")
        rows += chain_rows(obs, newest_capture() if obs.get("trace") is not None else [],
                           args.worst, old) + [""]
    rows += late_rows(obs)
    if args.trace and not args.rehearse:
        # (``run.py`` prints these untraced only: what the profiler costs)
        e2e = harness.read_metrics(harness.metrics_of(man, cell["name"], False), obs)
        rows.append("end-to-end metrics of this TRACED run: " + ", ".join(
            f"{k} {v['value']:.4f}" for k, v in e2e.items()))
    text = "\n".join(rows)
    out = harness.ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / f"describe_collects_{cell['name']}_{args.seed}_t{args.trace}.txt").write_text(text + "\n")
    print(text, flush=True)
    # the line ``run.py`` prints, so that one run serves both
    breakdown = None
    if obs.get("trace") is not None and not args.rehearse:
        device["busy_s"], device["window_s"] = obs["trace"].busy_s(), obs["trace"].window_s
        breakdown = xplane.breakdown(obs["trace"])
    metrics = {} if args.rehearse else harness.read_metrics(
        harness.metrics_of(man, cell["name"], bool(args.trace)), obs)
    for note in obs.get("notes", []):
        harness.say(note)
    print(harness.result_line(correct=bool(obs["correct"]) and not args.rehearse,
                              attempted=obs["attempted"], failed=obs["failed"], metrics=metrics,
                              device=device, breakdown=breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
