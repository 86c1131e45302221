"""The device time a window lost to collects that returned LATE (ms), from the
program's spans alone: the whole window, traced or not.

One ahead, a collect is hidden by the execution enqueued behind it while it
is late by less than that execution lasts.  So a ``tick_collect`` is LATE
where it lasted longer than the median of the window's collects of its kind
(its ``what``) by more than the window's median ``sched.tick`` AND the tick
after it found its programs ended already (its collects took under half the
median tick's: the queue had run dry).  Without the second half a collect
behind a long EXECUTION reads late too (a tick of two packs and a step on
cell 6: the device was busy, the tick after waits as long as any).  ``what``:

- ``lost_ms``: the sum over late collects of (excess over the kind's median
  - the median tick), what the device idled for them;
- ``unready_ms``: the part of that sum that lies BEFORE the collects'
  ``ready`` marks (the runtime had not yet called the program's result
  defined: the device, or the completion notice); the rest is the copy and
  the thread's wake-up.

0.0 where no collect is late.  None where the recorder dropped spans, where
the window holds no collect or no tick, and for ``unready_ms`` where no
collect carries a ``ready_ms`` (a program without the mark)."""
import bisect
import statistics

from ..xprograms import COLLECT, spans_dropped

TICK = "sched.tick"


def late(obs):
    """[(the collect's span, ms of the window it ran past the kind's median +
    a tick, the part of those before its ``ready`` mark or None)] of the
    window's late collects, longest first; None where there is nothing to
    read.  Medians are over the spans that ended inside the window; a late
    collect that straddles an end of it counts for its part inside."""
    spans = obs.get("spans") or ()
    if spans_dropped(spans):
        return None
    t0, t1 = obs.get("window") or (float("-inf"), float("inf"))
    ticks = sorted((a, b) for name, a, b, _ in spans if name == TICK)
    collects = [s for s in spans if s[0] == COLLECT]
    inside = [1e3 * (b - a) for a, b in ticks if t0 <= b < t1]
    if not inside or not any(t0 <= s[2] < t1 for s in collects):
        return None
    tick = statistics.median(inside)
    # each tick's slack: what its collects took together (one thread: a
    # collect lies inside the tick that began last before it)
    starts, slack = [a for a, _ in ticks], [0.0] * len(ticks)
    for _, a, b, _ in collects:
        slack[bisect.bisect_right(starts, a) - 1] += 1e3 * (b - a)
    dry = 0.5 * statistics.median(v for v, (_, b) in zip(slack, ticks) if t0 <= b < t1)
    out = []
    for what in {s[3].get("what") for s in collects}:
        kind = [s for s in collects if s[3].get("what") == what and s[2] > t0 and s[1] < t1]
        ended = [1e3 * (b - a) for _, a, b, _ in kind if t0 <= b < t1]
        if not ended:
            continue
        usual = 1e-3 * (statistics.median(ended) + tick)
        for s in kind:
            _, a, b, args = s
            since = max(a + usual, t0)   # from here on the device had nothing to run
            over = 1e3 * (min(b, t1) - since)
            after = bisect.bisect_right(starts, a)   # the tick after the collect's own
            if over > 0.0 and after < len(ticks) and slack[after] < dry:
                ready = args.get("ready_ms")
                out.append((s, over, None if ready is None else
                            1e3 * max(min(a + 1e-3 * ready, b, t1) - since, 0.0)))
    return sorted(out, key=lambda x: -x[1])


def read(obs, what):
    found = late(obs)
    if found is None:
        return None
    if what == "lost_ms":
        return sum(over for _, over, _ in found)
    spans = obs.get("spans") or ()
    if not any("ready_ms" in s[3] for s in spans if s[0] == COLLECT):
        return None
    return sum(unready or 0.0 for _, _, unready in found)
