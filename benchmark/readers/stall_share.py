"""Share (%) of in-window token gaps that contain the START of a span of the
given name.  With ``engine.pack_emit`` it is the share of gaps stalled behind
someone else's prefill: a pack's result is booked, fetched or not, in the call
that returns the tokens of the execution the pack ran in, so the booking
starts inside the gap that execution made longer, one ahead or back to back
(the ``prefill_pack`` span itself opens one call EARLIER since PR 43: it marked
the gap before).  ``itl_p95_ms`` reads the stall only while this is well above
5%."""
import bisect


def read(obs, span):
    if "requests" not in obs:
        return None
    t0, t1 = obs["window"]
    starts = sorted(a for name, a, b, _ in obs.get("spans", ()) if name == span)
    total = stalled = 0
    for r in obs["requests"]:
        tt = r["token_times"]
        for a, b in zip(tt, tt[1:]):
            if t0 <= b < t1:
                total += 1
                i = bisect.bisect_left(starts, a)
                stalled += i < len(starts) and starts[i] < b
    return 100.0 * stalled / total if total else None
