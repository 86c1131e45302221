"""Prompt + output tokens processed INSIDE the window, over the window.

An output token counts at its stamp.  Prompt tokens count along the curve of
COMPLETED prefill: at each first token the harness is handed, that request's
prompt is done, so the points (first-token time, prompt tokens completed so
far) are known exactly, and between two of them the curve is taken as a
straight line.  The window's share is the curve's rise from its start to its
end.  (Counting a whole ~2000-token prompt at one instant, or a whole request
when it finishes, makes the rate jump by 4% a request at the ~25 requests a
window holds; spreading each prompt from its own submit to its first token
smears work the scheduler did late in that interval over all of it.)  The
driver ticks on after the window until one more prefill completes, so the
curve reaches past the window's end.
"""
import bisect


def read(obs):
    if "requests" not in obs:
        return None
    t0, t1 = obs["window"]
    reqs = [r for r in obs["requests"] if r["token_times"]]
    if not reqs:
        return None
    outputs = sum(1 for r in reqs for t in r["token_times"] if t0 <= t < t1)
    reqs.sort(key=lambda r: r["token_times"][0])
    times = [min(r["submit"] for r in obs["requests"])]
    done = [0.0]
    for r in reqs:
        times.append(r["token_times"][0])
        done.append(done[-1] + r["prompt_len"])

    def completed(t):
        i = bisect.bisect_right(times, t)
        if i == 0:
            return 0.0
        if i == len(times):
            return done[-1]
        a, b = times[i - 1], times[i]
        return done[i - 1] + (done[i] - done[i - 1]) * (t - a) / (b - a)

    return (outputs + completed(t1) - completed(t0)) / (t1 - t0)
