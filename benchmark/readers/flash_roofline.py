"""Flash kernel's share (%) of its roofline over the traced window: the least
time the steps in the window NEED in the kernel - one forward and one backward
a layer and step, on this chip's rows - over the device time of the flash
kernels (forward, both backward kernels).  The forward that selective remat
runs a second time is time spent and not work needed.  Steps in the window
come from the run's own rate (the device is busy throughout), not from
counting kernel events: other custom-calls of the same result shape would
count as calls, but add next to no time.  ``forward`` and ``backward`` are
regexes over op keys (``xplane.op_key``)."""
from .. import costs
from ..peaks import peaks_for


def read(obs, forward, backward):
    tr = obs.get("trace")
    if tr is None or obs["device"]["platform"] != "tpu" or not obs.get("steps"):
        return None
    m = obs["model"]
    hq, hkv, hd = costs.heads(m)
    peaks = peaks_for(obs["device"]["kind"])
    b, s = obs["micro"], obs["seq"]
    spent = tr.kernel_seconds(forward)[0] + tr.kernel_seconds(backward)[0]
    if spent <= 0:
        return None
    t0, t1 = obs["window"]
    steps = obs["steps"] / (t1 - t0) * tr.window_s
    a_step = m["num_hidden_layers"] * (
        costs.roofline_min_s(*costs.flash_fwd(b, s, hq, hkv, hd), peaks)
        + costs.roofline_min_s(*costs.flash_bwd(b, s, hq, hkv, hd), peaks))
    return 100.0 * steps * a_step / spent
