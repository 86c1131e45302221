"""The flash kernels' share (%) of their roofline in a step whose layers mask
DIFFERENTLY (a window on some, every key on others): the least time the traced
steps NEED - forward and backward of the (query, key) pairs each layer's mask
ALLOWS, counted from positions by the architecture's ``attended_pairs`` at
``costs.flash_fwd`` / ``flash_bwd``'s FLOPs a pair, q, k, v, out read and
written once a layer - over the device time of the flash kernels (forward, both
backward kernels, whichever family implements the window).  The same work
whatever walks the band; the forward that recomputation runs again is time
spent.  Steps in the trace come from the run's own rate (``flash_roofline``)."""
from .. import costs, harness
from ..peaks import peaks_for


def read(obs, forward, backward):
    tr = obs.get("trace")
    if tr is None or obs["device"]["platform"] != "tpu" or not obs.get("steps"):
        return None
    m = obs["model"]
    pairs_of = getattr(harness.module("models", m["model_type"]), "attended_pairs", None)
    spent = tr.kernel_seconds(forward)[0] + tr.kernel_seconds(backward)[0]
    if pairs_of is None or spent <= 0:
        return None
    hq, hkv, hd = costs.heads(m)
    peaks = peaks_for(obs["device"]["kind"])
    b, s = obs["micro"], obs["seq"]
    kinds = m["layer_types"][: m["num_hidden_layers"]]
    a_step = 0.0
    for kind, pairs in pairs_of(m, s).items():
        share = pairs / (kinds.count(kind) * costs.causal_pairs(s))  # of a causal layer's pairs
        for cost in (costs.flash_fwd, costs.flash_bwd):
            flops, by = cost(b, s, hq, hkv, hd)
            a_step += kinds.count(kind) * costs.roofline_min_s(flops * share, by, peaks)
    t0, t1 = obs["window"]
    steps = obs["steps"] / (t1 - t0) * tr.window_s
    return 100.0 * steps * a_step / spent
