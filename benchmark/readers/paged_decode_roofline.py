"""Paged-decode kernel's share (%) of its roofline over the traced ticks: the
bytes (and FLOPs) every traced decode tick's live contexts need, all layers,
over the kernel's device time.  The harness logs each tick's live context
lengths; ``bench.tick`` annotations say which ticks the trace holds."""
from .. import costs
from ..peaks import peaks_for


def read(obs, pattern):
    tr = obs.get("trace")
    if tr is None or obs["device"]["platform"] != "tpu" or "ticks" not in obs:
        return None
    m = obs["model"]
    hq, hkv, hd = costs.heads(m)
    peaks = peaks_for(obs["device"]["kind"])
    traced = tr.whole_spans("bench.tick", "tick")
    secs, calls = tr.kernel_seconds(pattern)
    if not traced or not calls:
        return None
    need = 0.0
    for i in traced:
        _, _, n_dec, ctx_sum = obs["ticks"][i][:4]
        if n_dec:
            # one call a layer; the sum over sequences is linear in ctx
            f, by = costs.paged_decode([ctx_sum], hq, hkv, hd)
            by += (n_dec - 1) * 2 * 2 * hq * hd  # q/out of the other rows
            need += m["num_hidden_layers"] * costs.roofline_min_s(f, by, peaks)
    return 100.0 * need / secs
