"""Packed-ctx kernel's share (%) of its roofline over the window's packs that
the trace holds: max(FLOPs / peak, bytes / bandwidth) of every traced ctx
pack's (start, end) ranges, all layers, over the kernel's device time.  Ranges
come from the requests' own prefill chunks (the program's request traces);
a pack is a ctx pack when any of its chunks starts past position 0."""
from .. import costs
from ..peaks import peaks_for


def read(obs, pattern):
    tr = obs.get("trace")
    if tr is None or obs["device"]["platform"] != "tpu" or "requests" not in obs:
        return None
    m = obs["model"]
    hq, hkv, hd = costs.heads(m)
    peaks = peaks_for(obs["device"]["kind"])
    # which host-clock interval the trace covers: the ticks it holds
    traced = tr.whole_spans("bench.tick", "tick")
    secs, calls = tr.kernel_seconds(pattern)
    if not traced or not calls:
        return None
    h0, h1 = obs["ticks"][traced[0]][0], obs["ticks"][traced[-1]][1]
    packs = {}
    for r in obs["requests"]:
        start = r["prompt_len"] - sum(n for _, _, n in r["chunks"])
        for a, b, n in r["chunks"]:
            if h0 <= a and b <= h1:
                packs.setdefault((a, b), []).append((start, start + n))
            start += n
    need = 0.0
    for entries in packs.values():
        if any(s > 0 for s, _ in entries):
            need += m["num_hidden_layers"] * costs.roofline_min_s(
                *costs.packed_ctx(entries, hq, hkv, hd), peaks)
    return 100.0 * need / secs if need else None
