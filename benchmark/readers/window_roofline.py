"""The share (%) of its roofline that gated attention of one kind takes in the
prefill packs the trace holds, in a model of full layers (K / V pages) and
window layers (K / V rings): max(FLOPs / peak, bytes / bandwidth) that the
packs NEED of the body, all layers of the kind, over the body's device time in
the pack program's executions.  The need comes from the traced packs' own span
arguments (``prefill_pack``: ``tokens``, ``ctx_pages``, and the runner's
``full_keys`` / ``window_keys`` / ``window_ctx``, counted from positions at
dispatch), ``cost`` names the kind (``costs_window.KINDS``).  None where the
program has no such scope or its spans no such argument."""
from .. import costs, costs_window
from ..peaks import peaks_for
from .scope_ops import per_execution


def _traced_pack_args(obs):
    """The arguments of the ``prefill_pack`` spans inside the ticks that lie
    wholly in the capture."""
    traced = obs["trace"].whole_spans("bench.tick", "tick")
    if not traced:
        return []
    h0, h1 = obs["ticks"][traced[0]][0], obs["ticks"][traced[-1]][1]
    return [args for name, a, b, args in obs.get("spans", ())
            if name == "prefill_pack" and h0 <= a and b <= h1]


def read(obs, module, scope, cost):
    if obs.get("trace") is None or obs["device"]["platform"] != "tpu" \
            or "ticks" not in obs:
        return None
    secs = per_execution(obs, module, scope)
    packs = _traced_pack_args(obs)
    keys = "full_keys" if cost == "full_attn" else "window_keys"
    if not secs or not sum(secs) or not packs or any(keys not in p for p in packs):
        return None
    m, peaks = obs["model"], peaks_for(obs["device"]["kind"])
    layer_type = costs_window.KINDS[cost]
    _, layers = costs_window.heads_of(m, layer_type)
    bs = obs["engine"]["block_size"]
    need = 0.0
    for p in packs:
        under = p["ctx_pages"] * bs * layers if cost == "full_attn" else p["window_ctx"]
        need += costs.roofline_min_s(*costs_window.attention(
            p[keys], p["tokens"] * layers, under + p["tokens"] * layers, m, layer_type), peaks)
    # the trace may hold one execution more or fewer than the ticks that lie
    # wholly in it: compare like with like, per pack
    return 100.0 * (need / len(packs)) / (sum(secs) / len(secs))
