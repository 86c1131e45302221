"""A named XLA body's share (%) of its roofline over the prefill packs the
trace holds: max(FLOPs / peak, bytes / bandwidth) that the packs' (start, end)
ranges NEED of the body, all layers that run it, over the body's device time
in the pack program's executions.  ``cost`` names the function of
``costs_latent.py``; ranges come from the requests' own prefill chunks, as in
``ctx_prefill_roofline``."""
from .. import costs, costs_latent
from ..peaks import peaks_for
from .scope_ops import per_execution


def _traced_packs(obs):
    tr = obs["trace"]
    traced = tr.whole_spans("bench.tick", "tick")
    if not traced:
        return []
    h0, h1 = obs["ticks"][traced[0]][0], obs["ticks"][traced[-1]][1]
    packs = {}
    for r in obs["requests"]:
        start = r["prompt_len"] - sum(n for _, _, n in r["chunks"])
        for a, b, n in r["chunks"]:
            if h0 <= a and b <= h1:
                packs.setdefault((a, b), []).append((start, start + n))
            start += n
    return list(packs.values())


def read(obs, module, scope, cost):
    if obs.get("trace") is None or obs["device"]["platform"] != "tpu" \
            or "requests" not in obs:
        return None
    secs = per_execution(obs, module, scope)
    packs = _traced_packs(obs)
    if not secs or not packs or not sum(secs):
        return None
    m, peaks = obs["model"], peaks_for(obs["device"]["kind"])
    kinds = m["layer_types"][: m["num_hidden_layers"]]
    need = 0.0
    for entries in packs:
        if cost == "expert_matmul":
            layers = m["num_hidden_layers"] - m["first_k_dense_replace"]
            c = obs.get("counters") or {}
            if not c.get("expert_pairs_routed"):
                return None
            tokens = sum(b - a for a, b in entries)
            held = tokens * m["num_experts_per_tok"] \
                * c["expert_pairs_held"] / c["expert_pairs_routed"]
            touched = min(m["n_routed_experts"], held)
            fl, by = costs_latent.expert_matmul(held, touched, tokens, m)
        else:
            layers = kinds.count("full_attention")
            fl, by = getattr(costs_latent, cost)(entries, m)
        need += layers * costs.roofline_min_s(fl, by, peaks)
    # the trace may hold one execution more or fewer than the ticks that lie
    # wholly in it: compare like with like, per pack
    return 100.0 * (need / len(packs)) / (sum(secs) / len(secs))
