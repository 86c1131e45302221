"""The share (%) of its roofline that a named XLA body of a model of latent
attention over EVERY cached row takes, over the dispatches the trace holds:
max(FLOPs / peak, bytes / bandwidth) that the dispatches NEED of the body, all
layers that run it, over the body's device time in the program's executions.

``cost`` names the need: ``mla_prefill`` (``costs_mla``: the traced packs'
``(start, end)`` ranges, from the requests' own prefill chunks, so a chunk
behind a prefix hit starts where the hit ends), ``mla_decode`` (``costs_mla``:
the traced ``decode_tick`` spans' ``batch`` and ``ctx_tokens``).  None where the
program has no such scope, its spans no such argument, or the configuration no
such keys.
"""
from .. import costs, costs_mla
from ..peaks import peaks_for
from .scope_ops import per_execution
from .scope_roofline import _traced_packs


def _traced_ticks(obs):
    """The arguments of the ``decode_tick`` spans inside the ticks that lie
    wholly in the capture."""
    traced = obs["trace"].whole_spans("bench.tick", "tick")
    if not traced:
        return []
    h0, h1 = obs["ticks"][traced[0]][0], obs["ticks"][traced[-1]][1]
    return [args for name, a, b, args in obs.get("spans", ())
            if name == "decode_tick" and h0 <= a and b <= h1]


def read(obs, module, scope, cost):
    if obs.get("trace") is None or obs["device"]["platform"] != "tpu" \
            or "requests" not in obs or "ticks" not in obs:
        return None
    m = obs["model"]
    if "kv_lora_rank" not in m:
        return None
    secs = per_execution(obs, module, scope)
    if not secs or not sum(secs):
        return None
    peaks, layers = peaks_for(obs["device"]["kind"]), m["num_hidden_layers"]
    if cost == "mla_decode":
        work = _traced_ticks(obs)
        if not work or any("ctx_tokens" not in t for t in work):
            return None
        need = sum(layers * costs.roofline_min_s(
            *costs_mla.mla_decode(t["ctx_tokens"], t["batch"], m), peaks) for t in work)
    else:
        work = _traced_packs(obs)
        if not work:
            return None
        need = sum(layers * costs.roofline_min_s(*costs_mla.mla_prefill(entries, m), peaks)
                   for entries in work)
    # the trace may hold one execution more or fewer than the dispatches that
    # lie wholly in it: compare like with like, per dispatch
    return 100.0 * (need / len(work)) / (sum(secs) / len(secs))
