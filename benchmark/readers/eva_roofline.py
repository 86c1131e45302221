"""The share (%) of its roofline that EVA attention takes in the packs or the
decode ticks the trace holds: max(FLOPs / peak, bytes / bandwidth) that the
dispatches NEED of the body (``costs_eva.attention``, every layer), over the
body's device time in the program's executions.  The need comes from the traced
dispatches' own span arguments (``span``: ``prefill_pack`` | ``decode_tick``;
the runner's ``rows_total`` and ``eva_pairs``, counted from positions at
dispatch, and the span's ``tokens`` or ``batch``: its query rows).  None where
the program has no such scope or its spans no such argument."""
from .. import costs, costs_eva
from ..peaks import peaks_for
from .scope_ops import per_execution


def _traced_args(obs, span):
    """The arguments of the ``span`` spans inside the ticks that lie wholly in
    the capture."""
    traced = obs["trace"].whole_spans("bench.tick", "tick")
    if not traced:
        return []
    h0, h1 = obs["ticks"][traced[0]][0], obs["ticks"][traced[-1]][1]
    return [args for name, a, b, args in obs.get("spans", ())
            if name == span and h0 <= a and b <= h1]


def read(obs, module, scope, span):
    if obs.get("trace") is None or obs["device"]["platform"] != "tpu" \
            or "ticks" not in obs:
        return None
    secs = per_execution(obs, module, scope)
    calls = _traced_args(obs, span)
    if not secs or not sum(secs) or not calls or any("rows_total" not in c for c in calls):
        return None
    m, peaks = obs["model"], peaks_for(obs["device"]["kind"])
    need = sum(costs.roofline_min_s(*costs_eva.attention(
        c["eva_pairs"], c.get("tokens", c.get("batch", 0)), c["rows_total"], m), peaks)
        for c in calls) * m["num_hidden_layers"]
    # the trace may hold one execution more or fewer than the ticks that lie
    # wholly in it: compare like with like, per dispatch
    return 100.0 * (need / len(calls)) / (sum(secs) / len(secs))
