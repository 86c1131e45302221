"""A named XLA body's share (%) of its roofline in a model of single-mixer
blocks, over the executions the trace holds of ONE program (``module``):
max(FLOPs / peak, bytes / bandwidth) that the executions NEED of the body, all
blocks that run it, over the body's device time in them.  ``cost`` names the
function of ``costs_ssm.py``:

- ``ssm_step`` (the decode tick): the live slots of the traced ticks, from the
  host's record of each tick;
- ``ssm_scan`` (the pack): the chunks of the requests' own prefill chunks that
  lie in the trace, a page each and the last of a prompt shorter;
- ``expert_matmul`` (the decode tick): per tick and block the window's mean of
  pairs on held experts and of held experts TOUCHED, from the program's
  device-side counts of its decode ticks (``expert_pairs_held_decode``,
  ``experts_touched_decode`` over ``decode_ticks``).
"""
from .. import costs, costs_ssm
from ..peaks import peaks_for
from .scope_ops import per_execution
from .scope_roofline import _traced_packs


def _traced_ticks(obs):
    """(n decoding, ...) host records of the ticks wholly inside the capture."""
    traced = obs["trace"].whole_spans("bench.tick", "tick")
    return [obs["ticks"][i] for i in traced]


def read(obs, module, scope, cost):
    if obs.get("trace") is None or obs["device"]["platform"] != "tpu" \
            or "requests" not in obs:
        return None
    secs = per_execution(obs, module, scope)
    if not secs or not sum(secs):
        return None
    m, peaks = obs["model"], peaks_for(obs["device"]["kind"])
    kinds = m["hybrid_override_pattern"][: m["num_hidden_layers"]]
    c = obs.get("counters") or {}
    if cost == "ssm_step":
        live = [t[2] for t in _traced_ticks(obs) if t[2]]
        if not live:
            return None
        need = sum(costs.roofline_min_s(*costs_ssm.ssm_step(n, m), peaks) for n in live) / len(live)
        blocks = kinds.count("M")
    elif cost == "ssm_scan":
        packs = _traced_packs(obs)
        if not packs:
            return None
        bs = obs["engine"]["block_size"]
        need = sum(costs.roofline_min_s(*costs_ssm.ssm_scan(
            [min(bs, b - p) for a, b in entries for p in range(a, b, bs)], m), peaks)
            for entries in packs) / len(packs)
        blocks = kinds.count("M")
    else:
        n, blocks = c.get("decode_ticks", 0), kinds.count("E")
        if not n or not c.get("experts_touched_decode"):
            return None
        need = costs.roofline_min_s(*costs_ssm.expert_matmul(
            c["expert_pairs_held_decode"] / n / blocks,
            c["experts_touched_decode"] / n / blocks, m), peaks)
    return 100.0 * blocks * need / (sum(secs) / len(secs))
