"""A named XLA body's share (%) of its roofline in a model of two-norm blocks
(Gated DeltaNet beside gated attention, experts in every block), over the
executions the trace holds of ONE program (``module``): max(FLOPs / peak, bytes
/ bandwidth) that the executions NEED of the body, all blocks that run it, over
the body's device time in them.  ``cost`` names the function of
``costs_gdn.py``:

- ``gdn_step`` (the decode tick): the live slots of the traced ticks, from the
  host's record of each tick;
- ``gdn_scan`` (the pack): the chunks of the requests' own prefill chunks that
  lie in the trace, a page each and the last of a prompt shorter;
- ``expert_matmul`` (the pack): per pack and block the window's mean of pairs
  on held experts and of held experts TOUCHED in packs, from the program's
  device-side counts (all dispatches' less the decode ticks', over
  ``prefill_dispatches``).

Returns None where the program has no such scope or counter."""
from .. import costs, costs_gdn
from ..peaks import peaks_for
from .scope_ops import per_execution
from .scope_roofline import _traced_packs
from .state_roofline import _traced_ticks


def read(obs, module, scope, cost):
    if obs.get("trace") is None or obs["device"]["platform"] != "tpu" \
            or "requests" not in obs:
        return None
    secs = per_execution(obs, module, scope)
    if not secs or not sum(secs):
        return None
    m, peaks = obs["model"], peaks_for(obs["device"]["kind"])
    c = obs.get("counters") or {}
    if cost == "gdn_step":
        live = [t[2] for t in _traced_ticks(obs) if t[2]]
        if not live:
            return None
        need = sum(costs.roofline_min_s(*costs_gdn.gdn_step(n, m), peaks) for n in live) / len(live)
        blocks = costs_gdn.gdn_blocks(m)
    elif cost == "gdn_scan":
        packs = _traced_packs(obs)
        if not packs:
            return None
        bs = obs["engine"]["block_size"]
        need = sum(costs.roofline_min_s(*costs_gdn.gdn_scan(
            [min(bs, b - p) for a, b in entries for p in range(a, b, bs)], m), peaks)
            for entries in packs) / len(packs)
        blocks = costs_gdn.gdn_blocks(m)
    else:
        n, blocks = c.get("prefill_dispatches", 0), m["num_hidden_layers"]
        touched = c.get("experts_touched", 0) - c.get("experts_touched_decode", 0)
        if not n or touched <= 0:
            return None
        pairs = c["expert_pairs_held"] - c["expert_pairs_held_decode"]
        need = costs.roofline_min_s(*costs_gdn.expert_matmul(
            pairs / n / blocks, touched / n / blocks, m), peaks)
    return 100.0 * blocks * need / (sum(secs) / len(secs))
