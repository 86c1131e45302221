"""Operations and bytes the ALGORITHM of gated grouped-query attention needs in
a model whose layers attend every key (K / V pages) or a window (a K / V ring
a slot), from positions alone: the yardstick of the ``full_attn`` and
``window_attn`` rooflines (``costs.py``'s rules: needed work only, operands
read once, results written once).  ``m`` holds a configuration's published
keys.  The need is counted from what the mask ALLOWS, so it reads the same work
whatever implements the body: a body that walks keys outside the window, pads
heads or re-reads rows only takes longer for it.
"""
from __future__ import annotations

from typing import Tuple

KINDS = {"full_attn": "full_attention", "window_attn": "sliding_attention"}


def heads_of(m: dict, layer_type: str) -> Tuple[int, int]:
    """(query heads of a layer of ``layer_type``, how many such layers are held)."""
    n = m["num_hidden_layers"]
    mine = [h for t, h in zip(m["layer_types"][:n], m["num_attention_heads_per_layer"][:n])
            if t == layer_type]
    return (mine[0] if mine else 0), len(mine)


def attention(pairs: float, q_rows: float, kv_rows: float, m: dict, layer_type: str,
              *, bytes_per_el: int = 2) -> Tuple[float, float]:
    """All layers of ``layer_type`` together: ``pairs`` (query, key) pairs the
    mask allows, summed over those layers (q.k^T and p.v: 4 hd FLOPs a pair and
    query head); ``q_rows`` query rows in and as many output rows out,
    ``kv_rows`` K rows and as many V rows read once, both summed over the
    layers too."""
    hq, _ = heads_of(m, layer_type)
    hkv, hd = m["num_key_value_heads"], m["head_dim"]
    return (4.0 * hq * hd * pairs,
            float(bytes_per_el) * hd * (2 * hq * q_rows + 2 * hkv * kv_rows))
