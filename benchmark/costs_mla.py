"""Operations and bytes the ALGORITHM of latent attention over EVERY cached row
needs, from shapes alone: the yardstick of the ``mla_prefill`` and
``mla_decode`` rooflines (``costs.py``'s rules: needed work only, operands read
once, results written once, bf16).  ``m`` holds a configuration's published
keys; a SEGMENT is the ``(start, end)`` token range of one sequence that one
dispatch serves, for ONE layer.

One attention has two forms and the need is the CHEAPER one's, whichever body
runs: ABSORBED, ``2 (2 kv_lora_rank + rope)`` FLOPs a (query, key) pair and
head (the queries through ``W_uk`` before, the sum through ``W_uv`` after);
DECOMPRESSED, ``2 (nope + rope) + 2 v`` a pair and head plus ``2 kv_lora_rank
(nope + v)`` a key and head ONCE a segment (``W_uk`` and ``W_uv`` applied to the
key's row, shared by the segment's queries).  So a share of this roofline
cannot pass 100% by the choice of form.  Bytes: the segment's key rows read
once (``kv_lora_rank + rope`` wide), its queries in and its heads' values out.
"""
from __future__ import annotations

from typing import Sequence, Tuple

from .costs import causal_pairs

Entries = Sequence[Tuple[int, int]]


def widths(m: dict) -> Tuple[int, int, int, int, int]:
    """(heads, kv rank, nope, rope, v) of a configuration's published keys."""
    return (m["num_attention_heads"], m["kv_lora_rank"], m["qk_nope_head_dim"],
            m["qk_rope_head_dim"], m["v_head_dim"])


def absorbed_flops(pairs: float, m: dict) -> float:
    h, r, _, rope, _ = widths(m)
    return 2.0 * h * (2 * r + rope) * pairs


def decompressed_flops(pairs: float, keys: float, m: dict) -> float:
    h, r, nope, rope, v = widths(m)
    return 2.0 * h * (nope + rope + v) * pairs + 2.0 * h * r * (nope + v) * keys


def crossing(m: dict) -> int:
    """Queries a segment from which the decompressed form is the cheaper one
    over a long context (171 at DeepSeek-V2's widths)."""
    _, r, nope, rope, v = widths(m)
    saved = (2 * r + rope) - (nope + rope + v)
    return -(-r * (nope + v) // saved) if saved > 0 else 1 << 62


def segment(start: int, end: int, m: dict) -> Tuple[float, float]:
    """Queries at positions ``start .. end - 1`` of one sequence over its keys
    ``0 .. end - 1``, causal: (FLOPs, bytes)."""
    h, r, nope, rope, v = widths(m)
    n, pairs = end - start, causal_pairs(end - start, start)
    flops = min(absorbed_flops(pairs, m), decompressed_flops(pairs, end, m))
    return flops, 2.0 * (r + rope) * end + 2.0 * n * h * (nope + rope + v)


def mla_prefill(entries: Entries, m: dict) -> Tuple[float, float]:
    """One pack's segments, one layer."""
    flops = by = 0.0
    for start, end in entries:
        f, b = segment(start, end, m)
        flops, by = flops + f, by + b
    return flops, by


def mla_decode(ctx_tokens: float, batch: int, m: dict) -> Tuple[float, float]:
    """One decode tick, one layer: ``batch`` slots of one query each over
    ``ctx_tokens`` keys in all (a slot's own new row included).  One query a
    segment, so the absorbed form."""
    h, r, nope, rope, v = widths(m)
    return absorbed_flops(ctx_tokens, m), \
        2.0 * (r + rope) * ctx_tokens + 2.0 * batch * h * (nope + rope + v)
