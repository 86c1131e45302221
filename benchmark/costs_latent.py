"""Operations and bytes the ALGORITHMS of latent attention with a key selector
and of a held share of experts need, from shapes alone: the yardstick of the
``indexer``, ``sparse_attn`` and ``expert_matmul`` rooflines (``costs.py``'s
rules: needed work only, operands read once, results written once, bf16 in
and float32 scores out).  ``m`` holds a configuration's published keys;
``entries`` are one pack's ``(start, end)`` token ranges, for ONE layer.
"""
from __future__ import annotations

from typing import Sequence, Tuple

from .costs import causal_pairs

Entries = Sequence[Tuple[int, int]]


def indexer(entries: Entries, m: dict) -> Tuple[float, float]:
    """Index scores of every (query, key <= query) pair: ``index_n_heads``
    dot products of ``index_head_dim`` a pair.  Reads the context's index keys
    and the queries (with their head weights) once, writes a float32 score a
    pair."""
    j, dim = m["index_n_heads"], m["index_head_dim"]
    flops = by = 0.0
    for start, end in entries:
        n, pairs = end - start, causal_pairs(end - start, start)
        flops += 2.0 * j * dim * pairs
        by += 2 * dim * end + 2 * n * j * dim + 4 * n * j + 4 * pairs
    return flops, by


def selected_keys(entries: Entries, topk: int) -> int:
    """(query, selected key) pairs: a query at position p attends
    min(p + 1, topk) keys."""
    total = 0
    for start, end in entries:
        mid = min(max(start, topk), end)
        total += causal_pairs(mid - start, start) + (end - mid) * topk
    return total


def sparse_attn(entries: Entries, m: dict) -> Tuple[float, float]:
    """Absorbed-form attention over the selected rows: per pair and head a
    ``kv_lora_rank + qk_rope_head_dim`` dot product and a ``kv_lora_rank``
    weighted sum.  Each query reads its own selected rows (selections differ
    by query), its absorbed queries, and writes its heads' latent outputs."""
    h, r, rope = m["num_attention_heads"], m["kv_lora_rank"], m["qk_rope_head_dim"]
    pairs = selected_keys(entries, m["index_topk"])
    n = sum(end - start for start, end in entries)
    return 2.0 * h * (2 * r + rope) * pairs, 2.0 * (r + rope) * pairs + 2.0 * n * h * (2 * r + rope)


def expert_matmul(pairs_held: float, experts_touched: int, tokens: int, m: dict
                  ) -> Tuple[float, float]:
    """The three matmuls of a SwiGLU of width ``moe_intermediate_size`` for
    every (token, held expert) pair.  Reads each touched expert's weights once
    and a row in and out per pair."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    return 6.0 * d * f * pairs_held, 2.0 * 3 * d * f * experts_touched + 2.0 * 2 * d * pairs_held
