"""Operations and bytes the ALGORITHMS of a Gated DeltaNet block's delta rule
and of a held share of SwiGLU experts need, from shapes alone: the yardstick of
the ``gdn_step``, ``gdn_scan`` and ``expert_matmul`` rooflines of a model of
two-norm blocks (``costs.py``'s rules: needed work only, operands read once,
results written once).  ``m`` holds a configuration's published keys; every
function is for ONE block.
"""
from __future__ import annotations

from typing import Sequence, Tuple


def _sizes(m: dict) -> Tuple[int, int, int, int]:
    return (m["linear_num_key_heads"], m["linear_key_head_dim"],
            m["linear_num_value_heads"], m["linear_value_head_dim"])


def gdn_blocks(m: dict) -> int:
    """Gated DeltaNet blocks held: every layer but each ``full_attention_interval``-th."""
    return m["num_hidden_layers"] - m["num_hidden_layers"] // m["full_attention_interval"]


def gdn_step(live: float, m: dict, *, state_bytes: int = 4) -> Tuple[float, float]:
    """One step of the delta rule on ``live`` sequences' matrix states: per
    state element the decay, the read ``S'^T k``, the rank-one update and the
    read-out ``S^T q`` (4 multiply-adds).  Bytes: each LIVE state read once and
    written once (float32), q, k, v, g, beta in and o out."""
    hk, dk, hv, dv = _sizes(m)
    per_state = hv * dk * dv
    small = 4 * (2 * hk * dk + 2 * hv * dv + 2 * hv)
    return 8.0 * per_state * live, live * (2.0 * state_bytes * per_state + small)


def gdn_scan(chunks: Sequence[int], m: dict, *, state_bytes: int = 4) -> Tuple[float, float]:
    """The chunked delta rule over chunks of ``chunks[i]`` valid tokens (at
    most a page each), per value head and chunk of L tokens: ``K K^T`` and ``Q
    K^T`` (the causal halves: L^2 Dk each), the forward substitution for W and
    U (L^2 (Dk + Dv)), ``(Q K^T) V'`` (its causal half: L^2 Dv), and the
    incoming state's share of W, of the outputs and the chunk's of the outgoing
    state (2 L Dk Dv each).  Bytes: a state in and out per chunk, q, k, v, g,
    beta in and o out per token (float32)."""
    hk, dk, hv, dv = _sizes(m)
    flops = by = 0.0
    for l in chunks:
        flops += hv * (l * l * (3.0 * dk + 2.0 * dv) + 6.0 * l * dk * dv)
        by += 2.0 * state_bytes * hv * dk * dv + 4.0 * l * (2 * hk * dk + 2 * hv * dv + 2 * hv)
    return flops, by


def expert_matmul(pairs_held: float, experts_touched: float, m: dict) -> Tuple[float, float]:
    """The three matmuls of a SwiGLU expert of width ``moe_intermediate_size``
    for every (token, held expert) pair.  Reads each TOUCHED expert's three
    matrices once and a row in and out per pair (bf16)."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    return 6.0 * d * f * pairs_held, 2.0 * 3 * d * f * experts_touched + 2.0 * 2 * d * pairs_held
