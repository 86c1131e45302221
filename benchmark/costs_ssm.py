"""Operations and bytes the ALGORITHMS of a Mamba-2 block's recurrence and of a
held share of latent-space two-matrix experts need, from shapes alone: the
yardstick of the ``ssm_step``, ``ssm_scan`` and ``expert_matmul`` rooflines of a
model of single-mixer blocks (``costs.py``'s rules: needed work only, operands
read once, results written once).  ``m`` holds a configuration's published
keys; every function is for ONE block.
"""
from __future__ import annotations

from typing import Sequence, Tuple


def _sizes(m: dict) -> Tuple[int, int, int, int]:
    return m["mamba_num_heads"], m["mamba_head_dim"], m["n_groups"], m["ssm_state_size"]


def ssm_step(live: float, m: dict, *, state_bytes: int = 4) -> Tuple[float, float]:
    """One step of the recurrence on ``live`` sequences' states: per state
    element a decay, an outer-product term and the read-out (3 multiply-adds).
    Bytes: each LIVE state read once and written once (float32), x, B, C, dt
    in and y out."""
    h, p, g, n = _sizes(m)
    per_state = h * p * n
    small = 4 * (2 * h * p + 2 * g * n + h)
    return 6.0 * per_state * live, live * (2.0 * state_bytes * per_state + small)


def ssm_scan(chunks: Sequence[int], m: dict, *, state_bytes: int = 4) -> Tuple[float, float]:
    """The chunked scan over chunks of ``chunks[i]`` valid tokens (at most a
    page each): per chunk of L tokens ``C B^T`` (2 G L^2 N), ``M x`` (2 H L^2 P,
    halved: the causal half is needed), the chunk's contribution to its state
    and the incoming state's to its outputs (2 L H P N each).  Bytes: a state
    in and out per chunk, x, B, C, dt in and y out per token (float32)."""
    h, p, g, n = _sizes(m)
    flops = by = 0.0
    for l in chunks:
        flops += 2.0 * g * l * l * n / 2 + 2.0 * h * l * l * p / 2 + 4.0 * l * h * p * n
        by += 2.0 * state_bytes * h * p * n + 4.0 * l * (2 * h * p + 2 * g * n + h)
    return flops, by


def expert_matmul(pairs_held: float, experts_touched: float, m: dict) -> Tuple[float, float]:
    """The two matmuls of a relu^2 expert of width ``moe_intermediate_size`` in
    the latent (``moe_latent_size``) for every (token, held expert) pair.
    Reads each TOUCHED expert's two matrices once and a latent row in and out
    per pair (bf16)."""
    r, f = m["moe_latent_size"], m["moe_intermediate_size"]
    return 4.0 * r * f * pairs_held, 2.0 * 2 * r * f * experts_touched + 2.0 * 2 * r * pairs_held
