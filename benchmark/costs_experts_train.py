"""Operations and bytes a TRAINING step needs of one layer's held experts,
from the (token, expert) pairs that fell on them alone (``costs.py``'s rules:
the algorithm's need, recomputation not counted, operands read once and
results written once, in the dtype the kernel is handed)."""
from __future__ import annotations

from typing import Tuple


def experts_fwd(pairs: float, experts: int, d: int, f: int, *,
                bytes_per_el: int = 2) -> Tuple[float, float]:
    """Three grouped products a pair (gate, up: d -> f; down: f -> d), 2 d f
    FLOPs each.  Reads each held expert's three matrices once and a row of d a
    pair; writes a row of d a pair."""
    flops = 3 * 2.0 * pairs * d * f
    by = bytes_per_el * (3.0 * experts * d * f + 2.0 * pairs * d)
    return flops, by


def experts_bwd(pairs: float, experts: int, d: int, f: int, *,
                bytes_per_el: int = 2) -> Tuple[float, float]:
    """Six grouped products a pair: for each of the three matrices the rows'
    gradient and the matrix's.  Reads the matrices once, a pair's input row and
    its output's gradient; writes the matrices' gradients and a row's."""
    flops = 6 * 2.0 * pairs * d * f
    by = bytes_per_el * (2 * 3.0 * experts * d * f + 3.0 * pairs * d)
    return flops, by
