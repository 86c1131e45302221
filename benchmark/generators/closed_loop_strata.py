"""Closed loop (``closed_loop``'s callers, requests and fixed multiset of
``pool`` quantile lengths) with the lengths DEALT in rounds, so that any run of
consecutive documents holds the distribution's mix, not a seed's luck.

The sorted pool is cut into ``strata`` equal strata (shortest to longest).  A
round is one document of each stratum, and the strata take their turns in
bit-reversed order (0 4 2 6 1 5 3 7 for eight: short and long alternate, and
every aligned pair, four and eight is itself spread over the range).  The
first ``fixed_rounds`` rounds are the same for every seed: round ``r`` takes of
the stratum at turn ``j`` its document of rank ``(r + j) mod rounds``, so a
round holds every rank once and the rounds' sums agree.  In the rounds after
them ``--seed`` decides which of a stratum's remaining documents goes to which
round.  The seed always draws the token ids.

Why a kind of its own: where a window serves only a part of the pool, a
request's cost depends on its length and the callers are few enough that the
engine sometimes waits for them, ``closed_loop``'s free permutation lets the
seed choose the window's mix and where it starves, and with it the rate.
``fixed_rounds`` 0 leaves the seed every round's composition; with as many
fixed rounds as a window reaches, the lengths a run meets are part of the cell
and the seed's part is the content.
"""
from __future__ import annotations

from ..distributions import permuted, quantiles, rng_for
from . import closed_loop


def dealt(values: list, strata: int, rng, fixed_rounds: int = 0) -> list:
    """``values`` (ascending) in rounds of one per stratum, bit-reversed turns."""
    rounds, rest = divmod(len(values), strata)
    if rest or strata & (strata - 1):
        raise ValueError(f"{strata} strata: need a power of two that divides "
                         f"the pool of {len(values)}")
    if not 0 <= fixed_rounds <= rounds:
        raise ValueError(f"{fixed_rounds} fixed rounds of {rounds}")
    bits = strata.bit_length() - 1
    turns = [int(format(i, f"0{bits}b")[::-1], 2) if bits else 0 for i in range(strata)]
    cols = []
    for j, s in enumerate(turns):
        column = values[s * rounds:(s + 1) * rounds]
        head = [column[(r + j) % rounds] for r in range(fixed_rounds)]
        tail = [column[(r + j) % rounds] for r in range(fixed_rounds, rounds)]
        cols.append(head + permuted(tail, rng))
    return [col[r] for r in range(rounds) for col in cols]


class Plan(closed_loop.Plan):
    def __init__(self, traffic: dict, seed: int, seconds: float, vocab: int):
        super().__init__(traffic, seed, seconds, vocab)
        self.lengths = dealt(quantiles(traffic["prompt_tokens"], int(traffic["pool"])),
                             int(traffic["strata"]), rng_for(seed, 1),
                             int(traffic.get("fixed_rounds", 0)))


def build(traffic: dict, *, seed: int, seconds: float, vocab: int) -> Plan:
    return Plan(traffic, seed, seconds, vocab)
