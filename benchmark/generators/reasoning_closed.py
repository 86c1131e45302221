"""Closed loop of reasoning workers: ``clients`` callers, each holds one
generation from a short task prompt and sends its next unshared task the
moment its last answer ends.  Unlike ``closed_loop`` the ANSWER's length is a
distribution too: a task is a (prompt tokens, answer tokens) pair.

The ``pool`` quantiles of ``prompt_tokens`` and the ``pool`` quantiles of
``answer_tokens`` are a fixed multiset each.  Each list is DEALT in rounds of
one per stratum (``closed_loop_strata.dealt``: bit-reversed turns, the
first ``fixed_rounds`` rounds the same for every seed, the seed dealing the
rest), and round ``r`` of the answers is rotated by ``r`` places against the
prompts' (a Latin square: over ``strata`` rounds every stratum of prompts
meets every stratum of answers once, so the two lengths of a pair are
uncorrelated by construction).
The seed always draws the token ids.  Tasks are handed out in that order,
cyclically.  The callers' first tasks are spread evenly over the first
``spread_s`` seconds of the ramp, so that their answers do not all end
together.
"""
from __future__ import annotations

from typing import List, Tuple

from ..distributions import quantiles, rng_for
from . import Request
from .closed_loop_strata import dealt


class Plan:
    def __init__(self, traffic: dict, seed: int, seconds: float, vocab: int):
        self.ramp_s = float(traffic["ramp_s"])
        self.spread_s = float(traffic["spread_s"])
        self.clients = int(traffic["clients"])
        self.vocab = vocab
        pool, strata = int(traffic["pool"]), int(traffic["strata"])

        def order(key: str, stream: int, rotate: bool) -> list:
            out = dealt(quantiles(traffic[key], pool), strata, rng_for(seed, stream),
                        int(traffic["fixed_rounds"]))
            rounds = [out[r:r + strata] for r in range(0, pool, strata)]
            return [v for r, row in enumerate(rounds)
                    for v in (row[r % strata:] + row[:r % strata] if rotate else row)]

        self.lengths = order("prompt_tokens", 1, False)
        self.answers = order("answer_tokens", 4, True)
        self._tok = rng_for(seed, 2)
        self._next = 0

    def _request(self, client: int, turn: int) -> Request:
        i = self._next % len(self.lengths)
        self._next += 1
        return Request(client, turn, self._tok.integers(0, self.vocab, self.lengths[i]).tolist(),
                       self.answers[i])

    def initial(self) -> List[Tuple[float, Request]]:
        gap = self.spread_s / self.clients
        return [(-self.ramp_s + c * gap, self._request(c, 0)) for c in range(self.clients)]

    def on_finish(self, req: Request, t: float, generated: List[int]):
        return [(t, self._request(req.session, req.turn + 1))]

    def multiset(self) -> dict:
        return {"prompts": sorted(self.lengths), "answers": sorted(self.answers),
                "clients": self.clients}


def build(traffic: dict, *, seed: int, seconds: float, vocab: int) -> Plan:
    return Plan(traffic, seed, seconds, vocab)
