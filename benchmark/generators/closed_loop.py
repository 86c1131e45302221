"""Closed loop: ``clients`` callers, each sends its next request the moment
its last one completes, so a slower system is offered less.

Prompt lengths are the ``pool`` quantiles of the stated distribution, dealt
to the clients in an order the seed permutes and reused cyclically with fresh
token ids (nothing is shared between prompts, so the prefix cache finds
nothing).  All clients start together ``ramp_s`` before the window.
"""
from __future__ import annotations

from typing import List, Tuple

from ..distributions import permuted, quantiles, rng_for
from . import Request


class Plan:
    def __init__(self, traffic: dict, seed: int, seconds: float, vocab: int):
        self.ramp_s = float(traffic["ramp_s"])
        self.clients = int(traffic["clients"])
        self.vocab = vocab
        self.lengths = permuted(
            quantiles(traffic["prompt_tokens"], int(traffic["pool"])), rng_for(seed, 1))
        self.max_new = int(traffic["output_tokens"])
        self._tok = rng_for(seed, 2)
        self._next = 0

    def _request(self, client: int, turn: int) -> Request:
        n = self.lengths[self._next % len(self.lengths)]
        self._next += 1
        return Request(client, turn, self._tok.integers(0, self.vocab, n).tolist(),
                       self.max_new)

    def initial(self) -> List[Tuple[float, Request]]:
        return [(-self.ramp_s, self._request(c, 0)) for c in range(self.clients)]

    def on_finish(self, req: Request, t: float, generated: List[int]):
        return [(t, self._request(req.session, req.turn + 1))]

    def multiset(self) -> dict:
        return {"prompts": sorted(self.lengths), "clients": self.clients,
                "output": self.max_new}


def build(traffic: dict, *, seed: int, seconds: float, vocab: int) -> Plan:
    return Plan(traffic, seed, seconds, vocab)
