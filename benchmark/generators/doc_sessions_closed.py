"""Closed loop of document SESSIONS: ``clients`` callers, each holds one
session at a time.  A session is one unshared document asked ``questions``
questions in turn: request ``k`` is the document followed by question ``k``
(earlier questions and answers are not carried), question ``k + 1`` is sent the
moment answer ``k`` returns, and the caller opens its next session, on a new
document, when the last answer returns.  Within a session the document's whole
blocks are what a prefix cache can serve again; between sessions nothing is
shared.

Three fixed multisets (``distributions.quantiles``): the ``pool`` document
lengths, and ``pool x questions`` question lengths and answer lengths.  Each
list is DEALT in rounds of one per stratum (``closed_loop_strata.dealt``:
bit-reversed turns; the first ``fixed_rounds`` rounds of the documents the same
for every seed; EVERY round of the questions and of the answers is), and round
``r`` of the answers is rotated ``r`` places against the questions' (the Latin
square of ``reasoning_closed``).  Sessions are handed out in that order,
cyclically.  The seed draws the token ids, and the rounds ``fixed_rounds``
leaves free.  The callers' first sessions are spread evenly over the first
``spread_s`` seconds of the ramp.

``Request.session`` is the caller, ``Request.turn`` counts the caller's requests:
``turn % questions`` is the question's place in its session.
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
        self.questions = int(traffic["questions"])
        self.vocab = vocab
        pool, strata = int(traffic["pool"]), int(traffic["strata"])
        turns = pool * self.questions

        def order(key: str, stream: int, rotate: bool) -> list:
            out = dealt(quantiles(traffic[key], turns), strata, rng_for(seed, stream),
                        turns // strata)
            rounds = [out[r:r + strata] for r in range(0, turns, strata)]
            return [v for r, row in enumerate(rounds)
                    for v in (row[r % strata:] + row[:r % strata] if rotate else row)]

        # the documents' lengths, in the order the sessions take them
        self.lengths = dealt(quantiles(traffic["document_tokens"], pool), strata,
                             rng_for(seed, 1), int(traffic["fixed_rounds"]))
        self.asked = order("question_tokens", 4, False)
        self.answers = order("answer_tokens", 5, True)
        self._tok = rng_for(seed, 2)
        self._sessions = 0           # sessions opened so far
        self._open: dict = {}        # caller -> (its session's number, the document's ids)

    def _ids(self, n: int) -> list:
        return self._tok.integers(0, self.vocab, n).tolist()

    def _request(self, client: int, turn: int) -> Request:
        k = turn % self.questions
        if k == 0:  # a new session, on the next document
            i = self._sessions
            self._sessions += 1
            self._open[client] = (i, self._ids(self.lengths[i % len(self.lengths)]))
        i, document = self._open[client]
        j = (i * self.questions + k) % len(self.asked)
        return Request(client, turn, document + self._ids(self.asked[j]), self.answers[j])

    def initial(self) -> List[Tuple[float, Request]]:
        gap = self.spread_s / self.clients
        return [(-self.ramp_s + c * gap, self._request(c, 0)) for c in range(self.clients)]

    def on_finish(self, req: Request, t: float, generated: List[int]):
        return [(t, self._request(req.session, req.turn + 1))]

    def multiset(self) -> dict:
        return {"documents": sorted(self.lengths), "questions": sorted(self.asked),
                "answers": sorted(self.answers), "clients": self.clients,
                "questions_a_session": self.questions}


def build(traffic: dict, *, seed: int, seconds: float, vocab: int) -> Plan:
    return Plan(traffic, seed, seconds, vocab)
