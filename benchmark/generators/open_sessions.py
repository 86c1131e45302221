"""Open-loop chat sessions.

Sessions START on a schedule that does not wait for the system (open loop);
inside a session the user waits for the answer and then thinks (closed).  All
sessions begin with one shared system prompt; each turn's prompt is the
session so far, with the answers the engine really generated, plus a new user
message.

The schedule is built in strata of ``stratum_s`` seconds: each stratum holds
the same number of session starts, whose gaps are the quantiles of the stated
gamma distribution (rescaled to fill the stratum exactly) and whose message,
answer and think lengths are, turn by turn, the quantiles of theirs.
``--seed`` permutes the values inside a stratum (and turn) and draws the token
ids.  So every run, whatever its seed, starts the same sessions per stratum
with the same multiset of lengths in each turn; only their order and the
host's timing differ.

The ramp starts cold: second and third turns only begin to arrive a turn
length or two into it, so the number in flight is still rising when a short
ramp ends.  (A warm start - sessions taken to be in progress joining at a later
turn with a synthetic history - was tried in PR 23 and dropped: prefilling the
uncached histories overloaded the ramp and left a backlog in the window.)
"""
from __future__ import annotations

import math
from typing import List, Tuple

from ..distributions import permuted, quantiles, rng_for
from . import Request


class Plan:
    def __init__(self, traffic: dict, seed: int, seconds: float, vocab: int):
        self.ramp_s = float(traffic["ramp_s"])
        self.turns = int(traffic["turns"])
        self.seconds = seconds
        stratum_s = float(traffic["stratum_s"])
        per = int(round(traffic["session_starts_per_s"] * stratum_s))
        if per < 1:
            raise ValueError("fewer than one session start per stratum")
        n_strata = math.ceil((self.ramp_s + seconds) / stratum_s)
        rng = rng_for(seed, 1)
        tok = rng_for(seed, 2)
        draw = lambda n: tok.integers(0, vocab, int(n)).tolist()
        gap_spec = {"dist": "gamma", "mean": stratum_s / per,
                    "cv": traffic["start_gap_cv"]}
        self.system = draw(traffic["system_prompt_tokens"])
        self.sessions: List[dict] = []
        for s in range(n_strata):
            gaps = permuted(quantiles(gap_spec, per), rng)
            by_turn = [{k: permuted(quantiles(traffic[k], per), rng)
                        for k in ("user_tokens", "answer_tokens", "think_s")}
                       for _ in range(self.turns)]
            t = -self.ramp_s + s * stratum_s
            for i in range(per):
                t += gaps[i]
                self.sessions.append({
                    # a start falls mid-gap so that a stratum's last start
                    # does not sit on the next one's first
                    "start": t - gaps[i] / 2,
                    "users": [draw(by_turn[k]["user_tokens"][i]) for k in range(self.turns)],
                    "answers": [by_turn[k]["answer_tokens"][i] for k in range(self.turns)],
                    "thinks": [by_turn[k]["think_s"][i] for k in range(self.turns)]})

    def initial(self) -> List[Tuple[float, Request]]:
        return [(s["start"], Request(i, 0, self.system + s["users"][0], s["answers"][0]))
                for i, s in enumerate(self.sessions) if s["start"] < self.seconds]

    def on_finish(self, req: Request, t: float, generated: List[int]):
        k = req.turn + 1
        if k >= self.turns:
            return []
        s = self.sessions[req.session]
        return [(t + s["thinks"][req.turn], Request(
            req.session, k, req.prompt + list(generated) + s["users"][k],
            s["answers"][k]))]

    def multiset(self) -> dict:
        """What must not depend on the seed (the tests compare it)."""
        return {
            "users": sorted(len(u) for s in self.sessions for u in s["users"]),
            "answers": sorted(a for s in self.sessions for a in s["answers"]),
            "thinks": sorted(round(x, 9) for s in self.sessions for x in s["thinks"]),
            "starts": len(self.sessions),
        }


def build(traffic: dict, *, seed: int, seconds: float, vocab: int) -> Plan:
    return Plan(traffic, seed, seconds, vocab)
