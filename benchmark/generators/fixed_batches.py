"""Training batches of one fixed shape: seeded token ids ``[rows, seq + 1]``
(inputs and shifted labels), ``distinct`` different batches served in turn for
as long as the loop asks."""
from __future__ import annotations

import itertools

from ..distributions import rng_for


class Plan:
    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.seq = int(traffic["seq_len"])
        self.micro = int(traffic["micro_batch_per_chip"])
        self.distinct = int(traffic["distinct_batches"])
        self.vocab = vocab
        self.seed = seed

    def batches(self, rows: int):
        """An endless iterator of ``{"input_ids": int32[rows, seq+1]}``."""
        import numpy as np

        rng = rng_for(self.seed, 1)
        pool = [{"input_ids": rng.integers(0, self.vocab, (rows, self.seq + 1),
                                           dtype=np.int32)}
                for _ in range(self.distinct)]
        return itertools.cycle(pool)

    def multiset(self) -> dict:
        return {"seq": self.seq, "micro": self.micro, "distinct": self.distinct}


def build(traffic: dict, *, seed: int, seconds: float, vocab: int) -> Plan:
    return Plan(traffic, seed, vocab)
