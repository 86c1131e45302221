"""Quantiles of the distributions traffic files state.

A traffic mix is a FIXED MULTISET: the ``n`` values a generator uses are the
mid-point quantiles ``(i + 0.5) / n`` of the stated distribution, so every run
of a cell draws on exactly the same lengths, gaps and think times whatever
``--seed`` it is given.  The seed only permutes them (``permuted``).
"""
from __future__ import annotations

import math
from typing import List

import numpy as np


def quantiles(spec: dict, n: int) -> List[float]:
    """``n`` mid-point quantiles of ``spec``, ascending.

    - ``{"dist": "const", "value": v}``
    - ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``
    - ``{"dist": "gamma", "mean": m, "cv": c}`` rescaled so the ``n`` values
      sum to ``n * mean`` exactly (a schedule built from them ends on time)

    ``"integer": true`` rounds to whole numbers (token counts).
    """
    from scipy import stats

    p = (np.arange(n) + 0.5) / n
    kind = spec["dist"]
    if kind == "const":
        xs = np.full(n, float(spec["value"]))
    elif kind == "lognormal":
        xs = np.exp(math.log(spec["median"]) + spec["sigma"] * stats.norm.ppf(p))
        xs = np.clip(xs, spec.get("min", -np.inf), spec.get("max", np.inf))
    elif kind == "gamma":
        shape = 1.0 / spec["cv"] ** 2
        xs = stats.gamma.ppf(p, shape, scale=spec["mean"] / shape)
        xs = xs * (n * spec["mean"] / xs.sum())
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if spec.get("integer"):
        return [int(round(x)) for x in xs]
    return [float(x) for x in xs]


def permuted(values: List, rng: np.random.Generator) -> List:
    return [values[i] for i in rng.permutation(len(values))]


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent streams from one ``--seed`` (any non-negative whole
    number, also one past 32 bits)."""
    return np.random.default_rng([int(seed), int(stream)])
