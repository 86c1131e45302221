"""Operations and bytes the ALGORITHM of EVA attention needs (a query attends
the exact rows of its own window of positions and one summary row per chunk of
every window before it), from positions alone: the yardstick of the
``eva_decode_attn`` and ``eva_prefill_attn`` rooflines (``costs.py``'s rules:
needed work only, operands read once, results written once).  ``m`` holds a
configuration's published keys.  The need is counted from the rows EVA KEEPS, so
it reads the same work whatever implements the body: one that re-reads rows,
pads heads or walks a page past the context only takes longer for it.
"""
from __future__ import annotations

from typing import Tuple


def rows(n: int, m: dict) -> int:
    """Rows a context of ``n`` positions keeps: one per chunk of every closed
    window, one per position of the open one."""
    w, c = m["window_size"], m["chunk_size"]
    return n // w * (w // c) + n % w


def row_bytes(m: dict, bytes_per_el: int = 2) -> int:
    """A row of ONE layer: every head's key and value."""
    return 2 * m["hidden_size"] * bytes_per_el


def attention(pairs: float, q_rows: float, kv_rows: float, m: dict,
              *, bytes_per_el: int = 2) -> Tuple[float, float]:
    """ONE layer: ``pairs`` (query, row) pairs the one softmax spans (q.k^T and
    p.v: 4 hd FLOPs a pair and head); ``q_rows`` query rows in and as many output
    rows out, ``kv_rows`` rows (a key and a value each) read once.  A tick is its
    live slots' queries over their rows, a pack its queries over the rows before
    it and its own."""
    d = m["hidden_size"]  # heads x head size: no grouping
    return 4.0 * d * pairs, float(bytes_per_el) * d * 2 * q_rows + row_bytes(m, bytes_per_el) * kv_rows


def summarise(chunks: float, m: dict, *, bytes_per_el: int = 2) -> Tuple[float, float]:
    """ONE layer: ``chunks`` whole chunks pooled, ``chunk_size`` rows in and one
    out each: a score a row and head (2 hd), the pooled key and value (2 x 2 hd)."""
    c, d = m["chunk_size"], m["hidden_size"]
    return 6.0 * d * c * chunks, row_bytes(m, bytes_per_el) * (c + 1) * chunks
