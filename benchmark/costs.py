"""Operations and bytes each kernel's ALGORITHM needs, from shapes alone.

The yardstick for every ``*_roofline`` and ``*_mfu`` metric.  Recomputed work
(remat, the backward kernels' second pass over q.k^T) is not counted: a share
is needed work over the time taken.  Bytes are the operands read once and the
results written once, in the dtypes the kernel is handed.
"""
from __future__ import annotations

from typing import Iterable, Sequence, Tuple


def causal_pairs(n_q: int, start: int = 0) -> int:
    """(query, key) pairs of ``n_q`` consecutive queries whose first sits at
    position ``start``: query at position p attends keys 0..p."""
    return n_q * start + n_q * (n_q + 1) // 2


def flash_fwd(b: int, s: int, hq: int, hkv: int, hd: int, *, causal: bool = True,
              bytes_per_el: int = 2) -> Tuple[float, float]:
    """Flash attention forward on ``[b, s, h, hd]``: q.k^T and p.v, two
    matmuls of 2*hd FLOPs a pair.  Reads q, k, v, writes out (+ fp32 lse)."""
    pairs = b * (causal_pairs(s) if causal else s * s)
    flops = 4.0 * hq * hd * pairs
    by = bytes_per_el * b * s * hd * (2 * hq + 2 * hkv) + 4 * b * s * hq
    return flops, float(by)


def flash_bwd(b: int, s: int, hq: int, hkv: int, hd: int, *, causal: bool = True,
              bytes_per_el: int = 2) -> Tuple[float, float]:
    """Flash attention backward: five matmuls a pair (s = q.k^T recomputed
    once, dp = do.v^T, dv = p^T.do, dk = ds^T.q, dq = ds.k).  Reads q, k, v,
    out, do, lse; writes dq, dk, dv."""
    pairs = b * (causal_pairs(s) if causal else s * s)
    flops = 10.0 * hq * hd * pairs
    by = bytes_per_el * b * s * hd * (4 * hq + 4 * hkv) + 4 * b * s * hq
    return flops, float(by)


def paged_decode(ctx_lens: Iterable[int], hq: int, hkv: int, hd: int, *,
                 bytes_per_el: int = 2) -> Tuple[float, float]:
    """One decode step of the paged kernel for ONE layer: each sequence's one
    query attends its ``ctx`` cached keys.  Bytes: the live K and V rows, q
    and out."""
    flops = by = 0.0
    for ctx in ctx_lens:
        flops += 4.0 * hq * hd * ctx
        by += bytes_per_el * (2 * hkv * hd * ctx + 2 * hq * hd)
    return flops, by


def packed_ctx(entries: Sequence[Tuple[int, int]], hq: int, hkv: int, hd: int, *,
               bytes_per_el: int = 2) -> Tuple[float, float]:
    """One packed-suffix prefill pack for ONE layer.  ``entries`` are
    ``(start, end)`` token ranges: ``start`` keys come from cached pages, the
    pack's own ``end - start`` keys are causal.  Bytes: cached K/V rows, the
    pack's q/k/v in, the fp32 accumulator and (m, l) out."""
    flops = by = 0.0
    for start, end in entries:
        n = end - start
        flops += 4.0 * hq * hd * causal_pairs(n, start)
        by += bytes_per_el * (2 * hkv * hd * (start + n) + hq * hd * n)
        by += 4 * (hq * hd * n + 2 * hq * n)
    return flops, by


def roofline_min_s(flops: float, nbytes: float, peaks: dict) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def heads(m: dict) -> Tuple[int, int, int]:
    """(query heads, kv heads, head size) of a configuration's published keys."""
    hq = m["num_attention_heads"]
    return hq, m["num_key_value_heads"], m.get("head_dim") or m["hidden_size"] // hq


def matmul_params(m: dict) -> int:
    """Parameters that sit in a matmul of the forward pass (the embedding
    lookup is a gather, norm scales are elementwise).  ``m`` holds the
    published config keys."""
    d, f, L, v = (m["hidden_size"], m["intermediate_size"],
                  m["num_hidden_layers"], m["vocab_size"])
    hq, hkv, hd = heads(m)
    attn = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
    return L * (attn + 3 * d * f) + d * v


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward FLOPs one token of a causal sequence of ``seq``
    REQUIRES: 6 per matmul parameter, and for attention three times the
    forward's 4*hq*hd FLOPs a (query, key) pair at a mean of (seq+1)/2 keys."""
    hq, _, hd = heads(m)
    attn = 3 * 4.0 * hq * hd * (seq + 1) / 2 * m["num_hidden_layers"]
    return 6.0 * matmul_params(m) + attn
