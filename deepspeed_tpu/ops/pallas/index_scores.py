"""Pallas kernel for the key selector's index scores over a PAGED key cache
(DeepSeek-V3.2's indexer): ``I[c, s] = scale * sum_j w[c, j] * relu(q[c, j] .
k[s])`` for a page of ``C`` queries of one sequence against every live page of
that sequence's index keys.

The XLA body (``ops/latent_attention.py:index_scores``) writes the ``[C, J,
keys]`` products to memory before it reduces over the heads: 67 MB a block of
2048 keys, which bounds it at a twentieth of the MXU's peak.  Here a grid step
holds one page of keys and the group's queries in VMEM, runs the ``J`` heads'
matmuls back to back and keeps only their weighted sum.

TPU design:
- grid = (groups, pages); the block table is a prefetched scalar operand and
  the key page's ``BlockSpec`` index map looks ``table[g, i]`` up, so the keys
  are read where the allocator put them, with no gathered copy;
- work bounded by length: steps past a group's last live page skip their
  compute, and their index map repeats that page, which the pipeline
  recognises and does not fetch again;
- the product is computed TRANSPOSED, ``[keys, C]`` = page ``[bs, D]`` x
  ``q_j^T [D, C]``, so that a query's head weight ``w[c, j]`` lies along lanes
  and scales the product as a row; the caller transposes the result once.

``supports()`` gates dispatch as in ``ops/pallas/paged_attention.py``; the jnp
body remains the fallback and the ground truth.
"""
from __future__ import annotations

import contextlib
import contextvars

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# interpret mode (CPU tests) for what is TRACED inside ``interpreted()``: the
# calling context's, not the process's
_INTERPRET: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "index_scores_interpret", default=False)


@contextlib.contextmanager
def interpreted():
    token = _INTERPRET.set(True)
    try:
        yield
    finally:
        _INTERPRET.reset(token)


def interpret() -> bool:
    return _INTERPRET.get()


def supports(c: int, j: int, d: int, bs: int) -> bool:
    """Tiles the MXU takes whole: a page of keys and a page of queries of at
    least a lane row, head size a lane multiple."""
    if interpret():
        return c % 8 == 0 and bs % 8 == 0
    return c % 128 == 0 and bs % 128 == 0 and d % 128 == 0


def _kernel(live_ref, tables_ref, q_ref, w_ref, k_ref, o_ref, *, heads: int, scale: float):
    g, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i < live_ref[g])
    def _():
        k = k_ref[...]  # [bs, D]
        acc = jnp.zeros(o_ref.shape[1:], jnp.float32)
        for j in range(heads):
            s = jnp.dot(k, q_ref[0, j], preferred_element_type=jnp.float32)  # [bs, C]
            acc += jnp.maximum(s, 0.0) * w_ref[0, j:j + 1, :]
        o_ref[0] = acc * scale

    @pl.when(i >= live_ref[g])
    def _():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], jnp.float32)


def paged_index_scores(q_i, w, pages, tables, live_pages, scale: float):
    """q_i [G, C, J, D], w [G, C, J] float32, pages [nb, bs, D], tables [G, P]
    int32 (non-negative), live_pages [G] int32 (pages that hold a key some
    query of the group may score).  Returns UNMASKED scores [G, C, P * bs]
    float32: key ``s`` of group ``g`` is row ``s % bs`` of page ``tables[g, s
    // bs]``; the columns of pages past ``live_pages`` are zero."""
    g, c, j, d = q_i.shape
    nb, bs, _ = pages.shape
    p = tables.shape[1]
    q_t = jnp.transpose(q_i, (0, 2, 3, 1))  # [G, J, D, C]
    w_t = jnp.transpose(w, (0, 2, 1)).astype(jnp.float32)  # [G, J, C]
    last = lambda gi, i, live: jnp.minimum(i, jnp.maximum(live[gi] - 1, 0))
    out = pl.pallas_call(
        lambda *refs: _kernel(*refs, heads=j, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(g, p),
            in_specs=[
                pl.BlockSpec((1, j, d, c), lambda gi, i, live, tab: (gi, 0, 0, 0)),
                pl.BlockSpec((1, j, c), lambda gi, i, live, tab: (gi, 0, 0)),
                pl.BlockSpec((None, bs, d),
                             lambda gi, i, live, tab: (tab[gi, last(gi, i, live)], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, bs, c), lambda gi, i, live, tab: (gi, i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((g, p * bs, c), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret(),
        name="index_scores",
    )(live_pages.astype(jnp.int32), tables.astype(jnp.int32), q_t, w_t, pages)
    return jnp.transpose(out, (0, 2, 1))
