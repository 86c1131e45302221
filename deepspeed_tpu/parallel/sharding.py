"""Mesh context + activation-sharding helpers (GSPMD side).

The reference threads process-group handles through every module
(deepspeed/utils/groups.py getters).  Here the analogue is one ambient mesh:
``set_current_mesh`` installs it, ``shard_activation`` applies a
``PartitionSpec`` constraint against it inside jit.  Constraints drop axis
entries that don't divide the dimension (tiny test shapes) instead of
failing, but keep full specs on real shapes so layout errors surface.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

_CURRENT_MESH = None


def shard_map_compat(f, mesh, in_specs, out_specs, axis_names=None,
                     check_vma: bool = False):
    """``jax.shard_map`` with the repo's default of unchecked replication
    (``check_vma=False``).  Every manual region in the repo routes through
    here."""
    kw = {} if axis_names is None else {"axis_names": axis_names}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=check_vma, **kw)


def set_current_mesh(mesh) -> None:
    global _CURRENT_MESH
    _CURRENT_MESH = mesh


def get_current_mesh():
    return _CURRENT_MESH


class mesh_disabled:
    """Trace-time context: suppress shard_activation constraints inside —
    used by the pipeline executor, where explicit sharding constraints in a
    partially-manual shard_map region crash XLA's backward partitioner
    ('Invalid binary instruction opcode copy')."""

    def __enter__(self):
        global _CURRENT_MESH
        self._prev = _CURRENT_MESH
        _CURRENT_MESH = None

    def __exit__(self, *exc):
        global _CURRENT_MESH
        _CURRENT_MESH = self._prev


def axis_size(name: str) -> int:
    """Size of a mesh axis in the ambient mesh (1 if absent / no mesh)."""
    if _CURRENT_MESH is None:
        return 1
    sizes = dict(zip(_CURRENT_MESH.axis_names, _CURRENT_MESH.devices.shape))
    return sizes.get(name, 1)


def collective_axis_size(axis_name) -> int:
    """World size of a collective axis (a name or a sequence of names) from
    INSIDE a traced collective region."""
    if isinstance(axis_name, str):
        return jax.lax.axis_size(axis_name)
    return math.prod(jax.lax.axis_size(ax) for ax in axis_name)


def filter_spec(shape, spec: P, mesh=None) -> P:
    """Drop spec entries whose mesh-axis product doesn't divide the dim —
    keeps tiny test shapes working while real shapes get the full spec."""
    mesh = mesh if mesh is not None else _CURRENT_MESH
    if mesh is None:
        return P(*([None] * len(shape)))
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def ok(dim, entry):
        axes = entry if isinstance(entry, tuple) else (entry,)
        return dim % math.prod(sizes.get(a, 1) for a in axes) == 0

    return P(*(
        e if (e is None or ok(d, e)) else None for d, e in zip(shape, tuple(spec))
    ))


def _drop_manual_axes(spec: P) -> P:
    """Strip mesh axes that are Manual in the current trace (i.e. we are
    inside a shard_map over them): with_sharding_constraint may only name
    non-manual axes there.  Makes model code usable both under plain jit
    (GSPMD) and inside whole-step shard_map optimizers (1-bit family)."""
    manual = set(jax.sharding.get_abstract_mesh().manual_axes)
    if not manual:
        return spec

    def clean(entry):
        if entry is None:
            return None
        axes = entry if isinstance(entry, tuple) else (entry,)
        kept = tuple(a for a in axes if a not in manual)
        return kept if len(kept) > 1 else (kept[0] if kept else None)

    return P(*(clean(e) for e in tuple(spec)))


def shard_activation(x: jax.Array, spec: P) -> jax.Array:
    if _CURRENT_MESH is None:
        return x
    # strip manual axes FIRST: filter_spec's divisibility check must not count
    # axes we're about to drop (their sizes don't apply to local block shapes)
    spec = filter_spec(x.shape, _drop_manual_axes(spec))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_CURRENT_MESH, spec)
    )
