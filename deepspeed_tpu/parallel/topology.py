"""Device-mesh topology: the TPU-native replacement for process groups.

The reference builds NCCL process groups per parallel dimension
(``deepspeed/utils/groups.py``, ``runtime/pipe/topology.py:251
PipelineParallelGrid``).  On TPU all parallelism is expressed as named axes of
one ``jax.sharding.Mesh``; collectives ride ICI when the axis maps onto the
intra-slice torus and DCN when it crosses slices.  This module owns axis
naming, mesh construction, and the grid arithmetic the rest of the framework
uses instead of process-group getters.

Axis vocabulary (superset of the reference's dp/tp/pp/ep/sp):

- ``data``    pure data parallelism (gradient psum)
- ``fsdp``    ZeRO parameter/optimizer sharding (weight-update sharding)
- ``model``   tensor parallelism (megatron-style row/col sharding)
- ``seq``     sequence parallelism (Ulysses all-to-all / ring attention)
- ``expert``  expert parallelism for MoE dispatch
- ``stage``   pipeline parallelism
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

DATA_AXIS = "data"
# Serving alias: on a 2-D batch×model serve mesh the continuous-batching
# engine shards its KV pool, block tables, and slot groups over the same
# mesh axis training uses for pure data parallelism — each ``batch``
# coordinate is one serving replica (weights replicated over it, sharded
# over ``model``).  ``initialize_mesh(batch=2, model=2)`` accepts the alias.
BATCH_AXIS = DATA_AXIS
FSDP_AXIS = "fsdp"
SUB_AXIS = "sub"  # inner factor of fsdp: ZeRO++ hpZ secondary partition /
# MiCS shard group (reference utils/groups.py:650, runtime/zero/mics.py:64)
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
EXPERT_AXIS = "expert"
STAGE_AXIS = "stage"

ALL_AXES = (
    DATA_AXIS, FSDP_AXIS, SUB_AXIS, MODEL_AXIS, SEQ_AXIS, EXPERT_AXIS, STAGE_AXIS
)

# Axes over which gradients are averaged for the dense parameters.
BATCH_AXES = (DATA_AXIS, FSDP_AXIS, SUB_AXIS)
# The full weight-update-sharding extent (fsdp x its inner sub factor).
FSDP_AXES = (FSDP_AXIS, SUB_AXIS)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh shape.  Axes of size 1 still exist in the mesh so that
    sharding rules never need to special-case a missing axis.
    """

    data: int = 1
    fsdp: int = 1
    sub: int = 1  # inner fsdp factor (hpZ secondary partition / MiCS group)
    model: int = 1
    seq: int = 1
    expert: int = 1
    stage: int = 1
    # axes that should be laid out over DCN (slowest-varying) on multi-slice
    dcn_axes: Tuple[str, ...] = ()

    @property
    def sizes(self) -> Dict[str, int]:
        return {
            DATA_AXIS: self.data,
            FSDP_AXIS: self.fsdp,
            SUB_AXIS: self.sub,
            MODEL_AXIS: self.model,
            SEQ_AXIS: self.seq,
            EXPERT_AXIS: self.expert,
            STAGE_AXIS: self.stage,
        }

    @property
    def world_size(self) -> int:
        return math.prod(self.sizes.values())

    @property
    def dp_world_size(self) -> int:
        """Number of gradient-averaging replicas (reference: dp_world_size)."""
        return self.data * self.fsdp * self.sub

    def replace(self, **kw) -> "MeshSpec":
        return dataclasses.replace(self, **kw)

    @staticmethod
    def from_dict(d: Dict) -> "MeshSpec":
        known = {f.name for f in dataclasses.fields(MeshSpec)}
        return MeshSpec(**{k: v for k, v in d.items() if k in known})


def infer_spec(world_size: int, **fixed: int) -> MeshSpec:
    """Fill the leftover world size into the ``data`` axis.

    ``infer_spec(8, fsdp=4)`` -> data=2, fsdp=4.  Raises if the fixed axes do
    not divide the world size — same invariant the reference enforces when
    triangulating batch sizes (runtime/config.py _configure_train_batch_size).
    """
    spec = MeshSpec(**fixed)
    fixed_prod = math.prod(spec.sizes.values())
    if world_size % fixed_prod != 0:
        raise ValueError(
            f"world_size {world_size} not divisible by fixed axes product {fixed_prod}"
        )
    if "data" in fixed:
        if spec.world_size != world_size:
            raise ValueError(
                f"mesh spec {spec.sizes} covers {spec.world_size} devices, expected {world_size}"
            )
        return spec
    return spec.replace(data=world_size // fixed_prod)


def build_mesh(spec: MeshSpec, devices: Optional[Sequence] = None):
    """Construct a ``jax.sharding.Mesh`` with all six named axes.

    Uses ``mesh_utils.create_device_mesh`` so the axis order maps contiguously
    onto the ICI torus (fastest-varying axes get nearest-neighbour links);
    ``stage``/``data`` are placed slowest-varying so pipeline hops and pure-DP
    psums tolerate DCN, while ``model``/``seq``/``expert`` sit innermost on ICI.
    """
    import jax
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if spec.world_size != len(devices):
        raise ValueError(
            f"MeshSpec covers {spec.world_size} devices but {len(devices)} are available"
        )
    # slowest -> fastest varying; ``sub`` sits just inside ``fsdp`` so the
    # hpZ/MiCS secondary gathers ride the tightest ICI neighbourhood
    order = (STAGE_AXIS, DATA_AXIS, FSDP_AXIS, SUB_AXIS, EXPERT_AXIS, SEQ_AXIS, MODEL_AXIS)
    shape = tuple(spec.sizes[a] for a in order)
    dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    return Mesh(dev_array, order)


@dataclasses.dataclass
class Grid:
    """Coordinate arithmetic over the mesh — the TPU analogue of the
    reference's ``PipelineParallelGrid`` (runtime/pipe/topology.py:251) and the
    group getters in ``deepspeed/utils/groups.py``.

    On TPU there are no group handles; "groups" are just axis names handed to
    collectives.  The grid answers size/rank questions for host-side logic
    (dataloader sharding, checkpoint naming, logging).
    """

    mesh: "object"  # jax.sharding.Mesh
    spec: MeshSpec

    @property
    def world_size(self) -> int:
        return self.spec.world_size

    def axis_size(self, axis: str) -> int:
        return self.spec.sizes[axis]

    @property
    def dp_world_size(self) -> int:
        return self.spec.dp_world_size

    @property
    def model_parallel_size(self) -> int:
        return self.spec.model

    @property
    def pipe_parallel_size(self) -> int:
        return self.spec.stage

    @property
    def sequence_parallel_size(self) -> int:
        return self.spec.seq

    @property
    def expert_parallel_size(self) -> int:
        return self.spec.expert

    def coords_of(self, device) -> Dict[str, int]:
        idx = np.argwhere(self.mesh.devices == device)
        if idx.size == 0:
            raise ValueError(f"device {device} not in mesh")
        return dict(zip(self.mesh.axis_names, idx[0].tolist()))

    def local_dp_rank(self) -> int:
        """DP replica index of this *process* (for dataloader sharding).

        Each process owns a contiguous block of devices; we take the dp coords
        of its first addressable device.
        """
        import jax

        dev = jax.local_devices()[0]
        c = self.coords_of(dev)
        return (
            c[DATA_AXIS] * self.spec.fsdp + c[FSDP_AXIS]
        ) * self.spec.sub + c.get(SUB_AXIS, 0)


def initialize_mesh(spec: Optional[MeshSpec] = None, devices=None, **axes) -> Grid:
    """One-call mesh bring-up: ``initialize_mesh(fsdp=8)``.

    ``batch=`` is the serving alias of ``data=`` (see BATCH_AXIS):
    ``initialize_mesh(batch=2, model=2)`` builds the 2-D serve mesh the v2
    engine shards its KV pool and slot groups over."""
    import jax

    if "batch" in axes:
        if "data" in axes:
            raise ValueError("pass either batch= or data=, not both "
                             "(batch is the serving alias of the data axis)")
        axes["data"] = axes.pop("batch")
    n = len(devices) if devices is not None else len(jax.devices())
    if spec is None:
        spec = infer_spec(n, **axes)
    mesh = build_mesh(spec, devices)
    return Grid(mesh=mesh, spec=spec)
