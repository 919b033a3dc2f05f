"""Device-mesh construction — the single abstraction that replaces the
reference's per-strategy code paths.

In the reference, DDP / FSDP / ZeRO / TP / Megatron-SP are ~3k LoC of separate
wrapper branches (reference: src/accelerate/accelerator.py:1447-2285). On TPU
every one of them is a *layout* of the same ``jax.sharding.Mesh``:

=================  ==========================================================
reference strategy  mesh layout
=================  ==========================================================
DDP                 ``MeshConfig(data=N)`` — params replicated, batch sharded
FSDP / ZeRO-3       ``MeshConfig(fsdp=N)`` — params+opt state sharded
ZeRO-1/2 (passive)  ``MeshConfig(data=N)`` + ``ParallelismPlugin(shard_optimizer_state=True)``
ZeRO-1 (explicit)   ``MeshConfig(data=N)`` + ``ParallelismPlugin(zero_stage=1)`` — reduce-scatter/update/all-gather wire, quantizable
TP (Megatron)       ``MeshConfig(tensor=K)`` — column/row param splits
SP (Megatron)       ``MeshConfig(seq=K)`` — activation seq-dim sharding
PP                  ``MeshConfig(pipe=K)`` — stage axis (shard_map+ppermute)
EP                  ``MeshConfig(expert=K)`` — MoE expert axis
hybrid (3D)         any product, e.g. ``MeshConfig(data=2, fsdp=2, tensor=2)``
=================  ==========================================================

Axis order is chosen so the fastest-varying (innermost, best-ICI) axis is
``tensor``: collectives on ``tensor`` happen every layer, collectives on
``data``/``fsdp`` once per step, DCN-crossing traffic should land on the
outermost axes.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Sequence

import numpy as np

AXIS_NAMES = ("pipe", "data", "fsdp", "expert", "seq", "tensor")

# Axes over which the *batch* dimension of inputs is sharded. ``fsdp`` ranks
# see distinct data (ZeRO-style: fsdp is also a data axis), ``tensor``/``seq``
# ranks see the same batch (reference keeps TP groups on identical batches:
# src/accelerate/data_loader.py:1109-1141).
BATCH_AXES = ("data", "fsdp")


@dataclass
class MeshConfig:
    """Logical mesh shape. ``-1`` on exactly one axis means "fill with all
    remaining devices" (so ``MeshConfig()`` is pure data parallelism).

    ``num_devices`` restricts the mesh to the first N devices instead of
    all of them — the topology-elasticity lever: a job resuming on a
    machine with more devices than the checkpoint's mesh (or a test
    simulating a shrunk fleet on the 8-device fake-CPU harness) can
    rebuild the *saved* topology, or any smaller one, without changing
    the hardware. ``None`` (default) uses every device.

    Plays the role of the reference's strategy plugins
    (``FullyShardedDataParallelPlugin``, ``TorchTensorParallelPlugin``,
    ``MegatronLMPlugin`` tp/pp/sp degrees — reference:
    src/accelerate/utils/dataclasses.py:1489,2070,2208-2216).
    """

    data: int = -1
    fsdp: int = 1
    tensor: int = 1
    seq: int = 1
    pipe: int = 1
    expert: int = 1
    num_devices: Optional[int] = None

    def sizes(self, num_devices: int) -> dict[str, int]:
        vals = {name: getattr(self, _FIELD_BY_AXIS[name]) for name in AXIS_NAMES}
        fills = [k for k, v in vals.items() if v == -1]
        if len(fills) > 1:
            raise ValueError(f"at most one mesh axis may be -1, got {fills}")
        fixed = math.prod(v for v in vals.values() if v != -1)
        if fills:
            if num_devices % fixed != 0:
                raise ValueError(
                    f"cannot fill axis {fills[0]!r}: {num_devices} devices not divisible by fixed product {fixed}"
                )
            vals[fills[0]] = num_devices // fixed
        else:
            total = fixed
            if total != num_devices:
                raise ValueError(f"mesh shape {vals} uses {total} devices but {num_devices} are present")
        return vals

    def build(self, devices=None) -> "jax.sharding.Mesh":  # noqa: F821
        """Build the physical mesh. Device order is delegated to
        ``jax.make_mesh`` which picks an ICI-friendly assignment on TPU."""
        import jax

        if devices is None:
            devices = jax.devices()
        if self.num_devices is not None:
            if self.num_devices > len(devices):
                raise ValueError(
                    f"MeshConfig(num_devices={self.num_devices}) but only {len(devices)} devices are present"
                )
            devices = list(devices)[: self.num_devices]
        sizes = self.sizes(len(devices))
        shape = tuple(sizes[a] for a in AXIS_NAMES)
        # Auto axis types = classic GSPMD propagation (jax defaults new
        # meshes to Explicit sharding-in-types, which changes jit semantics)
        axis_types = (jax.sharding.AxisType.Auto,) * len(AXIS_NAMES)
        return jax.make_mesh(shape, AXIS_NAMES, devices=devices, axis_types=axis_types)

    @classmethod
    def from_env(cls) -> "MeshConfig":
        """Read mesh shape from the ``ACCELERATE_MESH_*`` env protocol
        (the launcher->script channel, reference: utils/launch.py:203-352)."""
        import os

        kwargs = {}
        for name in AXIS_NAMES:
            field = _FIELD_BY_AXIS[name]
            val = os.environ.get(f"ACCELERATE_MESH_{name.upper()}")
            if val is not None:
                kwargs[field] = int(val)
        limit = os.environ.get("ACCELERATE_MESH_NUM_DEVICES")
        if limit is not None:
            kwargs["num_devices"] = int(limit)
        return cls(**kwargs)

    @property
    def is_trivial(self) -> bool:
        return all(
            getattr(self, name) in (1, -1) or name == "data"
            for name in _FIELD_BY_AXIS.values()
        )


_FIELD_BY_AXIS = {"pipe": "pipe", "data": "data", "fsdp": "fsdp", "expert": "expert", "seq": "seq", "tensor": "tensor"}


# -- axis transport metadata (ICI vs DCN) ---------------------------------
#
# On a single TPU slice every mesh axis rides the ICI torus. Multi-slice
# ("multipod") topologies route the OUTERMOST axes over the data-center
# network instead — orders of magnitude less bandwidth — so the cost model
# (analysis.costmodel) must know which axes cross DCN. The launcher sets
# ``ACCELERATE_MESH_DCN_AXES`` (comma-separated axis names) on multi-slice
# jobs; single-slice runs leave it unset and everything is ICI.

ICI = "ici"
DCN = "dcn"

DCN_AXES_ENV = "ACCELERATE_MESH_DCN_AXES"


def dcn_axes() -> tuple[str, ...]:
    """Mesh axes that cross the data-center network, from the
    ``ACCELERATE_MESH_DCN_AXES`` launcher protocol (empty == single slice,
    every axis on ICI)."""
    import os

    raw = os.environ.get(DCN_AXES_ENV, "")
    return tuple(a.strip() for a in raw.split(",") if a.strip())


def axis_transport(mesh, axis: str, dcn: Sequence[str] | None = None) -> str:
    """``"ici"`` or ``"dcn"`` for a mesh axis. ``dcn`` overrides the env
    protocol (analysis passes an explicit list when modelling a topology
    that is not the ambient one). Trivial (size-1) axes carry no traffic
    and report ICI."""
    names = tuple(dcn) if dcn is not None else dcn_axes()
    if axis in names and mesh.shape.get(axis, 1) > 1:
        return DCN
    return ICI


def batch_sharding(mesh) -> "jax.sharding.NamedSharding":  # noqa: F821
    """Sharding for a global batch: leading dim split over the batch axes."""
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec(BATCH_AXES))


def replicated(mesh) -> "jax.sharding.NamedSharding":  # noqa: F821
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec())


def axis_spec(mesh, axes):
    """Normalise an axis name (or tuple of names) to the subset that is
    actually non-trivial on ``mesh`` — ``None`` when none are, a bare name
    for one, a tuple for several. This is the shared PartitionSpec-entry
    builder for batch/head dims across context/pipeline/attention."""
    if axes is None:
        return None
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    present = tuple(a for a in axes if a in mesh.shape and mesh.shape[a] > 1)
    if not present:
        return None
    return present if len(present) > 1 else present[0]


def axis_size(mesh, axes) -> int:
    """Product of the mesh sizes of ``axes`` (names absent from the mesh
    count as 1)."""
    if axes is None:
        return 1
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    return int(np.prod([mesh.shape.get(a, 1) for a in axes]))


def data_parallel_size(mesh) -> int:
    """Number of distinct data shards (product of the batch axes)."""
    return axis_size(mesh, BATCH_AXES)
