"""Device mesh management.

TPU-native replacement for the reference's NCCL ring/communicator registry
(/root/reference/paddle/fluid/platform/collective_helper.h:62
NCCLCommContext keyed by ring_id, nccl_helper.h:234 InitFlatCtxs /
:265 InitHierarchicalCtxs): instead of rings, a named jax.sharding.Mesh
whose axes ('dp','pp','tp','sp','ep') are what collectives address.
Hierarchical inter/intra-node rings become mesh factorizations with the
DCN axis outermost.
"""
from __future__ import annotations

from contextlib import contextmanager
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

_global_mesh: list = [None]

AXES = ("dp", "pp", "tp", "sp", "ep")

# axes a feed's batch dim rides by default (data_sharding / the static
# executor's feed shardings): plain data parallel ('dp') or the classic
# CompiledProgram 'data' axis. Explicit batch axes (e.g. ('dp', 'sp'))
# go through data_sharding(..., axes=...).
DATA_AXIS_NAMES = ("dp", "data")


def create_mesh(mesh_shape: Optional[Dict[str, int]] = None,
                devices: Optional[Sequence] = None) -> Mesh:
    """create_mesh({'dp': 2, 'tp': 4}) over local (or given) devices.

    Axes with size 1 may be omitted; remaining devices fold into 'dp'.
    DCN-reaching axes should be listed first (outermost) so XLA keeps
    high-traffic collectives on ICI.
    """
    devices = list(devices if devices is not None else jax.devices())
    mesh_shape = dict(mesh_shape or {})
    sized = {k: v for k, v in mesh_shape.items() if v and v > 1}
    total = int(np.prod(list(sized.values()))) if sized else 1
    if total > len(devices):
        raise ValueError(f"mesh needs {total} devices, have {len(devices)}")
    if total < len(devices):
        if "dp" in sized:
            sized["dp"] *= len(devices) // total
        else:
            sized = {"dp": len(devices) // total, **sized}
    names = tuple(sized.keys())
    shape = tuple(sized.values())
    arr = np.asarray(devices[: int(np.prod(shape))]).reshape(shape)
    mesh = Mesh(arr, names)
    _global_mesh[0] = mesh
    return mesh


def get_mesh() -> Optional[Mesh]:
    return _global_mesh[0]


def set_mesh(mesh: Mesh):
    _global_mesh[0] = mesh


# -- trace-time mesh marker -------------------------------------------------
# TrainStep sets this while TRACING its pjit'd step (same trace-time
# pattern as ring.sequence_parallel): kernels whose pallas custom calls
# XLA cannot SPMD-partition (fused_xent — not wrapped in shard_map)
# consult it to self-gate under multi-device traces. The ambient
# _global_mesh is NOT used for that decision: it leaks across tests and
# may differ from the mesh actually governing the trace.

_trace_mesh: list = [(None, ())]


@contextmanager
def trace_mesh(mesh: Optional[Mesh], row_axes: Sequence[str] = ()):
    """row_axes: the mesh axes the BATCH rows are sharded over (from
    TrainStep's data_spec/data_axes) — what a row-parallel kernel needs
    to shard_map itself and psum its reductions."""
    prev = _trace_mesh[0]
    _trace_mesh[0] = (mesh, tuple(row_axes))
    try:
        yield
    finally:
        _trace_mesh[0] = prev


def active_trace_mesh() -> Optional[Mesh]:
    """The mesh of the TrainStep trace currently being built, if any."""
    return _trace_mesh[0][0]


def active_trace_row_axes() -> tuple:
    """The batch-row sharding axes of the current TrainStep trace."""
    return _trace_mesh[0][1]


def auto_partitioned_trace() -> bool:
    """True while tracing a multi-device TrainStep OUTSIDE a full-manual
    shard_map — where XLA, not the program, partitions each op. A Pallas
    TPU kernel cannot lower there (jax: "Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map"), so
    the kernel gates route such dispatches to XLA; inside a shard_map
    over every mesh axis the same kernels are fine."""
    mesh = active_trace_mesh()
    if mesh is None or mesh.size <= 1:
        return False
    manual = jax.sharding.get_abstract_mesh().manual_axes
    return set(manual) != set(mesh.axis_names)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def data_sharding(mesh: Mesh, batch_ndim: int = 1,
                  axes: Optional[Sequence[str]] = None) -> NamedSharding:
    """Shard the leading (batch) dim over the mesh's data-like axes.

    ``axes`` names the batch axes explicitly (e.g. ``('dp', 'sp')`` for
    batch rows split over data AND sequence-parallel ranks); names absent
    from the mesh are dropped. With ``axes=None`` the default derives
    from the active mesh's axis names (every :data:`DATA_AXIS_NAMES`
    axis present), so the executor's feed sharding works on any mesh
    shape — 'dp', the classic CompiledProgram 'data' axis, or both."""
    if axes is None:
        axes = [a for a in DATA_AXIS_NAMES if a in mesh.axis_names]
    else:
        axes = [a for a in axes if a in mesh.axis_names]
    spec = [tuple(axes) if axes else None] + [None] * (batch_ndim - 1)
    return NamedSharding(mesh, PartitionSpec(*spec))


# (axis sizes tuple, device ids tuple) -> Mesh: the static executor
# resolves BuildStrategy.mesh_shape through here on every step, so the
# Mesh object must be stable (jax mesh/sharding caches key on identity)
_mesh_cache: Dict[tuple, Mesh] = {}


def mesh_for_shape(mesh_shape: Dict[str, int],
                   devices: Optional[Sequence] = None) -> Mesh:
    """A Mesh of exactly ``mesh_shape`` (no dp-folding of leftover
    devices, unlike :func:`create_mesh`) over the first
    prod(sizes) local (or given) devices, cached — repeated calls with
    the same shape return the SAME Mesh object and never touch the
    ambient global mesh."""
    devices = list(devices if devices is not None else jax.devices())
    sized = {str(k): int(v) for k, v in (mesh_shape or {}).items()
             if int(v) > 1}
    if not sized:
        raise ValueError(f"mesh_for_shape: no axis with size > 1 in "
                         f"{mesh_shape!r}")
    total = int(np.prod(list(sized.values())))
    if total > len(devices):
        raise ValueError(
            f"mesh_shape {mesh_shape!r} needs {total} devices, have "
            f"{len(devices)} (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=N for CPU tests)")
    key = (tuple(sized.items()), tuple(id(d) for d in devices[:total]))
    mesh = _mesh_cache.get(key)
    if mesh is None:
        arr = np.asarray(devices[:total]).reshape(tuple(sized.values()))
        mesh = Mesh(arr, tuple(sized.keys()))
        _mesh_cache[key] = mesh
    return mesh


def axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1
