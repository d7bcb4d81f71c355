"""Device mesh management.

TPU-native replacement for the reference's communicator-group machinery
(``HybridCommunicateGroup`` topology ``fleet/base/topology.py:36,117``, NCCL
ring ids ``platform/collective_helper.h:71``): one ``jax.sharding.Mesh``
whose named axes (dp/pp/tp/sp/ep…) ARE the communicator groups — XLA lowers
per-axis collectives onto ICI rings automatically.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

_global_mesh: Optional[Mesh] = None


def build_mesh(axis_names: Sequence[str], shape: Sequence[int], devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    n = int(np.prod(shape))
    if n > len(devices):
        raise ValueError(f"mesh needs {n} devices, have {len(devices)}")
    arr = np.asarray(devices[:n]).reshape(tuple(shape))
    return Mesh(arr, tuple(axis_names))


def set_global_mesh(mesh: Mesh):
    global _global_mesh
    _global_mesh = mesh


def global_mesh() -> Mesh:
    global _global_mesh
    if _global_mesh is None:
        devs = jax.devices()
        _global_mesh = Mesh(np.asarray(devs), ("dp",))
    return _global_mesh


_partitioned = threading.local()


@contextlib.contextmanager
def partitioned_over(mesh: Mesh):
    """Declare, for the duration of a trace, that the program being traced is
    compiled with arguments sharded over ``mesh`` (the hybrid engine's GSPMD
    step). Code that GSPMD cannot partition by itself — a Mosaic kernel —
    reads it through :func:`partitioned_mesh` and maps itself over the mesh
    by hand. A tracer carries no sharding, so the compiling side has to say."""
    prev = getattr(_partitioned, "mesh", None)
    _partitioned.mesh = mesh
    try:
        yield
    finally:
        _partitioned.mesh = prev


def partitioned_mesh() -> Optional[Mesh]:
    """The mesh of the enclosing :func:`partitioned_over`, or None. Unlike
    :func:`global_mesh` it never invents one: a step compiled for one device
    on a four-chip host must not be mapped over four."""
    return getattr(_partitioned, "mesh", None)


def shard_map_compat():
    """(shard_map, check_kwargs) — delegates to ``core/compat.py``, the one
    import point for the jax names that moved between releases."""
    from ..core.compat import shard_map, shard_map_check_kwargs

    return shard_map, shard_map_check_kwargs(False)


def mesh_axis_size(axis: str) -> int:
    m = global_mesh()
    return m.shape.get(axis, 1) if hasattr(m.shape, "get") else dict(zip(m.axis_names, m.devices.shape)).get(axis, 1)


def named_sharding(*spec) -> NamedSharding:
    return NamedSharding(global_mesh(), PartitionSpec(*spec))
