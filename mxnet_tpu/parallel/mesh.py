"""Device meshes.

The reference models parallelism as an explicit device list (``ctx=[gpu(0),
gpu(1), ...]`` split by ``_split_input_slice``, ``executor_manager.py:15``)
plus ``group2ctx`` placement for model parallelism.  The TPU-native model is
a named mesh: axes ``data``/``model``/``pipe``/``seq``/``expert`` over the
chip grid, with per-array shardings — XLA lays collectives onto ICI
neighbors automatically when the mesh axis order follows the physical
topology (jax's default device order does).
"""
from __future__ import annotations

import threading
from typing import Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["Mesh", "NamedSharding", "PartitionSpec", "get_mesh",
           "make_mesh", "current_mesh", "data_parallel_mesh",
           "global_data_parallel_mesh", "batch_sharding", "replicated",
           "zero_spec"]


_LOCAL = threading.local()


def make_mesh(axis_shapes: dict, devices: Optional[Sequence] = None) -> Mesh:
    """Build a Mesh from ``{axis_name: size}``.

    ``{"data": 4, "model": 2}`` over 8 chips puts the model axis on
    adjacent chips (fastest-varying), which keeps tensor-parallel
    collectives on one ICI link hop — the layout recipe of the scaling
    playbook (contrast: the reference's Comm tree is topology-blind).
    """
    devices = list(devices if devices is not None else jax.devices())
    sizes = list(axis_shapes.values())
    total = int(np.prod(sizes))
    if total > len(devices):
        raise ValueError("mesh of %d devices requested, %d available"
                         % (total, len(devices)))
    grid = np.array(devices[:total]).reshape(sizes)
    return Mesh(grid, tuple(axis_shapes.keys()))


def data_parallel_mesh(num_devices: Optional[int] = None) -> Mesh:
    """1-D ``data`` mesh over all (or the first N) devices."""
    devices = jax.devices()
    n = num_devices or len(devices)
    return make_mesh({"data": n}, devices)


def global_data_parallel_mesh(per_process: Optional[int] = None,
                              axis: str = "data",
                              local_batch: Optional[int] = None
                              ) -> Optional[Mesh]:
    """Process-spanning 1-D mesh: the ``data`` axis covers EVERY
    process's devices in rank-major order (rank =
    ``jax.process_index()``), so batch dim 0 shards across hosts and the
    fused step's gradient psum rides DCN/ICI between them.  Call after
    ``jax.distributed.initialize`` (the launcher env contract does this
    at package import).

    ``per_process`` caps the devices taken from each process — the mesh
    must stay rectangular, so the default is the MINIMUM local device
    count across processes; ``local_batch`` further lowers it to the
    largest count dividing the per-process batch (k=1 always
    qualifies).  Returns None for a single-process job: the caller
    should use a local mesh (and never believe it has cross-host sync
    when it does not)."""
    per = {}
    for d in jax.devices():
        per.setdefault(d.process_index, []).append(d)
    if len(per) <= 1:
        return None
    k = min(len(v) for v in per.values())
    if per_process is not None:
        k = min(k, int(per_process))
    if local_batch is not None:
        while k > 1 and local_batch % k != 0:
            k -= 1
    devs = []
    for p in sorted(per):
        devs.extend(sorted(per[p], key=lambda d: d.id)[:k])
    return make_mesh({axis: len(devs)}, devs)


def get_mesh(num_devices: Optional[int] = None) -> Mesh:
    """The active mesh: innermost ``with mesh:`` scope, else a fresh
    data-parallel mesh."""
    cur = current_mesh()
    if cur is not None:
        return cur
    return data_parallel_mesh(num_devices)


def current_mesh() -> Optional[Mesh]:
    """The mesh of the innermost ``with use_mesh(m):`` scope, or None."""
    return getattr(_LOCAL, "mesh", None)


class _MeshScope:
    def __init__(self, mesh):
        self.mesh = mesh

    def __enter__(self):
        self.prev = getattr(_LOCAL, "mesh", None)
        _LOCAL.mesh = self.mesh
        return self.mesh

    def __exit__(self, *exc):
        _LOCAL.mesh = self.prev


def use_mesh(mesh: Mesh) -> _MeshScope:
    """``with use_mesh(m): ...`` sets the framework-level active mesh."""
    return _MeshScope(mesh)


def batch_sharding(mesh: Mesh, ndim: int, axis: str = "data") -> NamedSharding:
    """Shard dim 0 (batch) along ``axis``, replicate the rest."""
    spec = [None] * ndim
    spec[0] = axis
    return NamedSharding(mesh, PartitionSpec(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def zero_spec(base_spec: PartitionSpec, shape: Sequence[int], n: int,
              axis: str = "data") -> PartitionSpec:
    """ZeRO sharding of a per-weight state leaf: ``base_spec`` (the
    weight's own partitioning) with ``axis`` folded into the first
    unsharded dim whose size divides by ``n`` — the TPU-mesh analog of
    the reference kvstore's per-server key slices (each server owns a
    contiguous slice of every value and updates only that slice).

    A leaf with no divisible free dim (small biases, scalars) keeps
    ``base_spec`` — replicating a few KB costs less than padded
    collectives.  A ``base_spec`` that already names ``axis`` is
    returned unchanged (the caller sharded it; nothing left to fold).
    """
    entries = list(base_spec) + [None] * (len(shape) - len(base_spec))
    used = [a for e in entries if e is not None
            for a in (e if isinstance(e, tuple) else (e,))]
    if axis in used:
        return PartitionSpec(*entries)
    for d, (e, dim) in enumerate(zip(entries, shape)):
        if e is None and dim >= n and dim % n == 0:
            entries[d] = axis
            break
    return PartitionSpec(*entries)
