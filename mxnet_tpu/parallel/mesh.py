"""Device mesh utilities.

The reference's Context-list world (`ctx=[gpu(0)..gpu(3)]`) maps onto a
`jax.sharding.Mesh` with named axes.  Conventions:

* axis "data" — batch (data parallelism; KVStore device/dist_sync semantics)
* axis "model" — tensor/model parallelism (the ctx_group analogue)
* axis "seq" — sequence/context parallelism (ring attention)

`make_mesh` builds a mesh from the visible devices; tests force 8 CPU devices
(`xla_force_host_platform_device_count`) so every sharding path runs without
TPU hardware, the same trick as the reference testing model parallelism on
cpu(0)/cpu(1) (`tests/python/unittest/test_model_parallel.py`).
"""
from __future__ import annotations

import numpy as np

import jax
from jax import shard_map  # noqa: F401  (the package imports it from here)
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import MXNetError

_current_mesh = None


def make_mesh(shape=None, axis_names=("data",), devices=None):
    """Create a Mesh.  shape=None → all devices on the first axis."""
    if devices is None:
        devices = jax.devices()
    if shape is None:
        shape = (len(devices),) + (1,) * (len(axis_names) - 1)
    n = int(np.prod(shape))
    if n > len(devices):
        raise MXNetError(
            "mesh shape %s needs %d devices, have %d" % (shape, n, len(devices))
        )
    arr = np.array(devices[:n]).reshape(shape)
    return Mesh(arr, axis_names)


class MeshContext:
    """`with MeshContext(mesh):` — scope the current mesh like the
    reference's Context stack."""

    def __init__(self, mesh):
        self.mesh = mesh
        self._old = None

    def __enter__(self):
        global _current_mesh
        self._old = _current_mesh
        _current_mesh = self.mesh
        return self.mesh

    def __exit__(self, *args):
        global _current_mesh
        _current_mesh = self._old


def get_mesh():
    return _current_mesh


def data_parallel_sharding(mesh, axis="data"):
    """Sharding for batch-major arrays: batch split over `axis`, everything
    else replicated."""
    return NamedSharding(mesh, P(axis))


def replicated_sharding(mesh):
    return NamedSharding(mesh, P())


def mesh_signature(mesh):
    """Hashable identity of a mesh's PROGRAM SPACE: axis names, axis
    sizes, and device platform.  Two meshes with the same signature
    compile identical partitioned programs, two with different
    signatures must never share an AOT cache entry — `AotCache`
    appends this tuple to every key on a sub-mesh serving replica, so
    a 2-shard and a 4-shard replica sharing one cache cannot collide.
    `None` (single-device callers) signs as the empty tuple."""
    if mesh is None:
        return ()
    devs = np.asarray(mesh.devices)
    first = devs.reshape(-1)[0]
    return (tuple(mesh.axis_names), tuple(devs.shape),
            str(getattr(first, "platform", first)))


def submeshes(devices, per_mesh, axis_names=("model",)):
    """Partition ``devices`` into consecutive groups of ``per_mesh``
    and return one 1-axis Mesh per group — the sub-mesh serving
    replica's fleet layout (`ReplicaRouter.from_mesh(...,
    devices_per_replica=k)`).  A remainder that cannot fill a whole
    group is dropped (a half-width replica would compile a different
    program space than its peers)."""
    devices = list(devices)
    per_mesh = int(per_mesh)
    if per_mesh < 1:
        raise MXNetError("submeshes: need per_mesh >= 1, got %d" % per_mesh)
    groups = [devices[i:i + per_mesh]
              for i in range(0, len(devices) - per_mesh + 1, per_mesh)]
    if not groups:
        raise MXNetError(
            "submeshes: %d devices cannot fill one %d-device sub-mesh"
            % (len(devices), per_mesh))
    return [Mesh(np.array(g), axis_names) for g in groups]
