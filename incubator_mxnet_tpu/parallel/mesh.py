"""Device mesh construction & sharding helpers.

The reference's device topology handling (src/kvstore/gpu_topology.h link-matrix
tree reduce) becomes: declare a jax.sharding.Mesh over the ICI torus and let
XLA place collectives on it. DCN (multi-host) is just an outer mesh axis.
"""
from __future__ import annotations

import contextlib
import threading

import numpy as onp

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["make_mesh", "current_mesh", "set_current_mesh", "replicated",
           "shard_spec", "step_mesh_scope", "step_mesh", "P", "NamedSharding",
           "Mesh"]

_CURRENT = [None]
_STEP = threading.local()


def make_mesh(axes=None, devices=None):
    """Create a Mesh from {'axis': size} (sizes must multiply to #devices;
    one axis may be -1 to absorb the remainder)."""
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if axes is None:
        axes = {"dp": n}
    names = list(axes.keys())
    sizes = list(axes.values())
    if -1 in sizes:
        known = 1
        for s in sizes:
            if s != -1:
                known *= s
        sizes[sizes.index(-1)] = n // known
    total = 1
    for s in sizes:
        total *= s
    if total != n:
        raise ValueError("mesh axes %s do not cover %d devices" % (dict(zip(names, sizes)), n))
    arr = onp.array(devices).reshape(sizes)
    mesh = Mesh(arr, axis_names=tuple(names))
    set_current_mesh(mesh)
    return mesh


def set_current_mesh(mesh):
    _CURRENT[0] = mesh


def current_mesh():
    return _CURRENT[0]


@contextlib.contextmanager
def step_mesh_scope(mesh, data_axis):
    """Declared by a train step around the trace of its forward: the mesh
    the program will be partitioned over and the axis its batch is split
    on (nothing to declare for a step on one device). Unlike
    ``current_mesh()`` — a process-wide default that outlives the step
    that set it — this is true exactly while that step traces, so a block
    can rely on it."""
    prev = getattr(_STEP, "value", None)
    _STEP.value = (mesh, data_axis) \
        if mesh is not None and mesh.size > 1 else None
    try:
        yield
    finally:
        _STEP.value = prev


def step_mesh():
    """(mesh, data_axis) of the multi-device train step being traced on
    this thread, or None (eager code, steps on one device)."""
    return getattr(_STEP, "value", None)


def replicated(mesh):
    return NamedSharding(mesh, P())


def shard_spec(mesh, *axes):
    """NamedSharding partitioning consecutive dims over the given axis names
    (None entries mean 'replicated on that dim')."""
    return NamedSharding(mesh, P(*axes))
