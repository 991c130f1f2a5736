"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to build these meshes on CPU.
"""
from __future__ import annotations

import jax
import numpy as np


def _build_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _build_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh for tests / elastic re-mesh."""
    return _build_mesh(shape, axes)


def make_replica_meshes(dp: int, tp: int = 1):
    """Per-replica meshes for data-parallel serving: ``dp`` engine
    replicas, each tensor-parallel over its own ``tp`` contiguous devices
    (see ``repro.distributed.sharding.replica_device_groups``).  Replicas
    never communicate — the async router fans requests out host-side — so
    there is no global dp axis.  With ``tp == 1`` each replica gets a
    one-device mesh, which the engine reads as "place params and caches
    on this device"."""
    from repro.distributed.sharding import replica_device_groups

    groups = replica_device_groups(dp, tp)
    return [jax.sharding.Mesh(np.asarray(g), ("model",),
                              axis_types=(jax.sharding.AxisType.Auto,))
            for g in groups]
