"""Production mesh builders.

Functions (not module-level constants) so importing never touches jax device
state.  Single pod: (16, 16) = 256 v5e chips, axes (data, model).
Multi-pod: (2, 16, 16) = 512 chips, axes (pod, data, model); the pod axis
composes with data for batch/FSDP sharding (repro.distributed.sharding).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """A device mesh whose axes are all ``Auto`` (shard_map with explicit
    specs everywhere, no explicit-sharding types)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for host-device tests (requires matching device count)."""
    return make_mesh(shape, axes)
