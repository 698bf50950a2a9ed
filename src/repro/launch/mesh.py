"""Production meshes.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the 'pod' axis is the
slow (DCN/cross-pod) link — gradient compression targets it.

Defined as functions so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: the partitioner places what the code does not pin, and
    # with_sharding_constraint accepts the logical specs the models
    # write (jax.make_mesh defaults to Explicit axes, which refuse them)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(shape=(2, 2, 2), axes=("pod", "data", "model")):
    """Small mesh over host devices for distribution tests."""
    return _auto_mesh(shape, axes)


def describe(mesh) -> str:
    return (f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))} = "
            f"{mesh.devices.size} devices on "
            f"{mesh.devices.flat[0].platform}")
