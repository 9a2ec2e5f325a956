"""Production mesh construction.

Defined as functions (not module-level constants) so importing this module
never touches jax device state. The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to build these meshes on the CPU container.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, (jax.sharding.AxisType.Auto,) * len(shape))


def make_debug_mesh(n_data: int = 2, n_model: int = 4):
    """Small mesh for CPU multi-device tests (8 fake devices)."""
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         (jax.sharding.AxisType.Auto,) * 2)


def data_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def all_axes(mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)
