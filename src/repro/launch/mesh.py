"""Production meshes (assignment: 16x16 single-pod, 2x16x16 multi-pod).

`make_production_mesh` is a FUNCTION so importing this module never
touches jax device state; the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax
import to build these meshes on a CPU host.
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_host_mesh", "batch_axes"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many host devices exist (tests)."""
    return jax.make_mesh((data, model), ("data", "model"))


def batch_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axes of a mesh (everything except 'model')."""
    return tuple(n for n in mesh.axis_names if n != "model")
