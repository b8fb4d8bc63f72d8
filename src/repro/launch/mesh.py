"""Production mesh construction (DESIGN §3).

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (the dry-run sets the host-device-count flag
before any jax initialization)."""

from __future__ import annotations

import jax
from jax.sharding import Mesh


def _mesh(shape, axes, devices=None) -> Mesh:
    """``devices`` defaults to ``jax.devices()``; pass a described topology's
    devices to compile for a chip that is not attached."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod; multi_pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """Single-device mesh for CPU tests/examples (1x1)."""
    return _mesh((1, 1), ("data", "model"))


def data_axis_size(mesh: Mesh) -> int:
    size = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        size *= mesh.shape["pod"]
    return size
