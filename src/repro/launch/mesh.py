"""Production mesh construction.

Axis roles: ``pod`` (inter-pod DCN-ish axis), ``data`` (intra-pod data
parallel), ``model`` (tensor/expert parallel).  Constructed lazily as a
function so importing this module never touches jax device state — the
dry-run must set XLA_FLAGS before first jax init.
"""
from __future__ import annotations

import jax


def _mk(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """(16, 16) = 256 chips/pod single-pod; (2, 16, 16) = 512 chips over
    two pods.  Requires that many (possibly host-platform) devices."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_debug_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """Tiny mesh with the same axis roles (pytest-sized: 8 host devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)
