"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — required because the dry-run must set
XLA_FLAGS before jax initializes devices.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: Tuple[int, ...],
               axes: Tuple[str, ...]) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axes: the sharding rules place arrays
    with ``with_sharding_constraint``, which Explicit axes refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 chips per pod; multi-pod adds a leading 'pod' axis (2 pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...],
              axes: Optional[Tuple[str, ...]] = None) -> jax.sharding.Mesh:
    """Arbitrary mesh for tests / elastic re-meshing."""
    if axes is None:
        axes = ("pod", "data", "model")[-len(shape):]
    return _auto_mesh(shape, axes)


def host_mesh() -> jax.sharding.Mesh:
    """Whatever devices exist locally (tests: 1 CPU device => (1,1))."""
    n = len(jax.devices())
    return _auto_mesh((n, 1), ("data", "model"))
