"""Production mesh factories.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state — only launch/dryrun.py sets the 512-placeholder-
device XLA flag, and only before its first jax import.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh():
    """1-device mesh with the production axis names (smoke tests)."""
    return _mesh((1, 1), ("data", "model"))
