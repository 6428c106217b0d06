"""Production meshes.  A FUNCTION (not module constant) so importing never
touches jax device state — required by the dry-run contract."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: model code shards activations with with_sharding_constraint,
    # which only accepts Auto mesh axes (make_mesh defaults to Explicit)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod (v5e); multi_pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh():
    """Whatever this host actually has (tests / examples)."""
    n = len(jax.devices())
    return _mesh((n,), ("data",))
