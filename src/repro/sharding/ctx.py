"""Ambient sharding context: activation constraints inside model code.

Model code calls ``constrain(x, logical_axes)`` at key points; outside a
mesh context (unit tests on one CPU device) it is the identity, inside
``use_sharding`` it becomes ``with_sharding_constraint`` with the active
rule set.  This keeps the model pure while letting experiments flip rules.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding

from .rules import AxisVal, resolve

_tls = threading.local()


@contextlib.contextmanager
def use_sharding(mesh: Mesh, rules: Dict[str, AxisVal]):
    prev = getattr(_tls, "cur", None)
    _tls.cur = (mesh, rules)
    try:
        yield
    finally:
        _tls.cur = prev


def current() -> Optional[tuple]:
    return getattr(_tls, "cur", None)


def constrain(x: jax.Array, axes) -> jax.Array:
    cur = current()
    if cur is None:
        return x
    mesh, rules = cur
    spec = resolve(x.shape, axes, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
