"""Jit'd wrapper for the RG-LRU kernel (+ custom_vjp via reference)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode

from . import kernel as _k
from .ref import rglru_ref


def supported(T, W) -> bool:
    return T % 128 == 0 and W % 128 == 0


@jax.custom_vjp
def _rglru(x, r, i, lam):
    return _k.rglru_fwd(x, r, i, lam, interpret=interpret_mode())


def _fwd(x, r, i, lam):
    return _rglru(x, r, i, lam), (x, r, i, lam)


def _bwd(res, g):
    x, r, i, lam = res
    _, vjp = jax.vjp(rglru_ref, x, r, i, lam)
    return vjp(g.astype(jnp.float32))


_rglru.defvjp(_fwd, _bwd)


def rglru(x, r, i, lam):
    return _rglru(x.astype(jnp.float32), r.astype(jnp.float32),
                  i.astype(jnp.float32), lam.astype(jnp.float32))
