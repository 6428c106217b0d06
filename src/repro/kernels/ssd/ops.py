"""Jit'd wrapper for the SSD kernel (+ custom_vjp via reference)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode

from . import kernel as _k
from .ref import ssd_reference


def supported(T, chunk, Pd, N) -> bool:
    return T % chunk == 0 and Pd % 8 == 0 and N % 8 == 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd(x, dt, a_log, b, c, chunk):
    # model layout (B, T, H, .) -> kernel layout (B, H, T, .)
    B, T, H, _ = x.shape
    dt = jnp.swapaxes(dt, 1, 2)                                # (B, H, T)
    da = dt * -jnp.exp(a_log.astype(jnp.float32))[:, None]
    cum = jnp.cumsum(da.reshape(B, H, T // chunk, chunk), axis=-1)
    y = _k.ssd_fwd(jnp.swapaxes(x, 1, 2), dt.reshape(B, H, 1, T),
                   cum.reshape(B, H, 1, T),
                   jnp.swapaxes(b, 1, 2), jnp.swapaxes(c, 1, 2),
                   chunk=chunk, interpret=interpret_mode())
    return jnp.swapaxes(y, 1, 2)


def _fwd(x, dt, a_log, b, c, chunk):
    return _ssd(x, dt, a_log, b, c, chunk), (x, dt, a_log, b, c)


def _bwd(chunk, res, g):
    x, dt, a_log, b, c = res

    def f(x, dt, a_log, b, c):
        return ssd_reference(x, dt, a_log, b, c, chunk=chunk)

    _, vjp = jax.vjp(f, x, dt, a_log, b, c)
    return vjp(g.astype(jnp.float32))


_ssd.defvjp(_fwd, _bwd)


def ssd(x, dt, a_log, b, c, *, chunk: int = 128):
    """x: (B,T,H,P); dt: (B,T,H); a_log: (H,); b,c: (B,T,G,N).

    Head h reads group h // (H // G) inside the kernel (no broadcast)."""
    out = _ssd(x, dt.astype(jnp.float32), a_log, b, c, chunk)
    return out.astype(jnp.float32)
