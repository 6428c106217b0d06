"""Jit'd public wrapper for flash attention.

Layout adapter: the model uses (B, S, H, D); the kernel uses (B, H, S, D).
Backward pass: custom_vjp recomputing with the chunked-jnp reference (the
flash forward is exact, so gradients from the reference are exact too) —
a dedicated backward kernel is future work, noted in DESIGN.md.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode

from . import kernel as _k
from . import ref as _ref


def supported(q, k, window, softcap) -> bool:
    B, S, H, D = q.shape
    if S < 256 or S % 128 != 0:
        return False
    if D % 64 != 0:
        return False
    return True


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _fa(q, k, v, scale, causal, window, softcap):
    # (B,S,H,D) -> (B,H,S,D)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = _k.flash_attention_fwd(qt, kt, vt, scale=scale, causal=causal,
                                 window=window, softcap=softcap,
                                 interpret=interpret_mode())
    return jnp.swapaxes(out, 1, 2)


def _fa_fwd(q, k, v, scale, causal, window, softcap):
    return _fa(q, k, v, scale, causal, window, softcap), (q, k, v)


def _fa_bwd(scale, causal, window, softcap, res, g):
    q, k, v = res

    def f(q, k, v):
        from repro.models.attention import chunked_attention, ref_attention
        B, S = q.shape[:2]
        if S >= 8192 and S % 2048 == 0:
            return chunked_attention(q, k, v, scale=scale, window=window,
                                     cap=softcap, causal=causal)
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        return ref_attention(q, k, v, scale=scale, q_pos=pos, k_pos=pos,
                             window=window, cap=softcap, causal=causal)

    _, vjp = jax.vjp(f, q, k, v)
    return vjp(g)


_fa.defvjp(_fa_fwd, _fa_bwd)


def flash_attention(q, k, v, *, scale: float, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None):
    """q: (B, S, H, D); k/v: (B, S, KH, D[v]) -> (B, S, H, Dv)."""
    return _fa(q, k, v, scale, causal, window, softcap)


def attention_ref(q, k, v, *, scale, causal=True, window=None, softcap=None):
    """(B,S,H,D)-layout oracle."""
    out = _ref.attention_ref(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                             jnp.swapaxes(v, 1, 2), scale=scale,
                             causal=causal, window=window, softcap=softcap)
    return jnp.swapaxes(out, 1, 2)
