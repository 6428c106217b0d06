"""Parameter-spec system, norms, rotary embeddings, shared layer pieces.

Parameters are plain pytrees (nested dicts) of ``jnp`` arrays.  Every leaf is
declared once as a :class:`P` spec carrying its *logical axes* (MaxText-style)
— ``sharding/rules.py`` maps logical axes onto mesh axes, and
:func:`abstract_tree` derives ``ShapeDtypeStruct`` trees from the same specs
without ever materialising weights.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class P:
    """Declarative parameter spec: shape + logical axes + initializer."""

    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"          # normal | zeros | ones | embed
    scale: Optional[float] = None  # stddev override (default: fan-in)
    dtype: Any = None              # default: model dtype

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _path_seed(path: str) -> int:
    return int.from_bytes(hashlib.sha256(path.encode()).digest()[:4], "big")


def init_param(spec: P, key: jax.Array, path: str, dtype) -> jax.Array:
    dt = spec.dtype or dtype
    k = jax.random.fold_in(key, _path_seed(path))
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dt)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dt)
    if spec.init == "embed":
        std = spec.scale or 1.0
        return (jax.random.normal(k, spec.shape, jnp.float32) * std).astype(dt)
    fan_in = spec.shape[0] if len(spec.shape) >= 2 else max(spec.shape[-1], 1)
    std = spec.scale if spec.scale is not None else fan_in ** -0.5
    return (jax.random.normal(k, spec.shape, jnp.float32) * std).astype(dt)


def _tree_paths(tree, prefix=""):
    if isinstance(tree, dict):
        out = {}
        for k, v in tree.items():
            out[k] = _tree_paths(v, f"{prefix}/{k}")
        return out
    return prefix


def init_tree(specs, key: jax.Array, dtype) -> Any:
    """Materialise a spec tree into parameters (deterministic per path)."""
    paths = _tree_paths(specs)
    return jax.tree.map(
        lambda s, p: init_param(s, key, p, dtype), specs, paths,
        is_leaf=lambda x: isinstance(x, P))


def abstract_tree(specs, dtype) -> Any:
    """ShapeDtypeStruct tree (no allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype or dtype), specs,
        is_leaf=lambda x: isinstance(x, P))


def axes_tree(specs) -> Any:
    return jax.tree.map(lambda s: s.axes, specs,
                        is_leaf=lambda x: isinstance(x, P))


def stack_spec(spec_tree, n: int, axis_name: str = "layers"):
    """Prepend a stacked-layers dimension to every leaf (scan-over-layers)."""
    return jax.tree.map(
        lambda s: P((n,) + s.shape, (axis_name,) + s.axes, s.init, s.scale,
                    s.dtype),
        spec_tree, is_leaf=lambda x: isinstance(x, P))


# ----------------------------------------------------------------- numerics
def rms_norm(x, w, *, eps=1e-6, plus_one=False):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    scale = (1.0 + w.astype(jnp.float32)) if plus_one else w.astype(jnp.float32)
    return (x * scale).astype(dt)


def layer_norm(x, w, b, *, eps=1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    y = y * w.astype(jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    return y.astype(dt)


def softcap(x, cap: Optional[float]):
    """Gemma-2 style logit soft-capping."""
    if cap is None:
        return x
    return jnp.tanh(x / cap) * cap


def rotary(x, positions, *, theta: float = 10000.0, fraction: float = 1.0):
    """Apply RoPE to ``x`` (..., seq, heads, head_dim).

    ``fraction`` < 1 rotates only the leading slice of head_dim (StableLM)."""
    hd = x.shape[-1]
    rot = int(hd * fraction)
    rot -= rot % 2
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    half = rot // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, half)
    ang = ang[..., None, :]                                  # broadcast heads
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x_rot[..., :half], x_rot[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1) \
        if rot < hd else out.astype(x.dtype)


def sinusoid_positions(length: int, dim: int) -> jnp.ndarray:
    """Whisper-style fixed sinusoidal embeddings (any length)."""
    half = dim // 2
    freq = np.exp(-np.log(10000.0) * np.arange(half) / max(half - 1, 1))
    pos = np.arange(length)[:, None] * freq[None, :]
    return jnp.asarray(
        np.concatenate([np.sin(pos), np.cos(pos)], axis=1), jnp.float32)


def gelu(x):
    return jax.nn.gelu(x, approximate=True)
