"""Pre-norm decoder block: full causal attention (multi-head, or grouped
query when ``n_kv_heads < n_heads``) with rotary embedding on the leading
``rope_fraction`` of each head, then a gated-SiLU MLP (StableLM-2).

Canonical weights of one layer, and their random initialisation:
``ln1``/``ln2`` norm scales 1 + N(0, 0.1^2) (and shifts N(0, 0.1^2) for
LayerNorm); ``wq`` ``(d, H, hd)``, ``wk``/``wv`` ``(d, KH, hd)``, ``wo``
``(H, hd, d)``, ``wg``/``wu`` ``(d, f)``, ``wd`` ``(f, d)``, each
N(0, 1/fan_in).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from .. import reference as ref
from .. import weights as W


def _dims(m):
    return (m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"],
            m["d_ff"])


def init(m: Dict[str, Any], key, n: int) -> Dict[str, Any]:
    """Float32 weights of ``n`` layers, stacked on a leading axis."""
    d, H, KH, hd, f = _dims(m)
    if m["mlp"] != "gated_silu":
        raise NotImplementedError(m["mlp"])
    shapes = {"wq": ((d, H, hd), d), "wk": ((d, KH, hd), d),
              "wv": ((d, KH, hd), d), "wo": ((H, hd, d), H * hd),
              "wg": ((d, f), d), "wu": ((d, f), d), "wd": ((f, d), f)}
    keys = iter(jax.random.split(key, 16))
    w = {name: W.normal(next(keys), (n,) + shp, fan ** -0.5)
         for name, (shp, fan) in shapes.items()}
    w["ln1"] = W.norm_params(m, next(keys), n)
    w["ln2"] = W.norm_params(m, next(keys), n)
    return w


def to_program(m: Dict[str, Any], w: Dict[str, Any]) -> Dict[str, Any]:
    """The program's layer tree (``repro.models.lm.layer_specs``)."""
    return {"ln1": w["ln1"],
            "mix": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
            "ln2": w["ln2"],
            "mlp": {k: w[k] for k in ("wg", "wu", "wd")}}


def rotary(x, positions, theta: float, fraction: float):
    """Rotate-half rotary embedding on the leading ``fraction`` of the
    head dimension; the rest passes through.  x: (B, T, H, hd)."""
    hd = x.shape[-1]
    rot = int(hd * fraction) // 2 * 2
    half = rot // 2
    inv_freq = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = positions[..., None].astype(jnp.float32) * inv_freq   # (B,T,half)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def block(m: Dict[str, Any], w: Dict[str, Any], x, positions, dot):
    d, H, KH, hd, f = _dims(m)
    T = x.shape[1]
    h = ref.norm(m, x, w["ln1"])
    q = rotary(dot(h, w["wq"], 1), positions, m["rope_theta"],
               m["rope_fraction"])
    k = rotary(dot(h, w["wk"], 1), positions, m["rope_theta"],
               m["rope_fraction"])
    v = dot(h, w["wv"], 1)
    k = jnp.repeat(k, H // KH, axis=2)
    v = jnp.repeat(v, H // KH, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=ref.HI) * hd ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=ref.HI)
    x = x + dot(o, w["wo"], 2)
    h = ref.norm(m, x, w["ln2"])
    g = jax.nn.silu(dot(h, w["wg"], 1)) * dot(h, w["wu"], 1)
    return x + dot(g, w["wd"], 1)
