"""Pre-norm Mamba-2 block (SSD, Dao & Gu 2024, arXiv:2405.21060), with no
MLP half, computed by its definition: the per-token recurrence

    S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T,    y_t = C_t S_t + D x_t

after the input projection and the causal depthwise convolution, then
y * silu(z) through an RMSNorm (norm_before_gate=False) and the output
projection.  ``in_proj`` columns are ``[z, x, B, C, dt]``.

Canonical weights of one layer, and their random initialisation:
``ln`` RMSNorm scale 1 + N(0, 0.1^2); ``in_proj`` ``(d, 2 d_in + 2 G N +
H)`` and ``out_proj`` ``(d_in, d)`` N(0, 1/fan_in); ``conv_w`` ``(K,
d_xbc)`` uniform in +-1/sqrt(K), ``conv_b`` N(0, 0.1^2); ``a_log`` with
exp(a_log) uniform in [1, 16]; ``dt_bias`` the inverse softplus of a
log-uniform draw in [1e-3, 1e-1]; ``d_skip`` 1 + N(0, 0.1^2); ``norm``
1 + N(0, 0.1^2).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from .. import reference as ref
from .. import weights as W


def dims(m):
    s = m["ssm"]
    d = m["d_model"]
    d_in = s["expand"] * d
    nh = d_in // s["head_dim"]
    G, N = s["n_groups"], s["d_state"]
    return d, d_in, nh, G, N, s["head_dim"], s["d_conv"]


def init(m: Dict[str, Any], key, n: int) -> Dict[str, Any]:
    d, d_in, nh, G, N, P, K = dims(m)
    d_xbc = d_in + 2 * G * N
    k = iter(jax.random.split(key, 16))
    dt = jnp.exp(jax.random.uniform(next(k), (n, nh), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "ln": {"w": 1.0 + 0.1 * jax.random.normal(next(k), (n, d))},
        "in_proj": W.normal(next(k), (n, d, 2 * d_in + 2 * G * N + nh),
                            d ** -0.5),
        "conv_w": jax.random.uniform(next(k), (n, K, d_xbc), jnp.float32,
                                     -K ** -0.5, K ** -0.5),
        "conv_b": 0.1 * jax.random.normal(next(k), (n, d_xbc)),
        "a_log": jnp.log(jax.random.uniform(next(k), (n, nh), jnp.float32,
                                            1.0, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "d_skip": 1.0 + 0.1 * jax.random.normal(next(k), (n, nh)),
        "norm": 1.0 + 0.1 * jax.random.normal(next(k), (n, d_in)),
        "out_proj": W.normal(next(k), (n, d_in, d), d_in ** -0.5),
    }


def to_program(m: Dict[str, Any], w: Dict[str, Any]) -> Dict[str, Any]:
    """The program's layer tree (``repro.models.mamba2.mamba2_specs``)."""
    return {"ln1": w["ln"],
            "mix": {k: w[k] for k in ("in_proj", "conv_w", "conv_b", "a_log",
                                      "dt_bias", "d_skip", "norm",
                                      "out_proj")}}


def block(m: Dict[str, Any], w: Dict[str, Any], x, positions, dot):
    d, d_in, nh, G, N, P, K = dims(m)
    B, T, _ = x.shape
    f32 = jnp.float32
    h = ref.norm(m, x, w["ln"])
    zxbcdt = dot(h, w["in_proj"], 1)
    z = zxbcdt[..., :d_in]
    xbc = zxbcdt[..., d_in:2 * d_in + 2 * G * N]
    dt_raw = zxbcdt[..., 2 * d_in + 2 * G * N:]
    xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    cw = w["conv_w"].astype(f32)
    conv = sum(xp[:, i:i + T] * cw[i] for i in range(K)) + w["conv_b"]
    xbc = jax.nn.silu(conv)
    xs = xbc[..., :d_in].reshape(B, T, nh, P)
    rep = nh // G
    bm = jnp.repeat(xbc[..., d_in:d_in + G * N].reshape(B, T, G, N), rep, 2)
    cm = jnp.repeat(xbc[..., d_in + G * N:].reshape(B, T, G, N), rep, 2)
    dt = jax.nn.softplus(dt_raw + w["dt_bias"].astype(f32))     # (B,T,H)
    A = -jnp.exp(w["a_log"].astype(f32))

    def step(S, inp):
        dt_t, b_t, c_t, x_t = inp
        S = (S * jnp.exp(dt_t * A)[:, :, None, None]
             + dt_t[:, :, None, None] * b_t[..., None] * x_t[:, :, None, :])
        return S, jnp.einsum("bhn,bhnp->bhp", c_t, S, precision=ref.HI)

    seq = [jnp.moveaxis(a, 1, 0) for a in (dt, bm, cm, xs)]
    _, y = jax.lax.scan(step, jnp.zeros((B, nh, N, P), f32), seq)
    y = jnp.moveaxis(y, 0, 1) + w["d_skip"].astype(f32)[:, None] * xs
    y = y.reshape(B, T, d_in) * jax.nn.silu(z)
    y = ref.norm({"norm": "rmsnorm", "norm_eps": m["norm_eps"]}, y,
                 {"w": w["norm"]})
    return x + dot(y, w["out_proj"], 1)
