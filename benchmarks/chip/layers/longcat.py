"""LongCat-Flash's double layer (transformers' ``LongcatFlashDecoderLayer``,
arXiv:2509.01322): two MLA sublayers, each followed by a gated-SiLU MLP,
and a shortcut MoE fed from the first sublayer's MLP input and added
after the second::

    h0 = x  + MLA_0(rms(x));   u = rms(h0);   h1 = h0 + MLP_0(u)
    h2 = h1 + MLA_1(rms(h1));  y = h2 + MLP_1(rms(h2)) + MoE(u)

MLA: q = q_b(rms(q_a(x))) per head ``[nope, rope]``; ``kv_a(x)`` gives the
latent ``[kv_lora]`` and one shared rope key ``[rope]``; the normed latent
goes through ``kv_b`` to per-head ``[nope, v]``.  q is scaled by
``(d/q_lora)^0.5``, the normed latent by ``(d/kv_lora)^0.5``, the latent
norms use eps 1e-6, rotary embedding pairs interleaved columns
``(0,1), (2,3), ...``, the softmax scale is ``(nope+rope)^-0.5``.

MoE: a float32 softmax over ``n_routed + n_zero`` router outputs, the top
``top_k`` by score plus ``router_bias``, each weighed by its score times
``routed_scale``.  A routed expert is a gated-SiLU MLP of ``d_expert``;
an identity expert returns its input.  The layer holds routed experts
``held_start .. held_start + held_experts - 1`` (the chip's share of the
configuration's stated deployment): a choice of an expert outside them
adds nothing, here as in the program.

Canonical weights of one layer (every matrix ``(in, out)``, in the
published column order), and their random initialisation: per sublayer
``i``: ``in_norm{i}``, ``post_norm{i}`` scales 1 + N(0, 0.1^2);
``q_a_norm{i}`` ``(q_lora,)`` and ``kv_a_norm{i}`` ``(kv_lora,)`` scales
``(rank / d) ** 0.5`` times 1 + N(0, 0.1^2), so that with the LoRA scales
q and the latent come out of unit size: at scales near 1 the random
model's attention scores spread about 6 wide, so
sharp that bfloat16 rounding alone moves served logits by as much as
the fp8 control does (section 6 of ``PERF.md``); ``q_a{i}`` ``(d,
q_lora)``, ``q_b{i}`` ``(q_lora, H, nope+rope)``, ``kv_a{i}`` ``(d,
kv_lora+rope)``, ``kv_b{i}`` ``(kv_lora, H, nope+v)``, ``o{i}`` ``(H, v,
d)``, ``gate{i}``/``up{i}`` ``(d, f)``, ``down{i}`` ``(f, d)``; then
``router`` ``(d, E)``, ``expert_gate``/``expert_up`` ``(n, d, d_expert)``,
``expert_down`` ``(n, d_expert, d)``: all N(0, 1/fan_in);
``router_bias`` ``(E,)`` N(0, 1/E^2), the size of a mean score.  Leaves
are cast to the configuration's dtype as they are made.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from .. import reference as ref
from .. import weights as W

#: heads per block of the reference's attention (its scores stay small)
HEAD_BLOCK = 8


def dims(m: Dict[str, Any]):
    a, e = m["mla_dims"], m["experts"]
    return (m["d_model"], m["n_heads"], a["q_lora"], a["kv_lora"],
            a["nope_dim"], a["rope_dim"], a["v_dim"], m["d_ff"], e)


def init(m: Dict[str, Any], key, n: int) -> Dict[str, Any]:
    """Weights of ``n`` layers, stacked on a leading axis."""
    d, H, ql, kl, nope, rope, vd, f, e = dims(m)
    if m["mlp"] != "gated_silu":
        raise NotImplementedError(m["mlp"])
    E = e["n_routed"] + e["n_zero"]
    nh, fe = e["n_held"], e["d_expert"]
    dt = jnp.dtype(m["dtype"])
    keys = iter(jax.random.split(key, 40))

    def mat(shape, fan):
        return W.normal(next(keys), (n,) + shape, fan ** -0.5).astype(dt)

    def scale(k, mean=1.0):
        return (mean * (1.0 + 0.1 * jax.random.normal(next(keys), (n, k)))
                ).astype(dt)

    w: Dict[str, Any] = {}
    for i in (0, 1):
        w[f"in_norm{i}"] = {"w": scale(d)}
        w[f"post_norm{i}"] = {"w": scale(d)}
        w[f"q_a{i}"] = mat((d, ql), d)
        w[f"q_a_norm{i}"] = scale(ql, (ql / d) ** 0.5)
        w[f"q_b{i}"] = mat((ql, H, nope + rope), ql)
        w[f"kv_a{i}"] = mat((d, kl + rope), d)
        w[f"kv_a_norm{i}"] = scale(kl, (kl / d) ** 0.5)
        w[f"kv_b{i}"] = mat((kl, H, nope + vd), kl)
        w[f"o{i}"] = mat((H, vd, d), H * vd)
        w[f"gate{i}"] = mat((d, f), d)
        w[f"up{i}"] = mat((d, f), d)
        w[f"down{i}"] = mat((f, d), f)
    w["router"] = mat((d, E), d)
    w["router_bias"] = W.normal(next(keys), (n, E), 1.0 / E).astype(dt)
    w["expert_gate"] = mat((nh, d, fe), d)
    w["expert_up"] = mat((nh, d, fe), d)
    w["expert_down"] = mat((nh, fe, d), fe)
    return w


def rope_halves(r: int) -> np.ndarray:
    """Interleaved rotary pairs (0,1), (2,3), ... as halves: columns 0, 2,
    4, ..., then 1, 3, 5, ..."""
    return np.concatenate([np.arange(0, r, 2), np.arange(1, r, 2)])


def to_program(m: Dict[str, Any], w: Dict[str, Any]) -> Dict[str, Any]:
    """The program's layer tree (``repro.models.lm.longcat_specs``).  The
    program rotates by halves, so the rope columns of ``q_b`` and of the
    shared rope key (the last ``rope`` columns of ``kv_a``) are permuted
    from interleaved pairs to halves: q.k is unchanged when q and k are
    permuted alike, so this is the published result exactly.  Every other
    leaf is the canonical array, sliced or as it is."""
    d, H, ql, kl, nope, rope, vd, f, e = dims(m)
    q_cols = np.concatenate([np.arange(nope), nope + rope_halves(rope)])
    out: Dict[str, Any] = {}
    for i in (0, 1):
        kv_a, kv_b = w[f"kv_a{i}"], w[f"kv_b{i}"]
        out[f"ln_attn{i}"] = w[f"in_norm{i}"]
        out[f"ln_mlp{i}"] = w[f"post_norm{i}"]
        out[f"mla{i}"] = {
            "wq_a": w[f"q_a{i}"], "q_norm": w[f"q_a_norm{i}"],
            "wq_b": w[f"q_b{i}"][..., q_cols],
            "wkv_a": kv_a[..., :kl], "kv_norm": w[f"kv_a_norm{i}"],
            "wk_rope": kv_a[..., kl + rope_halves(rope)],
            "wk_b": kv_b[..., :nope], "wv_b": kv_b[..., nope:],
            "wo": w[f"o{i}"]}
        out[f"mlp{i}"] = {"wg": w[f"gate{i}"], "wu": w[f"up{i}"],
                          "wd": w[f"down{i}"]}
    out["moe"] = {"router": w["router"], "router_bias": w["router_bias"],
                  "wg": w["expert_gate"], "wu": w["expert_up"],
                  "wd": w["expert_down"]}
    return out


def _rms(x, s, eps):
    s = s["w"] if isinstance(s, dict) else s
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * s.astype(jnp.float32)


def _rope(x, positions, theta: float):
    """Interleaved rotary embedding over the last axis of ``x`` ``(B, T,
    ..., r)``: the pair ``(x[2i], x[2i+1])`` turns by ``pos * theta^(-2i/r)``."""
    r = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = positions[..., None].astype(jnp.float32) * inv    # (B, T, r/2)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[2:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                     axis=-1).reshape(x.shape)


def _mla(m, w, i, x, positions, dot):
    d, H, ql, kl, nope, rope, vd, f, e = dims(m)
    B, T, _ = x.shape
    q = dot(_rms(dot(x, w[f"q_a{i}"], 1), w[f"q_a_norm{i}"], 1e-6),
            w[f"q_b{i}"], 1) * (d / ql) ** 0.5              # (B, T, H, qk)
    ckv = dot(x, w[f"kv_a{i}"], 1)
    lat = _rms(ckv[..., :kl], w[f"kv_a_norm{i}"], 1e-6) * (d / kl) ** 0.5
    kv = dot(lat, w[f"kv_b{i}"], 1)                         # (B, T, H, nope+v)
    q = jnp.concatenate([q[..., :nope],
                         _rope(q[..., nope:], positions, m["rope_theta"])], -1)
    k_rope = _rope(ckv[..., kl:], positions, m["rope_theta"])
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_rope[:, :, None], (B, T, H, rope))], -1)
    v = kv[..., nope:]
    causal = jnp.tril(jnp.ones((T, T), bool))

    def heads(blk):
        qb, kb, vb = blk
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, kb, precision=ref.HI) \
            * (nope + rope) ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, vb, precision=ref.HI)

    hb = min(HEAD_BLOCK, H)

    def blocks(a):       # (B, T, H, c) -> (H / hb, B, T, hb, c)
        return jnp.moveaxis(a.reshape(B, T, H // hb, hb, a.shape[-1]), 2, 0)

    o = jax.lax.map(heads, (blocks(q), blocks(k), blocks(v)))
    o = jnp.moveaxis(o, 0, 2).reshape(B, T, H, vd)
    return dot(o, w[f"o{i}"], 2)


def _mlp(w, i, x, dot):
    return dot(jax.nn.silu(dot(x, w[f"gate{i}"], 1)) * dot(x, w[f"up{i}"], 1),
               w[f"down{i}"], 1)


def _moe(m, w, u, dot):
    e = m["experts"]
    scores = jax.nn.softmax(dot(u, w["router"], 1), axis=-1)
    _, idx = jax.lax.top_k(scores + w["router_bias"].astype(jnp.float32),
                           e["top_k"])
    wts = jnp.take_along_axis(scores, idx, axis=-1) * e["routed_scale"]

    def expert(out, xs):
        j, wg, wu, wd = xs
        we = jnp.sum(jnp.where(idx == e["held_start"] + j, wts, 0.0), -1)
        y = dot(jax.nn.silu(dot(u, wg, 1)) * dot(u, wu, 1), wd, 1)
        return out + y * we[..., None], None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(u),
                          (jnp.arange(e["n_held"]), w["expert_gate"],
                           w["expert_up"], w["expert_down"]))
    zero = jnp.sum(jnp.where(idx >= e["n_routed"], wts, 0.0), axis=-1)
    return out + u * zero[..., None]


def block(m: Dict[str, Any], w: Dict[str, Any], x, positions, dot):
    eps = m["norm_eps"]
    x = x + _mla(m, w, 0, _rms(x, w["in_norm0"], eps), positions, dot)
    u = _rms(x, w["post_norm0"], eps)
    shortcut = _moe(m, w, u, dot)
    x = x + _mlp(w, 0, u, dot)
    x = x + _mla(m, w, 1, _rms(x, w["in_norm1"], eps), positions, dot)
    x = x + _mlp(w, 1, _rms(x, w["post_norm1"], eps), dot)
    return x + shortcut
