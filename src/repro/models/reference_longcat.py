"""Plain float32 reference of LongCat-Flash-Chat's language model.

It follows ``transformers``' ``models/longcat_flash/modeling_longcat_flash.py``
(``LongcatFlashForCausalLM``) line by line, in ``jax.numpy`` at the highest
matmul precision: one sequence, no cache, no batching, no kernels, rotary
embedding in the published interleaved form.  It shares no code with the
program (``repro.models.lm``'s ``longcat`` kind).

``c`` holds the published config's keys (``hidden_size``,
``num_attention_heads``, ``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``ffn_hidden_size``, ``expert_ffn_hidden_size``, ``n_routed_experts``,
``zero_expert_num``, ``moe_topk``, ``routed_scaling_factor``,
``rms_norm_eps``, ``rope_theta``).  Weights ``w``: ``embed`` ``(V, d)``,
``norm`` ``(d,)``, ``lm_head`` ``(d, V)`` and ``layers``, one dict per
layer; every matrix is stored ``(in, out)``, the transpose of the
``nn.Linear`` weight, with the same column order::

    input_layernorm, post_attention_layernorm: [w0, w1] (d,)
    self_attn: two dicts of q_a_proj (d, q_lora), q_a_layernorm (q_lora,),
        q_b_proj (q_lora, H*(nope+rope)), kv_a_proj_with_mqa
        (d, kv_lora+rope), kv_a_layernorm (kv_lora,), kv_b_proj
        (kv_lora, H*(nope+v)), o_proj (H*v, d)
    mlps: two dicts of gate_proj, up_proj (d, f), down_proj (f, d)
    classifier (d, n_routed_experts + zero_expert_num),
    e_score_correction_bias (n_routed_experts + zero_expert_num,)
    experts: gate_proj, up_proj (n, d, f_e), down_proj (n, f_e, d)

Departures from the published model, each deliberate:

* The held share: ``experts`` holds ``n`` routed experts, global ids
  ``held_start .. held_start + n - 1``.  The router still scores all
  ``n_routed_experts + zero_expert_num`` experts; a token's choices of
  routed experts outside the share add nothing, as on a chip that holds
  only this share.  With every routed expert held this is the published
  layer.
* Everything runs in float32; the published model runs in its
  checkpoint's dtype.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _mm(x, w):
    return jnp.matmul(x, w.astype(jnp.float32), precision=HI)


def rms_norm(x, w, eps):
    """``LongcatFlashRMSNorm``: the latent norms keep its default 1e-6."""
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return w.astype(jnp.float32) * (x * jax.lax.rsqrt(var + eps))


def rotary_cos_sin(T: int, dim: int, theta: float):
    """``LongcatFlashRotaryEmbedding`` (default rope, no scaling)."""
    inv_freq = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    freqs = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


def rotate_half(x):
    h = x.shape[-1] // 2
    return jnp.concatenate([-x[..., h:], x[..., :h]], axis=-1)


def rope_interleave(x, cos, sin):
    """``apply_rotary_pos_emb_interleave`` for one of q or k: the pairs
    ``(x0, x1), (x2, x3), ...`` are regrouped as ``x0, x2, ..., x1, x3,
    ...`` and then rotated by halves."""
    *lead, d = x.shape
    x = jnp.swapaxes(x.reshape(*lead, d // 2, 2), -1, -2).reshape(*lead, d)
    return x * cos + rotate_half(x) * sin


def mla(c: Dict[str, Any], p: Dict[str, Any], x, cos, sin):
    """``LongcatFlashMLA`` over one sequence ``x`` ``(T, d)``, causal."""
    T = x.shape[0]
    H = c["num_attention_heads"]
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    kv_lora = c["kv_lora_rank"]
    q = _mm(rms_norm(_mm(x, p["q_a_proj"]), p["q_a_layernorm"], 1e-6),
            p["q_b_proj"])
    q = jnp.swapaxes(q.reshape(T, H, nope + rope), 0, 1)      # (H, T, qk)
    q_pass, q_rot = q[..., :nope], q[..., nope:]
    ckv = _mm(x, p["kv_a_proj_with_mqa"])
    k_pass, k_rot = ckv[:, :kv_lora], ckv[:, kv_lora:]
    k_pass = rms_norm(k_pass, p["kv_a_layernorm"], 1e-6)
    d = c["hidden_size"]
    if c["mla_scale_q_lora"]:
        q_pass = q_pass * (d / c["q_lora_rank"]) ** 0.5
        q_rot = q_rot * (d / c["q_lora_rank"]) ** 0.5
    if c["mla_scale_kv_lora"]:
        k_pass = k_pass * (d / kv_lora) ** 0.5
    kv = jnp.swapaxes(_mm(k_pass, p["kv_b_proj"]).reshape(T, H, nope + vd),
                      0, 1)
    k_pass, v = kv[..., :nope], kv[..., nope:]
    q_rot = rope_interleave(q_rot, cos, sin)
    k_rot = rope_interleave(k_rot[None], cos, sin)
    k_rot = jnp.broadcast_to(k_rot, (H, T, rope))
    qs = jnp.concatenate([q_pass, q_rot], axis=-1)
    ks = jnp.concatenate([k_pass, k_rot], axis=-1)
    s = jnp.einsum("hqd,hkd->hqk", qs, ks, precision=HI) \
        * (nope + rope) ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    probs = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,hkd->hqd", probs, v, precision=HI)
    return _mm(jnp.swapaxes(o, 0, 1).reshape(T, H * vd), p["o_proj"])


def mlp(p: Dict[str, Any], x):
    """``LongcatFlashMLP`` (gated SiLU)."""
    return _mm(jax.nn.silu(_mm(x, p["gate_proj"])) * _mm(x, p["up_proj"]),
               p["down_proj"])


def moe(c: Dict[str, Any], p: Dict[str, Any], x, held_start: int):
    """``LongcatFlashMoE`` for the held share (module docstring): the
    ``LongcatFlashTopkRouter`` over every expert, then each held routed
    expert over the rows that chose it, and the identity experts."""
    n_routed = c["n_routed_experts"]
    logits = _mm(x, p["classifier"])
    scores = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(scores + p["e_score_correction_bias"].astype(
        jnp.float32), c["moe_topk"])
    weights = jnp.take_along_axis(scores, idx, axis=1) \
        * c["routed_scaling_factor"]

    def expert(out, ew):
        e, wg, wu, wd = ew
        we = jnp.sum(jnp.where(idx == held_start + e, weights, 0.0), axis=1)
        y = _mm(jax.nn.silu(_mm(x, wg)) * _mm(x, wu), wd)
        return out + y * we[:, None], None

    ex = p["experts"]
    n = ex["gate_proj"].shape[0]
    out, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                          (jnp.arange(n), ex["gate_proj"], ex["up_proj"],
                           ex["down_proj"]))
    zero = jnp.sum(jnp.where(idx >= n_routed, weights, 0.0), axis=1)
    return out + x * zero[:, None]


def decoder_layer(c: Dict[str, Any], p: Dict[str, Any], x, cos, sin,
                  held_start: int):
    """``LongcatFlashDecoderLayer``: two sublayers and the shortcut MoE."""
    eps = c["rms_norm_eps"]
    residual = x
    h = mla(c, p["self_attn"][0],
            rms_norm(x, p["input_layernorm"][0], eps), cos, sin)
    h = residual + h
    residual = h
    h = rms_norm(h, p["post_attention_layernorm"][0], eps)
    shortcut = moe(c, p, h, held_start)
    h = residual + mlp(p["mlps"][0], h)
    residual = h
    h = residual + mla(c, p["self_attn"][1],
                       rms_norm(h, p["input_layernorm"][1], eps), cos, sin)
    residual = h
    h = mlp(p["mlps"][1], rms_norm(h, p["post_attention_layernorm"][1], eps))
    return residual + h + shortcut


def forward(c: Dict[str, Any], w: Dict[str, Any], tokens, *,
            held_start: int = 0):
    """Float32 logits ``(T, V)`` of one sequence ``tokens`` ``(T,)`` that
    starts at position 0."""
    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
        cos, sin = rotary_cos_sin(x.shape[0], c["qk_rope_head_dim"],
                                  c["rope_theta"])
        for p in w["layers"]:
            x = decoder_layer(c, p, x, cos, sin, held_start)
        x = rms_norm(x, w["norm"], c["rms_norm_eps"])
        return _mm(x, w["lm_head"])
