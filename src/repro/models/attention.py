"""Attention mixers: GQA (global / sliding-window) and DeepSeek MLA.

Training/prefill paths can dispatch to the Pallas flash kernel
(``cfg.attn_impl == 'pallas'``); decode uses the XLA reference path.
Caches carry an explicit per-slot ``pos`` array so global caches and
ring-buffered sliding-window caches share one masking rule.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import P, rms_norm, rotary, softcap
from .config import ModelCfg
from repro.sharding.ctx import constrain

NEG_INF = -2.0e38


def ref_attention(q, k, v, *, scale, q_pos, k_pos, window: Optional[int],
                  cap: Optional[float], causal: bool = True):
    """Grouped-query attention, fp32 softmax.

    q: (B, Sq, H, D); k/v: (B, Sk, KH, D); q_pos: (B, Sq); k_pos: (B, Sk).
    Masks: causal (k_pos <= q_pos), optional sliding window, and empty
    cache slots (k_pos < 0)."""
    B, Sq, H, D = q.shape
    KH = k.shape[2]
    g = H // KH
    qr = q.reshape(B, Sq, KH, g, D)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qr, k,
                        preferred_element_type=jnp.float32) * scale
    logits = softcap(logits, cap)
    mask = k_pos[:, None, :] >= 0
    if causal:
        mask &= k_pos[:, None, :] <= q_pos[:, :, None]
    if window is not None:
        mask &= (q_pos[:, :, None] - k_pos[:, None, :]) < window
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v)
    return out.reshape(B, Sq, H, v.shape[-1])  # v dim may differ (MLA)


def write_cache(cache: dict, layer, slot, new: dict) -> Tuple[dict, dict]:
    """Write ``new[name]`` (B, S', ...) over cache entries ``slot`` (B, S')
    of each batch row; returns ``(written cache, this layer's view)``.

    With ``layer`` None every leaf is one layer's (B, L, ...).  Otherwise
    the leaves are stacked over layers, as a scanned segment carries them,
    S' is 1, and each row's entry goes in place at ``(layer, row, slot)``
    by its own ``dynamic_update_slice``: an update of a few entries that,
    unlike a scatter, leaves the stack in the layout it is stored in.  The
    view is the layer's slice, read once by attention."""
    if layer is None:
        bidx = jnp.arange(slot.shape[0])[:, None]
        out = {n: cache[n].at[bidx, slot].set(v) for n, v in new.items()}
        return out, out
    out = {}
    for n, c in cache.items():
        v = new[n].astype(c.dtype)
        for b in range(slot.shape[0]):
            start = (layer, b, slot[b, 0]) + (0,) * (c.ndim - 3)
            c = jax.lax.dynamic_update_slice(c, v[b][None, None], start)
        out[n] = c
    return out, {n: jax.lax.dynamic_index_in_dim(c, layer, keepdims=False)
                 for n, c in out.items()}


# =============================================================== GQA mixer
def gqa_specs(cfg: ModelCfg) -> Dict[str, P]:
    d, H, KH, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    sp = {
        "wq": P((d, H, hd), ("embed", "heads", "head_dim")),
        "wk": P((d, KH, hd), ("embed", "kv_heads", "head_dim")),
        "wv": P((d, KH, hd), ("embed", "kv_heads", "head_dim")),
        "wo": P((H, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.bias:
        sp["bq"] = P((H, hd), ("heads", "head_dim"), "zeros")
        sp["bk"] = P((KH, hd), ("kv_heads", "head_dim"), "zeros")
        sp["bv"] = P((KH, hd), ("kv_heads", "head_dim"), "zeros")
        sp["bo"] = P((d,), ("embed",), "zeros")
    if cfg.qk_norm:
        sp["q_norm"] = P((hd,), ("head_dim",), "zeros")
        sp["k_norm"] = P((hd,), ("head_dim",), "zeros")
    return sp


def gqa_apply(p, x, *, cfg: ModelCfg, kind: str, positions,
              cache: Optional[dict] = None,
              layer=None) -> Tuple[jax.Array, Optional[dict]]:
    """kind: 'attn' (global) or 'local' (window=cfg.window).

    positions: (B, S) int32 absolute positions of x's tokens.
    cache: {'k','v': (B, L, KH, D), 'pos': (B, L)} or None (training);
    with ``layer`` given, every leaf is stacked over layers (a leading
    layers dim) and this layer's entries are written in place."""
    B, S, _ = x.shape
    window = cfg.window if kind == "local" else None
    theta = cfg.local_rope_theta if kind == "local" else cfg.rope_theta

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], plus_one=True)
        k = rms_norm(k, p["k_norm"], plus_one=True)
    if cfg.rope:
        q = rotary(q, positions, theta=theta, fraction=cfg.rope_fraction)
        k = rotary(k, positions, theta=theta, fraction=cfg.rope_fraction)
    q = constrain(q, ("batch", "seq", "heads", "head_dim"))
    k = constrain(k, ("batch", "seq", "kv_heads", "head_dim"))
    scale = cfg.attn_scale if cfg.attn_scale is not None else cfg.hd ** -0.5

    new_cache = None
    if cache is None:
        out = _train_attention(q, k, v, scale=scale, positions=positions,
                               window=window, cfg=cfg,
                               causal=kind != "enc")
    else:
        L = cache["k"].shape[-3]
        # ring-buffer slot for window caches; append slot for global caches.
        # If the update covers >= L tokens only the last L may be written
        # (duplicate-index scatter order is undefined otherwise).
        if S >= L:
            k_w, v_w, pos_w = k[:, -L:], v[:, -L:], positions[:, -L:]
        else:
            k_w, v_w, pos_w = k, v, positions
        new_cache, c = write_cache(cache, layer, pos_w % L,
                                   {"k": k_w, "v": v_w, "pos": pos_w})
        out = ref_attention(q, c["k"], c["v"], scale=scale, q_pos=positions,
                            k_pos=c["pos"], window=window,
                            cap=cfg.attn_softcap)

    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if cfg.bias:
        out = out + p["bo"]
    return out, new_cache


CHUNKED_THRESHOLD = 8192  # use online-softmax chunking above this length


def chunked_attention(q, k, v, *, scale, window: Optional[int],
                      cap: Optional[float], causal: bool = True,
                      q_chunk: int = 2048, kv_chunk: int = 2048):
    """Online-softmax attention (flash-style) in pure jnp: O(S * chunk)
    memory instead of O(S^2).  Causal/window chunks that are fully masked
    are still computed (static loop) but stay tiny; the Pallas kernel skips
    them on TPU."""
    B, S, H, D = q.shape
    KH = k.shape[2]
    g = H // KH
    Dv = v.shape[-1]
    nq, nk = S // q_chunk, S // kv_chunk
    qr = q.reshape(B, nq, q_chunk, KH, g, D)

    def q_block(qi, qb):
        q_pos = qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, ki):
            acc, m, l = carry
            kb = jax.lax.dynamic_slice_in_dim(k, ki * kv_chunk, kv_chunk, 1)
            vb = jax.lax.dynamic_slice_in_dim(v, ki * kv_chunk, kv_chunk, 1)
            k_pos = ki * kv_chunk + jnp.arange(kv_chunk)
            lg = jnp.einsum("bqkgd,bskd->bkgqs", qb, kb,
                            preferred_element_type=jnp.float32) * scale
            lg = softcap(lg, cap)
            mask = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                mask &= k_pos[None, :] <= q_pos[:, None]
            if window is not None:
                mask &= (q_pos[:, None] - k_pos[None, :]) < window
            lg = jnp.where(mask[None, None, None], lg, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(lg, axis=-1))
            p = jnp.exp(lg - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p.astype(vb.dtype), vb
            ).astype(jnp.float32)
            return (acc, m_new, l), None

        acc0 = jnp.zeros((B, KH, g, q_chunk, Dv), jnp.float32)
        m0 = jnp.full((B, KH, g, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, KH, g, q_chunk), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(kv_step, (acc0, m0, l0),
                                      jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.moveaxis(out, 3, 1)  # (B, q_chunk, KH, g, Dv)

    outs = jax.lax.map(lambda qi: q_block(qi, qr[:, qi]), jnp.arange(nq))
    # (nq, B, q_chunk, KH, g, Dv) -> (B, S, H, Dv)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, S, H, Dv)
    return out.astype(v.dtype)


def _train_attention(q, k, v, *, scale, positions, window, cfg: ModelCfg,
                     causal: bool = True):
    if cfg.attn_impl == "pallas" and causal:
        from repro.kernels.flash_attention import ops as flash_ops
        if flash_ops.supported(q, k, window, cfg.attn_softcap):
            return flash_ops.flash_attention(
                q, k, v, scale=scale, causal=True, window=window,
                softcap=cfg.attn_softcap)
    S = q.shape[1]
    if S >= CHUNKED_THRESHOLD and S % 2048 == 0:
        return chunked_attention(q, k, v, scale=scale, window=window,
                                 cap=cfg.attn_softcap, causal=causal)
    return ref_attention(q, k, v, scale=scale, q_pos=positions,
                         k_pos=positions, window=window,
                         cap=cfg.attn_softcap, causal=causal)


def gqa_cache_spec(cfg: ModelCfg, kind: str, batch: int,
                   max_len: int) -> Dict[str, P]:
    L = min(cfg.window, max_len) if kind == "local" else max_len
    KH, hd = cfg.n_kv_heads, cfg.hd
    return {
        "k": P((batch, L, KH, hd), ("batch", "cache", "kv_heads", "head_dim"),
               "zeros"),
        "v": P((batch, L, KH, hd), ("batch", "cache", "kv_heads", "head_dim"),
               "zeros"),
        "pos": P((batch, L), ("batch", "cache"), "zeros", dtype=jnp.int32),
    }


def init_cache_pos(cache: dict) -> dict:
    """Empty slots are marked pos = -1 (masked out)."""
    out = dict(cache)
    out["pos"] = jnp.full_like(cache["pos"], -1)
    return out


# ================================================================ MLA mixer
def mla_specs(cfg: ModelCfg) -> Dict[str, P]:
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    qk = m.nope_dim + m.rope_dim
    return {
        "wq_a": P((d, m.q_lora), ("embed", "q_lora")),
        "q_norm": P((m.q_lora,), ("q_lora",), "ones"),
        "wq_b": P((m.q_lora, H, qk), ("q_lora", "heads", "head_dim")),
        "wkv_a": P((d, m.kv_lora), ("embed", "kv_lora")),
        "kv_norm": P((m.kv_lora,), ("kv_lora",), "ones"),
        "wk_rope": P((d, m.rope_dim), ("embed", "head_dim")),
        "wk_b": P((m.kv_lora, H, m.nope_dim), ("kv_lora", "heads", "head_dim")),
        "wv_b": P((m.kv_lora, H, m.v_dim), ("kv_lora", "heads", "head_dim")),
        "wo": P((H, m.v_dim, d), ("heads", "head_dim", "embed")),
    }


def mla_apply(p, x, *, cfg: ModelCfg, positions,
              cache: Optional[dict] = None,
              layer=None) -> Tuple[jax.Array, Optional[dict]]:
    """DeepSeek-V3 Multi-head Latent Attention.

    Cache stores only the compressed latent (kv_lora) + shared rope key —
    the paper's memory saving.  Decode uses the absorbed formulation (no
    materialised per-head K/V of length L).  ``layer``: as in
    :func:`gqa_apply`.  ``cfg.mla.lora_scale`` (LongCat-Flash) scales q by
    ``(d_model / q_lora) ** 0.5`` and the normed latent by
    ``(d_model / kv_lora) ** 0.5``; the latent norms keep eps 1e-6."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    scale = (m.nope_dim + m.rope_dim) ** -0.5

    q = jnp.einsum("bsd,dl->bsl", x, p["wq_a"])
    q = rms_norm(q, p["q_norm"])
    q = jnp.einsum("bsl,lhk->bshk", q, p["wq_b"])       # (B,S,H,nope+rope)
    if m.lora_scale:
        q = q * jnp.asarray((cfg.d_model / m.q_lora) ** 0.5, q.dtype)
    q_nope, q_rope = q[..., :m.nope_dim], q[..., m.nope_dim:]
    q_rope = rotary(q_rope, positions, theta=cfg.rope_theta)

    c_kv = jnp.einsum("bsd,dl->bsl", x, p["wkv_a"])
    c_kv = rms_norm(c_kv, p["kv_norm"])
    if m.lora_scale:   # the cache holds the scaled latent
        c_kv = c_kv * jnp.asarray((cfg.d_model / m.kv_lora) ** 0.5,
                                  c_kv.dtype)
    k_rope = jnp.einsum("bsd,dr->bsr", x, p["wk_rope"])
    k_rope = rotary(k_rope[:, :, None, :], positions,
                    theta=cfg.rope_theta)[:, :, 0, :]

    if cache is None:
        k_nope = jnp.einsum("bsl,lhk->bshk", c_kv, p["wk_b"])
        v = jnp.einsum("bsl,lhk->bshk", c_kv, p["wv_b"])
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (B, S, H, m.rope_dim))], axis=-1)
        qf = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = _train_attention(qf, k, v, scale=scale, positions=positions,
                               window=None, cfg=cfg)
        new_cache = None
    else:
        L = cache["c_kv"].shape[-2]
        new_cache, c = write_cache(
            cache, layer, positions % L,
            {"c_kv": c_kv, "k_rope": k_rope, "pos": positions})
        cc, cr, cpos = c["c_kv"], c["k_rope"], c["pos"]
        # absorbed: q_nope^T k_nope = (q_nope W_uk) . c_kv
        q_abs = jnp.einsum("bshk,lhk->bshl", q_nope, p["wk_b"])
        logits = (jnp.einsum("bshl,btl->bhst", q_abs, cc,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bshr,btr->bhst", q_rope, cr,
                               preferred_element_type=jnp.float32)) * scale
        mask = (cpos[:, None, :] <= positions[:, :, None]) & \
               (cpos[:, None, :] >= 0)
        logits = jnp.where(mask[:, None, :, :], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        ctx_l = jnp.einsum("bhst,btl->bshl", probs.astype(cc.dtype), cc)
        out = jnp.einsum("bshl,lhk->bshk", ctx_l, p["wv_b"])

    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return out, new_cache


def mla_cache_spec(cfg: ModelCfg, batch: int, max_len: int) -> Dict[str, P]:
    m = cfg.mla
    return {
        "c_kv": P((batch, max_len, m.kv_lora), ("batch", "cache", "kv_lora"),
                  "zeros"),
        "k_rope": P((batch, max_len, m.rope_dim),
                    ("batch", "cache", "head_dim"), "zeros"),
        "pos": P((batch, max_len), ("batch", "cache"), "zeros",
                 dtype=jnp.int32),
    }
