"""Encoder-decoder LM (Whisper-family backbone).

The audio conv frontend is a STUB per the assignment: ``input_specs``
provides precomputed frame embeddings (B, S_enc, d).  Sinusoidal positions
are used on both stacks so arbitrary assigned sequence lengths lower
cleanly (deviation from Whisper's learned decoder positions — noted in
DESIGN.md).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention as attn
from .common import P, abstract_tree, axes_tree, init_tree, sinusoid_positions, stack_spec
from .config import ModelCfg
from .lm import _xent, mlp_apply, mlp_specs, norm_apply, norm_specs
from repro.sharding.ctx import constrain


def cross_attn_specs(cfg: ModelCfg) -> Dict[str, P]:
    d, H, KH, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    return {
        "wq": P((d, H, hd), ("embed", "heads", "head_dim")),
        "wk": P((d, KH, hd), ("embed", "kv_heads", "head_dim")),
        "wv": P((d, KH, hd), ("embed", "kv_heads", "head_dim")),
        "wo": P((H, hd, d), ("heads", "head_dim", "embed")),
    }


def cross_attn_apply(p, x, enc_kv, *, cfg: ModelCfg):
    """enc_kv: (k, v) precomputed from encoder output."""
    k, v = enc_kv
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    B, Sq = q.shape[:2]
    Sk = k.shape[1]
    q_pos = jnp.full((B, Sq), Sk, jnp.int32)     # attend to everything
    k_pos = jnp.zeros((B, Sk), jnp.int32)
    out = attn.ref_attention(q, k, v, scale=cfg.hd ** -0.5, q_pos=q_pos,
                             k_pos=k_pos, window=None, cap=None)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def enc_kv(p, enc_out):
    return (jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"]),
            jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"]))


class EncDecLM:
    """Whisper-shaped enc-dec transformer; n_layers per stack."""

    def __init__(self, cfg: ModelCfg):
        self.cfg = cfg

    # -- specs -------------------------------------------------------------
    def _enc_layer(self):
        cfg = self.cfg
        return {"ln1": norm_specs(cfg), "mix": attn.gqa_specs(cfg),
                "ln2": norm_specs(cfg), "mlp": mlp_specs(cfg)}

    def _dec_layer(self):
        cfg = self.cfg
        return {"ln1": norm_specs(cfg), "self": attn.gqa_specs(cfg),
                "lnx": norm_specs(cfg), "cross": cross_attn_specs(cfg),
                "ln2": norm_specs(cfg), "mlp": mlp_specs(cfg)}

    def param_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        n = cfg.n_layers
        return {
            "embed": P((cfg.vocab, cfg.d_model), ("vocab", "embed_tbl"),
                       "embed", scale=cfg.d_model ** -0.5),
            "enc": stack_spec(self._enc_layer(), n),
            "enc_norm": norm_specs(cfg),
            "dec": stack_spec(self._dec_layer(), n),
            "dec_norm": norm_specs(cfg),
        }

    def init(self, key):
        return init_tree(self.param_specs(), key, jnp.dtype(self.cfg.dtype))

    def abstract_params(self):
        return abstract_tree(self.param_specs(), jnp.dtype(self.cfg.dtype))

    def param_axes(self):
        return axes_tree(self.param_specs())

    # -- encoder -------------------------------------------------------------
    def encode(self, params, frame_embeds):
        cfg = self.cfg
        B, S, _ = frame_embeds.shape
        x = frame_embeds.astype(jnp.dtype(cfg.dtype))
        x = x + sinusoid_positions(S, cfg.d_model).astype(x.dtype)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                     (B, S))

        def body(x, lp):
            h = norm_apply(lp["ln1"], x, cfg)
            mix, _ = attn.gqa_apply(lp["mix"], h, cfg=cfg, kind="enc",
                                    positions=positions, cache=None)
            x = x + mix
            h = norm_apply(lp["ln2"], x, cfg)
            x = x + mlp_apply(lp["mlp"], h, cfg)
            return constrain(x, ("batch", "seq", "embed")), None

        body_fn = body
        if cfg.remat != "none":
            body_fn = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)
        x, _ = jax.lax.scan(body_fn, x, params["enc"])
        return norm_apply(params["enc_norm"], x, cfg)

    # -- decoder -------------------------------------------------------------
    def _dec_body(self, positions, use_cache):
        cfg = self.cfg

        def body(x, slices):
            lp, kv, cache = slices
            h = norm_apply(lp["ln1"], x, cfg)
            mix, nc = attn.gqa_apply(lp["self"], h, cfg=cfg, kind="attn",
                                     positions=positions, cache=cache)
            x = x + mix
            h = norm_apply(lp["lnx"], x, cfg)
            x = x + cross_attn_apply(lp["cross"], h, kv, cfg=cfg)
            h = norm_apply(lp["ln2"], x, cfg)
            x = x + mlp_apply(lp["mlp"], h, cfg)
            return constrain(x, ("batch", "seq", "embed")), nc
        return body

    def decode(self, params, tokens, enc_out, *, positions, caches=None,
               cross_kv=None):
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0)
        # sinusoidal absolute positions (same positions across batch)
        x = x + sinusoid_positions(cfg.max_target_length,
                                   cfg.d_model).astype(x.dtype)[positions[0]]
        if cross_kv is None:
            cross_kv = jax.vmap(
                lambda lp: enc_kv(lp["cross"], enc_out),
                in_axes=(0,))(params["dec"])

        body = self._dec_body(positions, caches is not None)
        if cfg.remat != "none" and caches is None:
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)

        # caches=None is an empty pytree: scan carries it through untouched
        x, new_caches = jax.lax.scan(body, x,
                                     (params["dec"], cross_kv, caches))
        x = norm_apply(params["dec_norm"], x, cfg)
        lg = jnp.einsum("bsd,vd->bsv", x, params["embed"])
        return constrain(lg, ("batch", "seq", "vocab")), new_caches, cross_kv

    # -- public API (mirrors TransformerLM) ----------------------------------
    def loss(self, params, batch):
        enc_out = self.encode(params, batch["frame_embeds"])
        tokens = batch["tokens"]
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                     (B, S))
        lg, _, _ = self.decode(params, tokens, enc_out, positions=positions)
        ce = _xent(lg, batch["labels"])
        return ce, {"ce": ce}

    def cache_specs(self, batch: int, max_len: int):
        cfg = self.cfg
        return stack_spec(attn.gqa_cache_spec(cfg, "attn", batch, max_len),
                          cfg.n_layers)

    def init_cache(self, batch: int, max_len: int):
        c = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype or jnp.dtype(self.cfg.dtype)),
            self.cache_specs(batch, max_len),
            is_leaf=lambda x: isinstance(x, P))
        c["pos"] = jnp.full_like(c["pos"], -1)
        return c

    def prefill(self, params, tokens, caches, *, frame_embeds=None):
        enc_out = self.encode(params, frame_embeds)
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                     (B, S))
        lg, caches, cross_kv = self.decode(params, tokens, enc_out,
                                           positions=positions, caches=caches)
        return lg[:, -1:], (caches, cross_kv)

    def decode_step(self, params, state, tokens, pos):
        """state = (self_caches, cross_kv) from prefill."""
        caches, cross_kv = state
        lg, caches, _ = self.decode(params, tokens, None, positions=pos,
                                    caches=caches, cross_kv=cross_kv)
        return lg, (caches, cross_kv)

    def decode_counted(self, params, state, tokens, pos):
        """:meth:`decode_step`; no layer routes to experts (None)."""
        return (*self.decode_step(params, state, tokens, pos), None)
