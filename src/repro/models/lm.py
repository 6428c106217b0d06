"""Decoder-only LM assembly: segments, scan-over-layers, loss, decode.

A model is a sequence of *segments*; each segment is a repeating unit of
layer descriptors scanned with stacked parameters (keeps HLO size O(unit),
compile time O(1) in depth).  Heterogeneous patterns (gemma3 5:1,
recurrentgemma 2:1, deepseek dense-prefix) are factored automatically.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from . import attention as attn
from . import mamba2 as m2
from . import rglru as rg
from .common import (P, abstract_tree, axes_tree, gelu, init_tree, layer_norm,
                     rms_norm, sinusoid_positions)
from .config import ModelCfg
from .moe import moe_apply, moe_specs
from repro.sharding.ctx import constrain, current as current_mesh

Desc = Tuple[str, str]  # (mixer kind, mlp kind)


def build_segments(descs: List[Desc]) -> List[Tuple[Tuple[Desc, ...], int]]:
    """Factor a layer list into (unit, repeats) segments, greedily maximising
    unit*repeats coverage (unit length <= 8)."""
    segments = []
    i, n = 0, len(descs)
    while i < n:
        best = (1, 1)
        for u in range(1, 9):
            if i + u > n:
                break
            unit = descs[i:i + u]
            r = 1
            while i + (r + 1) * u <= n and descs[i + r * u:i + (r + 1) * u] == unit:
                r += 1
            if u * r > best[0] * best[1]:
                best = (u, r)
        u, r = best
        segments.append((tuple(descs[i:i + u]), r))
        i += u * r
    return segments


# --------------------------------------------------------------- norms/mlp
def norm_specs(cfg: ModelCfg) -> Dict[str, P]:
    d = cfg.d_model
    if cfg.norm == "layernorm":
        return {"w": P((d,), ("embed",), "ones"),
                "b": P((d,), ("embed",), "zeros")}
    init = "zeros" if cfg.norm_plus_one else "ones"
    return {"w": P((d,), ("embed",), init)}


def norm_apply(p, x, cfg: ModelCfg):
    if cfg.norm == "layernorm":
        return layer_norm(x, p["w"], p["b"])
    return rms_norm(x, p["w"], plus_one=cfg.norm_plus_one)


def mlp_specs(cfg: ModelCfg) -> Dict[str, P]:
    d, f = cfg.d_model, cfg.d_ff
    if cfg.mlp in ("gated_silu", "gated_gelu"):
        return {"wg": P((d, f), ("embed", "mlp")),
                "wu": P((d, f), ("embed", "mlp")),
                "wd": P((f, d), ("mlp", "embed"))}
    sp = {"w1": P((d, f), ("embed", "mlp")),
          "w2": P((f, d), ("mlp", "embed"))}
    if cfg.bias:
        sp["b1"] = P((f,), ("mlp",), "zeros")
        sp["b2"] = P((d,), ("embed",), "zeros")
    return sp


def mlp_apply(p, x, cfg: ModelCfg):
    if cfg.mlp in ("gated_silu", "gated_gelu"):
        act = jax.nn.silu if cfg.mlp == "gated_silu" else gelu
        h = act(x @ p["wg"]) * (x @ p["wu"])
        h = constrain(h, ("batch", "seq", "mlp"))
        return h @ p["wd"]
    h = x @ p["w1"]
    if cfg.bias:
        h = h + p["b1"]
    h = constrain(gelu(h), ("batch", "seq", "mlp"))
    h = h @ p["w2"]
    if cfg.bias:
        h = h + p["b2"]
    return h


# ------------------------------------------------------------------ layers
MIXER_SPECS = {
    "attn": attn.gqa_specs,
    "local": attn.gqa_specs,
    "enc": attn.gqa_specs,
    "mla": attn.mla_specs,
    "ssd": m2.mamba2_specs,
    "rglru": rg.rglru_specs,
}


def layer_specs(cfg: ModelCfg, desc: Desc) -> Dict[str, Any]:
    mixer, mlp_kind = desc
    sp: Dict[str, Any] = {
        "ln1": norm_specs(cfg),
        "mix": MIXER_SPECS[mixer](cfg),
    }
    if mlp_kind != "none":  # mamba2: the block IS the layer, no FFN half
        sp["ln2"] = norm_specs(cfg)
        sp["mlp"] = moe_specs(cfg) if mlp_kind == "moe" else (
            _dense_ff_specs(cfg, mlp_kind))
    if cfg.post_norms:
        sp["ln1p"] = norm_specs(cfg)
        if mlp_kind != "none":
            sp["ln2p"] = norm_specs(cfg)
    return sp


def _dense_ff_specs(cfg: ModelCfg, mlp_kind: str):
    if mlp_kind == "dense_big" and cfg.moe is not None:
        big = cfg.replace(d_ff=cfg.moe.d_ff_dense)
        return mlp_specs(big)
    return mlp_specs(cfg)


#: mixers whose decode writes one entry per row of a position-indexed
#: cache; a scanned segment decoding one token carries their stacked caches
#: and writes in place.  The others rewrite their whole state every step and
#: keep the scan's xs->ys, as does a prefill, which writes whole prompts.
STACK_WRITTEN = ("attn", "local", "mla")


def stored_layouts(tree):
    """The layout the default device stores each leaf of ``tree`` in (on a
    TPU a stack of 64-wide heads is kept position-minor, unpadded).  A
    carried stack pinned to it is copied once at entry as it is, where
    XLA's own choice for the loop would relayout it there and back."""
    dev = jax.config.jax_default_device
    if not isinstance(dev, jax.Device):
        dev = jax.devices(dev)[0]
    return jax.tree.map(
        lambda a: Layout(major_to_minor=Layout.from_pjrt_layout(
            dev.client.get_default_layout(np.dtype(a.dtype), a.shape, dev)
        ).major_to_minor), tree)


def mixer_apply(kind: str, p, x, *, cfg, positions, cache, layer=None):
    if kind in ("attn", "local", "enc"):
        return attn.gqa_apply(p, x, cfg=cfg, kind=kind, positions=positions,
                              cache=cache, layer=layer)
    if kind == "mla":
        return attn.mla_apply(p, x, cfg=cfg, positions=positions, cache=cache,
                              layer=layer)
    if kind == "ssd":
        return m2.mamba2_apply(p, x, cfg=cfg, cache=cache)
    if kind == "rglru":
        return rg.rglru_apply(p, x, cfg=cfg, cache=cache)
    raise ValueError(kind)


def layer_apply(lp, x, *, cfg: ModelCfg, desc: Desc, positions, cache,
                layer=None):
    """``layer``: this layer's index when ``cache`` is the whole segment's
    stack (see ``STACK_WRITTEN``), else None."""
    mixer, mlp_kind = desc
    h = norm_apply(lp["ln1"], x, cfg)
    mix, new_cache = mixer_apply(mixer, lp["mix"], h, cfg=cfg,
                                 positions=positions, cache=cache,
                                 layer=layer)
    if cfg.post_norms:
        mix = norm_apply(lp["ln1p"], mix, cfg)
    x = x + mix
    x = constrain(x, ("batch", "residual_seq", "embed"))
    aux = jnp.zeros((), jnp.float32)
    if mlp_kind == "none":
        return x, new_cache, aux
    h = norm_apply(lp["ln2"], x, cfg)
    if mlp_kind == "moe":
        out, aux = moe_apply(lp["mlp"], h, cfg=cfg)
    elif mlp_kind == "dense_big" and cfg.moe is not None:
        out = mlp_apply(lp["mlp"], h, cfg.replace(d_ff=cfg.moe.d_ff_dense))
    else:
        out = mlp_apply(lp["mlp"], h, cfg)
    if cfg.post_norms:
        out = norm_apply(lp["ln2p"], out, cfg)
    x = x + out
    return (constrain(x, ("batch", "residual_seq", "embed")),
            new_cache, aux)


def mixer_cache_spec(cfg: ModelCfg, kind: str, batch: int, max_len: int):
    if kind == "attn":
        return attn.gqa_cache_spec(cfg, "attn", batch, max_len)
    if kind == "local":
        return attn.gqa_cache_spec(cfg, "local", batch, max_len)
    if kind == "mla":
        return attn.mla_cache_spec(cfg, batch, max_len)
    if kind == "ssd":
        return m2.mamba2_cache_spec(cfg, batch)
    if kind == "rglru":
        return rg.rglru_cache_spec(cfg, batch)
    return None


# ---------------------------------------------------------------- the model
class TransformerLM:
    """Decoder-only LM (all families except enc-dec)."""

    def __init__(self, cfg: ModelCfg):
        self.cfg = cfg
        self.descs = self._descs()
        self.segments = build_segments(self.descs)

    def _descs(self) -> List[Desc]:
        cfg = self.cfg
        kinds = cfg.layer_kinds()
        descs = []
        for i, k in enumerate(kinds):
            if cfg.moe is not None:
                mlp_kind = "dense_big" if i < cfg.moe.first_dense else "moe"
            else:
                mlp_kind = cfg.mlp
            descs.append((k, mlp_kind))
        return descs

    # -- specs ---------------------------------------------------------------
    def param_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        from .common import stack_spec
        specs: Dict[str, Any] = {
            # 1/sqrt(d) embedding init keeps tied logits ~unit variance
            # (scale_embed models multiply activations back up by sqrt(d)).
            # 'embed_tbl' (not 'embed'): the table's d-dim must NOT be
            # FSDP-sharded over 'data' — the logits contraction over a
            # data-sharded d produces a giant cross-data all-reduce of the
            # (tokens, vocab) logits every microbatch (§Perf iteration 2).
            "embed": P((cfg.vocab, cfg.d_model), ("vocab", "embed_tbl"),
                       "embed", scale=cfg.d_model ** -0.5),
            "final_norm": norm_specs(cfg),
        }
        if not cfg.tie_embeddings:
            specs["lm_head"] = P((cfg.d_model, cfg.vocab),
                                 ("embed_tbl", "vocab"))
        for si, (unit, reps) in enumerate(self.segments):
            seg: Dict[str, Any] = {}
            for ui, desc in enumerate(unit):
                ls = layer_specs(cfg, desc)
                seg[f"u{ui}"] = stack_spec(ls, reps) if reps > 1 else ls
            specs[f"seg{si}"] = seg
        if cfg.mtp_depth:
            specs["mtp"] = {
                "proj": P((2 * cfg.d_model, cfg.d_model), ("mlp", "embed")),
                "norm_h": norm_specs(cfg),
                "norm_e": norm_specs(cfg),
                "layer": layer_specs(cfg, self.descs[-1]),
            }
        return specs

    def init(self, key: jax.Array):
        return init_tree(self.param_specs(), key, _dt(self.cfg))

    def abstract_params(self):
        return abstract_tree(self.param_specs(), _dt(self.cfg))

    def param_axes(self):
        return axes_tree(self.param_specs())

    # -- forward ---------------------------------------------------------------
    def embed(self, params, tokens):
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0)
        if cfg.scale_embed:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        return x

    def _unit_body(self, unit, positions, cache_mode):
        cfg = self.cfg

        def body(x_aux, slices):
            x, aux = x_aux
            pslices, cslices = slices
            new_caches = []
            for ui, desc in enumerate(unit):
                x, nc, a = layer_apply(
                    pslices[f"u{ui}"], x, cfg=cfg, desc=desc,
                    positions=positions, cache=cslices[ui])
                new_caches.append(nc)
                aux = aux + a
            return (x, aux), new_caches
        return body

    def forward(self, params, x, *, positions, caches=None):
        """x: embedded inputs (B, S, d).  Returns (hidden, new_caches, aux).

        caches: list per segment of per-unit cache trees (stacked when the
        segment is scanned), or None for training."""
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        new_caches = []
        for si, (unit, reps) in enumerate(self.segments):
            seg_p = params[f"seg{si}"]
            seg_c = caches[si] if caches is not None else [None] * len(unit)
            # under a mesh (``use_sharding``) the batch may be split, and a
            # row's write into a split stack would gather it: keep xs->ys
            if (caches is not None and reps > 1 and x.shape[1] == 1
                    and any(desc[0] in STACK_WRITTEN for desc in unit)
                    and current_mesh() is None):
                x, aux, ncs = self._scan_stacked(unit, positions, x, aux,
                                                 seg_p, seg_c, reps)
                new_caches.append(ncs)
                continue
            body = self._remat(self._unit_body(unit, positions,
                                               caches is not None),
                               prevent_cse=reps == 1)
            if reps == 1:
                (x, aux), ncs = body((x, aux), (seg_p, seg_c))
                new_caches.append(ncs)
            else:
                (x, aux), ncs = jax.lax.scan(body, (x, aux), (seg_p, seg_c))
                new_caches.append(ncs)
        x = norm_apply(params["final_norm"], x, cfg)
        return x, (new_caches if caches is not None else None), aux

    def _remat(self, body, *, prevent_cse: bool):
        cfg = self.cfg
        if cfg.remat == "none":
            return body
        policy = (jax.checkpoint_policies.nothing_saveable
                  if cfg.remat == "full" else
                  jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        return jax.checkpoint(body, policy=policy, prevent_cse=prevent_cse)

    def _scan_stacked(self, unit, positions, x, aux, seg_p, seg_c, reps):
        """One scanned segment decoding one token with caches.  The stacks
        of ``STACK_WRITTEN`` units ride in the carry, held to the layout
        the device stores them in: one copy of each at entry (the caller's
        cache must stay intact), then each layer writes its new entries in
        place at its index and attention reads its slice.  Every other
        unit's cache scans as xs -> ys, rewritten whole by its layer."""
        cfg = self.cfg
        stacked = [desc[0] in STACK_WRITTEN for desc in unit]
        stacks = [c if s else None for c, s in zip(seg_c, stacked)]
        slices = [None if s else c for c, s in zip(seg_c, stacked)]
        layouts = stored_layouts(stacks)

        def body(carry, xs):
            x, aux, stacks = carry
            pslices, cslices, i = xs
            stacks, ys = list(with_layout_constraint(stacks, layouts)), []
            for ui, desc in enumerate(unit):
                x, nc, a = layer_apply(
                    pslices[f"u{ui}"], x, cfg=cfg, desc=desc,
                    positions=positions,
                    cache=stacks[ui] if stacked[ui] else cslices[ui],
                    layer=i if stacked[ui] else None)
                if stacked[ui]:
                    stacks[ui] = nc
                ys.append(None if stacked[ui] else nc)
                aux = aux + a
            return (x, aux, with_layout_constraint(stacks, layouts)), ys

        (x, aux, stacks), ys = jax.lax.scan(
            self._remat(body, prevent_cse=False), (x, aux, stacks),
            (seg_p, slices, jnp.arange(reps, dtype=jnp.int32)))
        return x, aux, [st if s else y
                        for st, y, s in zip(stacks, ys, stacked)]

    def logits(self, params, hidden):
        cfg = self.cfg
        if cfg.tie_embeddings:
            lg = jnp.einsum("bsd,vd->bsv", hidden, params["embed"])
        else:
            lg = jnp.einsum("bsd,dv->bsv", hidden, params["lm_head"])
        from .common import softcap
        lg = softcap(lg, cfg.final_softcap)
        return constrain(lg, ("batch", "seq", "vocab"))

    # -- losses -----------------------------------------------------------------
    def loss(self, params, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """batch: {'tokens': (B,S) int32, 'labels': (B,S) int32, and for
        stub frontends 'patch_embeds': (B,P,d)}."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = self.embed(params, tokens)
        offset = 0
        if cfg.frontend == "vision":
            pe = batch["patch_embeds"].astype(x.dtype)
            x = jnp.concatenate([pe, x], axis=1)
            offset = pe.shape[1]
        positions = jnp.broadcast_to(
            jnp.arange(x.shape[1], dtype=jnp.int32)[None], x.shape[:2])
        h, _, aux = self.forward(params, x, positions=positions)
        h = h[:, offset:]
        lg = self.logits(params, h)
        ce = _xent(lg, batch["labels"])
        loss = ce + 0.001 * aux
        metrics = {"ce": ce, "aux": aux}
        if cfg.mtp_depth:
            mtp = self._mtp_loss(params, h, tokens, batch["labels"])
            loss = loss + 0.3 * mtp
            metrics["mtp"] = mtp
        return loss, metrics

    def _mtp_loss(self, params, h, tokens, labels):
        """DeepSeek-V3 multi-token prediction (depth 1): predict t+2 from
        trunk state at t combined with the embedding of token t+1."""
        cfg = self.cfg
        mp = params["mtp"]
        h_in = norm_apply(mp["norm_h"], h[:, :-1], cfg)
        e_in = norm_apply(mp["norm_e"], self.embed(params, tokens[:, 1:]), cfg)
        x = jnp.concatenate([h_in, e_in], axis=-1) @ mp["proj"]
        positions = jnp.broadcast_to(
            jnp.arange(x.shape[1], dtype=jnp.int32)[None], x.shape[:2])
        x2, _, _aux = _single_layer(self, mp["layer"], x, positions)
        lg = self.logits(params, norm_apply(params["final_norm"], x2, cfg))
        return _xent(lg[:, :-1], labels[:, 2:] if labels.shape[1] > 2
                     else labels[:, :0])

    # -- serving -----------------------------------------------------------------
    def cache_specs(self, batch: int, max_len: int):
        cfg = self.cfg
        from .common import stack_spec
        segs = []
        for (unit, reps) in self.segments:
            us = []
            for desc in unit:
                cs = mixer_cache_spec(cfg, desc[0], batch, max_len)
                us.append(stack_spec(cs, reps) if reps > 1 else cs)
            segs.append(us)
        return segs

    def init_cache(self, batch: int, max_len: int):
        specs = self.cache_specs(batch, max_len)
        cache = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype or _dt(self.cfg)), specs,
            is_leaf=lambda x: isinstance(x, P))
        # mark attention cache slots empty (pos = -1)
        def fix(seg):
            return [
                (dict(u, pos=jnp.full_like(u["pos"], -1))
                 if isinstance(u, dict) and "pos" in u else u)
                for u in seg
            ]
        return [fix(seg) for seg in cache]

    def abstract_cache(self, batch: int, max_len: int):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype or _dt(self.cfg)),
            self.cache_specs(batch, max_len),
            is_leaf=lambda x: isinstance(x, P))

    def prefill(self, params, tokens, caches, *, patch_embeds=None):
        """Forward over a prompt, writing caches; returns (last_logits, caches)."""
        cfg = self.cfg
        x = self.embed(params, tokens)
        if cfg.frontend == "vision" and patch_embeds is not None:
            x = jnp.concatenate([patch_embeds.astype(x.dtype), x], axis=1)
        positions = jnp.broadcast_to(
            jnp.arange(x.shape[1], dtype=jnp.int32)[None], x.shape[:2])
        h, caches, _ = self.forward(params, x, positions=positions,
                                    caches=caches)
        return self.logits(params, h[:, -1:]), caches

    def decode_step(self, params, caches, tokens, pos):
        """One decode step.  tokens: (B,1); pos: (B,1) absolute positions."""
        x = self.embed(params, tokens)
        h, caches, _ = self.forward(params, x, positions=pos, caches=caches)
        return self.logits(params, h), caches


def _single_layer(model: "TransformerLM", lp, x, positions):
    return layer_apply(lp, x, cfg=model.cfg, desc=model.descs[-1],
                       positions=positions, cache=None)


def _xent(logits, labels):
    lg = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)


def _dt(cfg: ModelCfg):
    return jnp.dtype(cfg.dtype)
