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
from .moe import held_moe_apply, held_moe_specs, moe_apply, moe_specs
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
        return layer_norm(x, p["w"], p["b"],
                          eps=1e-5 if cfg.norm_eps is None else cfg.norm_eps)
    return rms_norm(x, p["w"], plus_one=cfg.norm_plus_one,
                    eps=1e-6 if cfg.norm_eps is None else cfg.norm_eps)


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
    if mixer == "longcat":
        return longcat_specs(cfg)
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
STACK_WRITTEN = ("attn", "local", "mla", "longcat")


def no_rows(x):
    """A layer's ``rows`` where it routes to no held expert: int32
    ``(batch, 2)`` zeros (see ``layer_apply``)."""
    return jnp.zeros((x.shape[0], 2), jnp.int32)


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
    """Returns ``(x, new_cache, aux, rows)``: ``aux`` the load-balance
    loss, ``rows`` int32 ``(batch, 2)``, each batch row's (token, expert)
    choices that fell on held and on identity experts in a held-share
    expert layer (zeros for the others).  ``layer``: this layer's index when
    ``cache`` is the whole segment's stack (see ``STACK_WRITTEN``), else
    None."""
    mixer, mlp_kind = desc
    aux = jnp.zeros((), jnp.float32)
    if mixer == "longcat":
        x, new_cache, rows = longcat_apply(lp, x, cfg=cfg,
                                           positions=positions, cache=cache,
                                           layer=layer)
        return x, new_cache, aux, rows
    rows = no_rows(x)
    h = norm_apply(lp["ln1"], x, cfg)
    mix, new_cache = mixer_apply(mixer, lp["mix"], h, cfg=cfg,
                                 positions=positions, cache=cache,
                                 layer=layer)
    if cfg.post_norms:
        mix = norm_apply(lp["ln1p"], mix, cfg)
    x = x + mix
    x = constrain(x, ("batch", "residual_seq", "embed"))
    if mlp_kind == "none":
        return x, new_cache, aux, rows
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
            new_cache, aux, rows)


# ------------------------------------------- LongCat-Flash's double layer
def longcat_specs(cfg: ModelCfg) -> Dict[str, Any]:
    sp: Dict[str, Any] = {}
    for i in (0, 1):
        sp[f"ln_attn{i}"] = norm_specs(cfg)
        sp[f"mla{i}"] = attn.mla_specs(cfg)
        sp[f"ln_mlp{i}"] = norm_specs(cfg)
        sp[f"mlp{i}"] = mlp_specs(cfg)
    sp["moe"] = held_moe_specs(cfg)
    return sp


def longcat_apply(lp, x, *, cfg: ModelCfg, positions, cache, layer=None):
    """LongCat-Flash's layer: two MLA sublayers, each followed by a dense
    MLP, and a shortcut MoE fed from the first sublayer's MLP input and
    added after the second::

        h0 = x + MLA_0(norm(x));   u = norm(h0);   h1 = h0 + MLP_0(u)
        h2 = h1 + MLA_1(norm(h1)); y = h2 + MLP_1(norm(h2)) + MoE(u)

    ``cache``: ``{"mla0", "mla1"}`` latent caches (stacked over layers
    when ``layer`` is given).  Returns ``(y, new_cache, rows)``."""
    new_cache = None if cache is None else {}
    shortcut = rows = None
    for i in (0, 1):
        with jax.named_scope("mla"):
            h = norm_apply(lp[f"ln_attn{i}"], x, cfg)
            mix, nc = attn.mla_apply(
                lp[f"mla{i}"], h, cfg=cfg, positions=positions,
                cache=None if cache is None else cache[f"mla{i}"],
                layer=layer)
        if cache is not None:
            new_cache[f"mla{i}"] = nc
        x = x + mix
        u = norm_apply(lp[f"ln_mlp{i}"], x, cfg)
        if i == 0:
            with jax.named_scope("moe"):
                shortcut, rows = held_moe_apply(lp["moe"], u, cfg=cfg)
        with jax.named_scope("mlp"):
            x = x + mlp_apply(lp[f"mlp{i}"], u, cfg)
    return x + shortcut, new_cache, rows


def mixer_cache_spec(cfg: ModelCfg, kind: str, batch: int, max_len: int):
    if kind == "attn":
        return attn.gqa_cache_spec(cfg, "attn", batch, max_len)
    if kind == "local":
        return attn.gqa_cache_spec(cfg, "local", batch, max_len)
    if kind == "mla":
        return attn.mla_cache_spec(cfg, batch, max_len)
    if kind == "longcat":
        return {f"mla{i}": attn.mla_cache_spec(cfg, batch, max_len)
                for i in (0, 1)}
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
        #: whether a layer routes to held experts (decode counts its rows)
        self.routes = any(d[0] == "longcat" for d in self.descs)

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

        def body(carry, slices):
            x, aux, rows = carry
            pslices, cslices = slices
            new_caches = []
            for ui, desc in enumerate(unit):
                x, nc, a, r = layer_apply(
                    pslices[f"u{ui}"], x, cfg=cfg, desc=desc,
                    positions=positions, cache=cslices[ui])
                new_caches.append(nc)
                aux, rows = aux + a, rows + r
            return (x, aux, rows), new_caches
        return body

    def forward(self, params, x, *, positions, caches=None):
        """x: embedded inputs (B, S, d).  Returns (hidden, new_caches, aux).

        caches: list per segment of per-unit cache trees (stacked when the
        segment is scanned), or None for training."""
        return self.forward_counted(params, x, positions=positions,
                                    caches=caches)[:3]

    def forward_counted(self, params, x, *, positions, caches=None):
        """:meth:`forward`, and the expert choices summed over layers
        (``layer_apply``'s ``rows``)."""
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        rows = no_rows(x)
        new_caches = []
        for si, (unit, reps) in enumerate(self.segments):
            seg_p = params[f"seg{si}"]
            seg_c = caches[si] if caches is not None else [None] * len(unit)
            # under a mesh (``use_sharding``) the batch may be split, and a
            # row's write into a split stack would gather it: keep xs->ys
            if (caches is not None and reps > 1 and x.shape[1] == 1
                    and any(desc[0] in STACK_WRITTEN for desc in unit)
                    and current_mesh() is None):
                x, aux, rows, ncs = self._scan_stacked(
                    unit, positions, (x, aux, rows), seg_p, seg_c, reps)
                new_caches.append(ncs)
                continue
            body = self._remat(self._unit_body(unit, positions,
                                               caches is not None),
                               prevent_cse=reps == 1)
            if reps == 1:
                (x, aux, rows), ncs = body((x, aux, rows), (seg_p, seg_c))
                new_caches.append(ncs)
            else:
                (x, aux, rows), ncs = jax.lax.scan(body, (x, aux, rows),
                                                   (seg_p, seg_c))
                new_caches.append(ncs)
        x = norm_apply(params["final_norm"], x, cfg)
        return x, (new_caches if caches is not None else None), aux, rows

    def _remat(self, body, *, prevent_cse: bool):
        cfg = self.cfg
        if cfg.remat == "none":
            return body
        policy = (jax.checkpoint_policies.nothing_saveable
                  if cfg.remat == "full" else
                  jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        return jax.checkpoint(body, policy=policy, prevent_cse=prevent_cse)

    def _scan_stacked(self, unit, positions, carry, seg_p, seg_c, reps):
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
            x, aux, rows, stacks = carry
            pslices, cslices, i = xs
            stacks, ys = list(with_layout_constraint(stacks, layouts)), []
            for ui, desc in enumerate(unit):
                x, nc, a, r = layer_apply(
                    pslices[f"u{ui}"], x, cfg=cfg, desc=desc,
                    positions=positions,
                    cache=stacks[ui] if stacked[ui] else cslices[ui],
                    layer=i if stacked[ui] else None)
                if stacked[ui]:
                    stacks[ui] = nc
                ys.append(None if stacked[ui] else nc)
                aux, rows = aux + a, rows + r
            return (x, aux, rows,
                    with_layout_constraint(stacks, layouts)), ys

        (x, aux, rows, stacks), ys = jax.lax.scan(
            self._remat(body, prevent_cse=False), (*carry, stacks),
            (seg_p, slices, jnp.arange(reps, dtype=jnp.int32)))
        return x, aux, rows, [st if s else y
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
        x2, *_ = _single_layer(self, mp["layer"], x, positions)
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
        # mark attention cache slots empty (pos = -1), in nested units too
        def fix(u):
            if not isinstance(u, dict):
                return u
            u = {k: fix(v) for k, v in u.items()}
            if "pos" in u:
                u["pos"] = jnp.full_like(u["pos"], -1)
            return u
        return [[fix(u) for u in seg] for seg in cache]

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
        return self.decode_counted(params, caches, tokens, pos)[:2]

    def decode_counted(self, params, caches, tokens, pos):
        """:meth:`decode_step`, and each row's expert choices summed over
        layers (``layer_apply``'s ``rows``; None where no layer routes)."""
        x = self.embed(params, tokens)
        h, caches, _, rows = self.forward_counted(params, x, positions=pos,
                                                  caches=caches)
        return self.logits(params, h), caches, rows if self.routes else None


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
