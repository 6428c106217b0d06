"""Cell builder: (architecture x input shape x mesh) -> lowerable closure.

A *cell* packages the step function, abstract inputs (ShapeDtypeStruct — no
allocation), and in/out shardings for one dry-run / roofline entry.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.configs import ARCHS, SHAPES, ArchSpec, ShapeCfg
from repro.models import build_model
from repro.optim import OptCfg, make_optimizer
from repro.sharding import (DEFAULT_RULES, fsdp_rules, serve_rules, sp_rules,
                            resolve, use_sharding)
from repro.train.step import make_prefill_step, make_serve_step, make_train_step

WHISPER_CROSS_LEN = 1500  # encoder frames for enc-dec decode cells


@dataclasses.dataclass
class Cell:
    arch: str
    shape: str
    fn: Callable
    args: Tuple[Any, ...]
    in_shardings: Any
    out_shardings: Any
    mesh: Mesh
    rules: dict
    meta: dict
    donate: Tuple[int, ...] = ()   # donated arg indices (in-place updates)


def _shard_tree(axes_tree, abs_tree, mesh, rules):
    def one(ax, ab):
        return NamedSharding(mesh, resolve(ab.shape, ax, mesh, rules))
    return jax.tree.map(
        one, axes_tree, abs_tree,
        is_leaf=lambda x: (isinstance(x, tuple)
                           and all(isinstance(a, (str, type(None)))
                                   for a in x)))


def _batch_abstract(spec: ArchSpec, shape: ShapeCfg):
    cfg = spec.cfg
    B, S = shape.global_batch, shape.seq
    batch = {}
    axes = {}
    if cfg.frontend == "vision":
        s_text = S - cfg.n_frontend_tokens
        batch["tokens"] = jax.ShapeDtypeStruct((B, s_text), jnp.int32)
        batch["labels"] = jax.ShapeDtypeStruct((B, s_text), jnp.int32)
        batch["patch_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.n_frontend_tokens, cfg.d_model), jnp.float32)
        axes["patch_embeds"] = ("batch", "seq", "embed")
    elif cfg.frontend == "audio":
        batch["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        batch["labels"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        batch["frame_embeds"] = jax.ShapeDtypeStruct(
            (B, S, cfg.d_model), jnp.float32)
        axes["frame_embeds"] = ("batch", "seq", "embed")
    else:
        batch["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        batch["labels"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
    axes.setdefault("tokens", ("batch", "seq"))
    axes.setdefault("labels", ("batch", "seq"))
    return batch, axes


def opt_for(spec: ArchSpec) -> OptCfg:
    name = getattr(spec, "optimizer", None) or (
        "adamw8" if spec.published_params and spec.published_params > 1e11
        else "adamw")
    return OptCfg(name=name)


def build_cell(arch: str, shape_name: str, mesh: Mesh, *,
               rules: Optional[dict] = None,
               microbatches: Optional[int] = None,
               remat: Optional[str] = None,
               acc_dtype: str = "float32",
               optimizer: Optional[str] = None,
               rg_block_heads: Optional[int] = None,
               tp_sp: bool = False) -> Cell:
    spec = ARCHS[arch]
    shape = SHAPES[shape_name]
    cfg = spec.cfg
    if rg_block_heads and cfg.rglru is not None:
        cfg = cfg.replace(rglru=dataclasses.replace(
            cfg.rglru, block_heads=rg_block_heads))
    if shape.kind == "decode":
        cfg = cfg.replace(max_target_length=max(shape.seq + 8,
                                                cfg.max_target_length))
    if remat is not None:
        cfg = cfg.replace(remat=remat)
    if shape.kind != "train":
        cfg = cfg.replace(remat="none")
    model = build_model(cfg)
    params_abs = model.abstract_params()
    params_axes = model.param_axes()

    if rules is None:
        if shape_name.startswith("long"):
            rules = sp_rules(serve_rules())
        elif shape.kind == "train":
            from repro.sharding.rules import tp_sp_rules
            rules = tp_sp_rules() if tp_sp else fsdp_rules()
        elif shape.kind == "prefill":
            # prefill is compute-shaped like training: FSDP weight
            # gathers per layer beat replicated-weight serving rules
            rules = fsdp_rules()
        else:
            rules = serve_rules()
            # kv-heads that cannot split the model axis: shard the cache
            # *length* over 'model' instead (keeps the cache in HBM bounds)
            if (not cfg.encdec and cfg.mla is None and cfg.ssm is None
                    and cfg.n_kv_heads % mesh.shape["model"] != 0):
                rules = dict(rules, cache="model", kv_heads=None)

    p_shard = _shard_tree(params_axes, params_abs, mesh, rules)
    meta = dict(kind=shape.kind, seq=shape.seq,
                global_batch=shape.global_batch,
                n_params=sum(int(jnp.prod(jnp.array(x.shape)))
                             for x in jax.tree.leaves(params_abs)))

    if shape.kind == "train":
        mb = microbatches
        if mb is None:
            mb = (spec.microbatches or {}).get(shape_name, 1)
            # production default: never slice the per-microbatch batch
            # below the data-parallel extent, or the whole step replicates
            # across 'data' (§Perf cell 1).  Explicit --microbatches
            # overrides (how the paper-faithful baseline is reproduced).
            dp = 1
            for ax in ("pod", "data"):
                if ax in mesh.axis_names:
                    dp *= mesh.shape[ax]
            while mb > 1 and shape.global_batch // mb < dp:
                mb //= 2
        ocfg = opt_for(spec)
        if optimizer:
            ocfg = OptCfg(name=optimizer)
        opt = make_optimizer(ocfg)
        opt_abs = opt.abstract_state(params_abs)
        opt_axes = opt.state_axes(params_axes)
        o_shard = _shard_tree(opt_axes, opt_abs, mesh, rules)
        batch_abs, batch_axes = _batch_abstract(spec, shape)
        b_shard = _shard_tree(batch_axes, batch_abs, mesh, rules)
        step_abs = jax.ShapeDtypeStruct((), jnp.int32)
        raw_step = make_train_step(model, opt, microbatches=mb,
                                   acc_dtype=jnp.dtype(acc_dtype))

        def fn(params, opt_state, batch, step):
            with use_sharding(mesh, rules):
                return raw_step(params, opt_state, batch, step)

        repl = NamedSharding(mesh, PartitionSpec())
        metrics_shard = {k: repl for k in
                         ["loss", "ce", "aux", "mtp", "grad_norm", "lr"]}
        meta["microbatches"] = mb
        return Cell(arch, shape_name, fn,
                    (params_abs, opt_abs, batch_abs, step_abs),
                    (p_shard, o_shard, b_shard, repl),
                    (p_shard, o_shard, None),
                    mesh, rules, meta, donate=(0, 1))   # params, opt_state

    if shape.kind == "prefill":
        batch_abs, batch_axes = _batch_abstract(spec, shape)
        b_shard = _shard_tree(batch_axes, batch_abs, mesh, rules)
        batch_abs.pop("labels")
        b_shard.pop("labels")
        raw = make_prefill_step(model)

        def fn(params, batch):
            with use_sharding(mesh, rules):
                return raw(params, batch)

        return Cell(arch, shape_name, fn, (params_abs, batch_abs),
                    (p_shard, b_shard), None, mesh, rules, meta)

    # decode: serve_step over a pre-existing cache of length seq
    B, S = shape.global_batch, shape.seq
    if cfg.encdec:
        cache_abs = (model.abstract_cache(B, S),
                     _cross_kv_abstract(model, B))
        cache_axes = (jax.tree.map(lambda s: s.axes, model.cache_specs(B, S),
                                   is_leaf=lambda x: hasattr(x, "axes")),
                      _cross_kv_axes(model))
    else:
        cache_abs = model.abstract_cache(B, S)
        cache_axes = jax.tree.map(lambda s: s.axes, model.cache_specs(B, S),
                                  is_leaf=lambda x: hasattr(x, "axes"))
    c_shard = _shard_tree(cache_axes, cache_abs, mesh, rules)
    tok_abs = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    pos_abs = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    t_shard = NamedSharding(mesh, resolve((B, 1), ("batch", "seq"),
                                          mesh, rules))
    raw = make_serve_step(model)

    def fn(params, caches, tokens, pos):
        with use_sharding(mesh, rules):
            return raw(params, caches, tokens, pos)

    return Cell(arch, shape_name, fn, (params_abs, cache_abs, tok_abs,
                                       pos_abs),
                (p_shard, c_shard, t_shard, t_shard),
                (t_shard, c_shard, None), mesh, rules, meta,
                donate=(1,))                             # KV caches in-place


def _cross_kv_abstract(model, B):
    cfg = model.cfg
    L, KH, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
    dt = jnp.dtype(cfg.dtype)
    sh = (L, B, WHISPER_CROSS_LEN, KH, hd)
    return (jax.ShapeDtypeStruct(sh, dt), jax.ShapeDtypeStruct(sh, dt))


def _cross_kv_axes(model):
    ax = ("layers", "batch", "cache", "kv_heads", "head_dim")
    return (ax, ax)
