"""Optimizers: AdamW, int8-moment AdamW, Adafactor, SGD-momentum.

All states are pytrees mirroring the parameter tree, so the logical-axis
sharding rules apply unchanged (ZeRO-1/3: under fsdp rules, moments shard
over 'data' exactly like the parameters).  ``abstract_state`` builds the
ShapeDtypeStruct tree without allocating anything.

int8 moments (``adamw8``) store per-tensor absmax-scaled int8 m/v — a 7x
optimizer-memory cut vs fp32 Adam.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class OptCfg:
    name: str = "adamw"          # adamw | adamw8 | adafactor | sgdm
    peak_lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 10000
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.01
    clip_norm: Optional[float] = 1.0
    master_fp32: bool = False    # keep fp32 master copy of bf16 params


@dataclasses.dataclass
class Optimizer:
    cfg: OptCfg
    init: Callable[[Any], Any]
    abstract_state: Callable[[Any], Any]
    state_axes: Callable[[Any], Any]     # logical axes for the state tree
    update: Callable[[Any, Any, Any, jax.Array], Tuple[Any, Any]]


def _lr(cfg: OptCfg, step):
    from .schedules import cosine_schedule
    return cosine_schedule(step, peak=cfg.peak_lr, warmup=cfg.warmup,
                           total=cfg.total_steps)


def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def _clipped(cfg: OptCfg, grads):
    if cfg.clip_norm is None:
        return grads, jnp.asarray(0.0)
    g = _global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / (g + 1e-9))
    return jax.tree.map(lambda x: (x.astype(jnp.float32) * scale).astype(
        x.dtype), grads), g


# ---------------------------------------------------------------- quantised
def _q8(x32):
    amax = jnp.max(jnp.abs(x32)) + 1e-12
    q = jnp.round(x32 / amax * 127.0).astype(jnp.int8)
    return q, amax.astype(jnp.float32)


def _dq8(q, amax):
    return q.astype(jnp.float32) * (amax / 127.0)


# ------------------------------------------------------------------- adamw
def make_optimizer(cfg: OptCfg) -> Optimizer:
    if cfg.name in ("adamw", "adamw8"):
        return _adamw(cfg, quantised=cfg.name == "adamw8")
    if cfg.name == "adafactor":
        return _adafactor(cfg)
    if cfg.name == "sgdm":
        return _sgdm(cfg)
    raise ValueError(cfg.name)


def _adamw(cfg: OptCfg, quantised: bool) -> Optimizer:
    def init(params):
        def leaf(p):
            if quantised:
                z8 = jnp.zeros(p.shape, jnp.int8)
                sc = jnp.zeros((), jnp.float32)
                st = {"m": z8, "m_s": sc, "v": z8, "v_s": sc}
            else:
                st = {"m": jnp.zeros(p.shape, jnp.float32),
                      "v": jnp.zeros(p.shape, jnp.float32)}
            if cfg.master_fp32:
                st["master"] = p.astype(jnp.float32)
            return st
        return {"mu": jax.tree.map(leaf, params),
                "count": jnp.zeros((), jnp.int32)}

    def abstract_state(aparams):
        def leaf(p):
            if quantised:
                st = {"m": jax.ShapeDtypeStruct(p.shape, jnp.int8),
                      "m_s": jax.ShapeDtypeStruct((), jnp.float32),
                      "v": jax.ShapeDtypeStruct(p.shape, jnp.int8),
                      "v_s": jax.ShapeDtypeStruct((), jnp.float32)}
            else:
                st = {"m": jax.ShapeDtypeStruct(p.shape, jnp.float32),
                      "v": jax.ShapeDtypeStruct(p.shape, jnp.float32)}
            if cfg.master_fp32:
                st["master"] = jax.ShapeDtypeStruct(p.shape, jnp.float32)
            return st
        return {"mu": jax.tree.map(leaf, aparams),
                "count": jax.ShapeDtypeStruct((), jnp.int32)}

    def state_axes(param_axes):
        def leaf(ax):
            if quantised:
                st = {"m": ax, "m_s": (), "v": ax, "v_s": ()}
            else:
                st = {"m": ax, "v": ax}
            if cfg.master_fp32:
                st["master"] = ax
            return st
        return {"mu": jax.tree.map(leaf, param_axes,
                                   is_leaf=lambda x: isinstance(x, tuple)),
                "count": ()}

    def update(grads, state, params, step):
        cnt = state["count"] + 1
        lr = _lr(cfg, step)
        grads, gnorm = _clipped(cfg, grads)
        b1c = 1 - cfg.b1 ** cnt.astype(jnp.float32)
        b2c = 1 - cfg.b2 ** cnt.astype(jnp.float32)

        def leaf(g, st, p):
            g32 = g.astype(jnp.float32)
            if quantised:
                m = _dq8(st["m"], st["m_s"])
                v = _dq8(st["v"], st["v_s"])
            else:
                m, v = st["m"], st["v"]
            m = cfg.b1 * m + (1 - cfg.b1) * g32
            v = cfg.b2 * v + (1 - cfg.b2) * jnp.square(g32)
            upd = (m / b1c) / (jnp.sqrt(v / b2c) + cfg.eps)
            base = st["master"] if cfg.master_fp32 else p.astype(jnp.float32)
            decay = cfg.weight_decay if p.ndim >= 2 else 0.0
            new = base - lr * (upd + decay * base)
            out = {}
            if quantised:
                out["m"], out["m_s"] = _q8(m)
                out["v"], out["v_s"] = _q8(v)
            else:
                out["m"], out["v"] = m, v
            if cfg.master_fp32:
                out["master"] = new
            return new.astype(p.dtype), out

        flat = jax.tree.map(leaf, grads, state["mu"], params,
                            is_leaf=lambda x: isinstance(x, dict) and "m" in x)
        # tree.map over 3 trees with dict leaves: leaf() returned tuples
        new_params = jax.tree.map(lambda t: t[0], flat,
                                  is_leaf=lambda x: isinstance(x, tuple))
        new_mu = jax.tree.map(lambda t: t[1], flat,
                              is_leaf=lambda x: isinstance(x, tuple))
        metrics = {"grad_norm": gnorm, "lr": lr}
        return new_params, {"mu": new_mu, "count": cnt}, metrics

    return Optimizer(cfg, init, abstract_state, state_axes, update)


# ---------------------------------------------------------------- adafactor
def _adafactor(cfg: OptCfg) -> Optimizer:
    def _shapes(p):
        if p.ndim >= 2:
            row = p.shape[:-1]
            col = p.shape[:-2] + p.shape[-1:]
            return row, col
        return None, None

    def init(params):
        def leaf(p):
            row, col = _shapes(p)
            if row is None:
                return {"v": jnp.zeros(p.shape, jnp.float32)}
            return {"vr": jnp.zeros(row, jnp.float32),
                    "vc": jnp.zeros(col, jnp.float32)}
        return {"mu": jax.tree.map(leaf, params),
                "count": jnp.zeros((), jnp.int32)}

    def abstract_state(aparams):
        def leaf(p):
            row, col = _shapes(p)
            if row is None:
                return {"v": jax.ShapeDtypeStruct(p.shape, jnp.float32)}
            return {"vr": jax.ShapeDtypeStruct(row, jnp.float32),
                    "vc": jax.ShapeDtypeStruct(col, jnp.float32)}
        return {"mu": jax.tree.map(leaf, aparams),
                "count": jax.ShapeDtypeStruct((), jnp.int32)}

    def state_axes(param_axes):
        def leaf(ax):
            if len(ax) < 2:
                return {"v": ax}
            return {"vr": ax[:-1], "vc": ax[:-2] + ax[-1:]}
        return {"mu": jax.tree.map(leaf, param_axes,
                                   is_leaf=lambda x: isinstance(x, tuple)),
                "count": ()}

    def update(grads, state, params, step):
        cnt = state["count"] + 1
        lr = _lr(cfg, step)
        grads, gnorm = _clipped(cfg, grads)
        decay = 1.0 - (cnt.astype(jnp.float32)) ** -0.8

        def leaf(g, st, p):
            g32 = g.astype(jnp.float32)
            g2 = jnp.square(g32) + 1e-30
            if "v" in st:
                v = decay * st["v"] + (1 - decay) * g2
                upd = g32 * jax.lax.rsqrt(v + cfg.eps)
                new_st = {"v": v}
            else:
                vr = decay * st["vr"] + (1 - decay) * jnp.mean(g2, axis=-1)
                vc = decay * st["vc"] + (1 - decay) * jnp.mean(g2, axis=-2)
                denom = (vr / jnp.mean(vr, axis=-1, keepdims=True) + 1e-30)
                pre = jax.lax.rsqrt(denom)[..., None] * \
                    jax.lax.rsqrt(vc + 1e-30)[..., None, :]
                upd = g32 * pre
                new_st = {"vr": vr, "vc": vc}
            # update clipping (Adafactor RMS rule)
            rms = jnp.sqrt(jnp.mean(jnp.square(upd)) + 1e-30)
            upd = upd / jnp.maximum(1.0, rms)
            base = p.astype(jnp.float32)
            wd = cfg.weight_decay if p.ndim >= 2 else 0.0
            new = base - lr * (upd + wd * base)
            return new.astype(p.dtype), new_st

        flat = jax.tree.map(leaf, grads, state["mu"], params,
                            is_leaf=lambda x: isinstance(x, dict) and (
                                "v" in x or "vr" in x))
        new_params = jax.tree.map(lambda t: t[0], flat,
                                  is_leaf=lambda x: isinstance(x, tuple))
        new_mu = jax.tree.map(lambda t: t[1], flat,
                              is_leaf=lambda x: isinstance(x, tuple))
        return new_params, {"mu": new_mu, "count": cnt}, \
            {"grad_norm": gnorm, "lr": lr}

    return Optimizer(cfg, init, abstract_state, state_axes, update)


# -------------------------------------------------------------------- sgdm
def _sgdm(cfg: OptCfg) -> Optimizer:
    def init(params):
        return {"mu": jax.tree.map(
            lambda p: {"m": jnp.zeros(p.shape, jnp.float32)}, params),
            "count": jnp.zeros((), jnp.int32)}

    def abstract_state(aparams):
        return {"mu": jax.tree.map(
            lambda p: {"m": jax.ShapeDtypeStruct(p.shape, jnp.float32)},
            aparams), "count": jax.ShapeDtypeStruct((), jnp.int32)}

    def state_axes(param_axes):
        return {"mu": jax.tree.map(lambda ax: {"m": ax}, param_axes,
                                   is_leaf=lambda x: isinstance(x, tuple)),
                "count": ()}

    def update(grads, state, params, step):
        cnt = state["count"] + 1
        lr = _lr(cfg, step)
        grads, gnorm = _clipped(cfg, grads)

        def leaf(g, st, p):
            m = cfg.b1 * st["m"] + g.astype(jnp.float32)
            new = p.astype(jnp.float32) - lr * m
            return new.astype(p.dtype), {"m": m}

        flat = jax.tree.map(leaf, grads, state["mu"], params,
                            is_leaf=lambda x: isinstance(x, dict) and "m" in x)
        new_params = jax.tree.map(lambda t: t[0], flat,
                                  is_leaf=lambda x: isinstance(x, tuple))
        new_mu = jax.tree.map(lambda t: t[1], flat,
                              is_leaf=lambda x: isinstance(x, tuple))
        return new_params, {"mu": new_mu, "count": cnt}, \
            {"grad_norm": gnorm, "lr": lr}

    return Optimizer(cfg, init, abstract_state, state_axes, update)
