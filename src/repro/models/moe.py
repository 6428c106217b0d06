"""Mixture-of-Experts layer (DeepSeek-V3 / Granite-MoE style).

Sort-based capacity dispatch: token→expert assignments are sorted by expert
id and scattered into an (E, C, d) table with gather/scatter *indices* — no
(T, E, C) one-hot einsum, so the dispatch memory is O(E·C·d), not O(T·E·C).
Experts are sharded over the 'model' mesh axis (expert parallelism); GSPMD
turns the gathers into the dispatch all-to-alls.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .common import P
from .config import ModelCfg
from repro.sharding.ctx import constrain


def moe_specs(cfg: ModelCfg) -> Dict[str, P]:
    m = cfg.moe
    d, f, E = cfg.d_model, m.d_expert, m.n_experts
    sp = {
        "router": P((d, E), ("embed", "expert"), scale=d ** -0.5),
        "wg": P((E, d, f), ("expert", "embed", "moe_mlp")),
        "wu": P((E, d, f), ("expert", "embed", "moe_mlp")),
        "wd": P((E, f, d), ("expert", "moe_mlp", "embed")),
    }
    if m.router_scale:  # DeepSeek aux-loss-free bias
        sp["router_bias"] = P((E,), ("expert",), "zeros", dtype=jnp.float32)
    if m.n_shared:
        fs = m.d_expert * m.n_shared
        sp["shared_wg"] = P((d, fs), ("embed", "mlp"))
        sp["shared_wu"] = P((d, fs), ("embed", "mlp"))
        sp["shared_wd"] = P((fs, d), ("mlp", "embed"))
    return sp


def moe_apply(p, x, *, cfg: ModelCfg) -> Tuple[jax.Array, jax.Array]:
    """Returns (output, aux_load_balance_loss)."""
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    E, k = m.n_experts, m.top_k
    xf = x.reshape(T, d)

    logits = jnp.einsum("td,de->te", xf, p["router"],
                        preferred_element_type=jnp.float32)
    if m.router_scale:            # DeepSeek-V3: sigmoid affinity + bias
        affin = jax.nn.sigmoid(logits)
        gval, gidx = jax.lax.top_k(affin + p["router_bias"], k)
        gval = jnp.take_along_axis(affin, gidx, axis=1)
        weights = gval / (jnp.sum(gval, axis=1, keepdims=True) + 1e-20)
        probs = affin / (jnp.sum(affin, axis=-1, keepdims=True) + 1e-20)
    else:                         # Granite: softmax router
        probs = jax.nn.softmax(logits, axis=-1)
        weights, gidx = jax.lax.top_k(probs, k)
        weights = weights / (jnp.sum(weights, axis=1, keepdims=True) + 1e-20)

    # load-balance aux loss: E * sum_e f_e * p_e
    ones = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], gidx].set(1.0)
    f_e = jnp.mean(ones, axis=0) * E / k
    p_e = jnp.mean(probs, axis=0)
    aux = jnp.sum(f_e * p_e) * E / E  # = E * mean(f*p) with f normalised

    # ---- sort-based dispatch --------------------------------------------
    import math
    C = int(max(1, math.ceil(T * k / E * m.capacity_factor)))
    flat_e = gidx.reshape(T * k)
    flat_t = jnp.repeat(jnp.arange(T), k)
    flat_w = weights.reshape(T * k)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    first = jnp.searchsorted(se, se, side="left")
    pos_in_e = jnp.arange(T * k) - first       # rank within expert run
    keep = pos_in_e < C
    slot = jnp.where(keep, se * C + pos_in_e, E * C)  # E*C = drop bin

    table = jnp.full((E * C + 1,), T, jnp.int32).at[slot].set(
        st.astype(jnp.int32), mode="drop")
    table = table[:E * C]
    wtab = jnp.zeros((E * C + 1,), jnp.float32).at[slot].set(
        jnp.where(keep, sw, 0.0), mode="drop")[:E * C]

    xg = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)[table]
    xg = constrain(xg.reshape(E, C, d), ("expert", "capacity", "embed"))

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xg, p["wg"])) * \
        jnp.einsum("ecd,edf->ecf", xg, p["wu"])
    h = constrain(h, ("expert", "capacity", "moe_mlp"))
    ye = jnp.einsum("ecf,efd->ecd", h, p["wd"])
    ye = constrain(ye, ("expert", "capacity", "embed"))

    # ---- combine ----------------------------------------------------------
    yflat = (ye.reshape(E * C, d) * wtab[:, None].astype(ye.dtype))
    out = jnp.zeros((T + 1, d), ye.dtype).at[table].add(yflat)[:T]

    if m.n_shared:
        sh = jax.nn.silu(xf @ p["shared_wg"]) * (xf @ p["shared_wu"])
        out = out + sh @ p["shared_wd"]
    return out.reshape(B, S, d), aux


# ------------------------------------------------------ held-share experts
def held_moe_specs(cfg: ModelCfg) -> Dict[str, P]:
    """The router over every routed and identity expert, and the held
    routed experts' weights (``cfg.held_experts`` of ``n_experts``)."""
    m = cfg.moe
    d, f = cfg.d_model, m.d_expert
    n = cfg.held_experts or m.n_experts
    E = m.n_experts + m.n_zero
    return {
        "router": P((d, E), ("embed", None), scale=d ** -0.5),
        "router_bias": P((E,), (None,), "zeros"),
        "wg": P((n, d, f), ("expert", "embed", "moe_mlp")),
        "wu": P((n, d, f), ("expert", "embed", "moe_mlp")),
        "wd": P((n, f, d), ("expert", "moe_mlp", "embed")),
    }


def held_moe_apply(p, x, *, cfg: ModelCfg) -> Tuple[jax.Array, jax.Array]:
    """LongCat-Flash's expert layer, for the routed experts this chip
    holds (``cfg.held_start`` .. ``cfg.held_start + cfg.held_experts - 1``).

    The float32 router scores all ``n_experts + n_zero`` experts with a
    softmax, chooses the top ``top_k`` of score plus ``router_bias``, and
    weighs each chosen expert by its score times ``routed_scale`` (no
    renormalisation).  The result is the held experts' part for the rows
    routed to them plus the identity experts' part (each returns its
    input), which every chip computes alike.  No row is dropped: every
    held expert runs over every row, weighted 0 where it was not chosen.

    Returns ``(out, rows)``: ``rows`` int32 ``(B, 2)``, each batch row's
    (token, expert) choices that fell on the held experts and on the
    identity experts."""
    m = cfg.moe
    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    n = cfg.held_experts or m.n_experts
    with jax.named_scope("moe.router"):
        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                            p["router"].astype(jnp.float32))
        scores = jax.nn.softmax(logits, axis=-1)
        _, idx = jax.lax.top_k(
            scores + p["router_bias"].astype(jnp.float32), m.top_k)
        w = jnp.take_along_axis(scores, idx, axis=1) * m.routed_scale
        local = idx - cfg.held_start
        held = (local >= 0) & (local < n)
        zero = idx >= m.n_experts
        # (rows, held experts): the weight of each, 0 where not chosen
        comb = jnp.einsum("tk,tkn->tn", jnp.where(held, w, 0.0),
                          jax.nn.one_hot(jnp.where(held, local, 0), n))
    with jax.named_scope("moe.experts"):
        h = jax.nn.silu(jnp.einsum("td,ndf->ntf", xf, p["wg"])) * \
            jnp.einsum("td,ndf->ntf", xf, p["wu"])
        h = h * comb.T[:, :, None].astype(h.dtype)
        out = jnp.einsum("ntf,nfd->td", h, p["wd"],
                         preferred_element_type=jnp.float32)
    with jax.named_scope("moe.zero"):
        out = out + jnp.sum(jnp.where(zero, w, 0.0), axis=1)[:, None] * \
            xf.astype(jnp.float32)
    rows = jnp.stack([jnp.sum(held, axis=1), jnp.sum(zero, axis=1)], -1)
    rows = jnp.sum(rows.reshape(B, S, 2), axis=1).astype(jnp.int32)
    return out.astype(x.dtype).reshape(B, S, d), rows
