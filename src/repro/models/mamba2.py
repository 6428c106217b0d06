"""Mamba-2 block: state-space duality (SSD), chunked exact computation.

Reference (pure jnp) implementation of the SSD algorithm of Dao & Gu 2024:
within a chunk the recurrence is computed as a masked quadratic form (maps
to the MXU); across chunks a cheap state recurrence carries the SSM state.
The Pallas kernel in ``repro.kernels.ssd`` mirrors this chunk structure.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import P, rms_norm
from .config import ModelCfg
from repro.sharding.ctx import constrain


def _dims(cfg: ModelCfg):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    nh = d_in // s.head_dim
    d_xbc = d_in + 2 * s.n_groups * s.d_state
    return s, d_in, nh, d_xbc


def mamba2_specs(cfg: ModelCfg) -> Dict[str, P]:
    s, d_in, nh, d_xbc = _dims(cfg)
    d = cfg.d_model
    return {
        "in_proj": P((d, 2 * d_in + 2 * s.n_groups * s.d_state + nh),
                     ("embed", "rec")),
        "conv_w": P((s.d_conv, d_xbc), ("dconv", "rec"), scale=0.5),
        "conv_b": P((d_xbc,), ("rec",), "zeros"),
        "a_log": P((nh,), ("ssm_heads",), "ones"),
        "dt_bias": P((nh,), ("ssm_heads",), "zeros"),
        "d_skip": P((nh,), ("ssm_heads",), "ones"),
        "norm": P((d_in,), ("rec",), "ones"),
        "out_proj": P((d_in, d), ("rec", "embed")),
    }


def ssd_reference(x, dt, a_log, b, c, *, chunk: int, init_state=None,
                  return_final_state: bool = False):
    """Chunked SSD scan (pure jnp oracle).

    x: (B, T, H, P)   values per head
    dt: (B, T, H)     softplus-discretised step
    a_log: (H,)       A = -exp(a_log)
    b, c: (B, T, G, N) input/output projections (groups broadcast to heads)
    Returns y: (B, T, H, P)  [and the final state (B,H,N,P) if requested].
    """
    B, T, H, Pd = x.shape
    G, N = b.shape[2], b.shape[3]
    nc = T // chunk
    A = -jnp.exp(a_log.astype(jnp.float32))              # (H,)
    dta = dt.astype(jnp.float32) * A                     # (B,T,H) log-decay
    rep = H // G

    xr = x.reshape(B, nc, chunk, H, Pd)
    dtr = dt.reshape(B, nc, chunk, H).astype(jnp.float32)
    da = dta.reshape(B, nc, chunk, H)
    br = jnp.repeat(b.reshape(B, nc, chunk, G, N), rep, axis=3)  # (...,H,N)
    cr = jnp.repeat(c.reshape(B, nc, chunk, G, N), rep, axis=3)

    cum = jnp.cumsum(da, axis=2)                         # (B,nc,Q,H)
    # intra-chunk: y_i += sum_{j<=i} exp(cum_i - cum_j) dt_j (c_i.b_j) x_j
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,nc,Qi,Qj,H)
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))[None, None, :, :, None]
    # mask inside the exponent: exp of masked (positive) entries would be
    # inf and 0*inf => NaN gradients
    decay = jnp.exp(jnp.where(mask, seg, -1e30))
    cb = jnp.einsum("bnihd,bnjhd->bnijh", cr.astype(jnp.float32),
                    br.astype(jnp.float32))              # (B,nc,Qi,Qj,H)
    att = cb * decay * dtr[:, :, None, :, :]
    y = jnp.einsum("bnijh,bnjhp->bnihp", att, xr.astype(jnp.float32))

    # chunk-final states: S_n = sum_j exp(cum_last - cum_j) dt_j b_j x_j^T
    last = cum[:, :, -1:, :]                             # (B,nc,1,H)
    w = jnp.exp(last - cum) * dtr                        # (B,nc,Q,H)
    states = jnp.einsum("bnjh,bnjhd,bnjhp->bnhdp",
                        w, br.astype(jnp.float32), xr.astype(jnp.float32))

    # inter-chunk recurrence over nc:  S <- exp(sum da_n) S + states_n
    chunk_decay = jnp.exp(jnp.sum(da, axis=2))           # (B,nc,H)

    def step(s, inp):
        dec, st = inp
        s = s * dec[:, :, None, None] + st
        return s, s
    init = init_state if init_state is not None else \
        jnp.zeros((B, H, N, Pd), jnp.float32)
    _, all_states = jax.lax.scan(
        step, init, (jnp.moveaxis(chunk_decay, 1, 0),
                     jnp.moveaxis(states, 1, 0)))
    prev = jnp.concatenate([init[None], all_states[:-1]], axis=0)
    prev = jnp.moveaxis(prev, 0, 1)                      # (B,nc,H,N,P)

    # inter-chunk contribution: y_i += exp(cum_i) c_i . S_prev
    y = y + jnp.einsum("bnih,bnihd,bnhdp->bnihp",
                       jnp.exp(cum), cr.astype(jnp.float32), prev)
    y = y.reshape(B, T, H, Pd)
    if return_final_state:
        return y, all_states[-1]                         # (B,H,N,P)
    return y


def _split_in(cfg: ModelCfg, zxbcdt):
    s, d_in, nh, d_xbc = _dims(cfg)
    z = zxbcdt[..., :d_in]
    xbc = zxbcdt[..., d_in:d_in + d_xbc]
    dt = zxbcdt[..., d_in + d_xbc:]
    return z, xbc, dt


def _conv1d(xbc, w, b, state: Optional[jax.Array]):
    """Depthwise causal conv; state = trailing (d_conv-1) inputs or None."""
    K = w.shape[0]
    if state is None:
        pad = jnp.zeros(xbc.shape[:1] + (K - 1,) + xbc.shape[2:], xbc.dtype)
    else:
        pad = state
    xp = jnp.concatenate([pad, xbc], axis=1)             # (B, T+K-1, C)
    out = sum(xp[:, i:i + xbc.shape[1]] * w[i] for i in range(K)) + b
    new_state = xp[:, -(K - 1):] if K > 1 else None
    return jax.nn.silu(out), new_state


def mamba2_apply(p, x, *, cfg: ModelCfg,
                 cache: Optional[dict] = None) -> Tuple[jax.Array, Optional[dict]]:
    s, d_in, nh, d_xbc = _dims(cfg)
    B, T, _ = x.shape
    G, N, Pd = s.n_groups, s.d_state, s.head_dim

    zxbcdt = x @ p["in_proj"]
    z, xbc, dt_raw = _split_in(cfg, zxbcdt)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])

    if cache is None:
        xbc, _ = _conv1d(xbc, p["conv_w"], p["conv_b"], None)
        xs = xbc[..., :d_in].reshape(B, T, nh, Pd)
        b = xbc[..., d_in:d_in + G * N].reshape(B, T, G, N)
        c = xbc[..., d_in + G * N:].reshape(B, T, G, N)
        xs = constrain(xs, ("batch", "seq", "ssm_heads", None))
        if cfg.attn_impl == "pallas":
            from repro.kernels.ssd import ops as ssd_ops
            if ssd_ops.supported(T, s.chunk, Pd, N):
                y = ssd_ops.ssd(xs, dt, p["a_log"], b, c, chunk=s.chunk)
            else:
                y = ssd_reference(xs, dt, p["a_log"], b, c, chunk=s.chunk)
        else:
            pad = (-T) % s.chunk
            if pad:
                xs_p = jnp.pad(xs, ((0, 0), (0, pad), (0, 0), (0, 0)))
                dtp = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
                bp = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
                cp = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))
                y = ssd_reference(xs_p, dtp, p["a_log"], bp, cp,
                                  chunk=s.chunk)[:, :T]
            else:
                y = ssd_reference(xs, dt, p["a_log"], b, c, chunk=s.chunk)
        new_cache = None
    elif T == 1:
        # single-token decode: O(1) state update (the SSM selling point).
        # The state is (B,H,P,N), d_state minor, the order the chip stores
        # the stack in (see mamba2_cache_spec): the fused update reads each
        # layer's slice from the stack and writes the new one into the
        # output stack in place, with no padded copy of the slice.
        xp = jnp.concatenate([cache["conv"], xbc], axis=1)
        conv_out = sum(xp[:, i] * p["conv_w"][i]
                       for i in range(s.d_conv)) + p["conv_b"]
        xbc1 = jax.nn.silu(conv_out)[:, None]
        xs = xbc1[..., :d_in].reshape(B, nh, Pd)
        b = xbc1[..., d_in:d_in + G * N].reshape(B, G, N)
        c = xbc1[..., d_in + G * N:].reshape(B, G, N)
        rep = nh // G
        bh = jnp.repeat(b, rep, axis=1)                  # (B,H,N)
        ch = jnp.repeat(c, rep, axis=1)
        A = -jnp.exp(p["a_log"].astype(jnp.float32))
        dt1 = dt[:, 0]                                   # (B,H)
        da = jnp.exp(dt1 * A)[:, :, None, None]
        dtx = dt1[:, :, None] * xs.astype(jnp.float32)   # (B,H,P)
        state = cache["state"] * da + dtx[..., None] * bh[:, :, None, :]
        y = jnp.sum(ch.astype(jnp.float32)[:, :, None, :] * state,
                    axis=-1)                             # (B,H,P)
        y = y[:, None]                                   # (B,1,H,P)
        xs = xs[:, None]
        new_cache = {"conv": xp[:, 1:], "state": state}
    else:
        # prefill: full-sequence compute, carrying conv/ssm state out
        xbc_raw = xbc
        xbc, _ = _conv1d(xbc, p["conv_w"], p["conv_b"], cache["conv"])
        xs = xbc[..., :d_in].reshape(B, T, nh, Pd)
        b = xbc[..., d_in:d_in + G * N].reshape(B, T, G, N)
        c = xbc[..., d_in + G * N:].reshape(B, T, G, N)
        pad = (-T) % s.chunk
        if pad:
            xs_p = jnp.pad(xs, ((0, 0), (0, pad), (0, 0), (0, 0)))
            dt_p = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
            b_p = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
            c_p = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))
        else:
            xs_p, dt_p, b_p, c_p = xs, dt, b, c
        # the cache holds the state (B,H,P,N); ssd_reference's is (B,H,N,P)
        y, final_state = ssd_reference(
            xs_p, dt_p, p["a_log"], b_p, c_p, chunk=s.chunk,
            init_state=jnp.swapaxes(cache["state"], -1, -2),
            return_final_state=True)
        y = y[:, :T]
        conv_tail = jnp.concatenate([cache["conv"], xbc_raw],
                                    axis=1)[:, -(s.d_conv - 1):]
        new_cache = {"conv": conv_tail,
                     "state": jnp.swapaxes(final_state, -1, -2)}

    y = y + p["d_skip"].astype(jnp.float32)[:, None] * xs.astype(jnp.float32)
    y = y.reshape(B, T, d_in).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype),
                 p["norm"])
    return y @ p["out_proj"], new_cache


def mamba2_cache_spec(cfg: ModelCfg, batch: int) -> Dict[str, P]:
    """Conv tail and float32 SSM state per slot.  The state is
    ``(batch, heads, head_dim, d_state)``, d_state minor: on a TPU a 64-wide
    head_dim as the minor axis pads to 128 lanes, and decode would relay
    each layer's state slice out to that padded form and back."""
    s, d_in, nh, d_xbc = _dims(cfg)
    return {
        "conv": P((batch, s.d_conv - 1, d_xbc), ("batch", "dconv", "rec"),
                  "zeros"),
        "state": P((batch, nh, s.head_dim, s.d_state),
                   ("batch", "ssm_heads", None, "state"), "zeros",
                   dtype=jnp.float32),
    }
