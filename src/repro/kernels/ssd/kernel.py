"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

Grid = (batch, heads, chunks); the chunk axis is sequential ('arbitrary')
and carries the (d_state x head_dim) SSM state in VMEM scratch.  Within a
chunk everything is dense (Q x Q attention-like quadratic + two (Q x N) x
(N x P) matmuls), so the MXU does the heavy lifting; chunk=128 aligns the
tiles.  This mirrors ``ref.ssd_reference`` exactly (same masking-in-log-
space trick to avoid masked-inf gradients).

Layout is heads-before-time so every block's last two dims are a
``(chunk, P)`` / ``(chunk, N)`` tile; ``dt`` and the per-chunk prefix sums
``cum`` of the log decay ``dt * A`` arrive as one ``(1, chunk)`` row per
block.  Mosaic lowers no ``cumsum``, so the caller computes ``cum`` in XLA
(the same ``cumsum`` the reference takes, so the decays agree bit for
bit); the kernel turns rows into columns with a masked lane reduction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ssd_kernel(x_ref, dt_ref, cum_ref, b_ref, c_ref, y_ref, state_ref, *,
                chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)              # (Q, P)
    dt_row = dt_ref[0, 0]                            # (1, Q)
    cum_row = cum_ref[0, 0]                          # (1, Q)
    b = b_ref[0, 0].astype(jnp.float32)              # (Q, N)
    c = c_ref[0, 0].astype(jnp.float32)              # (Q, N)

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    mask = col <= row
    diag = row == col

    def as_col(v):                                   # (1, Q) -> (Q, 1)
        return jnp.sum(jnp.where(diag, v, 0.0), axis=1, keepdims=True)

    dt_col = as_col(dt_row)
    cum_col = as_col(cum_row)

    # intra-chunk quadratic: y_i += sum_{j<=i} e^{cum_i-cum_j} dt_j (c_i.b_j) x_j
    decay = jnp.exp(jnp.where(mask, cum_col - cum_row, NEG_INF))  # (Q, Q)
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    att = cb * decay * dt_row
    y = jnp.dot(att, x, preferred_element_type=jnp.float32)       # (Q, P)

    # inter-chunk: y_i += e^{cum_i} c_i . S_prev
    s_prev = state_ref[...]                          # (N, P)
    y += jnp.exp(cum_col) * jnp.dot(c, s_prev,
                                    preferred_element_type=jnp.float32)

    # state update: S = e^{sum da} S_prev + sum_j e^{cum_last-cum_j} dt_j b_j x_j^T
    total = cum_col[chunk - 1:]                      # (1, 1) chunk's sum
    w_col = jnp.exp(total - cum_col) * dt_col        # (Q, 1)
    local = jnp.dot((b * w_col).T, x,
                    preferred_element_type=jnp.float32)  # (N, P)
    state_ref[...] = jnp.exp(total) * s_prev + local

    y_ref[0, 0] = y.astype(y_ref.dtype)


def ssd_fwd(x, dt, cum, b, c, *, chunk: int = 128,
            interpret: bool = False):
    """x: (B, H, T, P); dt, cum: (B, H, 1, T) f32, ``cum`` the prefix sums
    of ``dt * A`` restarting at every chunk; b, c: (B, G, T, N) with G
    dividing H (head h reads group h // (H // G)).  T % chunk == 0.
    Returns y: (B, H, T, P)."""
    B, H, T, Pd = x.shape
    G, N = b.shape[1], b.shape[-1]
    assert T % chunk == 0 and H % G == 0
    nc, rep = T // chunk, H // G

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, Pd),
                         lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, 1, chunk),
                         lambda ib, ih, ic: (ib, ih, 0, ic)),
            pl.BlockSpec((1, 1, 1, chunk),
                         lambda ib, ih, ic: (ib, ih, 0, ic)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda ib, ih, ic: (ib, ih // rep, ic, 0)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda ib, ih, ic: (ib, ih // rep, ic, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, Pd),
                               lambda ib, ih, ic: (ib, ih, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, Pd), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, Pd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, cum, b, c)
