"""Pallas TPU kernel for the RG-LRU diagonal recurrence.

h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t),  a_t = exp(-c*sp(Λ)*r_t)

Grid = (batch, width_blocks, time_blocks); time is sequential and carries
h (one (1, block_w) row) in VMEM scratch.  Within a time block the
recurrence is computed in log space, vectorised over the block: the prefix
sums of log a come from a matmul with a lower-triangular ones matrix
(Mosaic lowers no ``cumsum``), and h is a masked weighted sum over the
block's (block_t, block_t, block_w) decay tensor.  That tensor sets
``block_t``: 32 x 32 x 256 f32 is 1 MiB of VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_C = 8.0


def _rglru_kernel(x_ref, r_ref, i_ref, lam_ref, y_ref, h_ref, *,
                  block_t: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)      # (bt, bw)
    r = r_ref[0].astype(jnp.float32)
    i = i_ref[0].astype(jnp.float32)
    lam = lam_ref[...].astype(jnp.float32)  # (1, bw)

    log_a = -_C * jax.nn.softplus(lam) * r               # (bt, bw) <= 0
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * (i * x)

    # blocked scan in log space: cum_t = sum_{j<=t} log a_j, A_t = e^{cum_t}
    row = jax.lax.broadcasted_iota(jnp.int32, (block_t, block_t), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (block_t, block_t), 1)
    mask = col <= row
    cum = jnp.dot(mask.astype(jnp.float32), log_a,
                  precision=jax.lax.Precision.HIGHEST,
                  preferred_element_type=jnp.float32)    # (bt, bw)
    # h_t = A_t*h0 + sum_{j<=t} exp(cum_t - cum_j) b_j  (the masked form:
    # b_j / A_j alone could overflow)
    shape3 = (block_t, block_t, x.shape[-1])
    mask3 = (jax.lax.broadcasted_iota(jnp.int32, shape3, 1)
             <= jax.lax.broadcasted_iota(jnp.int32, shape3, 0))
    seg = cum[:, None, :] - cum[None, :, :]              # (bt, bt, bw)
    w = jnp.exp(jnp.where(mask3, seg, -1e30))
    h_series = jnp.sum(w * b[None, :, :], axis=1) + jnp.exp(cum) * h_ref[...]

    h_ref[...] = h_series[block_t - 1:]
    y_ref[0] = h_series.astype(y_ref.dtype)


def rglru_fwd(x, r, i, lam, *, block_t: int = 32, block_w: int = 256,
              interpret: bool = False):
    """x, r, i: (B, T, W) fp32; lam: (W,).  Returns h: (B, T, W)."""
    B, T, W = x.shape
    block_t = min(block_t, T)
    block_w = min(block_w, W)
    assert T % block_t == 0 and W % block_w == 0
    nt, nw = T // block_t, W // block_w

    kernel = functools.partial(_rglru_kernel, block_t=block_t)
    seq = pl.BlockSpec((1, block_t, block_w), lambda b, iw, it: (b, it, iw))
    return pl.pallas_call(
        kernel,
        grid=(B, nw, nt),
        in_specs=[seq, seq, seq,
                  pl.BlockSpec((1, block_w), lambda b, iw, it: (0, iw))],
        out_specs=seq,
        out_shape=jax.ShapeDtypeStruct((B, T, W), x.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, r, i, lam.reshape(1, W))
