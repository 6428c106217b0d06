"""Pallas TPU flash attention (forward): online softmax, causal + sliding
window + logit softcap + GQA.

Tiling: grid = (batch, q_head, q_blocks, kv_blocks); the innermost kv axis
is sequential ('arbitrary') and accumulates into VMEM scratch (acc, m, l).
Block shapes are (block_q x head_dim) / (block_k x head_dim) — multiples of
128 on the sequence axes so the MXU sees aligned tiles.  GQA is handled in
the k/v index_map (q head h reads kv head h // group) — no materialised
broadcast.  Causal/window-skipped tiles are predicated out with pl.when so
the TPU pipeline never streams them.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
               scale: float, causal: bool, window: Optional[int],
               softcap: Optional[float], block_q: int, block_k: int,
               num_kv_blocks: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * block_q
    k_start = ik * block_k

    # static-shape predication: is any (q, k) pair in this tile live?
    live = jnp.asarray(True)
    if causal:
        live &= k_start <= q_start + block_q - 1
    if window is not None:
        live &= (q_start - (k_start + block_k - 1)) < window

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)           # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)           # (bk, D)
        v = v_ref[0, 0]                                # (bk, Dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                            # (bq,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, scale: float, causal: bool = True,
                        window: Optional[int] = None,
                        softcap: Optional[float] = None,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = False):
    """q: (B, H, S, D); k/v: (B, KH, S, D[v]).  Returns (B, H, S, Dv)."""
    B, H, S, D = q.shape
    KH = k.shape[1]
    Dv = v.shape[-1]
    group = H // KH
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0
    nq, nk = S // block_q, S // block_k

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k, num_kv_blocks=nk)

    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, Dv),
                         lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dv),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, Dv), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
