"""Step builders: train_step (remat + microbatch accumulation), prefill_step,
serve_step.  These are the functions the launcher jits/lowers; the dry-run
lowers exactly these with abstract inputs."""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.optim import Optimizer
from repro.sharding.ctx import constrain


def make_train_step(model, opt: Optimizer, *, microbatches: int = 1,
                    acc_dtype=jnp.float32) -> Callable:
    """Returns train_step(params, opt_state, batch, step) ->
    (params, opt_state, metrics).

    microbatches > 1: gradient accumulation via lax.scan over batch slices
    (peak activation memory divides by the accumulation factor).  Keep the
    per-microbatch batch >= the data-parallel mesh extent or the whole
    model replicates across 'data' (see EXPERIMENTS.md §Perf, deepseek).

    acc_dtype: gradient-accumulator dtype.  bfloat16 halves both the
    accumulator HBM traffic and the per-microbatch gradient reduction
    bytes, at the cost of ~3 mantissa bits across the accumulation sum."""

    def loss_fn(p, mb):
        loss, metrics = model.loss(p, mb)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(params, opt_state, batch, step):
        if microbatches == 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            def split(x):
                b = x.shape[0]
                assert b % microbatches == 0, (b, microbatches)
                return x.reshape((microbatches, b // microbatches)
                                 + x.shape[1:])
            mbs = jax.tree.map(split, batch)

            def acc(carry, mb):
                g_acc, l_acc = carry
                (loss, _m), g = grad_fn(params, mb)
                g_acc = jax.tree.map(
                    lambda a, b_: a + b_.astype(acc_dtype), g_acc, g)
                return (g_acc, l_acc + loss), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, acc_dtype), params)
            (g_sum, l_sum), _ = jax.lax.scan(acc, (g0, 0.0), mbs)
            grads = jax.tree.map(lambda g: (g / microbatches), g_sum)
            loss = l_sum / microbatches
            metrics = {"ce": loss}
        new_params, new_state, opt_metrics = opt.update(
            grads, opt_state, params, step)
        out = {"loss": loss, **{k: v for k, v in metrics.items()},
               **opt_metrics}
        return new_params, new_state, out

    return train_step


def make_prefill_step(model, *, max_len: Optional[int] = None) -> Callable:
    """prefill_step(params, tokens [, frontend]) -> (last_logits, cache).

    ``max_len`` overrides the cache length (default: exactly the prompt).
    The serving engine passes its decode-cache length here so a prefilled
    single-request cache has the same per-layer shapes as one batch slot
    of the decode cache and can be spliced in directly; decoding then
    continues past the prompt without reallocating."""

    def prefill_step(params, batch):
        B, S = batch["tokens"].shape
        extra = {}
        if "frame_embeds" in batch:
            extra["frame_embeds"] = batch["frame_embeds"]
        if "patch_embeds" in batch:
            extra["patch_embeds"] = batch["patch_embeds"]
        total = S + (batch.get("patch_embeds").shape[1]
                     if "patch_embeds" in batch else 0)
        caches = model.init_cache(B, max_len or total)
        return model.prefill(params, batch["tokens"], caches, **extra)

    return prefill_step


def make_serve_step(model) -> Callable:
    """serve_step(params, caches, tokens, pos) -> (next_tokens, caches,
    rows).

    One decode step for the whole batch: greedy argmax next token.
    ``rows``: each row's expert choices that fell on held and on identity
    experts, int32 ``(batch, 2)``, or None where no layer routes to held
    experts (``TransformerLM.decode_counted``)."""

    def serve_step(params, caches, tokens, pos):
        logits, caches, rows = model.decode_counted(params, caches, tokens,
                                                    pos)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        return nxt, caches, rows

    return serve_step
