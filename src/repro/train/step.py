"""Step builders: prefill_step and serve_step, the two programs the
serving engine jits."""
from __future__ import annotations

from typing import Callable, Optional

import jax.numpy as jnp


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
