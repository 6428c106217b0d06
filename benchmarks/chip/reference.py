"""Plain float32 reference of the served models, and the gap readings that
decide ``correct``.

The reference imports nothing of the program.  It reads the weights the
benchmark made from the seed (``weights.canonical``), in the canonical
layout of ``layers/<kind>.py``, and runs the whole sequence at once —
no cache, no batching across requests, no kernels — in float32 at the
highest matmul precision.  Each layer kind's block is in
``layers/<kind>.py``; embedding, final norm and unembedding are here.

``mode="fp8"`` is the control: the same reference with every linear
layer's weights rounded to float8 e4m3 per output channel and its
activations per row, the step below the bf16 the configuration states.
"""
from __future__ import annotations

import importlib
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def kind_module(kind: str):
    return importlib.import_module(f"{__package__}.layers.{kind}")


def _fp8(x, axes):
    """Round to float8 e4m3 with one scale per slice over ``axes``."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def make_dot(mode: str) -> Callable:
    """``dot(x, w, nc)``: contract the last ``nc`` axes of ``x`` with the
    first ``nc`` axes of ``w``, in float32 at the highest precision."""
    if mode not in ("f32", "fp8"):
        raise ValueError(mode)

    def dot(x, w, nc):
        x = x.astype(jnp.float32)
        w = w.astype(jnp.float32)
        if mode == "fp8":
            x = _fp8(x, tuple(range(x.ndim - nc, x.ndim)))
            w = _fp8(w, tuple(range(nc)))
        return jnp.tensordot(x, w, axes=nc, precision=HI)
    return dot


def norm(m: Dict[str, Any], x, p: Dict[str, Any]):
    """The configuration's norm over the last axis, in float32."""
    eps = m["norm_eps"]
    x = x.astype(jnp.float32)
    w = p["w"].astype(jnp.float32)
    if m["norm"] == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * w + p["b"].astype(jnp.float32)
    if m["norm"] == "rmsnorm":
        return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w
    raise ValueError(m["norm"])


def forward(m: Dict[str, Any], w: Dict[str, Any], tokens, mode: str = "f32"):
    """Logits ``(rows, T, vocab)`` in float32 for token ids ``(rows, T)``
    that start at position 0."""
    dot = make_dot(mode)
    rows, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (rows, T))
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    kinds = [kind_module(k) for k in m["pattern"]]

    def period(x, ws):
        for mod, wl in zip(kinds, ws):
            x = mod.block(m, wl, x, positions, dot)
        return x, None

    x, _ = jax.lax.scan(period, x, w["layers"])
    x = norm(m, x, w["final_norm"])
    head = w["embed"].T if m["tie_embeddings"] else w["head"]
    return dot(x, head, 1)


def make_gap_fns(m: Dict[str, Any]):
    """Two jitted readings over ``tokens`` and ``targets`` ``(rows, T)``:
    at each position whose target is >= 0, how far the reference's
    float32 logit of the target lies below its best logit (``served``),
    and of the token the fp8 control puts first (``control``)."""

    def served(w, tokens, targets):
        lg = forward(m, w, tokens, "f32")
        tl = jnp.take_along_axis(lg, jnp.maximum(targets, 0)[..., None],
                                 axis=-1)[..., 0]
        return jnp.where(targets >= 0, jnp.max(lg, axis=-1) - tl, 0.0)

    def control(w, tokens, targets):
        lg = forward(m, w, tokens, "f32")
        top = jnp.argmax(forward(m, w, tokens, "fp8"), axis=-1)
        tl = jnp.take_along_axis(lg, top[..., None], axis=-1)[..., 0]
        return jnp.where(targets >= 0, jnp.max(lg, axis=-1) - tl, 0.0)

    return jax.jit(served), jax.jit(control)
