"""FLOPs and HBM bytes of one attention layer (``layers/attn.py``: norm,
q/k/v/o projections, causal attention over the cache, gated MLP).

``prefill(m, n)``: one prompt of ``n`` tokens.  Attention is causal, so
query ``i`` meets ``i + 1`` keys.  Bytes: the layer's weights once, and
the ``n`` new keys and values written to the cache.

``decode(m, ctx)``: one token for each live slot, where ``ctx`` lists
how many keys each attends to (its position + 1).  Bytes: the weights
once, each live slot's ``ctx - 1`` cached keys and values read and its
new pair written.

Each returns ``(flops, bytes)``.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

from .common import W_BYTES, norm_elems


def _weights(m: Dict[str, Any]) -> Tuple[int, int]:
    d, H, KH, hd, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["head_dim"], m["d_ff"])
    proj = d * (H + 2 * KH) * hd + H * hd * d
    mlp = 3 * d * f
    return proj + mlp, proj + mlp + 2 * norm_elems(m)


def _per_token_pair(m: Dict[str, Any]) -> int:
    """Flops of one query against one key: q.k and p.v over all heads."""
    return 4 * m["n_heads"] * m["head_dim"]


def _kv_bytes(m: Dict[str, Any]) -> int:
    return 2 * m["n_kv_heads"] * m["head_dim"] * W_BYTES


def prefill(m: Dict[str, Any], n: int) -> Tuple[int, int]:
    mat, params = _weights(m)
    pairs = n * (n + 1) // 2
    flops = 2 * n * mat + pairs * _per_token_pair(m)
    return flops, params * W_BYTES + n * _kv_bytes(m)


def decode(m: Dict[str, Any], ctx: Sequence[int]) -> Tuple[int, int]:
    mat, params = _weights(m)
    flops = 2 * len(ctx) * mat + sum(ctx) * _per_token_pair(m)
    return flops, params * W_BYTES + sum(ctx) * _kv_bytes(m)
