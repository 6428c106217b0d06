"""FLOPs and HBM bytes of one LongCat-Flash double layer
(``layers/longcat.py``: two MLA sublayers, two MLPs, the router, the held
experts and the identity experts).

``prefill(m, n)``: one prompt of ``n`` tokens.  Attention is causal and
the least work keeps no absorbed form: each token's keys and values are
expanded once (``kv_b``), and query ``i`` meets ``i + 1`` keys at
``2 H (nope + rope)`` FLOPs for the score and ``2 H v`` for the value.
Bytes: the weights outside the experts once, the held experts that the
prompt's rows reach, and the ``n`` new latents and rope keys written.

``decode(m, ctx)``: one token for each live slot, where ``ctx`` lists
how many positions each attends to.  With only the latent cached, a
query meets a position at ``2 H (kv_lora + rope)`` FLOPs for the score
and ``2 H kv_lora`` for the value (the absorbed form).  Bytes: the
weights outside the experts once, the held experts the step's rows
reach, each live slot's ``ctx - 1`` cached latents and rope keys read
and its new ones written, in both sublayers.

The held experts' bytes and FLOPs count what routing can need: under
uniform routing of ``rows`` tokens, each choosing ``top_k`` of the
``n_routed + n_zero`` experts, a held expert is hit with probability
``1 - (1 - top_k / (n_routed + n_zero)) ** rows``, so
``n_held * (1 - (1 - top_k / E) ** rows)`` of them are read; the rows
routed to held experts are ``rows * top_k * n_held / E``.  A program
that skips idle experts cannot then pass its roofline.

Each returns ``(flops, bytes)``.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

from .common import W_BYTES


def _dims(m):
    a, e = m["mla_dims"], m["experts"]
    return (m["d_model"], m["n_heads"], a["q_lora"], a["kv_lora"],
            a["nope_dim"], a["rope_dim"], a["v_dim"], m["d_ff"], e)


def _dense(m: Dict[str, Any]) -> Tuple[int, int]:
    """Matmul weights outside the experts (the router included) and all
    parameters outside the experts."""
    d, H, ql, kl, nope, rope, vd, f, e = _dims(m)
    mla = d * ql + ql * H * (nope + rope) + d * (kl + rope) \
        + kl * H * (nope + vd) + H * vd * d
    E = e["n_routed"] + e["n_zero"]
    mat = 2 * mla + 2 * 3 * d * f + d * E
    norms = 2 * (2 * d + ql + kl)
    return mat, mat + norms + E


def _experts(m: Dict[str, Any], rows: int) -> Tuple[float, float]:
    """FLOPs and bytes of the held experts for ``rows`` routed tokens."""
    d, e = m["d_model"], m["experts"]
    E = e["n_routed"] + e["n_zero"]
    per = 3 * d * e["d_expert"]
    pairs = rows * e["top_k"] * e["n_held"] / E
    hit = e["n_held"] * (1.0 - (1.0 - e["top_k"] / E) ** rows)
    zero_pairs = rows * e["top_k"] * e["n_zero"] / E
    return 2 * pairs * per + 2 * zero_pairs * d, hit * per * W_BYTES


def _latent_bytes(m: Dict[str, Any]) -> int:
    """One position's cache entries in both sublayers."""
    a = m["mla_dims"]
    return 2 * (a["kv_lora"] + a["rope_dim"]) * W_BYTES


def prefill(m: Dict[str, Any], n: int) -> Tuple[float, float]:
    d, H, ql, kl, nope, rope, vd, f, e = _dims(m)
    mat, params = _dense(m)
    pairs = n * (n + 1) // 2
    attn = 2 * pairs * (2 * H * (nope + rope) + 2 * H * vd)
    ef, eb = _experts(m, n)
    flops = 2 * n * mat + attn + ef
    return flops, params * W_BYTES + eb + n * _latent_bytes(m)


def decode(m: Dict[str, Any], ctx: Sequence[int]) -> Tuple[float, float]:
    d, H, ql, kl, nope, rope, vd, f, e = _dims(m)
    mat, params = _dense(m)
    attn = 2 * sum(ctx) * (2 * H * (kl + rope) + 2 * H * kl)
    ef, eb = _experts(m, len(ctx))
    flops = 2 * len(ctx) * mat + attn + ef
    return flops, params * W_BYTES + eb + sum(ctx) * _latent_bytes(m)
