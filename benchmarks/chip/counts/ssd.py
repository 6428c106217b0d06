"""FLOPs and HBM bytes of one Mamba-2 layer (``layers/ssd.py``).

Per token: the input and output projections, the depthwise convolution,
and the state recurrence by its definition (decay, outer-product update
and read-out: 5 flops per state element), which is the least any
algorithm must do; the chunked form the program uses does more.

``prefill(m, n)``: bytes are the weights once and the final SSM state
(float32) and convolution tail written.  ``decode(m, ctx)``: one token
for each live slot (``ctx`` is only counted); bytes are the weights once
and each live slot's state and convolution tail read and written.

Each returns ``(flops, bytes)``.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

from .common import W_BYTES, norm_elems

STATE_BYTES = 4   # the SSM state is float32


def _sizes(m: Dict[str, Any]):
    s = m["ssm"]
    d = m["d_model"]
    d_in = s["expand"] * d
    nh = d_in // s["head_dim"]
    gn = s["n_groups"] * s["d_state"]
    d_xbc = d_in + 2 * gn
    state = nh * s["d_state"] * s["head_dim"]
    mat = d * (2 * d_in + 2 * gn + nh) + d_in * d
    params = mat + s["d_conv"] * d_xbc + d_xbc + 3 * nh + d_in + norm_elems(m)
    per_tok = 2 * mat + 2 * s["d_conv"] * d_xbc + 5 * state
    conv_tail = (s["d_conv"] - 1) * d_xbc * W_BYTES
    return params, per_tok, state * STATE_BYTES + conv_tail


def prefill(m: Dict[str, Any], n: int) -> Tuple[int, int]:
    params, per_tok, carried = _sizes(m)
    return n * per_tok, params * W_BYTES + carried


def decode(m: Dict[str, Any], ctx: Sequence[int]) -> Tuple[int, int]:
    params, per_tok, carried = _sizes(m)
    return len(ctx) * per_tok, params * W_BYTES + 2 * len(ctx) * carried
