"""FLOPs and HBM bytes that serving needs outside the layers: the
embedding rows gathered, the final norm, and the unembedding of the
positions whose next token is wanted.  Weights are read once per
program call; only the least the algorithm needs is counted, so a
share of the roofline from these counts cannot pass 100%.

Each function returns ``(flops, bytes)``.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

#: bytes of one element of the served weights and activations (bf16)
W_BYTES = 2


def norm_elems(m: Dict[str, Any]) -> int:
    return m["d_model"] * (2 if m["norm"] == "layernorm" else 1)


def outside(m: Dict[str, Any], tokens: int, heads: int) -> Tuple[int, int]:
    """``tokens`` embedded, ``heads`` of them unembedded."""
    d, V = m["d_model"], m["vocab"]
    flops = 2 * heads * d * V + 4 * heads * d
    nbytes = (tokens * d * W_BYTES            # embedding rows
              + norm_elems(m) * W_BYTES       # final norm
              + d * V * W_BYTES)              # unembedding matrix
    return flops, nbytes
