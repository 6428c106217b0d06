"""Shared set-up of the chip benchmark's CPU tests: the package on the
path, and small configurations of both layer kinds."""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "benchmarks"), os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_ATTN = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
             "head_dim": 16, "d_ff": 96, "vocab": 256, "pattern": ["attn"],
             "rope_theta": 10000.0, "rope_fraction": 0.25,
             "norm": "layernorm", "norm_eps": 1e-5, "mlp": "gated_silu",
             "tie_embeddings": False, "bias": False, "dtype": "float32"}
TINY_SSD = {"n_layers": 2, "d_model": 64, "vocab": 256, "pattern": ["ssd"],
            "norm": "rmsnorm", "norm_eps": 1e-6, "mlp": "none",
            "tie_embeddings": True, "dtype": "float32",
            "ssm": {"d_state": 16, "d_conv": 4, "expand": 2, "head_dim": 16,
                    "n_groups": 1, "chunk": 32}}
ARCH = {"attn": "stablelm-1.6b", "ssd": "mamba2-370m"}
MODELS = {"attn": TINY_ATTN, "ssd": TINY_SSD}


def tiny_config(kind: str, dtype: str = "float32") -> dict:
    return {"arch": ARCH[kind], "model": dict(MODELS[kind], dtype=dtype),
            "serving": {"slots": 4, "max_len": 64, "queue_bound": 8,
                        "reference_rows": 4}}


def tiny_cell(kind: str, dtype: str = "float32", limit: float = 1e-3,
              per_layer=(), end_to_end=()):
    from chip import harness
    mix = {"arrival": "poisson", "rate_rps": 20.0, "clients": 2,
           "prompt_lens": [8, 16], "prompt_weights": [1, 1],
           "max_new": [4, 12], "check_requests": 4}
    return harness.Cell(f"tiny.{kind}", 1, tiny_config(kind, dtype), mix,
                        list(end_to_end), list(per_layer),
                        {"max_logit_gap": {"limit": limit}})
