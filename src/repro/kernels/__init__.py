"""Pallas TPU kernels: compiled by Mosaic on a TPU, run in interpret mode
on the CPU backend (tests).

Each kernel package: kernel.py (pl.pallas_call + BlockSpec VMEM tiling),
ops.py (jit'd public wrapper + custom_vjp), ref.py (pure-jnp oracle).
"""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """Whether a kernel call traced now runs in Pallas interpret mode.

    Asked when the call is traced, never at import (the query starts the
    backend): compiled on a TPU, interpreted on the CPU backend, and
    refused on any other backend rather than silently interpreted."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise NotImplementedError(
        f"the Pallas TPU kernels do not run on the {backend!r} backend; "
        f"use attn_impl='ref'")
