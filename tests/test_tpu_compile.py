"""The Pallas kernels compile for a TPU v5e chip at real widths.

Interpret mode (tests/test_kernels.py) checks the numbers but not what
Mosaic accepts: block tiling, VMEM budget, lowerable ops.  These tests
compile each kernel for a *described* v5e chip (the TPU compiler is
installed; no chip is attached) and assert a Mosaic kernel is in the HLO.

The topology is described only inside module-scoped fixtures: describing
it loads libtpu, which one process at a time may hold, so nothing here
runs at import or collection time.
"""
import os

import pytest

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import kernel as fa
from repro.kernels.rglru import kernel as rg
from repro.kernels.ssd import kernel as ssd


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _flash(sds):
    # stablelm-1.6b / gemma-class prefill: 32 heads, head_dim 64, 2k tokens
    q = sds((1, 32, 2048, 64), jnp.bfloat16)
    return (lambda q, k, v: fa.flash_attention_fwd(q, k, v, scale=0.125),
            (q, q, q))


def _ssd(sds):
    # mamba2-370m: d_inner 2048 / head_dim 64 = 32 heads, d_state 128, one
    # group, chunk 128
    B, H, T, P, N = 1, 32, 2048, 64, 128
    return (lambda x, dt, cum, b, c: ssd.ssd_fwd(x, dt, cum, b, c,
                                                 chunk=128),
            (sds((B, H, T, P), jnp.bfloat16),
             sds((B, H, 1, T), jnp.float32), sds((B, H, 1, T), jnp.float32),
             sds((B, 1, T, N), jnp.bfloat16),
             sds((B, 1, T, N), jnp.bfloat16)))


def _rglru(sds):
    # recurrentgemma-9b: lru_width 4096
    x = sds((1, 2048, 4096), jnp.float32)
    return rg.rglru_fwd, (x, x, x, sds((4096,), jnp.float32))


KERNELS = {"flash_attention": _flash, "ssd": _ssd, "rglru": _rglru}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = KERNELS[name](sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
