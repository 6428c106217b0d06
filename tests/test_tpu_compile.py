"""The Pallas kernels, and the serving decode step's cache form, compile
for a TPU v5e chip at real widths.

Interpret mode (tests/test_kernels.py) checks the numbers but not what
Mosaic accepts: block tiling, VMEM budget, lowerable ops.  These tests
compile each kernel for a *described* v5e chip (the TPU compiler is
installed; no chip is attached) and assert a Mosaic kernel is in the HLO.

The topology is described only inside module-scoped fixtures: describing
it loads libtpu, which one process at a time may hold, so nothing here
runs at import or collection time.
"""
import os
import re

import pytest

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import kernel as fa
from repro.kernels.rglru import kernel as rg
from repro.kernels.ssd import kernel as ssd
from repro.configs import ARCHS
from repro.models import build_model
from repro.serve.engine import serving_cfg
from repro.train import make_serve_step


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


def _compiled_serve_step(arch, slots, topo, one_chip):
    """``jit(make_serve_step(model))`` for ``arch`` at its widths, 4 layers,
    ``slots`` x 1024, compiled for one described v5e chip."""
    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    model = build_model(serving_cfg(
        ARCHS[arch].cfg.replace(n_layers=4), 1024))
    params = sds(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    caches = sds(jax.eval_shape(lambda: model.init_cache(slots, 1024)))
    tok = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)
    with jax.default_device(topo.devices[0]):
        return jax.jit(make_serve_step(model)).lower(
            params, caches, tok, tok).compile()


def test_decode_step_keeps_the_stored_cache_layout(topo, one_chip,
                                                   no_persistent_cache):
    """StableLM-2 widths, 4 layers, 16 slots x 1024: the chip stores the
    stacked K/V position-minor (64-wide heads, unpadded).  The layer loop
    carries them in that layout, so the only whole-stack copies are the
    two at entry, and no layer's K/V slice is copied (relayout) at all."""
    text = _compiled_serve_step("stablelm-1.6b", 16, topo,
                                one_chip).as_text()
    copies = re.findall(r"= bf16\[([\d,]+)\]\S* copy\(", text)
    assert copies.count("4,16,1024,32,64") == 2, copies
    assert not {"1,16,1024,32,64", "16,1024,32,64"} & set(copies), copies


def test_ssd_decode_updates_the_state_slice_in_place(topo, one_chip,
                                                     no_persistent_cache):
    """Mamba2-370m widths, 4 layers, 32 slots x 1024: the float32 state is
    held d_state-minor, the order the chip stores the stack in (128 wide,
    unpadded).  Each layer's update reads its slice from the stack and
    writes the new slice into the output stack, so no slice is copied
    (relaid out to a padded head_dim-minor form and back), and the
    temporaries hold no slice."""
    compiled = _compiled_serve_step("mamba2-370m", 32, topo, one_chip)
    copies = re.findall(r"= f32\[([\d,]+)\]\S* copy\(",
                        compiled.as_text())
    assert not [c for c in copies if c.startswith(("1,32,32,", "32,32,"))], \
        copies
    assert compiled.memory_analysis().temp_size_in_bytes < 8e6
