"""A Mamba-2 layer served with its cache (a prefill of T tokens, then k
single-token decode steps) gives, in float32, the outputs and the final
state of ``ssd_reference`` run over the whole T+k sequence at once.

The cache holds the state ``(batch, heads, head_dim, d_state)``, the
transpose of ``ssd_reference``'s; the reference here is built on
``ssd_reference`` and swaps the axes only where it hands the state over.
d_state differs from head_dim, so a state held the wrong way round fails
on its shape, not only on its values.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduce_cfg
from repro.models import mamba2 as m2
from repro.models.common import init_tree, rms_norm

B, K = 2, 5


def _cfg():
    cfg = reduce_cfg(ARCHS["mamba2-370m"].cfg)
    cfg = cfg.replace(ssm=dataclasses.replace(cfg.ssm, d_state=24))
    assert cfg.dtype == "float32" and cfg.ssm.d_state != cfg.ssm.head_dim
    return cfg


def _params(cfg, key):
    p = init_tree(m2.mamba2_specs(cfg), key, jnp.float32)
    k1, k2 = jax.random.split(jax.random.fold_in(key, 1))
    nh = p["a_log"].shape[0]
    # per-head decays and steps that differ, so a head mix-up shows
    p["a_log"] = jax.random.uniform(k1, (nh,), jnp.float32, -1.0, 1.0)
    p["dt_bias"] = jax.random.uniform(k2, (nh,), jnp.float32, -2.0, 0.5)
    return p


def reference_layer(p, x, cfg, conv0, state0):
    """The layer over all of ``x`` at once from ``(conv0, state0)`` (the
    cache's form): ``ssd_reference`` does the scan.  Returns the layer's
    output and the final state in the cache's form."""
    s = cfg.ssm
    Bx, T, _ = x.shape
    d_in = s.expand * cfg.d_model
    nh, G, N = d_in // s.head_dim, s.n_groups, s.d_state
    z, xbc, dt_raw = m2._split_in(cfg, x @ p["in_proj"])
    dt = jax.nn.softplus(dt_raw + p["dt_bias"])
    xbc, _ = m2._conv1d(xbc, p["conv_w"], p["conv_b"], conv0)
    xs = xbc[..., :d_in].reshape(Bx, T, nh, s.head_dim)
    b = xbc[..., d_in:d_in + G * N].reshape(Bx, T, G, N)
    c = xbc[..., d_in + G * N:].reshape(Bx, T, G, N)
    pad = (-T) % s.chunk     # dt 0 on the padding: the state stays put
    widths = lambda a: ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)
    y, state = m2.ssd_reference(
        *(jnp.pad(a, widths(a)) for a in (xs, dt)), p["a_log"],
        *(jnp.pad(a, widths(a)) for a in (b, c)), chunk=s.chunk,
        init_state=jnp.swapaxes(state0, -1, -2), return_final_state=True)
    y = y[:, :T] + p["d_skip"][:, None] * xs
    y = rms_norm(y.reshape(Bx, T, d_in) * jax.nn.silu(z), p["norm"])
    return y @ p["out_proj"], jnp.swapaxes(state, -1, -2)


@pytest.mark.parametrize("start", ["empty", "filled"])
@pytest.mark.parametrize("T", [24, 40])
def test_prefill_then_decode_matches_the_whole_sequence(T, start):
    """``empty``: the zero cache a slot starts from; ``filled``: a random
    conv tail and state, so the prefill's hand-over of its initial state is
    checked too.  T=40 is not a multiple of the chunk (32)."""
    cfg = _cfg()
    key = jax.random.PRNGKey(T)
    p = _params(cfg, key)
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         m2.mamba2_cache_spec(cfg, B),
                         is_leaf=lambda a: isinstance(a, m2.P))
    assert cache["state"].shape == (B, cfg.ssm.expand * cfg.d_model
                                    // cfg.ssm.head_dim, cfg.ssm.head_dim,
                                    cfg.ssm.d_state)
    if start == "filled":
        kc, ks = jax.random.split(jax.random.fold_in(key, 2))
        cache = {"conv": jax.random.normal(kc, cache["conv"].shape),
                 "state": jax.random.normal(ks, cache["state"].shape)}
    x = jax.random.normal(jax.random.fold_in(key, 3), (B, T + K, cfg.d_model))
    ref_y, ref_state = reference_layer(p, x, cfg, cache["conv"],
                                       cache["state"])

    apply = jax.jit(lambda p, x, c: m2.mamba2_apply(p, x, cfg=cfg, cache=c))
    ys = []
    y, c = apply(p, x[:, :T], cache)
    ys.append(y)
    _, pre_state = reference_layer(p, x[:, :T], cfg, cache["conv"],
                                   cache["state"])
    np.testing.assert_allclose(c["state"], pre_state, rtol=1e-5, atol=1e-5)
    for t in range(T, T + K):
        y, c = apply(p, x[:, t:t + 1], c)
        ys.append(y)
    np.testing.assert_allclose(jnp.concatenate(ys, axis=1), ref_y,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(c["state"], ref_state, rtol=1e-5, atol=1e-5)
