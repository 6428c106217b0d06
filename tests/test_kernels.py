"""Per-kernel validation: shape/dtype sweeps, interpret=True vs pure-jnp
oracle (assert_allclose), plus gradient checks through the custom_vjp."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa
from repro.kernels.rglru import ops as rg
from repro.kernels.rglru.ref import rglru_ref
from repro.kernels.ssd import ops as ssd_ops
from repro.kernels.ssd.ref import ssd_reference


def rand(key, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32) \
        .astype(dtype)


# ----------------------------------------------------------- flash attention
FA_CASES = [
    # (S, H, KH, D, window, softcap, dtype)
    (256, 4, 4, 64, None, None, jnp.float32),
    (256, 4, 1, 64, None, None, jnp.float32),     # MQA
    (512, 8, 2, 64, None, None, jnp.bfloat16),    # GQA bf16
    (512, 4, 4, 128, 128, None, jnp.float32),     # sliding window
    (256, 4, 2, 128, None, 50.0, jnp.float32),    # softcap (gemma2)
    (384, 6, 6, 64, None, None, jnp.float32),     # non-128 block tail (S=384)
    (512, 2, 1, 256, 256, None, jnp.bfloat16),    # gemma3-like hd 256
]


@pytest.mark.parametrize("S,H,KH,D,window,softcap,dtype", FA_CASES)
def test_flash_attention_matches_ref(S, H, KH, D, window, softcap, dtype):
    B = 2
    q = rand(0, (B, S, H, D), dtype)
    k = rand(1, (B, S, KH, D), dtype)
    v = rand(2, (B, S, KH, D), dtype)
    out = fa.flash_attention(q, k, v, scale=D ** -0.5, window=window,
                             softcap=softcap)
    ref = fa.attention_ref(q, k, v, scale=D ** -0.5, window=window,
                           softcap=softcap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_grad_matches_ref():
    B, S, H, KH, D = 1, 256, 2, 1, 64
    q = rand(0, (B, S, H, D), jnp.float32)
    k = rand(1, (B, S, KH, D), jnp.float32)
    v = rand(2, (B, S, KH, D), jnp.float32)

    def f_k(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, scale=D ** -0.5) ** 2)

    def f_r(q, k, v):
        return jnp.sum(fa.attention_ref(q, k, v, scale=D ** -0.5) ** 2)

    gk = jax.grad(f_k, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------- SSD
SSD_CASES = [
    # (T, H, G, N, P, chunk, dtype)
    (256, 4, 1, 32, 32, 64, jnp.float32),
    (256, 8, 2, 64, 64, 128, jnp.float32),
    (128, 2, 2, 16, 64, 32, jnp.float32),
    (256, 4, 1, 128, 64, 128, jnp.bfloat16),      # mamba2-370m shapes
]


@pytest.mark.parametrize("T,H,G,N,P,chunk,dtype", SSD_CASES)
def test_ssd_matches_ref(T, H, G, N, P, chunk, dtype):
    B = 2
    x = rand(0, (B, T, H, P), dtype)
    dt = jax.nn.softplus(rand(1, (B, T, H), jnp.float32))
    a_log = rand(2, (H,), jnp.float32) * 0.5
    b = rand(3, (B, T, G, N), dtype)
    c = rand(4, (B, T, G, N), dtype)
    out = ssd_ops.ssd(x, dt, a_log, b, c, chunk=chunk)
    ref = ssd_reference(x.astype(jnp.float32), dt, a_log,
                        b.astype(jnp.float32), c.astype(jnp.float32),
                        chunk=chunk)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32).reshape(out.shape),
                               rtol=tol, atol=tol)


def test_ssd_chunk_invariance():
    """The chunked algorithm must be exact: chunk size cannot change y."""
    B, T, H, G, N, P = 1, 128, 2, 1, 16, 16
    x = rand(0, (B, T, H, P), jnp.float32)
    dt = jax.nn.softplus(rand(1, (B, T, H), jnp.float32))
    a_log = rand(2, (H,), jnp.float32) * 0.5
    b = rand(3, (B, T, G, N), jnp.float32)
    c = rand(4, (B, T, G, N), jnp.float32)
    y32 = ssd_reference(x, dt, a_log, b, c, chunk=32)
    y128 = ssd_reference(x, dt, a_log, b, c, chunk=128)
    np.testing.assert_allclose(np.asarray(y32), np.asarray(y128),
                               rtol=1e-4, atol=1e-4)


def test_ssd_grad_flows():
    B, T, H, G, N, P = 1, 64, 2, 1, 16, 16
    x = rand(0, (B, T, H, P), jnp.float32)
    dt = jax.nn.softplus(rand(1, (B, T, H), jnp.float32))
    a_log = rand(2, (H,), jnp.float32) * 0.5
    b = rand(3, (B, T, G, N), jnp.float32)
    c = rand(4, (B, T, G, N), jnp.float32)

    g = jax.grad(lambda x: jnp.sum(
        ssd_ops.ssd(x, dt, a_log, b, c, chunk=32) ** 2))(x)
    gr = jax.grad(lambda x: jnp.sum(
        ssd_reference(x, dt, a_log, b, c, chunk=32) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               rtol=1e-3, atol=1e-3)


# ------------------------------------------------------------------- RG-LRU
RG_CASES = [
    (128, 128), (256, 256), (128, 512), (512, 128),
]


@pytest.mark.parametrize("T,W", RG_CASES)
def test_rglru_matches_ref(T, W):
    B = 2
    x = rand(0, (B, T, W), jnp.float32)
    r = jax.nn.sigmoid(rand(1, (B, T, W), jnp.float32))
    i = jax.nn.sigmoid(rand(2, (B, T, W), jnp.float32))
    lam = jnp.abs(rand(3, (W,), jnp.float32)) + 0.2
    out = rg.rglru(x, r, i, lam)
    ref = rglru_ref(x, r, i, lam)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_rglru_grad_flows():
    B, T, W = 1, 128, 128
    x = rand(0, (B, T, W), jnp.float32)
    r = jax.nn.sigmoid(rand(1, (B, T, W), jnp.float32))
    i = jax.nn.sigmoid(rand(2, (B, T, W), jnp.float32))
    lam = jnp.abs(rand(3, (W,), jnp.float32)) + 0.2
    g = jax.grad(lambda x: jnp.sum(rg.rglru(x, r, i, lam) ** 2))(x)
    gr = jax.grad(lambda x: jnp.sum(rglru_ref(x, r, i, lam) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               rtol=1e-3, atol=1e-3)


# -------------------------------------------- model-level pallas dispatch
def test_model_pallas_path_matches_ref_path():
    """A reduced gemma2 (attention) forward under attn_impl=pallas must match
    attn_impl=ref."""
    from repro.configs import ARCHS, reduce_cfg
    from repro.models import build_model

    cfg = reduce_cfg(ARCHS["gemma2-2b"].cfg).replace(
        window=128, max_target_length=512)
    model_ref = build_model(cfg.replace(attn_impl="ref"))
    model_pl = build_model(cfg.replace(attn_impl="pallas"))
    params = model_ref.init(jax.random.PRNGKey(0))
    B, S = 2, 256   # >= 256 so the pallas path engages
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    l_ref, _ = model_ref.loss(params, batch)
    l_pl, _ = model_pl.loss(params, batch)
    np.testing.assert_allclose(float(l_ref), float(l_pl), rtol=1e-4)


# ------------------------------------------------- compiled vs interpreted
@pytest.mark.parametrize("backend,interpret", [("tpu", False), ("cpu", True),
                                               ("gpu", None)])
def test_interpret_mode_follows_backend(monkeypatch, backend, interpret):
    """Compiled on a TPU, interpreted on the CPU, refused elsewhere —
    never a silent interpret-mode run on an accelerator."""
    from repro import kernels
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(NotImplementedError, match="attn_impl='ref'"):
            kernels.interpret_mode()
    else:
        assert kernels.interpret_mode() is interpret
