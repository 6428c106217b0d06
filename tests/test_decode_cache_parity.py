"""Decode with the stacked caches carried through the layer scan gives the
same logits and caches, bit for bit in float32, as the form it replaced:
each layer's cache slice a scan input (xs) written by a scatter and
returned as a scan output (ys).  That form lives here alone, as the
reference.

Six steps over four slots: three live, one dead (its position pinned, so
it rewrites the same entry every step), and sliding-window rings that
wrap during the run.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduce_cfg
from repro.models import build_model
from repro.models.config import MLACfg
from repro.models.lm import layer_apply, norm_apply

B, MAX_LEN, STEPS = 4, 16, 6
STARTS = np.array([5, 7, 9, 2], np.int32)
LIVE = np.array([True, True, True, False])

CONFIGS = {
    "attn": lambda: reduce_cfg(ARCHS["stablelm-1.6b"].cfg).replace(
        n_layers=4),
    "local": lambda: reduce_cfg(ARCHS["gemma2-2b"].cfg).replace(
        n_layers=8, window=8),
    "mla": lambda: reduce_cfg(ARCHS["stablelm-1.6b"].cfg).replace(
        n_layers=4, pattern=("mla",),
        mla=MLACfg(q_lora=64, kv_lora=32, rope_dim=16, nope_dim=32,
                   v_dim=32)),
    "ssd": lambda: reduce_cfg(ARCHS["mamba2-370m"].cfg).replace(n_layers=4),
    "rglru+local": lambda: reduce_cfg(ARCHS["recurrentgemma-9b"].cfg).replace(
        n_layers=6, window=8),
}


def xs_ys_decode_step(model, params, caches, tokens, pos):
    """One decode step with every scanned segment's caches as scan xs and
    ys: a layer writes its new entries into its own slice of the stack."""
    cfg = model.cfg
    x = model.embed(params, tokens)
    aux = jnp.zeros((), jnp.float32)
    new_caches = []
    for si, (unit, reps) in enumerate(model.segments):
        def body(x_aux, slices, unit=unit):
            x, aux = x_aux
            pslices, cslices = slices
            out = []
            for ui, desc in enumerate(unit):
                x, nc, a, _ = layer_apply(pslices[f"u{ui}"], x, cfg=cfg,
                                       desc=desc, positions=pos,
                                       cache=cslices[ui])
                out.append(nc)
                aux = aux + a
            return (x, aux), out
        if cfg.remat != "none":
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable,
                prevent_cse=reps == 1)
        slices = (params[f"seg{si}"], caches[si])
        if reps == 1:
            (x, aux), ncs = body((x, aux), slices)
        else:
            (x, aux), ncs = jax.lax.scan(body, (x, aux), slices)
        new_caches.append(ncs)
    h = norm_apply(params["final_norm"], x, cfg)
    return model.logits(params, h), new_caches


def filled_cache(model, key):
    """A cache as if slot ``b`` had seen positions ``0..STARTS[b]-1``:
    random entries and states, and each ring entry ``j`` marked with the
    newest position that maps to it (-1 if none yet)."""
    caches = model.init_cache(B, MAX_LEN)
    leaves, tree = jax.tree_util.tree_flatten_with_path(caches)
    keys = jax.random.split(key, len(leaves))
    out = []
    for (path, leaf), k in zip(leaves, keys):
        if path[-1].key == "pos":
            L = leaf.shape[-1]
            j = np.arange(L)[None]
            last = STARTS[:, None] - 1
            p = last - (last - j) % L
            out.append(jnp.broadcast_to(
                jnp.asarray(np.where(p >= 0, p, -1), jnp.int32), leaf.shape))
        else:
            out.append(jax.random.normal(k, leaf.shape, leaf.dtype))
    return jax.tree_util.tree_unflatten(tree, out)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_carried_decode_matches_xs_ys_decode(name):
    cfg = CONFIGS[name]().replace(remat="full")
    assert cfg.dtype == "float32"
    model = build_model(cfg)
    assert any(reps > 1 for _, reps in model.segments)
    key = jax.random.PRNGKey(7)
    params = model.init(key)
    caches = ref_caches = filled_cache(model, jax.random.fold_in(key, 1))
    step = jax.jit(model.decode_step)
    ref_step = jax.jit(lambda p, c, t, q: xs_ys_decode_step(model, p, c, t, q))
    tokens = np.array(jax.random.randint(key, (B, 1), 0, cfg.vocab),
                      np.int32)
    pos = STARTS[:, None].copy()
    for _ in range(STEPS):
        lg, caches = step(params, caches, jnp.asarray(tokens),
                          jnp.asarray(pos))
        ref_lg, ref_caches = ref_step(params, ref_caches,
                                      jnp.asarray(tokens), jnp.asarray(pos))
        np.testing.assert_array_equal(np.asarray(lg), np.asarray(ref_lg))
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), caches, ref_caches)
        nxt = np.asarray(jnp.argmax(ref_lg[:, -1], axis=-1), np.int32)
        tokens[LIVE, 0] = nxt[LIVE]
        pos[LIVE, 0] += 1
    assert (pos[:, 0] >= 8).sum() >= 2  # two slots went round an 8-entry ring
