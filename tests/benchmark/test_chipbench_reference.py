"""The plain reference against the program at a small size on the CPU,
both in float32: the same weights (made by the benchmark, regrouped into
the program's tree) give the same logits, prefill and decode."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _chipbench
from chip import harness, reference, weights


@pytest.mark.parametrize("kind", ["attn", "ssd"])
def test_reference_matches_program(kind):
    from repro.models import build_model
    cfg_file = _chipbench.tiny_config(kind)
    m = cfg_file["model"]
    model = build_model(harness.program_cfg(cfg_file))
    canon = weights.canonical(m, 2**33 + 5)
    params = weights.to_program(m, canon, model)
    rng = np.random.default_rng(0)
    prompt, more = 40, 6
    toks = rng.integers(0, m["vocab"], size=(1, prompt + more)).astype(
        np.int32)
    ref = np.asarray(jax.jit(lambda w, t: reference.forward(m, w, t))(
        canon, jnp.asarray(toks)))[0]
    caches = model.init_cache(1, 64)
    lg, caches = jax.jit(model.prefill)(params, jnp.asarray(toks[:, :prompt]),
                                        caches)
    got = [np.asarray(lg)[0, -1]]
    step = jax.jit(model.decode_step)
    for i in range(more):
        p = prompt + i
        lg, caches = step(params, caches, jnp.asarray(toks[:, p:p + 1]),
                          jnp.full((1, 1), p, jnp.int32))
        got.append(np.asarray(lg)[0, -1])
    want = ref[prompt - 1:prompt + more]
    scale = np.abs(want).max()
    np.testing.assert_allclose(np.stack(got), want, atol=2e-4 * scale,
                               rtol=0)


@pytest.mark.parametrize("kind", ["attn", "ssd"])
def test_weights_fit_the_program_tree(kind):
    from repro.models import build_model
    cfg_file = _chipbench.tiny_config(kind, "bfloat16")
    m = cfg_file["model"]
    model = build_model(harness.program_cfg(cfg_file))
    canon = weights.canonical(m, 3)
    tree = weights.to_program(m, canon, model)
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(tree))
    again = weights.canonical(m, 3)
    assert all(bool(jnp.array_equal(a, b)) for a, b in
               zip(jax.tree.leaves(canon), jax.tree.leaves(again)))
    other = weights.canonical(m, 3 + 2**32)
    assert not jnp.array_equal(canon["embed"], other["embed"])


def test_weights_refused_where_the_tree_differs():
    from repro.models import build_model
    cfg_file = _chipbench.tiny_config("attn")
    m = cfg_file["model"]
    wrong = dict(cfg_file, model=dict(m, d_ff=m["d_ff"] * 2))
    model = build_model(harness.program_cfg(wrong))
    with pytest.raises(ValueError, match="program wants"):
        weights.to_program(m, weights.canonical(m, 1), model)
