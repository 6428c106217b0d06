"""Random weights from the seed, made on the device in one jitted call, in
the type they are served in, and laid out for the program.

``canonical`` is the benchmark's own layout: ``embed`` ``(vocab, d)``,
``head`` ``(d, vocab)`` when the embedding is not tied, ``final_norm``,
and ``layers``: one dict per position of ``pattern``, each leaf stacked
over the model's periods.  The reference reads this layout;
``to_program`` only regroups the same arrays into the program's
parameter tree, so the program and the reference are handed the same
bits.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp


def key_for(seed: int, stream: int):
    """A PRNG key from a seed of any size: low and high 32 bits both count."""
    s = int(seed) % (1 << 64)
    key = jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)
    return jax.random.fold_in(key, stream)


def normal(key, shape, std: float):
    return std * jax.random.normal(key, shape, jnp.float32)


def norm_params(m: Dict[str, Any], key, n: int) -> Dict[str, Any]:
    """Norm scale 1 + N(0, 0.1^2), and for LayerNorm a shift N(0, 0.1^2),
    for ``n`` layers (``n = 0``: one, unstacked)."""
    shape = (n, m["d_model"]) if n else (m["d_model"],)
    kw, kb = jax.random.split(key)
    p = {"w": 1.0 + 0.1 * jax.random.normal(kw, shape)}
    if m["norm"] == "layernorm":
        p["b"] = 0.1 * jax.random.normal(kb, shape)
    return p


def _kinds(m):
    from .reference import kind_module
    return [kind_module(k) for k in m["pattern"]]


@functools.lru_cache(maxsize=None)
def _generator(model_json: str):
    import json
    m = json.loads(model_json)
    kinds = _kinds(m)
    periods = m["n_layers"] // len(m["pattern"])
    if periods * len(m["pattern"]) != m["n_layers"]:
        raise ValueError("n_layers must be a whole number of pattern periods")
    dtype = jnp.dtype(m["dtype"])
    d, V = m["d_model"], m["vocab"]

    def make(key):
        ks = jax.random.split(key, 3 + len(kinds))
        w = {"embed": normal(ks[0], (V, d),
                             d ** -0.5 if m["tie_embeddings"] else 1.0),
             "final_norm": norm_params(m, ks[1], 0),
             "layers": [mod.init(m, ks[3 + i], periods)
                        for i, mod in enumerate(kinds)]}
        if not m["tie_embeddings"]:
            w["head"] = normal(ks[2], (d, V), d ** -0.5)
        return jax.tree.map(lambda a: a.astype(dtype), w)

    return jax.jit(make)


def canonical(m: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The weights of ``seed`` in the canonical layout (one jitted call)."""
    import json
    return _generator(json.dumps(m, sort_keys=True))(key_for(seed, 0))


def to_program(m: Dict[str, Any], w: Dict[str, Any], model) -> Dict[str, Any]:
    """Regroup canonical weights into the parameter tree of ``model``
    (a ``repro.models.lm.TransformerLM``), checked leaf by leaf against
    its ``abstract_params()``.  A segment that covers every period of a
    pattern position takes the stacked arrays as they are (no copy)."""
    kinds = _kinds(m)
    P = len(m["pattern"])
    periods = m["n_layers"] // P
    tree: Dict[str, Any] = {"embed": w["embed"],
                            "final_norm": w["final_norm"]}
    if not m["tie_embeddings"]:
        tree["lm_head"] = w["head"]
    offset = 0
    for si, (unit, reps) in enumerate(model.segments):
        seg = {}
        for ui, desc in enumerate(unit):
            layers = [offset + r * len(unit) + ui for r in range(reps)]
            pos = {i % P for i in layers}
            if len(pos) != 1 or desc[0] != m["pattern"][pos.copy().pop()]:
                raise NotImplementedError(
                    f"program segment {si} unit {ui} does not map onto one "
                    f"position of the pattern {m['pattern']}")
            j = pos.pop()
            idx = [i // P for i in layers]
            if reps == 1:
                pick = functools.partial(lambda a, i: a[i], i=idx[0])
            elif idx == list(range(periods)):
                pick = lambda a: a  # noqa: E731
            else:
                pick = functools.partial(lambda a, i: a[jnp.asarray(i)],
                                         i=idx)
            seg[f"u{ui}"] = kinds[j].to_program(
                m, jax.tree.map(pick, w["layers"][j]))
        tree[f"seg{si}"] = seg
        offset += len(unit) * reps
    want = model.abstract_params()
    got_struct, want_struct = (jax.tree.structure(tree),
                               jax.tree.structure(want))
    if got_struct != want_struct:
        raise ValueError(f"weights do not fit the program's parameter tree:"
                         f"\n made {got_struct}\n want {want_struct}")
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(want)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"weight {a.shape} {a.dtype} where the program "
                             f"wants {b.shape} {b.dtype}")
    return tree
