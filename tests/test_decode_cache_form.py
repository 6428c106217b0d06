"""The compiled form of the decode step: caches that decode writes one
entry per row of (GQA global and sliding-window K/V/pos, MLA latents, the
two latents of a LongCat-Flash layer) ride in the layer scan's carry and
are written in place, so the while body
holds no copy, scatter or dynamic-update-slice of one layer's cache slice,
and the whole step copies each stacked leaf at most once, at entry (the
caller's cache is not donated).  Caches rewritten whole every step (SSD
state) keep the scan's xs->ys form: no entry copy of the stacked state.

Compiled on the CPU from ``jax.jit(make_serve_step(model))``, the program
``ServeEngine`` runs, and read from the optimised HLO text.  Under a mesh
the form differs, the results do not: prefill and decode inside
``use_sharding`` match the run without one.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.configs import ARCHS, reduce_cfg
from repro.models import build_model
from repro.models.config import MLACfg
from repro.models.lm import STACK_WRITTEN
from repro.sharding import serve_rules, use_sharding
from repro.train import make_prefill_step, make_serve_step

B, MAX_LEN = 3, 16

_COMP = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{$")
_INST = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(")
_TUPLE = re.compile(r"^\s*(?:ROOT )?%(\S+) = \(.*?\) ([\w-]+)\(")
_CALLED = re.compile(r"(?:calls|to_apply|body|condition)=%([\w.-]+)")


def _cfg(kind):
    if kind == "attn+local":
        return reduce_cfg(ARCHS["gemma2-2b"].cfg).replace(
            n_layers=8, window=8, remat="full")
    if kind == "mla":
        return reduce_cfg(ARCHS["stablelm-1.6b"].cfg).replace(
            n_layers=4, pattern=("mla",), remat="full",
            mla=MLACfg(q_lora=64, kv_lora=32, rope_dim=16, nope_dim=32,
                       v_dim=32))
    if kind == "longcat":
        # two MLA latent caches per layer, both carried and written in place
        return reduce_cfg(ARCHS["longcat-flash-chat"].cfg).replace(
            n_layers=4, remat="full")
    if kind == "ssd":
        return reduce_cfg(ARCHS["mamba2-370m"].cfg).replace(
            n_layers=4, remat="full")
    raise ValueError(kind)


def parse_hlo(text):
    """``(computations, entry)``: each computation's instructions as
    ``(dtype, dims, opcode, called computations)``."""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
            if line.startswith("ENTRY"):
                entry = m.group(1)
            continue
        if cur is None:
            continue
        m = _INST.match(line)
        if m:
            dims = tuple(int(d) for d in m.group(3).split(",") if d)
            cur.append((m.group(2), dims, m.group(4),
                        _CALLED.findall(line)))
        elif _TUPLE.match(line):
            cur.append(("tuple", (), _TUPLE.match(line).group(2),
                        _CALLED.findall(line)))
    return comps, entry


def _reachable(comps, roots):
    seen, todo = set(), list(roots)
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        todo.extend(called for *_, calls in comps[c] for called in calls)
    return seen


def compiled_step(model, step=None):
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    caches = jax.eval_shape(lambda: model.init_cache(B, MAX_LEN))
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    text = jax.jit(step or make_serve_step(model)).lower(
        params, caches, tok, tok).compile().as_text()
    return parse_hlo(text), caches


def _loop_and_outside(comps, entry):
    bodies = [c for *_, op, calls in comps[entry] if op == "while"
              for c in calls]
    assert bodies, "the decode step has no layer loop"
    inside = _reachable(comps, bodies)
    outside = _reachable(comps, [entry]) - inside
    return inside, outside


def _leaves(model, caches, written):
    """``(dtype, stacked shape)`` of each stacked cache leaf whose unit is
    (``written``) or is not one that decode writes in place."""
    out = []
    for (unit, reps), seg in zip(model.segments, caches):
        if reps == 1:
            continue
        for desc, c in zip(unit, seg):
            if (desc[0] in STACK_WRITTEN) == written and c is not None:
                out += [(np.dtype(a.dtype).name, a.shape)
                        for a in jax.tree.leaves(c)]
    return out


def _hlo_dtype(name):
    return {"float32": "f32", "bfloat16": "bf16", "int32": "s32"}[name]


@pytest.mark.parametrize("kind", ["attn+local", "mla", "longcat"])
def test_written_caches_are_carried_and_written_in_place(kind):
    model = build_model(_cfg(kind))
    (comps, entry), caches = compiled_step(model)
    inside, outside = _loop_and_outside(comps, entry)
    leaves = _leaves(model, caches, written=True)
    assert leaves
    per_layer = {(_hlo_dtype(dt), shp[1:]) for dt, shp in leaves}
    bad = [(dt, dims, op) for c in inside for dt, dims, op, _ in comps[c]
           if op in ("copy", "scatter", "dynamic-update-slice")
           and (dt, dims) in per_layer]
    assert not bad, f"per-layer cache slices rewritten in the loop: {bad}"
    # the in-place writes land on the stacked leaves themselves
    stacked = {(_hlo_dtype(dt), shp) for dt, shp in leaves}
    writes = {(dt, dims) for c in inside for dt, dims, op, _ in comps[c]
              if op == "dynamic-update-slice" and (dt, dims) in stacked}
    assert writes == stacked
    for key in stacked:
        n_leaves = sum((_hlo_dtype(dt), shp) == key for dt, shp in leaves)
        copies = sum(1 for c in outside for dt, dims, op, _ in comps[c]
                     if op == "copy" and (dt, dims) == key)
        assert copies <= n_leaves, (key, copies, n_leaves)


def test_rewritten_state_keeps_its_form():
    model = build_model(_cfg("ssd"))
    (comps, entry), caches = compiled_step(model)
    _, outside = _loop_and_outside(comps, entry)
    leaves = _leaves(model, caches, written=False)
    assert leaves and not _leaves(model, caches, written=True)
    for dt, shp in leaves:
        copies = [op for c in outside for t, dims, op, _ in comps[c]
                  if op == "copy" and (t, dims) == (_hlo_dtype(dt), shp)]
        assert not copies, (dt, shp, copies)


def test_under_a_mesh_written_caches_keep_xs_ys():
    """With a mesh active the batch may be split across devices, where a
    row's write into the stack would gather it: the scan keeps xs->ys."""
    model = build_model(_cfg("attn+local"))
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    raw = make_serve_step(model)

    def step(params, caches, tokens, pos):
        with use_sharding(mesh, serve_rules()):
            return raw(params, caches, tokens, pos)

    (comps, entry), caches = compiled_step(model, step)
    inside, _ = _loop_and_outside(comps, entry)
    per_layer = {(_hlo_dtype(dt), shp[1:])
                 for dt, shp in _leaves(model, caches, written=True)}
    scattered = {(dt, dims) for c in inside for dt, dims, op, _ in comps[c]
                 if op == "scatter"}
    assert scattered == per_layer


@pytest.mark.parametrize("arch", ["gemma3-1b", "granite-moe-1b-a400m",
                                  "whisper-tiny", "longcat-flash-chat"])
def test_tokens_unchanged_under_sharding_rules(arch):
    """Prefill and three decode steps give the same tokens, logits and
    caches inside ``use_sharding`` (serving rules, the host's one-device
    mesh) as without a mesh: the rules place arrays, never change them."""
    cfg = reduce_cfg(ARCHS[arch].cfg)
    if arch == "longcat-flash-chat":
        # a held share: 4 of the 8 routed experts, the rest routed away
        cfg = cfg.replace(held_experts=4, held_start=2)
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    prompt = 8
    batch = {"tokens": jax.random.randint(key, (B, prompt), 0, cfg.vocab)}
    if cfg.frontend == "audio":
        batch["frame_embeds"] = jax.random.normal(
            key, (B, prompt, cfg.d_model), jnp.float32)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)

    def run(sharded):
        def jit(fn):
            def wrapped(*args):
                with (use_sharding(mesh, serve_rules()) if sharded
                      else contextlib.nullcontext()):
                    return fn(*args)
            return jax.jit(wrapped)

        logits, caches = jit(make_prefill_step(model, max_len=MAX_LEN))(
            params, batch)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        toks, step = [tok], jit(make_serve_step(model))
        for i in range(3):
            pos = jnp.full((B, 1), prompt + i, jnp.int32)
            tok, caches, _ = step(params, caches, tok, pos)
            toks.append(tok)
        return logits, np.concatenate(toks, axis=1), caches

    lg, toks, caches = run(sharded=False)
    lg_s, toks_s, caches_s = run(sharded=True)
    np.testing.assert_array_equal(toks_s, toks)
    np.testing.assert_allclose(np.asarray(lg_s), np.asarray(lg),
                               rtol=1e-5, atol=1e-5)
    assert jax.tree.structure(caches_s) == jax.tree.structure(caches)
    for a, b in zip(jax.tree.leaves(caches_s), jax.tree.leaves(caches)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
