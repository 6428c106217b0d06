"""Substrate layers: checkpoint store, synthetic data, optimizers,
sharding rules."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_optional import given, settings, st

from repro import checkpoint as ck
from repro.data import DataCfg, SyntheticLM
from repro.optim import OptCfg, make_optimizer
from repro.sharding import DEFAULT_RULES, fsdp_rules, resolve


# ------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": np.arange(12, dtype=np.float32).reshape(3, 4),
            "b": {"c": np.float32(3.5), "d": [np.ones(2), np.zeros(3)]},
            "e": None}
    path = ck.save(str(tmp_path), 7, tree, extra={"cursor": 123})
    assert ck.latest_step(str(tmp_path)) == 7
    step, out, extra = ck.restore(str(tmp_path), tree)
    assert step == 7 and extra["cursor"] == 123
    np.testing.assert_array_equal(out["a"], tree["a"])
    np.testing.assert_array_equal(out["b"]["d"][0], tree["b"]["d"][0])
    assert out["e"] is None


def test_checkpoint_latest_pointer_advances(tmp_path):
    t = {"x": np.zeros(2)}
    ck.save(str(tmp_path), 1, t)
    ck.save(str(tmp_path), 2, t)
    assert ck.latest_step(str(tmp_path)) == 2
    step, _, _ = ck.restore(str(tmp_path), t, step=1)
    assert step == 1


# ------------------------------------------------------------------ data
def test_data_deterministic_and_topology_invariant():
    d = SyntheticLM(DataCfg(vocab=64, seq=16, global_batch=8, seed=3))
    b1 = d.batch(5, 0, 1)
    b2 = d.batch(5, 0, 1)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # sharded batches tile the global batch
    s0 = d.batch(5, 0, 2)
    s1 = d.batch(5, 1, 2)
    np.testing.assert_array_equal(
        np.concatenate([s0["tokens"], s1["tokens"]]), b1["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(b1["labels"][:, :-1], b1["tokens"][:, 1:])


def test_data_has_learnable_structure():
    d = SyntheticLM(DataCfg(vocab=64, seq=64, global_batch=16, seed=3))
    b = d.batch(0)
    # bigram entropy must be far below uniform (log 64 = 4.16 nats)
    pairs = {}
    for row in np.stack([b["tokens"][:, :-1].ravel(),
                         b["tokens"][:, 1:].ravel()], 1):
        pairs.setdefault(row[0], []).append(row[1])
    ent = []
    for k, v in pairs.items():
        if len(v) < 8:
            continue
        _, counts = np.unique(v, return_counts=True)
        p = counts / counts.sum()
        ent.append(-(p * np.log(p)).sum())
    assert np.mean(ent) < 2.0


# ------------------------------------------------------------- optimizers
@pytest.mark.parametrize("name", ["adamw", "adamw8", "adafactor", "sgdm"])
def test_optimizer_reduces_quadratic(name):
    opt = make_optimizer(OptCfg(name=name, peak_lr=0.1, warmup=1,
                                total_steps=100, weight_decay=0.0))
    params = {"w": jnp.ones((8, 8)) * 3.0, "b": jnp.ones((8,))}
    state = opt.init(params)

    def loss(p):
        return jnp.sum(p["w"] ** 2) + jnp.sum(p["b"] ** 2)

    l0 = float(loss(params))
    for i in range(30):
        g = jax.grad(loss)(params)
        params, state, _ = opt.update(g, state, params, jnp.asarray(i))
    assert float(loss(params)) < 0.5 * l0


def test_adamw8_state_is_int8():
    opt = make_optimizer(OptCfg(name="adamw8"))
    params = {"w": jnp.ones((16, 16))}
    st = opt.init(params)
    assert st["mu"]["w"]["m"].dtype == jnp.int8
    # abstract state matches concrete
    ab = opt.abstract_state({"w": jax.ShapeDtypeStruct((16, 16),
                                                       jnp.float32)})
    assert ab["mu"]["w"]["m"].shape == (16, 16)


# --------------------------------------------------------------- sharding
def _mesh1():
    return jax.make_mesh((1,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def test_resolve_divisibility_fallback():
    mesh = _mesh1()  # single device: size-1 axes
    spec = resolve((8, 64), ("heads", "embed"), mesh, DEFAULT_RULES)
    assert spec == jax.sharding.PartitionSpec(None, None)


def test_resolve_conflict_drops_second():
    # both dims map to 'model': second must be dropped
    rules = dict(DEFAULT_RULES, embed="model", mlp="model")
    import jax.sharding as js
    devs = np.array(jax.devices() * 4)[:4] if len(jax.devices()) >= 4 \
        else None
    # build an abstract 4-way mesh via make_mesh if devices permit;
    # otherwise just exercise the code path with the host mesh
    mesh = _mesh1()
    spec = resolve((16, 16), ("embed", "mlp"), mesh, rules)
    assert len(spec) == 2


@given(st.integers(1, 4), st.integers(1, 4))
@settings(max_examples=10, deadline=None)
def test_resolve_never_overshards(a, b):
    mesh = _mesh1()
    spec = resolve((a * 3, b * 5), ("heads", "mlp"), mesh, DEFAULT_RULES)
    assert len(spec) == 2


def test_resolve_suffix_fallback():
    """32 experts on ('data','model')=mesh product that doesn't divide must
    fall back to a shardable suffix, not to full replication."""
    import numpy as np
    from jax.sharding import PartitionSpec
    # simulate with a 1x1 mesh: suffix fallback cannot find >1 divisor
    mesh = _mesh1()
    rules = dict(DEFAULT_RULES, expert=("data", "model"))
    spec = resolve((32, 8, 8), ("expert", "embed", "moe_mlp"), mesh, rules)
    assert spec == PartitionSpec(None, None, None)
