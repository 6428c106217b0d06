"""Logical-axis → mesh-axis rules (MaxText-style), with divisibility guards.

Every parameter/activation dimension carries a *logical* axis name; a rule
set maps logical names to mesh axes.  ``resolve`` drops a mapping whenever
the dimension is not divisible by the mesh-axis extent (e.g. 4 query heads
cannot shard over a 16-way 'model' axis — gemma3-1b), so every config lowers
on every mesh.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

AxisVal = Union[None, str, Tuple[str, ...]]

# Baseline rule set: DP over (pod, data), TP/EP over model.
DEFAULT_RULES: Dict[str, AxisVal] = {
    "batch": ("pod", "data"),
    "seq": None,
    "residual_seq": None,   # Megatron-SP: 'model' shards the residual seq
    "cache": None,
    "embed": None,
    "embed_tbl": None,   # embedding-table d-dim: never FSDP-shard (§Perf)
    "mlp": "model",
    "moe_mlp": "model",
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "vocab": "model",
    "expert": "model",
    "layers": None,
    "q_lora": None,
    "kv_lora": None,
    "rec": "model",        # RG-LRU width / mamba d_inner
    "ssm_heads": "model",
    "state": None,
    "groups": None,
    "dconv": None,
    "capacity": None,
}


def with_updates(base: Dict[str, AxisVal], **kw) -> Dict[str, AxisVal]:
    out = dict(base)
    out.update(kw)
    return out


# FSDP: additionally shard the 'embed' dimension of parameters over 'data'
# (ZeRO-3 via GSPMD: XLA all-gathers per layer inside the step).
def fsdp_rules(base: Dict[str, AxisVal] = None) -> Dict[str, AxisVal]:
    return with_updates(base or DEFAULT_RULES, embed="data")


# Serving rules: experts spread over BOTH axes (256 experts / 256 chips),
# MLA latent dim TP-sharded; weights otherwise replicated over 'data' for
# gather-free decode.
def serve_rules(base: Dict[str, AxisVal] = None) -> Dict[str, AxisVal]:
    return with_updates(base or DEFAULT_RULES,
                        expert=("data", "model"), kv_lora="model")


def _axis_size(mesh: Mesh, axis: AxisVal) -> int:
    if axis is None:
        return 1
    if isinstance(axis, str):
        return mesh.shape[axis] if axis in mesh.axis_names else 0
    n = 1
    for a in axis:
        s = mesh.shape[a] if a in mesh.axis_names else 0
        if s == 0:
            return 0
        n *= s
    return n


def resolve(shape: Sequence[int], axes: Sequence[Optional[str]],
            mesh: Mesh, rules: Dict[str, AxisVal]) -> PartitionSpec:
    """PartitionSpec for one array; drops indivisible / conflicting axes."""
    used: set = set()
    parts = []
    for dim, name in zip(shape, axes):
        val: AxisVal = rules.get(name) if name else None
        if val is not None:
            # filter to axes present in this mesh
            tup = (val,) if isinstance(val, str) else tuple(val)
            tup = tuple(a for a in tup if a in mesh.axis_names)
            val = tup if tup else None
        if val is None:
            parts.append(None)
            continue
        flat = val if isinstance(val, tuple) else (val,)
        # suffix fallback: if the full product is indivisible, drop leading
        # axes one at a time (e.g. 32 experts on ('data','model')=256 chips
        # still shard over ('model',)=16)
        chosen = None
        for start in range(len(flat)):
            cand = flat[start:]
            size = _axis_size(mesh, cand)
            if (size > 1 and dim % size == 0
                    and not any(a in used for a in cand)):
                chosen = cand
                break
        if chosen is None:
            parts.append(None)  # indivisible or conflicting: replicate
            continue
        used.update(chosen)
        parts.append(chosen if len(chosen) > 1 else chosen[0])
    return PartitionSpec(*parts)


def tree_shardings(axes_tree, shape_tree, mesh: Mesh,
                   rules: Dict[str, AxisVal]):
    """NamedSharding tree matching a (axes, shapes) spec tree pair."""
    def one(axes, shaped):
        return NamedSharding(mesh, resolve(shaped.shape, axes, mesh, rules))
    return jax.tree.map(one, axes_tree, shape_tree,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            isinstance(a, (str, type(None))) for a in x))
