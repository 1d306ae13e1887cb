"""Logical-axis sharding rules.

Models annotate every parameter/input dimension with a *logical* axis name
("vocab", "heads", "ff", "expert", "batch", ...). At lowering time
``resolve_specs`` maps logical names to mesh axes with divisibility
fallbacks (a dimension that does not divide evenly over the candidate mesh
axis is replicated instead — e.g. minicpm's vocab 122753 over model=16).

A logical spec is a tuple of logical names (or None), one per array dim.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> ordered candidate mesh axes (first that divides & is free wins)
DEFAULT_RULES = {
    # the member dim shards over BOTH axes of the hierarchical
    # ('host', 'pod') mesh when one is in play, else over the flat 1-D
    # 'pod' axis — the tuple candidate resolves to size 0 (skipped) on
    # meshes without a 'host' axis
    "member": (("host", "pod"), "pod"),
    "batch": ("data",),
    "vocab": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "ff": ("model",),
    "expert": ("model",),
    "kv_seq": ("model",),   # sharded KV-cache sequence (decode)
    "ssm_heads": ("model",),
    "embed": (),            # d_model stays replicated by default
    "layers": (),
    "seq": (),
    "head_dim": (),
    "state": (),
    "classes": (),
    "feature": (),
}


def resolve_spec(shape: Sequence[int], logical: Sequence[Optional[str]],
                 mesh: Mesh, rules=None) -> P:
    """Turn one logical spec into a PartitionSpec valid for ``shape`` on
    ``mesh``. Rule candidates may be a mesh-axis name or a TUPLE of names
    (sharding one dim over several mesh axes, e.g. batch over
    ('pod','data')). First candidate that divides evenly and whose axes are
    all unused wins; otherwise the dim is replicated."""
    rules = {**DEFAULT_RULES, **(rules or {})}
    used = set()
    out = []
    if len(logical) != len(shape):
        raise ValueError(f"logical {logical} does not match shape {shape}")
    for dim, name in zip(shape, logical):
        axis = None
        if name is not None:
            for cand in rules.get(name, ()):
                axes = cand if isinstance(cand, tuple) else (cand,)
                size = 1
                for a in axes:
                    size *= mesh.shape.get(a, 0) or 0
                if (size and not (set(axes) & used) and dim % size == 0):
                    axis = cand
                    used.update(axes)
                    break
        out.append(axis)
    return P(*out)


def resolve_tree(shapes_tree, logical_tree, mesh: Mesh, rules=None):
    """Map a pytree of shapes + matching pytree of logical specs -> PartitionSpecs."""
    return jax.tree.map(
        lambda shp, log: resolve_spec(shp, log, mesh, rules),
        shapes_tree, logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, int) for e in x),
    )


def shapes_of(tree):
    return jax.tree.map(lambda a: tuple(a.shape), tree)


def named_sharding_tree(spec_tree, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def with_member_dim(logical_tree):
    """Prepend the 'member' logical axis (distributed-averaging pod dim)."""
    return jax.tree.map(lambda log: ("member",) + tuple(log), logical_tree,
                        is_leaf=_is_logical_leaf)


def _is_logical_leaf(x):
    return isinstance(x, tuple) and all(e is None or isinstance(e, str) for e in x)


def member_dim_specs(tree, mesh: Mesh, rules=None):
    """PartitionSpec pytree for member-stacked arrays (leading dim = the
    'member' logical axis, everything else replicated) — the spec-level
    twin of ``member_dim_shardings``, consumed as shard_map in/out_specs
    by ``trainer.make_average_step``."""
    def one(a):
        logical = ("member",) + (None,) * (a.ndim - 1)
        return resolve_spec(a.shape, logical, mesh, rules)
    return jax.tree.map(one, tree)


def member_dim_shardings(tree, mesh: Mesh, rules=None):
    """NamedSharding pytree for member-stacked arrays (leading dim = the
    'member' logical axis, everything else replicated): each pod holds
    k/|pod| members and the Reduce mean lowers to one all-reduce across
    pods. Falls back to full replication when 'member' resolves to no mesh
    axis (e.g. k not divisible by the pod count, or a mesh without a 'pod'
    axis — the stacked executor instead pads k to a pod multiple and
    places its members itself)."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        member_dim_specs(tree, mesh, rules),
                        is_leaf=lambda x: isinstance(x, P))


def constrain(x, logical, mesh: Mesh, rules=None):
    """In-function sharding constraint from a logical spec."""
    spec = resolve_spec(x.shape, logical, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def bytes_of_tree(tree) -> int:
    return int(sum(np.prod(a.shape) * a.dtype.itemsize
                   for a in jax.tree.leaves(tree)))
