"""Logical-axis sharding planner.

Every parameter / cache dim carries a logical name (emitted by the model's
``init`` alongside the params; see ``repro.models.common.split_tree``). Rules
map each logical name to an ordered list of mesh-axis candidates; the planner
picks the first candidate whose axes (a) all exist in the mesh, (b) are not
already used by another dim of the same array, and (c) whose product divides
the dim size. Exhausting the candidates replicates the dim — so every
(arch x mesh) cell shards coherently without per-arch special cases
(e.g. qwen1.5-4b's 20 heads fall back to replicated heads while d_ff/vocab
still carry the TP).

Rule sets:
  TRAIN  — FSDP over "data" (+"pod") on the big parameter dims, TP over
           "model" for vocab/mlp/heads/experts; batch over ("pod","data").
  SERVE  — params TP over "model" only (replicated over data/pod so decode
           needs no weight collectives); caches shard batch over
           ("pod","data") and kv_heads over "model", with a documented
           fallback to sequence-dim sharding when head counts don't divide.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import numpy as np
from jax.sharding import AbstractMesh, Mesh, NamedSharding, PartitionSpec as P

PyTree = Any

__all__ = [
    "RuleSet",
    "TRAIN_RULES",
    "SERVE_RULES",
    "abstract_mesh",
    "plan_sharding",
    "plan_tree",
    "batch_spec",
]


def abstract_mesh(axis_sizes, axis_names) -> AbstractMesh:
    """Device-free mesh with ``Auto`` axes, like ``launch.mesh.make_mesh``."""
    return AbstractMesh(tuple(axis_sizes), tuple(axis_names),
                        axis_types=(jax.sharding.AxisType.Auto,)
                        * len(axis_names))


@dataclasses.dataclass(frozen=True)
class RuleSet:
    """logical axis name -> ordered candidates, each a tuple of mesh axes."""

    rules: dict

    def candidates(self, name):
        if name is None:
            return ((),)
        return self.rules.get(name, ((),))


TRAIN_RULES = RuleSet(
    {
        # activations / inputs
        "batch": (("pod", "data"), ("data",), ()),
        "seq": ((),),
        # parameters — TP dims
        "vocab": (("model",), ()),
        "mlp": (("model",), ()),
        "heads_flat": (("model",), ()),
        "kv_flat": (("model",), ()),
        "heads": (("model",), ()),
        "experts": (("model",), ()),
        "rnn": (("model",), ()),
        # parameters — FSDP dim (the "other" big dim of each kernel)
        "embed": (("data",), ()),
        "experts_r": ((),),
        "rnn2": ((),),
        # stacking / small dims — replicated
        "layers": ((),),
        "sup": ((),),
        "kv_heads": (("model",), ()),
        "head_dim": ((),),
        "seq_sharded": (("model",), ()),
    }
)

SERVE_RULES = RuleSet(
    {
        "batch": (("pod", "data"), ("data",), ()),
        "seq": ((),),
        "vocab": (("model",), ()),
        "mlp": (("model",), ()),
        "heads_flat": (("model",), ()),
        "kv_flat": (("model",), ()),
        "heads": (("model",), ()),
        "experts": (("model",), ()),
        "rnn": (("model",), ()),
        "embed": ((),),  # no FSDP at serving: weights live TP-only
        "experts_r": ((),),
        "rnn2": ((),),
        "layers": ((),),
        "sup": ((),),
        "kv_heads": (("model",), ()),
        "head_dim": ((),),
        "seq_sharded": (("model",), ()),
    }
)


def plan_sharding(
    mesh: Mesh, shape: tuple, axes: tuple, rules: RuleSet
) -> NamedSharding:
    """Pick a PartitionSpec for one array given its logical axis names."""
    assert len(shape) == len(axes), (shape, axes)
    used: set[str] = set()
    spec = []
    for dim, name in zip(shape, axes):
        chosen = None
        for cand in rules.candidates(name):
            if not cand:
                chosen = None
                break
            if any(a not in mesh.shape or a in used for a in cand):
                continue
            prod = int(np.prod([mesh.shape[a] for a in cand]))
            if dim % prod == 0 and prod > 1:
                chosen = tuple(cand)
                break
        if chosen:
            used.update(chosen)
            spec.append(chosen if len(chosen) > 1 else chosen[0])
        else:
            spec.append(None)
    return NamedSharding(mesh, P(*spec))


def plan_tree(mesh: Mesh, abstract: PyTree, axes_tree: PyTree, rules: RuleSet) -> PyTree:
    """NamedSharding tree for a (ShapeDtypeStruct tree, logical-axes tree) pair."""
    flat_a, treedef = jax.tree_util.tree_flatten(abstract)
    flat_x = treedef.flatten_up_to(axes_tree)
    out = [plan_sharding(mesh, a.shape, tuple(x), rules) for a, x in zip(flat_a, flat_x)]
    return treedef.unflatten(out)


def batch_spec(mesh: Mesh, ndim: int, global_batch: int) -> NamedSharding:
    """Input batch sharding: dim0 over ("pod","data") with fallback."""
    for cand in (("pod", "data"), ("data",), ()):
        if all(a in mesh.shape for a in cand):
            prod = int(np.prod([mesh.shape[a] for a in cand])) if cand else 1
            if cand and global_batch % prod == 0:
                lead = tuple(cand) if len(cand) > 1 else cand[0]
                return NamedSharding(mesh, P(lead, *([None] * (ndim - 1))))
    return NamedSharding(mesh, P(*([None] * ndim)))
