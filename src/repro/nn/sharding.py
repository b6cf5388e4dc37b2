"""LM sharding rules: logical axes -> mesh axes, activation constrainers.

The 2-D scheme (DESIGN.md S5): parameters shard input dims over "data"
(FSDP-style just-in-time gather) and output dims over "model" (TP);
activations shard batch over ("pod","data") and sequence over "model"
(Megatron-style sequence parallelism on the residual stream).  Logical
axes that don't divide evenly fall back to replication.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.sharding import mesh_shape_dict
from repro.nn.param import DEFAULT_RULES, tree_pspecs
from repro.nn.transformer import model_specs


def make_rules(mesh: Mesh, seq_sharded: bool = True) -> Dict[str, object]:
    """Adapt DEFAULT_RULES to the mesh at hand (drop missing axes)."""
    names = set(mesh.axis_names)
    rules = {}
    for k, v in DEFAULT_RULES.items():
        if isinstance(v, tuple):
            v2 = tuple(a for a in v if a in names)
            rules[k] = v2 if v2 else None
        else:
            rules[k] = v if v in names else None
    if not seq_sharded:
        rules["seq"] = None
    return rules


def param_pspecs(cfg, mesh: Mesh, rules=None):
    """PartitionSpec tree matching the model parameter tree."""
    rules = rules or make_rules(mesh)
    return tree_pspecs(model_specs(cfg), mesh_shape_dict(mesh), rules)


def param_shardings(cfg, mesh: Mesh, rules=None):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_pspecs(cfg, mesh, rules))


class Constrainer:
    """Callable applying with_sharding_constraint from logical axes, with
    divisibility fallback per dimension (replicate what doesn't divide)."""

    def __init__(self, mesh: Mesh, rules: Optional[dict] = None):
        self.mesh = mesh
        self.rules = rules or make_rules(mesh)
        self.shape = mesh_shape_dict(mesh)

    def _axis_size(self, ax) -> int:
        if ax is None:
            return 1
        if isinstance(ax, tuple):
            return int(np.prod([self.shape.get(a, 1) for a in ax]))
        return self.shape.get(ax, 1)

    def __call__(self, x, logical_axes):
        spec = []
        for dim, ax in zip(x.shape, logical_axes):
            mesh_ax = self.rules.get(ax) if ax is not None else None
            if mesh_ax is None or dim % self._axis_size(mesh_ax) != 0:
                spec.append(None)
            else:
                spec.append(mesh_ax)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*spec)))
