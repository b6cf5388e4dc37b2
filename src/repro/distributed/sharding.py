"""Device meshes and batch partition specs.

`ring_mesh` builds the 1-D mesh of the RER ring dataflow (DESIGN.md
C2); `batch_pspec` shards a batch-leading array under a rules table
mapping logical axes to mesh axes.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def mesh_shape_dict(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def ring_mesh(num_shards: Optional[int] = None,
              axis: str = "ring") -> Mesh:
    """1-D device mesh for the RER ring dataflow (DESIGN.md C2).

    Defaults to all local devices; a smaller `num_shards` takes a prefix
    (useful for the 1-device degenerate ring in tests and CPU serving).
    """
    devs = jax.devices()
    p = num_shards or len(devs)
    if p > len(devs):
        raise ValueError(f"ring of {p} shards needs {p} devices, "
                         f"have {len(devs)}")
    return Mesh(np.asarray(devs[:p]), (axis,))


def batch_pspec(mesh: Mesh, rank: int, seq_axis: Optional[int] = None, *,
                rules: dict, shape=None) -> P:
    """PartitionSpec for a batch-leading array (tokens, labels, ...)
    under `rules`, which maps the logical axes "batch" and "seq" to
    mesh axes.

    When `shape` is given, any dim that does not divide its mesh-axis
    size falls back to replication (e.g. long_500k decode: batch=1
    cannot shard over data=16)."""
    spec = [rules.get("batch")] + [None] * (rank - 1)
    if seq_axis is not None and rules.get("seq"):
        spec[seq_axis] = rules["seq"]
    if shape is not None:
        ms = mesh_shape_dict(mesh)

        def _size(ax):
            if ax is None:
                return 1
            if isinstance(ax, tuple):
                return int(np.prod([ms.get(a, 1) for a in ax]))
            return ms.get(ax, 1)

        spec = [ax if (ax is not None and dim % _size(ax) == 0) else None
                for dim, ax in zip(shape, spec)]
    return P(*spec)
