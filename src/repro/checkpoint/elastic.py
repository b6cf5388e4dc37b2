"""Elastic scaling: resume a run on a different device count / mesh.

Checkpoints are mesh-agnostic (logical arrays), so elasticity is just:
build the best mesh for the surviving devices (launch.mesh.
make_elastic_mesh), derive the param shardings for that mesh, and restore
with device_put.  The data pipeline cursor stored in checkpoint metadata
lets the stream resume without sample loss; the global batch is preserved
by adjusting per-device batch (or gradient-accumulation steps when the
device count no longer divides it).
"""
from __future__ import annotations

import warnings
from typing import Optional, Tuple

import jax

from repro.checkpoint.manager import CheckpointManager
from repro.launch.mesh import make_elastic_mesh
from repro.nn.sharding import param_shardings


def _place_like_params(subtree, shardings):
    """device_put a params-shaped subtree (opt `m`/`v` mirror params)."""
    return jax.tree.map(jax.device_put, subtree, shardings)


def elastic_restore(cfg, ckpt: CheckpointManager, tree_like,
                    n_devices: Optional[int] = None,
                    model_parallel: int = 16,
                    shardings=None,
                    on_placement_error: str = "warn"):
    """Returns (mesh, restored_tree, metadata, step).

    Params AND the params-shaped optimizer moments (`opt["m"]`,
    `opt["v"]`) are re-placed under the surviving mesh's shardings.
    `shardings` overrides the derived `param_shardings(cfg, mesh)` (a
    params-shaped pytree of NamedSharding).  Placement failures are
    loud: `on_placement_error="warn"` (default) keeps the host-resident
    arrays and emits a RuntimeWarning; `"raise"` propagates.
    """
    if on_placement_error not in ("warn", "raise"):
        raise ValueError(f"on_placement_error={on_placement_error!r}")
    mesh = make_elastic_mesh(n_devices, model_parallel)
    sh = param_shardings(cfg, mesh) if shardings is None else shardings
    tree, meta, step = ckpt.restore(tree_like, shardings=None)
    if not (isinstance(tree, dict) and "params" in tree):
        return mesh, tree, meta, step
    try:
        placed = dict(tree)
        placed["params"] = _place_like_params(tree["params"], sh)
        if isinstance(tree.get("opt"), dict):
            opt = dict(tree["opt"])
            for moment in ("m", "v"):
                if moment in opt:
                    opt[moment] = _place_like_params(opt[moment], sh)
            placed["opt"] = opt
    except Exception as e:  # noqa: BLE001 — surfaced, never swallowed
        if on_placement_error == "raise":
            raise
        warnings.warn(
            f"elastic_restore: placement onto {mesh.shape} failed "
            f"({e!r}); returning host-resident arrays",
            RuntimeWarning, stacklevel=2)
        return mesh, tree, meta, step
    return mesh, placed, meta, step


def adjust_microbatching(global_batch: int, n_data_shards: int,
                         prev_micro_steps: int = 1) -> Tuple[int, int]:
    """Keep the global batch constant across a device-count change:
    returns (per_shard_batch, micro_steps) with
    per_shard * micro * n_shards == global_batch when an exact split
    exists, otherwise the largest feasible batch <= global_batch."""
    for micro in range(prev_micro_steps, global_batch + 1):
        if global_batch % (n_data_shards * micro) == 0:
            return global_batch // (n_data_shards * micro), micro
    # no exact split (shard count does not divide the batch):
    # best-effort under the target with one micro step
    return max(global_batch // n_data_shards, 1), 1
