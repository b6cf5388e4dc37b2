"""Elastic GNN ring training: re-mesh, re-plan, re-jit, resume.

`ElasticGNNTrainer` owns the mutable half of a `--gnn` training run —
the prepared plan (`PreparedPlan`, DESIGN.md C12) and the jitted train
step — so the fault-tolerance hooks can swap both out underneath a
running `FaultTolerantRunner` without touching its loop:

  * `on_failure` — a `ShardLossError` (distributed/chaos.py, or a real
    device failure surfaced by the runner) rebuilds the ring plan for
    the surviving shard count: `prepare_graph` re-runs
    `build_ring_tile_shards`/`prepare_ring` on the smaller mesh and the
    step is re-jitted against the new plan.  When the survivors cannot
    hold the per-shard footprint under `device_budget_bytes`, the
    budget gate degrades the plan to the streamed out-of-core `tiled`
    backend (auto_spill) — training continues through its custom_vjp
    reverse path instead of aborting.
  * `on_straggler` — repeated straggler episodes (a chronically slow
    shard) trigger the same re-mesh policy past `strike_limit` strikes:
    shrink the ring by one and rebalance.

Checkpoints are mesh-agnostic (logical arrays), so the runner's
restore-and-replay works unchanged across a re-mesh.  See DESIGN.md
C13.
"""
from __future__ import annotations

import time
from typing import Any, Dict, Optional

from repro.distributed.chaos import ShardLossError


class ElasticGNNTrainer:
    """Owns (plan, jitted step) for a GNN stack and re-meshes on demand.

    The `step` method is the stable callable handed to
    `FaultTolerantRunner`; `rebuild` swaps the plan and the jit under
    it atomically (between steps — the runner only calls hooks outside
    the step).
    """

    def __init__(self, *, layers, graph, x, y_true,
                 hidden: int, peak_lr: float, steps: int,
                 strike_limit: int = 3):
        self.layers = layers
        self.graph = graph
        self.x = x
        self.y_true = y_true
        self.hidden = hidden
        self.peak_lr = peak_lr
        self.steps = steps
        self.strike_limit = int(strike_limit)
        self.plan = None
        self.loss_fn = None
        self.consts = ()
        self._jit_step = None
        self.stats: Dict[str, Any] = {
            "remesh_count": 0, "remesh_s": 0.0, "strikes": 0,
            "degraded": 0, "shards": None,
        }
        self.rebuild()

    # ---------------------------------------------------------- build
    @property
    def backend(self) -> Optional[str]:
        return None if self.plan is None else self.plan.backend

    @property
    def shards(self) -> Optional[int]:
        """Current ring shard count (None when the plan is not a ring)."""
        if self.plan is None or self.plan.backend != "ring":
            return None
        return self.plan.meta.get("shards")

    def rebuild(self, num_shards: Optional[int] = None):
        """(Re)prepare the plan and re-jit the step.  `num_shards`
        re-targets the ring at that many survivors; the budget gate may
        still degrade the result to the tiled streamed backend."""
        from repro.core.engn import prepare_graph
        from repro.core.models import apply_stack
        from repro.core.plan import split_arrays
        from repro.training.train_lib import make_gnn_train_step
        import jax
        import jax.numpy as jnp

        if num_shards is not None:
            for layer in self.layers:
                layer.cfg.ring_shards = int(num_shards)
        self.plan = prepare_graph(self.graph, self.layers[0].cfg,
                                  out_dim=self.hidden)
        layers = self.layers
        # the plan's device operands, the features and the labels enter
        # the step as arguments, not as baked-in constants
        arrays, rebuild = split_arrays(
            (self.plan.carrier, self.x, self.y_true))

        def loss_fn(ps, batch, arrays):
            carrier, x, y_true = rebuild(arrays)
            nodes = jnp.asarray(batch["nodes"])
            labels = y_true[nodes]
            logits = apply_stack(layers, ps, carrier, x)[nodes]
            ll = jax.nn.log_softmax(logits, -1)
            return -jnp.mean(jnp.take_along_axis(ll, labels[:, None], 1))

        self.loss_fn, self.consts = loss_fn, (arrays,)
        self._jit_step = make_gnn_train_step(
            loss_fn, peak_lr=self.peak_lr, warmup=min(20, self.steps),
            total_steps=self.steps)
        self.stats["shards"] = self.plan.meta.get("shards") \
            if self.plan.backend == "ring" else None
        return self.plan

    def step(self, params, opt, batch):
        """Stable train-step callable; delegates to the current jit."""
        return self._jit_step(params, opt, batch, *self.consts)

    # --------------------------------------------------------- policy
    def remesh(self, num_shards: int):
        """Rebuild for `num_shards` survivors, recording recovery cost."""
        t0 = time.perf_counter()
        self.rebuild(num_shards=max(1, int(num_shards)))
        self.stats["remesh_s"] += time.perf_counter() - t0
        self.stats["remesh_count"] += 1
        if self.plan.backend != "ring":
            self.stats["degraded"] += 1
        self.stats["strikes"] = 0
        return self.plan

    def on_failure(self, exc: Exception):
        """FaultTolerantRunner hook: shard loss shrinks the ring to the
        survivor count; other failures retry-with-replay unchanged."""
        if not isinstance(exc, ShardLossError):
            return
        if self.layers[0].cfg.backend != "ring":
            return          # shard loss is only meaningful for the ring
        current = self.shards or self.layers[0].cfg.ring_shards or 1
        self.remesh(max(1, current - exc.lost_shards))

    def on_straggler(self, step: int, dt: float):
        """FaultTolerantRunner hook: `strike_limit` straggler episodes
        shrink the ring by one (evict the chronically slow shard)."""
        self.stats["strikes"] += 1
        if self.layers[0].cfg.backend != "ring":
            return
        current = self.shards
        if (self.stats["strikes"] >= self.strike_limit
                and current is not None and current > 1):
            self.remesh(current - 1)


__all__ = ["ElasticGNNTrainer"]
