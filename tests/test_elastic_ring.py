"""Elastic fault-tolerant ring training (DESIGN.md C13).

The acceptance scenario: a seeded chaos schedule — one transient step
exception, one torn checkpoint write, shard loss, one straggler episode
— against an 8-shard ring `--gnn` run.  The run must complete all
steps, re-mesh to the surviving shard count, and land on the fault-free
segment-backend trajectory.

Runs under the 8-device host view (tests/conftest.py forces
--xla_force_host_platform_device_count=8).
"""
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.distributed.chaos import (ChaosInjector, FaultEvent, FaultPlan,
                                     ShardLossError, VirtualClock)
from repro.distributed.fault import FaultConfig, FaultTolerantRunner


def _build(backend, steps, **kw):
    from repro.launch.train import build_gnn
    return build_gnn(model="gcn", dataset="pubmed", backend=backend,
                     steps=steps, hidden=8, batch=64,
                     max_vertices=300, max_edges=2000, feature_dim=128,
                     **kw)


def _segment_losses(steps):
    step, state, data, _gd, _aux = _build("segment", steps)
    ps, opt = state["params"], state["opt"]
    losses = []
    for _ in range(steps):
        ps, opt, m = step(ps, opt, next(data))
        losses.append(float(m["loss"]))
    return losses


def test_chaos_schedule_against_8_shard_ring(tmp_path):
    """The tentpole acceptance: all four fault kinds against a ring-8
    run; completes, re-meshes to 6 survivors, matches segment."""
    steps = 12
    seg = _segment_losses(steps)

    step, state, data, gd, aux = _build("ring", steps, ring_shards=8)
    trainer = aux["trainer"]
    assert gd.backend == "ring" and gd.meta["shards"] == 8

    losses = []

    def logged(ps, opt, batch):
        ps, opt, m = step(ps, opt, batch)
        losses.append(float(m["loss"]))
        return ps, opt, m

    # schedule (step = step-fn invocation index): transient at 3
    # replays through retry; the torn save lands between the transient
    # and the shard loss, so recovery from the shard loss must fall
    # back past the corrupt checkpoint; the straggler episode strikes
    # but stays under the strike limit
    plan = FaultPlan((
        FaultEvent(3, "transient"),
        FaultEvent(5, "torn_ckpt", style="leaf"),
        FaultEvent(7, "shard_loss", lost_shards=2),
        FaultEvent(10, "straggler", delay_s=50.0),
    ), seed=0)
    clock = VirtualClock()
    inj = ChaosInjector(plan, clock=clock, base_step_s=1.0)
    mgr = CheckpointManager(tmp_path, keep=3)
    runner = FaultTolerantRunner(
        inj.wrap_step(logged), inj.wrap_checkpoint(mgr),
        FaultConfig(ckpt_every=2, retry_backoff_s=0.5),
        on_failure=trainer.on_failure,
        on_straggler=trainer.on_straggler,
        clock=clock, sleep=clock.sleep)

    state, last = runner.run(state, data, num_steps=steps)
    mgr.wait()

    # every scheduled fault fired exactly once
    assert inj.stats == {"shard_loss": 1, "transient": 1,
                         "straggler": 1, "torn_ckpt": 1}
    # the run completed every step, exactly once per logical step
    assert last == steps
    assert int(state["opt"]["count"]) == steps
    # re-meshed to the surviving shard count
    assert trainer.stats["remesh_count"] == 1
    assert trainer.plan.backend == "ring"
    assert trainer.plan.meta["shards"] == 6
    # recovery telemetry is populated
    assert runner.stats["failures"] == 2        # transient + shard loss
    assert runner.stats["restores"] >= 1
    assert runner.stats["lost_steps"] >= 1
    assert runner.stats["mttr_s"] > 0
    assert runner.stats["stragglers"] == 1
    # ... and the trajectory lands where the fault-free segment run does
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    np.testing.assert_allclose(losses[-1], seg[-1], rtol=5e-3, atol=1e-4)


def test_shard_loss_degrades_to_tiled_under_budget():
    """When the survivor count cannot hold the per-shard footprint
    under the budget, the re-mesh degrades to the streamed tiled
    backend instead of aborting — and still trains on the segment
    trajectory."""
    steps = 3
    seg = _segment_losses(steps)
    step, state, data, gd, aux = _build("ring", steps, ring_shards=4)
    trainer = aux["trainer"]
    assert gd.backend == "ring" and gd.meta["shards"] == 4

    # the budget arrives after the initial build (a live reconfig):
    # too small for any ring stripe, so the next re-mesh spills
    for layer in trainer.layers:
        layer.cfg.device_budget_bytes = 50_000
    trainer.on_failure(ShardLossError(lost_shards=3))

    assert trainer.stats["remesh_count"] == 1
    assert trainer.stats["degraded"] == 1
    assert trainer.plan.backend == "tiled"
    assert trainer.plan.meta["trainable"] is True

    ps, opt = state["params"], state["opt"]
    losses = []
    for _ in range(steps):
        ps, opt, m = step(ps, opt, next(data))
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses))
    np.testing.assert_allclose(losses, seg, rtol=1e-3, atol=1e-4)


def test_straggler_strikes_shrink_ring():
    """`strike_limit` straggler episodes evict the slow shard."""
    _step, _state, _data, gd, aux = _build("ring", 3, ring_shards=4,
                                           strike_limit=2)
    trainer = aux["trainer"]
    assert gd.meta["shards"] == 4
    trainer.on_straggler(1, 99.0)
    assert trainer.stats["strikes"] == 1
    assert trainer.stats["remesh_count"] == 0   # under the limit
    trainer.on_straggler(2, 99.0)
    assert trainer.stats["remesh_count"] == 1
    assert trainer.stats["strikes"] == 0        # reset after re-mesh
    assert trainer.plan.meta["shards"] == 3


def test_non_shard_loss_failures_do_not_remesh():
    _step, _state, _data, _gd, aux = _build("ring", 3, ring_shards=2)
    trainer = aux["trainer"]
    trainer.on_failure(RuntimeError("transient blip"))
    assert trainer.stats["remesh_count"] == 0
    assert trainer.plan.meta["shards"] == 2


def test_shard_loss_on_non_ring_backend_is_ignored():
    _step, _state, _data, gd, aux = _build("segment", 3)
    trainer = aux["trainer"]
    trainer.on_failure(ShardLossError(lost_shards=1))
    assert trainer.stats["remesh_count"] == 0
    assert trainer.plan.backend == "segment"


def test_remesh_floor_is_one_shard():
    _step, _state, _data, _gd, aux = _build("ring", 3, ring_shards=2)
    trainer = aux["trainer"]
    trainer.on_failure(ShardLossError(lost_shards=5))
    assert trainer.plan.meta["shards"] == 1     # clamped, never 0
    plan = trainer.remesh(0)                    # degenerate ask clamps too
    assert plan.meta["shards"] == 1
