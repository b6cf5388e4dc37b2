"""The async SLO-driven serving pipeline (DESIGN.md C12): deadline
admission control, bounded in-flight backpressure, pipeline-vs-sync
equivalence, replicated engines, the workload generator, cache
warm-fill, the ServingConfig/EnGNConfig unification shim, and the typed
`PreparedPlan` returned by every prepare_* entry point."""
import time

import numpy as np
import pytest

from repro.serving.batcher import GNNBatcher, Request
from repro.serving.engine import GNNServingEngine, ServingConfig
from repro.serving.pipeline import ServingPipeline
from repro.serving.replicate import ReplicatedServer
from repro.serving.workload import (WorkloadSpec, make_trace, replay_closed)


def _echo_infer(ids):
    return np.stack([ids, ids * 2], axis=1).astype(np.float32)


def _fixture(batch_size=16, cache_capacity=0, **cfg_kw):
    import jax
    from repro.core.models import make_gnn_stack, init_stack
    from repro.graphs.generate import rmat_graph, random_features

    g = rmat_graph(300, 2400, seed=0).gcn_normalized()
    x = random_features(300, 8, seed=1)
    layers = make_gnn_stack("gcn", [8, 16, 4])
    params = init_stack(layers, jax.random.key(0))
    cfg = ServingConfig(batch_size=batch_size,
                        cache_capacity=cache_capacity, **cfg_kw)
    return g, x, layers, params, cfg


def _requests(n=24, n_vertices=300, seed=3):
    rng = np.random.default_rng(seed)
    return [(i, rng.integers(0, n_vertices,
                             rng.integers(1, 9)).astype(np.int32))
            for i in range(n)]


# ------------------------------------------------------ deadline shedding
def test_batcher_sheds_expired_requests():
    """A queued request whose deadline has passed is answered
    status="expired" with empty outputs; live ones survive."""
    b = GNNBatcher(_echo_infer, batch_size=8)
    now = time.monotonic()
    b.submit(Request(1, np.arange(3, dtype=np.int32),
                     deadline_s=now - 0.1))
    b.submit(Request(2, np.arange(3, dtype=np.int32),
                     deadline_s=now + 60.0))
    b.submit(Request(3, np.arange(3, dtype=np.int32)))   # no SLO
    shed = b.shed_expired(now)
    assert [r.rid for r in shed] == [1]
    assert shed[0].status == "expired" and shed[0].outputs.size == 0
    assert b.stats["shed"] == 1
    served = b.drain()
    assert sorted(r.rid for r in served) == [2, 3]
    assert all(r.status == "ok" for r in served)


def test_batcher_shed_uses_eta_and_spares_inflight():
    """With an ETA model, a deadline that the queue estimate says will
    be missed sheds proactively; partially-admitted requests are never
    shed (their slices are already in flight)."""
    b = GNNBatcher(_echo_infer, batch_size=4)
    now = time.monotonic()
    b.submit(Request(1, np.arange(10, dtype=np.int32),
                     deadline_s=now + 1.0))              # head: split
    b.step()                                             # admit one slice
    b.submit(Request(2, np.arange(4, dtype=np.int32),
                     deadline_s=now + 1.0))
    # brutal ETA: every queued vertex costs 1s => rid 2 cannot make it,
    # rid 1 is in flight and must survive regardless
    shed = b.shed_expired(now, eta_s=lambda ahead: float(ahead))
    assert [r.rid for r in shed] == [2]
    served = b.drain()
    assert [r.rid for r in served] == [1]


def test_pipeline_sheds_late_request_with_expired_status():
    pl = ServingPipeline(GNNServingEngine(*_fixture()[:4], _fixture()[4]))
    pl.submit(0, np.arange(4, dtype=np.int32))
    pl.drain()                                           # trains the EWMA
    assert pl._ewma_s_per_vertex is not None
    pl.submit(1, np.arange(4, dtype=np.int32),
              deadline_s=time.monotonic() - 1.0)
    shed = pl.pump()
    assert [(r.rid, r.status) for r in shed] == [(1, "expired")]
    assert not any(r.rid == 1 for r in pl.drain())


def test_pipeline_default_slo_applies_to_submissions():
    g, x, layers, params, _ = _fixture()
    cfg = ServingConfig(batch_size=16, default_slo_s=120.0)
    pl = ServingPipeline(GNNServingEngine(g, x, layers, params, cfg))
    pl.submit(0, np.arange(3, dtype=np.int32))
    assert pl.batcher.queue[0].deadline_s is not None
    pl.submit(1, np.arange(3, dtype=np.int32), deadline_s=None, slo_s=None)
    assert pl.batcher.queue[1].deadline_s is not None
    assert all(r.status == "ok" for r in pl.drain())


# ------------------------------------------------------- backpressure
def test_pipeline_bounds_inflight_to_depth():
    """The pump never holds more than `depth` batches in flight, however
    deep the backlog — extraction-pool saturation backpressures
    admission instead of queueing unbounded extractions."""
    g, x, layers, params, _ = _fixture()
    cfg = ServingConfig(batch_size=4, pipeline_depth=2, extract_workers=2,
                        adaptive_batching=False)
    pl = ServingPipeline(GNNServingEngine(g, x, layers, params, cfg))
    for rid, ids in _requests(n=30):
        pl.submit(rid, ids)
    # pump repeatedly WITHOUT completing: in-flight must clamp at depth
    for _ in range(5):
        pl.pump()
        assert len(pl.inflight) <= 2
    assert pl.stats["inflight_hwm"] == 2
    assert len(pl.drain()) == 30
    pl.close()


# ------------------------------------------------- pipeline equivalence
def test_pipeline_matches_sync_engine_on_fixed_traffic():
    """Async pipelined serving returns bit-comparable outputs to the
    synchronous loop on identical traffic (no cache, so every batch
    runs the model)."""
    g, x, layers, params, cfg = _fixture()
    reqs = _requests()
    sync = GNNServingEngine(g, x, layers, params, cfg)
    for rid, ids in reqs:
        sync.submit(rid, ids)
    want = {r.rid: r.outputs for r in sync.drain()}

    acfg = ServingConfig(batch_size=16, pipeline_depth=3,
                         extract_workers=2, adaptive_batching=True)
    pl = ServingPipeline(GNNServingEngine(g, x, layers, params, acfg))
    for rid, ids in reqs:
        pl.submit(rid, ids)
    got = {r.rid: r.outputs for r in pl.drain()}
    assert set(got) == set(want)
    for rid in want:
        np.testing.assert_allclose(got[rid], want[rid],
                                   rtol=2e-5, atol=2e-5)
    pl.close()


def test_engine_step_drain_are_pipeline_wrappers():
    """The engine's historical sync API now runs through an inline
    depth-1 pipeline — same responses, and the compat pipeline's
    telemetry confirms it carried the batches."""
    g, x, layers, params, cfg = _fixture()
    eng = GNNServingEngine(g, x, layers, params, cfg)
    eng.submit(0, np.arange(5, dtype=np.int32))
    res = eng.step()
    assert len(res) == 1 and res[0].status == "ok"
    assert eng._compat is not None
    assert eng._compat.stats["pumped_batches"] == 1
    assert eng._compat.pool is None          # inline: no worker threads


# ------------------------------------------------------- replication
def test_replicated_round_robin_balances_evenly():
    g, x, layers, params, cfg = _fixture()
    srv = ReplicatedServer(g, x, layers, params, replicas=3, config=cfg,
                           balancer="round_robin")
    reqs = _requests(n=30)
    for rid, ids in reqs:
        srv.submit(rid, ids)
    assert srv.routed.tolist() == [10, 10, 10]
    res = srv.drain()
    assert sorted(r.rid for r in res) == sorted(r for r, _ in reqs)
    srv.close()


def test_replicated_least_outstanding_tracks_load():
    """least_outstanding routes around a replica with a deep queue."""
    g, x, layers, params, cfg = _fixture()
    srv = ReplicatedServer(g, x, layers, params, replicas=2, config=cfg,
                           balancer="least_outstanding")
    srv.pipelines[0].submit(999, np.arange(64, dtype=np.int32))  # preload
    for rid, ids in _requests(n=8):
        srv.submit(rid, ids)
    assert srv.routed[1] > srv.routed[0]
    srv.drain()
    srv.close()


def test_replicated_hub_affinity_pins_hub_to_one_replica():
    """Every request targeting a pinned hub lands on the same replica."""
    g, x, layers, params, _ = _fixture()
    cfg = ServingConfig(batch_size=16, cache_capacity=64)
    srv = ReplicatedServer(g, x, layers, params, replicas=2, config=cfg,
                           balancer="hub_affinity")
    hub = int(np.argmax(g.degrees()))
    assert hub in srv.engines[0].cache.pinned_ids
    picks = {srv.submit(100 + i, np.array([hub], np.int32))
             for i in range(6)}
    assert len(picks) == 1
    srv.drain()
    srv.close()


def test_replicated_outputs_match_single_engine():
    g, x, layers, params, cfg = _fixture()
    reqs = _requests(n=12)
    single = GNNServingEngine(g, x, layers, params, cfg)
    for rid, ids in reqs:
        single.submit(rid, ids)
    want = {r.rid: r.outputs for r in single.drain()}
    srv = ReplicatedServer(g, x, layers, params, replicas=2, config=cfg)
    for rid, ids in reqs:
        srv.submit(rid, ids)
    got = {r.rid: r.outputs for r in srv.drain()}
    assert set(got) == set(want)
    for rid in want:
        np.testing.assert_allclose(got[rid], want[rid],
                                   rtol=2e-5, atol=2e-5)
    srv.close()


# ---------------------------------------------------- workload generator
def test_workload_trace_is_deterministic():
    g, *_ = _fixture()
    for shape in ("constant", "diurnal", "flash_crowd", "hub_storm"):
        s = WorkloadSpec(n_requests=40, duration_s=2.0, shape=shape,
                         seed=7)
        t1, t2 = (make_trace(s, g.degrees()) for _ in range(2))
        for a, b in zip(t1, t2):
            assert a.t_offset_s == b.t_offset_s
            np.testing.assert_array_equal(a.vertex_ids, b.vertex_ids)


def test_workload_flash_crowd_spikes_the_middle():
    g, *_ = _fixture()
    spec = WorkloadSpec(n_requests=400, duration_s=10.0,
                        shape="flash_crowd", burst_factor=6.0,
                        burst_frac=0.2, seed=1)
    t = np.array([r.t_offset_s for r in make_trace(spec, g.degrees())])
    mid = ((t >= 4.0) & (t <= 6.0)).sum()
    # 20% of the window at 6x rate vs 80% at 1x -> ~60% of arrivals
    assert mid / t.size > 0.4
    assert t.min() >= 0.0 and t.max() <= 10.0


def test_workload_hub_storm_targets_hubs_in_burst_window():
    g, *_ = _fixture()
    spec = WorkloadSpec(n_requests=200, duration_s=10.0,
                        shape="hub_storm", storm_hubs=8, seed=2)
    trace = make_trace(spec, g.degrees())
    order = np.argsort(-g.degrees(), kind="stable")
    hubs = set(order[:8].tolist())
    burst = [r for r in trace if 4.0 <= r.t_offset_s <= 6.0]
    assert burst
    for r in burst:
        assert set(r.vertex_ids.tolist()) <= hubs


def test_workload_replay_closed_serves_everything():
    g, x, layers, params, cfg = _fixture(cache_capacity=64)
    pl = ServingPipeline(GNNServingEngine(g, x, layers, params, cfg))
    spec = WorkloadSpec(n_requests=40, duration_s=0.5, shape="diurnal",
                        seed=4)
    res = replay_closed(pl, make_trace(spec, g.degrees()), pump_every=4)
    assert sorted(r.rid for r in res if r.status == "ok") == list(range(40))
    pl.close()


# -------------------------------------------------------- cache warm-fill
def test_warm_fill_precomputes_pinned_hubs():
    """With warm_cache on, the pinned hub region is served from cache on
    first touch — zero subgraph extractions for a hub-only request."""
    g, x, layers, params, _ = _fixture()
    cfg = ServingConfig(batch_size=16, cache_capacity=64, warm_cache=True,
                        warm_cache_max=16)
    eng = GNNServingEngine(g, x, layers, params, cfg)
    assert eng.stats["warm_filled"] == 16
    eng.reset_telemetry()
    hub = int(np.argmax(g.degrees()))
    eng.submit(0, np.array([hub], np.int32))
    res = eng.drain()
    assert len(res) == 1
    assert eng.stats["subgraphs"] == 0              # pure cache hit
    assert eng.cache.stats["pinned_hits"] == 1


def test_warm_fill_matches_cold_inference():
    g, x, layers, params, _ = _fixture()
    cold = GNNServingEngine(g, x, layers, params,
                            ServingConfig(batch_size=16))
    warm = GNNServingEngine(
        g, x, layers, params,
        ServingConfig(batch_size=16, cache_capacity=64, warm_cache=True,
                      warm_cache_max=8))
    hubs = np.argsort(-g.degrees(), kind="stable")[:4].astype(np.int32)
    cold.submit(0, hubs)
    warm.submit(0, hubs)
    np.testing.assert_allclose(warm.drain()[0].outputs,
                               cold.drain()[0].outputs,
                               rtol=2e-5, atol=2e-5)


# ------------------------------------- config unification (shim removed)
def test_serving_config_embeds_engn_config():
    from repro.core.engn import EnGNConfig
    cfg = ServingConfig(engn=EnGNConfig(in_dim=0, out_dim=0,
                                        device_budget_bytes=123,
                                        ring_shards=2,
                                        streaming_mode="callback",
                                        tile_value_dtype="int8"))
    # execution knobs live on the embedded config, nowhere else
    assert cfg.engn.device_budget_bytes == 123
    assert cfg.engn.ring_shards == 2
    assert cfg.engn.streaming_mode == "callback"
    assert cfg.engn.tile_value_dtype == "int8"


def test_serving_config_deprecated_mirrors_are_gone():
    """The one-release write-through shim was removed: the old mirror
    names are unknown fields (TypeError), not silent no-ops, and the
    resolved-mirror attributes no longer exist on instances."""
    for kw in ("device_budget_bytes", "ring_shards",
               "tiled_streaming_mode", "tiled_value_dtype"):
        with pytest.raises(TypeError):
            ServingConfig(**{kw: 1})
    cfg = ServingConfig()
    for name in ("device_budget_bytes", "ring_shards",
                 "tiled_streaming_mode", "tiled_value_dtype"):
        assert not hasattr(cfg, name)
    assert cfg.engn.device_budget_bytes is None


def test_reset_telemetry_alias_is_consistent():
    """reset_telemetry is the primary name on both engine and batcher;
    reset_stats stays as the batcher's historical alias."""
    b = GNNBatcher(_echo_infer, batch_size=4)
    b.submit(Request(0, np.arange(3, dtype=np.int32)))
    b.drain()
    assert b.stats["requests"] == 1
    b.reset_telemetry()
    assert b.stats["requests"] == 0
    b.submit(Request(1, np.arange(3, dtype=np.int32)))
    b.drain()
    b.reset_stats()                        # alias, same semantics
    assert b.stats["requests"] == 0


# ------------------------------------------------- PreparedPlan round-trip
@pytest.mark.parametrize("backend", ["segment", "blocked", "fused",
                                     "tiled", "ring"])
def test_prepared_plan_round_trip(backend):
    """Every prepare_* entry point returns a typed `PreparedPlan` whose
    typed attributes agree with the carrier's meta block and which
    drives `apply` directly; the removed dict view stays removed."""
    import jax
    import jax.numpy as jnp
    from repro.core.engn import prepare_graph
    from repro.core.models import make_gnn
    from repro.core.plan import PreparedPlan
    from repro.graphs.generate import rmat_graph, random_features

    g = rmat_graph(96, 700, seed=0).gcn_normalized()
    x = random_features(96, 8, seed=1)
    layer = make_gnn("gcn", 8, 4, backend=backend, tile=16)
    if backend == "ring":
        layer.cfg.ring_shards = 2
    elif backend == "tiled":
        layer.cfg.tile = 32
        layer.cfg.device_budget_bytes = 200_000
    plan = prepare_graph(g, layer.cfg)
    assert isinstance(plan, PreparedPlan)
    assert plan.backend == backend
    assert plan.n == 96
    # the MutableMapping view is gone: key access raises, the carrier
    # and typed attributes are the supported surfaces
    with pytest.raises(TypeError):
        plan["backend"]
    assert plan.as_dict() is plan.carrier
    assert plan.carrier["backend"] == backend
    if backend == "segment":
        assert plan.tile_format is None
        # the edge-chunk counters: one chunk, under EDGE_CHUNK_BYTES
        assert plan.meta["chunks"] == 1
        assert plan.footprint_bytes == plan.meta["device_bytes"] > 0
    else:
        assert plan.tile_format in ("dense", "packed")
        assert plan.footprint_bytes > 0
        assert plan.meta                     # the meta block resolves
    if backend == "tiled":
        assert plan.streaming_mode in ("chunk_queue", "callback")
    else:
        assert plan.streaming_mode is None
    y = layer.apply(layer.init(jax.random.key(0)), plan, jnp.asarray(x))
    assert np.asarray(y).shape == (96, 4)
