"""GNN inference serving: the paper's deployment scenario (real-time
recommendation queries against a large graph) through the full serving
engine — continuous batching, L-hop subgraph extraction, degree-aware
result caching.

Each request runs true 2-layer EnGN inference over the L-hop
in-neighbourhood of the requested vertices (not a lookup into a
precomputed table), so the served graph can be updated without a
whole-graph recompute.  Part two replays a flash-crowd workload with
per-request SLOs through the async pipeline (DESIGN.md C12).

    PYTHONPATH=src python examples/serve_gnn.py
"""
import time

import jax
import numpy as np

from repro.core.models import init_stack, make_gnn_stack
from repro.graphs.generate import make_dataset, random_features, zipf_traffic
from repro.serving import (GNNServingEngine, ServingConfig, ServingPipeline,
                           WorkloadSpec, make_trace, replay_closed)


def main():
    g, f, classes = make_dataset("pubmed")
    x = random_features(g.num_vertices, f, seed=0)
    layers = make_gnn_stack("gcn", [f, 32, classes])
    params = init_stack(layers, jax.random.key(0))
    gn = g.gcn_normalized()

    engine = GNNServingEngine(
        gn, x, layers, params,
        ServingConfig(batch_size=128, num_hops=2, fanout=16,
                      cache_capacity=2048, cache_reserved_frac=0.5))

    # simulate a stream of zipf-skewed recommendation queries
    rng = np.random.default_rng(0)
    sample = zipf_traffic(g.degrees(), seed=0)
    n_req = 200
    t0 = time.perf_counter()
    for rid in range(n_req):
        engine.submit(rid, sample(int(rng.integers(1, 20))))
    responses = engine.drain()
    dt = time.perf_counter() - t0

    served = sum(r.outputs.shape[0] for r in responses)
    tel = engine.telemetry()
    lat = tel["latency"]
    print(f"served {len(responses)} requests / {served} vertices in "
          f"{dt*1e3:.1f} ms ({len(responses)/dt:.0f} req/s, "
          f"{served/dt:.0f} vertices/s)")
    print(f"batches: {tel['batcher']['batches']}, coalesced: "
          f"{tel['batcher']['coalesced']} dup vertices, split: "
          f"{tel['batcher']['split_requests']} oversized requests")
    print(f"latency p50 {lat['p50_s']*1e3:.2f} ms  "
          f"p99 {lat['p99_s']*1e3:.2f} ms  mean queue delay "
          f"{lat['mean_queue_delay_s']*1e3:.2f} ms")
    print(f"cache hit rate {tel['cache']['hit_rate']:.1%} "
          f"({tel['cache']['pinned_hits']} pinned hits, "
          f"{tel['cache']['evictions']} evictions)")
    print(f"subgraphs: {tel['engine']['subgraphs']}, mean "
          f"{tel['engine']['subgraph_vertices'] / max(tel['engine']['subgraphs'], 1):.0f} "
          f"vertices each, {tel['engine']['compiles']} XLA compiles")
    assert len(responses) == n_req
    # the engine answers a failed batch with status="error" and carries
    # on: count the statuses, not just the responses
    assert all(r.status == "ok" for r in responses)
    assert all(r.outputs.shape[1] == classes for r in responses)

    # -- part two: flash crowd with SLOs through the async pipeline ------
    pl = ServingPipeline(GNNServingEngine(
        gn, x, layers, params,
        ServingConfig(batch_size=128, num_hops=2, fanout=16,
                      cache_capacity=2048, warm_cache=True,
                      warm_cache_max=128, adaptive_batching=True)))
    spec = WorkloadSpec(n_requests=200, duration_s=0.5, mean_size=8,
                        shape="flash_crowd", slo_s=5.0, seed=1)
    trace = make_trace(spec, g.degrees())
    t0 = time.perf_counter()
    wres = replay_closed(pl, trace, pump_every=0)
    wdt = time.perf_counter() - t0
    ok = sum(r.status == "ok" for r in wres)
    shed = sum(r.status == "expired" for r in wres)
    pstats = pl.telemetry()["pipeline"]
    print(f"pipeline (flash crowd): {ok} ok / {shed} shed in "
          f"{wdt*1e3:.1f} ms ({ok/wdt:.0f} req/s), "
          f"{pstats['adaptive_merges']} merged admissions, "
          f"{pl.engine.stats['warm_filled']} warm-filled hubs")
    pl.close()
    assert ok + shed == len(trace)


if __name__ == "__main__":
    main()
