"""Open-loop node-query serving through the program's ServingPipeline.

Set-up builds one `GNNServingEngine` over the configuration's graph with
features and weights drawn from the seed, wraps it in a
`ServingPipeline`, and warms it: first the full graph through the
engine's own subgraph path (its largest shape bucket, so no later batch
can lack a compiled bucket), then the engine's own `warm_fill` of the
cache's pinned hubs, then a warm-up trace drawn from a fixed stream (the
same in every run) replayed at the cell's rate, which compiles the
buckets the traffic reaches and fills the cache's LRU part.

The window is an open loop: every request of the seed's trace is
submitted when it falls due, whatever is still in flight, and its
latency runs from its due time to the moment the pipeline hands back its
answer.  Requests still open when the window closes are waited for, at
most `DRAIN_S` more; one never answered counts as failed.  How late the
load generator submitted (send time minus due time) is reported beside.

`verify` compares every answered row, cache hits included, with the
reference's full-graph forward.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import numpy as np

from bench import harness, reference
from bench.gen import inputs, traffic
from bench.gen.rmat import rmat_edges
from bench.harness import span

DRAIN_S = 60.0
IDLE_SLEEP_S = 0.0005
WARM_SEED = 0x5EED


def setup(cell: Dict, seed: int) -> Dict:
    from repro.core.models import make_gnn_stack
    from repro.graphs.format import COOGraph
    from repro.serving import GNNServingEngine, ServingConfig, ServingPipeline

    cfg, wl = cell["config"], cell["workload"]
    harness.matmul_precision(wl)
    n, dims = cfg["num_vertices"], cfg["dims"]
    t = time.perf_counter()
    src, dst = rmat_edges(n, cfg["num_edges"], cfg["graph_seed"],
                          *cfg["rmat_abc"])
    graph = COOGraph(n, src, dst).gcn_normalized()
    graph_s = time.perf_counter() - t
    x = np.asarray(inputs.features(n, dims[0], seed, cfg["feature_scale"]))
    params = inputs.weights(dims, seed)
    layers = make_gnn_stack(cfg["model"], dims, backend="segment")
    engine_cfg = wl["engine"]
    t = time.perf_counter()
    with span("bench.prepare"):
        engine = GNNServingEngine(
            graph, x, layers, params,
            ServingConfig(batch_size=engine_cfg["batch_size"],
                          num_hops=engine_cfg["num_hops"],
                          fanout=engine_cfg["fanout"],
                          cache_capacity=engine_cfg["cache_capacity"]))
    ctx = {"cell": cell, "wl": wl, "cfg": cfg, "seed": seed, "n": n,
           "src": src, "dst": dst, "x": x, "params": params,
           "engine": engine, "prepare_s": time.perf_counter() - t}
    ctx["pipe"] = ServingPipeline(engine)
    degrees = (np.bincount(graph.src, minlength=n)
               + np.bincount(graph.dst, minlength=n))
    ctx["order"] = traffic.degree_order(degrees)
    parts = {"graph_s": graph_s, "prepare_s": ctx["prepare_s"]}
    with span("bench.warm"):
        t = time.perf_counter()
        engine._run_subgraph(np.arange(n, dtype=np.int32))
        parts["full_graph_bucket_s"] = time.perf_counter() - t
        engine.warm_fill()
        parts["warm_fill_s"] = time.perf_counter() - t - sum(
            v for k, v in parts.items() if k.endswith("bucket_s"))
        t = time.perf_counter()
        warm = traffic.make_trace(wl["traffic"], ctx["order"],
                                  wl["warm_seconds"], WARM_SEED)
        drive(ctx["pipe"], warm, wl["warm_seconds"])
        parts["warm_traffic_s"] = time.perf_counter() - t
    ctx["setup_parts"] = parts
    engine.reset_telemetry()
    ctx["pipe"].reset_telemetry()
    return ctx


def drive(pipe, trace: List, seconds: float) -> Dict:
    """Submit each request of `trace` when due; pump and poll the
    pipeline until every request is answered or `DRAIN_S` have passed
    since the window closed."""
    due = np.array([t for t, _ in trace])
    latency = np.full(len(trace), np.inf)
    late = np.zeros(len(trace))
    answers: Dict[int, object] = {}
    i = 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter()
        while i < len(trace) and t0 + due[i] <= now:
            with span("bench.serve.submit"):
                pipe.submit(i, trace[i][1])
            late[i] = now - (t0 + due[i])
            i += 1
        with span("bench.serve.pump"):
            out = pipe.pump()
        with span("bench.serve.poll"):
            out += pipe.poll()
        done = time.perf_counter()
        for r in out:
            answers[r.rid] = r
            latency[r.rid] = done - (t0 + due[r.rid])
        idle = not pipe.inflight and not pipe.batcher.queue
        if i == len(trace) and (idle or done - t0 > seconds + DRAIN_S):
            break
        if not out:
            time.sleep(IDLE_SLEEP_S)
    return {"t0": t0, "closed": t0 + seconds, "end": time.perf_counter(),
            "due": due, "latency": latency, "late": late, "answers": answers,
            "open_at_close": int(np.sum(due + latency > seconds))}


def window(ctx: Dict, seconds: float) -> Dict:
    trace = traffic.make_trace(ctx["wl"]["traffic"], ctx["order"], seconds,
                               ctx["seed"])
    with span("bench.window"):
        run = drive(ctx["pipe"], trace, seconds)
    lat = run["latency"]
    ok = [r for r in run["answers"].values() if r.status == "ok"]
    ctx["answered"] = [(trace[r.rid][1], np.asarray(r.outputs)) for r in ok]
    tel = ctx["pipe"].telemetry()
    failed = len(trace) - len(ok)
    return {"t0": run["t0"], "window_s": seconds,
            "attempted": len(trace), "failed": failed,
            "due_s": run["due"], "latency_s": lat, "late_s": run["late"],
            "drain_s": run["end"] - run["closed"],
            "open_at_close": run["open_at_close"],
            "cache": tel.get("cache", {}), "engine": tel["engine"],
            "pipeline": tel["pipeline"],
            "engine_compiles": int(tel["engine"]["compiles"]),
            "setup_parts": ctx["setup_parts"]}


def release(ctx: Dict) -> None:
    pipe = ctx.pop("pipe", None)
    if pipe is not None:
        pipe.close()
    ctx.pop("engine", None)
    gc.collect()


def reference_logits(ctx: Dict, control: bool = False) -> np.ndarray:
    """The reference's full-graph logits; with `control`, the control's
    (the reference one step of precision below the workload's)."""
    import jax.numpy as jnp
    n = ctx["n"]
    s, d, v = reference.normalize(ctx["src"], ctx["dst"], n)
    dtype = reference.CONTROL_DTYPE if control else jnp.float32
    return reference.logits(ctx["params"], jnp.asarray(ctx["x"]), s, d, v,
                            n, dtype)


def row_gap(answered: List, want: np.ndarray) -> float:
    """The widest gap of an answered row from the reference's row, over
    the reference's largest output."""
    scale = max(float(np.max(np.abs(want))), 1e-30)
    worst = 0.0
    for ids, rows in answered:
        worst = max(worst, float(np.max(np.abs(rows - want[ids]))))
    return worst / scale


def verify(ctx: Dict) -> Dict[str, float]:
    release(ctx)
    return {"row_gap": row_gap(ctx["answered"], reference_logits(ctx))}
