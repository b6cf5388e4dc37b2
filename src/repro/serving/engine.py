"""End-to-end GNN serving engine (DESIGN.md S7).

Ties the serving stack together: requests enter the continuous
`GNNBatcher`; each batch probes the `DegreeAwareCache` for already-served
vertices; cache misses are answered by extracting the L-hop
in-neighbourhood of the miss set (`graphs/subgraph.py`) and running the
full multi-layer EnGN stack over just that subgraph — true per-request
GNN inference rather than a row lookup into a precomputed table.

Per-batch subgraphs have data-dependent shapes, which would force one XLA
compile per distinct (|V|, |E|).  The engine pads both to power-of-two
buckets (padding edges carry weight 0 and point at a padded dummy vertex,
so sum-aggregation is unaffected), keeping the number of compiled
programs logarithmic in batch size.  Bucketing is only applied when every
layer uses sum aggregation; other ops fall back to exact eager execution.

The model stack must use the "segment" aggregation backend: the engine
feeds each layer a per-batch edge-list graph dict, and segment is the
backend that consumes (src, dst, val) directly.  Relation-typed graphs
are first-class: the extractor carries per-edge `rel` through the CSR
and into each subgraph, so R-GCN / Gated-GCN stacks (the C10 stage
contract) serve, spill to the streamed tiled executor, and shard onto
the ring exactly like the untyped models.

Out-of-core guard (DESIGN.md C7): with `device_budget_bytes` set, a
batch whose L-hop subgraph would not fit on device (hub seeds can pull
in a large fraction of the graph) is executed through the streamed
tiled executor instead of OOMing — same results, bounded device
footprint, counted in `stats["tiled_batches"]`.

Resident features: without a `device_budget_bytes`, when layer 0 can
reduce its input row block by row block (a default-contract sum layer,
extraction first, whose update reads only the aggregate: GCN), and when
x takes at most `RESIDENT_SHARE` of the device's memory, the engine
puts `x` on the device once and each bucket program gathers its batch's
rows there, a block of rows at a time, straight into layer 0's
extraction.  A batch then sends only its edges and its vertex ids, and
`engn.serve.gather` does not run.  Other stacks, budgeted (out-of-core)
engines and larger features gather, pad and send the rows from the host
on every batch.

Shard-aware gate (DESIGN.md C2): with `ring_shards` additionally set,
an over-budget batch first tries the sharded ring-tiled backend — the
budget is per *shard*, so a P-device ring holds a P-times-larger
subgraph on the mesh before the engine has to fall back to host
streaming.  Batches served this way count in `stats["ring_batches"]`;
only when even the per-shard stripe exceeds the budget does the batch
drop to the tiled executor.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.engn import EnGNConfig
from repro.core.tiled import TiledExecutor, dense_footprint_bytes
from repro.graphs.format import COOGraph
from repro.graphs.subgraph import SubgraphExtractor
from repro.serving.batcher import GNNBatcher, Request, Response
from repro.serving.cache import DegreeAwareCache
from repro.trace import (EXTRACT, SERVE_EXTRACT, SERVE_FINISH,
                         SERVE_GATHER, SERVE_INFER, SERVE_PAD, SERVE_PROBE,
                         scope, span)

# device bytes of one gathered block of input rows: the bucket program
# never holds more of the (n_pad, F) input than this at once
BLOCK_BYTES = 256 << 20
# share of the device's memory the resident feature matrix may take; a
# larger one stays on the host and each batch's rows go over
RESIDENT_SHARE = 0.5

@dataclasses.dataclass
class ServingConfig:
    """Serving-loop knobs, with the *execution* knobs unified under an
    embedded `EnGNConfig` (DESIGN.md C12).

    The budget / ring / streaming / quantisation switches live on
    ``engn`` (`device_budget_bytes`, `ring_shards`, `streaming_mode`,
    `tile_value_dtype`) so serving and training read one config type.
    The serving-specific mirror names that bridged the move for one
    release (`device_budget_bytes`, `ring_shards`,
    `tiled_streaming_mode`, `tiled_value_dtype`) are gone — passing
    them raises `TypeError` like any unknown dataclass field.
    """

    batch_size: int = 128
    max_wait_s: float = 0.005
    num_hops: Optional[int] = None    # default: one hop per model layer
    fanout: Optional[int] = None      # per-hop neighbour sampling cap
    cache_capacity: int = 0           # 0 disables the result cache
    cache_reserved_frac: float = 0.5  # DAVC reserved-line fraction
    coalesce: bool = True
    bucketing: bool = True            # pad subgraphs to pow2 shape buckets
    # the embedded execution config: budget gate, ring shards, tiled
    # streaming regime and value quantisation all resolve from here
    engn: Optional[EnGNConfig] = None
    tiled_tile: int = 128             # interval size for tiled fallback
    ring_tile: int = 32               # tile size for per-batch ring plans
    # -- async pipeline (serving/pipeline.py, DESIGN.md C12) --------------
    pipeline_depth: int = 2           # in-flight batches (double buffer)
    extract_workers: int = 2          # subgraph-extraction thread pool
    # under backlog, merge up to max_batch_factor batch budgets into one
    # admission ticket: fewer, larger extractions with cross-request
    # frontier dedup (hub neighbourhoods overlap under zipf traffic)
    adaptive_batching: bool = True
    max_batch_factor: int = 8
    # default SLO applied to requests submitted without a deadline
    # (None = no deadline; requests are never shed)
    default_slo_s: Optional[float] = None
    # speculatively precompute the pinned hub region of the cache at
    # startup from the DAVC degree profile (engine.warm_fill)
    warm_cache: bool = False
    warm_cache_max: int = 512         # cap on hub vertices warm-filled
    # -- dynamic graphs (DESIGN.md C14) -----------------------------------
    # after `apply_updates`, recompute the cache's pinned hub set when
    # more than this fraction of it lost top-degree status (and re-run
    # the warm fill if warm_cache is set); <=0 repins on every epoch
    hub_drift_threshold: float = 0.25

    def __post_init__(self):
        if self.engn is None:
            # dims are per-model and unused at the config-carrier level;
            # the engine reads them from its layer stack
            self.engn = EnGNConfig(in_dim=0, out_dim=0, backend="segment")


def _affected_vertices(old_graph: COOGraph, new_graph: COOGraph,
                       touched_dst: np.ndarray, num_hops: int
                       ) -> np.ndarray:
    """Vertices whose L-hop in-neighbourhood a graph delta reached: the
    forward closure of the changed edges' destinations, up to
    (num_hops - 1) hops, over the union of old and new edges (an edge
    present on either side can carry staleness).  O(hops * E) boolean
    masking — no adjacency index is built."""
    n = max(old_graph.num_vertices, new_graph.num_vertices)
    affected = np.zeros(n, bool)
    affected[touched_dst] = True
    srcs = np.concatenate([old_graph.src, new_graph.src])
    dsts = np.concatenate([old_graph.dst, new_graph.dst])
    for _ in range(max(num_hops - 1, 0)):
        grown = affected.copy()
        grown[dsts[affected[srcs]]] = True
        if np.array_equal(grown, affected):
            break
        affected = grown
    return np.nonzero(affected)[0].astype(np.int32)


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def block_rows(n_pad: int, feat_dim: int) -> int:
    """Rows of one gathered block: the largest power of two whose
    float32 rows fit `BLOCK_BYTES`, at most `n_pad`."""
    rows = max(BLOCK_BYTES // (4 * feat_dim), 1)
    return min(1 << (rows.bit_length() - 1), n_pad)


def gather_extract(extract, x: jnp.ndarray, vids: jnp.ndarray,
                   rows: int) -> jnp.ndarray:
    """`extract(xf)` for the (len(vids), F) input `xf` whose rows are
    `x[vids]` where `vids` holds an id and zero where it holds -1 (the
    padding, after every id), made `rows` rows at a time so `xf` never
    exists whole.  `rows` divides len(vids); blocks of padding alone are
    not gathered: their rows are `extract` of a zero row."""
    n = vids.shape[0]
    zero = extract(jnp.zeros((1, x.shape[1]), x.dtype))
    out = jnp.broadcast_to(zero, (n, zero.shape[1]))

    def block(b, out):
        ids = lax.dynamic_slice_in_dim(vids, b * rows, rows)
        xb = jnp.where((ids >= 0)[:, None], x[jnp.maximum(ids, 0)], 0)
        return lax.dynamic_update_slice_in_dim(out, extract(xb), b * rows,
                                               0)
    n_real = jnp.sum(vids >= 0)
    return lax.fori_loop(0, (n_real + rows - 1) // rows, block, out)


def device_bytes_limit() -> Optional[int]:
    """Bytes the default device can allocate, or None where it does not
    say (a CPU)."""
    stats = jax.local_devices()[0].memory_stats() or {}
    return stats.get("bytes_limit")


def _to_device_f32(x: np.ndarray) -> Optional[jax.Array]:
    """`x` as float32 on the device, padded with zero columns to a
    multiple of 128, or None where that takes more than `RESIDENT_SHARE`
    of the device's memory.  A TPU lays a matrix out in whichever order
    pads it less, and NELL's 65,755 x 5,415 pads less column-major; a
    row gather from that copies all of x first, in every program
    (4.3 ms and 1.4 GB a batch on a v5e).  With whole 128-lane rows
    row-major pads less, and the gather reads rows in place."""
    n, f = x.shape
    limit = device_bytes_limit()
    if limit is not None and n * (f + -f % 128) * 4 > RESIDENT_SHARE * limit:
        return None
    x = np.asarray(x, np.float32)
    return jax.device_put(np.pad(x, ((0, 0), (0, -f % 128))))


def _gathers_on_device(layer) -> bool:
    """Whether `layer`, as layer 0 of a sum stack, can take its input
    through `gather_extract`: default stage contract, extraction first,
    and an update that reads the aggregate alone."""
    return (layer.stage_spec() is None and layer.dasr_order() == "fau"
            and not layer.update_reads_self())


class GNNServingEngine:
    """Serve vertex-embedding requests over a (normalised) graph.

    graph:  the full COOGraph, already normalised for the model (e.g.
            `gcn_normalized()` for GCN stacks).
    x:      (N, F) input features, a host array.  Where the stack
            gathers on the device (module docstring) and the copy takes
            at most `RESIDENT_SHARE` of the device's memory, a float32
            copy, its rows padded with zeros to a multiple of 128, is
            kept on the device as `x_device` and each batch's rows are
            gathered there; otherwise each batch's rows are gathered
            from `x` on the host.
    layers/params: an EnGN stack from `core.models.make_gnn_stack` /
            `init_stack`, segment backend.
    x_device: an existing device copy of `x` to share (ReplicatedServer
            runs N engines over one); made from `x` when None.
    """

    def __init__(self, graph: COOGraph, x: np.ndarray, layers, params,
                 config: Optional[ServingConfig] = None,
                 extractor: Optional[SubgraphExtractor] = None,
                 x_device: Optional[jax.Array] = None):
        config = config if config is not None else ServingConfig()
        bad = [ly.name for ly in layers if ly.cfg.backend != "segment"]
        if bad:
            raise ValueError(
                f"serving requires segment-backend layers, got non-segment "
                f"backend on {bad} (the engine feeds per-batch edge-list "
                f"graph dicts that only the segment backend consumes)")
        self.graph = graph
        self.x = np.asarray(x)
        self.layers = layers
        self.params = params
        self.config = config
        self.num_hops = config.num_hops or len(layers)
        # `extractor` may be shared across engines (ReplicatedServer runs
        # N engines over one graph store); extraction is read-only numpy
        # over the CSR, so sharing is thread-safe
        self.extractor = extractor or SubgraphExtractor(graph)
        self.cache: Optional[DegreeAwareCache] = None
        if config.cache_capacity > 0:
            self.cache = DegreeAwareCache(
                config.cache_capacity, graph.degrees(),
                config.cache_reserved_frac)
        # pad=False: the engine buckets subgraph shapes itself, and
        # padding ids must not reach the cache (phantom probes of a real
        # vertex would inflate the hit rate and trigger spurious work)
        self.batcher = GNNBatcher(self._infer_ids, config.batch_size,
                                  config.max_wait_s, config.coalesce,
                                  pad=False)
        self._can_bucket = config.bucketing and all(
            ly.cfg.aggregate_op == "sum" for ly in layers)
        # the device copy the bucket programs gather from; None where
        # rows go from the host (other stacks, out-of-core engines, an x
        # too large for the device)
        self.x_device = None
        if (self._can_bucket and not config.engn.device_budget_bytes
                and _gathers_on_device(layers[0])):
            self.x_device = (x_device if x_device is not None
                             else _to_device_f32(self.x))
        # keyed (n_pad, e_pad, resident): the bucket's program
        self._compiled: Dict = {}
        # padded_vertices: rows the device ran, summed over the
        # device_batches (a bucketed batch runs its whole bucket), so
        # subgraph_vertices / padded_vertices is the buckets' fill;
        # resident_batches: device batches whose rows were gathered on
        # the device; h2d_bytes: what the in-core paths sent the device
        # for their batches (features, edges, ids; the out-of-core
        # fallbacks stream under their own executors, uncounted)
        self.stats = {"subgraphs": 0, "subgraph_vertices": 0,
                      "device_batches": 0, "padded_vertices": 0,
                      "compiles": 0, "tiled_batches": 0,
                      "ring_batches": 0, "warm_filled": 0,
                      "resident_batches": 0, "h2d_bytes": 0}
        self._compat = None           # lazy inline pipeline for step/drain
        # the pipeline ticket whose device stage runs next, for the spans
        # of `_infer_batch` (which keeps its (sub, xs) signature for the
        # wrappers of that stage); -1 outside the pipeline
        self.ticket = -1
        if config.warm_cache:
            self.warm_fill(config.warm_cache_max)

    # -- public API --------------------------------------------------------
    def submit(self, rid: int, vertex_ids: np.ndarray,
               deadline_s: Optional[float] = None):
        ids = self._validate(rid, vertex_ids)
        self.batcher.submit(Request(rid, ids, deadline_s=deadline_s))

    def _validate(self, rid: int, vertex_ids: np.ndarray) -> np.ndarray:
        ids = np.asarray(vertex_ids, np.int32)
        if ids.size == 0:
            raise ValueError(f"request {rid}: vertex_ids is empty")
        if ids.min() < 0 or ids.max() >= self.graph.num_vertices:
            raise ValueError(
                f"request {rid}: vertex ids must be in "
                f"[0, {self.graph.num_vertices}), got "
                f"[{ids.min()}, {ids.max()}]")
        return ids

    def step(self, force: bool = True) -> List[Response]:
        """One synchronous serving step — a compatibility wrapper over
        the async pipeline run inline (depth 1, no worker threads, no
        adaptive merging), so both paths share one admission/flush
        implementation (DESIGN.md C12)."""
        return self._sync_pipeline().step(force=force)

    def drain(self) -> List[Response]:
        return self._sync_pipeline().drain()

    def _sync_pipeline(self):
        if self._compat is None:
            from repro.serving.pipeline import ServingPipeline
            self._compat = ServingPipeline(
                self, depth=1, extract_workers=0, adaptive_batching=False)
        return self._compat

    def warm_fill(self, max_vertices: Optional[int] = None) -> int:
        """Speculatively precompute embeddings for the cache's pinned hub
        region (DESIGN.md C12): the DAVC degree profile already names the
        vertices most likely to be requested under power-law traffic, so
        filling them at startup converts first-touch misses into hits.
        Returns the number of vertices filled."""
        if self.cache is None or not self.cache.pinned_ids:
            return 0
        hubs = np.fromiter(self.cache.pinned_ids, np.int64,
                           len(self.cache.pinned_ids)).astype(np.int32)
        deg = self.graph.degrees()
        hubs = hubs[np.argsort(-deg[hubs], kind="stable")]
        if max_vertices is not None:
            hubs = hubs[:max_vertices]
        for i in range(0, hubs.size, self.config.batch_size):
            chunk = np.unique(hubs[i:i + self.config.batch_size])
            y = self._run_subgraph(chunk)
            self.cache.insert(chunk, y)
        self.stats["warm_filled"] += int(hubs.size)
        return int(hubs.size)

    def apply_updates(self, snapshot, x_new: Optional[np.ndarray] = None
                      ) -> Dict[str, float]:
        """Swap in one `EpochSnapshot` of graph updates (DESIGN.md C14).

        The serving graph and extractor move to the epoch graph; the
        result cache is surgically invalidated rather than cleared: a
        cached embedding of vertex v is stale iff a changed edge's
        destination lies within v's (num_hops - 1)-hop *forward*
        closure — those rows (and only those) are evicted from both
        tiers.  When the degree profile has drifted past
        `config.hub_drift_threshold`, the pinned hub set is recomputed
        and, under `warm_cache`, refreshed via `warm_fill`.

        `x_new` replaces the feature matrix (required when vertices
        were added and features exist for them); otherwise new vertices
        get zero feature rows.  Either refreshes the device copy (which
        goes back to the host if it no longer fits).
        """
        old_graph = self.graph
        g = snapshot.graph
        x_changed = True
        if x_new is not None:
            x_new = np.asarray(x_new)
            if x_new.shape[0] != g.num_vertices:
                raise ValueError(
                    f"x_new has {x_new.shape[0]} rows, epoch graph has "
                    f"{g.num_vertices} vertices")
            self.x = x_new
        elif g.num_vertices > self.x.shape[0]:
            pad = np.zeros((g.num_vertices - self.x.shape[0],
                            self.x.shape[1]), self.x.dtype)
            self.x = np.concatenate([self.x, pad], axis=0)
        else:
            x_changed = False
        if x_changed and self.x_device is not None:
            self.x_device = _to_device_f32(self.x)
        self.graph = g
        self.extractor = SubgraphExtractor(g)
        out = {"affected": 0, "invalidated": 0, "pin_drift": 0.0,
               "repinned": 0, "warm_refilled": 0}
        if self.cache is not None:
            affected = _affected_vertices(old_graph, g,
                                          snapshot.touched_dst,
                                          self.num_hops)
            out["affected"] = int(affected.size)
            out["invalidated"] = self.cache.invalidate(affected)
            deg = g.degrees()
            drift = self.cache.pin_drift(deg)
            out["pin_drift"] = float(drift)
            if drift > self.config.hub_drift_threshold:
                out["repinned"] = self.cache.repin(deg)
                if self.config.warm_cache:
                    out["warm_refilled"] = self.warm_fill(
                        self.config.warm_cache_max)
        self.stats["updates_applied"] = (
            self.stats.get("updates_applied", 0) + 1)
        return out

    def reset_telemetry(self):
        """Zero all counters (cache *contents* and compiled programs are
        kept) — call between warm-up and measured traffic."""
        self.batcher.reset_telemetry()
        if self.cache is not None:
            self.cache.reset_stats()
        if self._compat is not None:
            self._compat.reset_telemetry()
        for k in self.stats:
            self.stats[k] = 0

    def telemetry(self) -> Dict:
        out = {"batcher": dict(self.batcher.stats),
               "latency": self.batcher.latency_stats(),
               "engine": dict(self.stats)}
        if self.cache is not None:
            out["cache"] = dict(self.cache.stats,
                                hit_rate=self.cache.hit_rate())
        return out

    # -- pipeline stage functions (DESIGN.md C12) --------------------------
    # The async pipeline drives these directly: probe and finish touch the
    # cache and MUST stay on the completion thread; extract is pure numpy
    # over read-only CSR state and is safe to run on pool workers.  Each
    # stage opens its span on the thread that runs it, with `batch` the
    # pipeline's ticket number (-1 outside the pipeline).
    def _probe_batch(self, ids: np.ndarray, batch: int = -1):
        """Cache-probe stage: split a batch into hits and the miss set."""
        with span(SERVE_PROBE, batch=batch):
            ids = np.asarray(ids, np.int32)
            if self.cache is not None:
                mask, out = self.cache.lookup(ids)
            else:
                mask, out = np.zeros(ids.size, bool), None
            miss = np.unique(ids[~mask])
        return ids, mask, out, miss

    def _extract_batch(self, miss: np.ndarray, batch: int = -1):
        """Extraction stage (thread-safe, host-side): L-hop subgraph of
        the miss set plus its input feature rows, gathered here from the
        host `x`, or None where the device gathers them from `x_device`."""
        with span(SERVE_EXTRACT, batch=batch):
            sub = self.extractor.extract(miss, self.num_hops,
                                         self.config.fanout)
        xs = None
        if self.x_device is None:
            with span(SERVE_GATHER, batch=batch):
                xs = self.x[sub.vertices]
        self.stats["subgraphs"] += 1
        self.stats["subgraph_vertices"] += sub.graph.num_vertices
        return sub, xs

    def _finish_batch(self, ids, mask, out, miss, y,
                      batch: int = -1) -> np.ndarray:
        """Completion stage: insert fresh rows into the cache and scatter
        hits + misses back into batch order."""
        with span(SERVE_FINISH, batch=batch):
            if self.cache is not None and miss.size:
                self.cache.insert(miss, y)
            if out is None:
                out = np.zeros((ids.size, y.shape[1]), np.float32)
            rows = ~mask
            out[rows] = y[np.searchsorted(miss, ids[rows])]
        return out

    # -- inference path (called by the batcher, one batch at a time) -------
    def _infer_ids(self, ids: np.ndarray) -> np.ndarray:
        ids, mask, out, miss = self._probe_batch(ids)
        if miss.size == 0:
            return out
        sub, xs = self._extract_batch(miss)
        y = self._infer_batch(sub, xs)                    # (|miss|, H)
        return self._finish_batch(ids, mask, out, miss, y)

    def _run_subgraph(self, seeds: np.ndarray) -> np.ndarray:
        return self._infer_batch(*self._extract_batch(seeds))

    def _infer_batch(self, sub, xs: Optional[np.ndarray]) -> np.ndarray:
        """Inference stage (device-side): run the stack over one
        extracted subgraph, routing over-budget batches through the
        ring / streamed-tiled fallbacks.  `xs` is the subgraph's input
        rows, or None on an engine with `x_device`: the bucket program
        then gathers them there."""
        g, batch = sub.graph, self.ticket
        resident = xs is None
        self.stats["device_batches"] += 1
        budget = self.config.engn.device_budget_bytes
        if budget and self._subgraph_footprint(g) > budget:
            self.stats["padded_vertices"] += g.num_vertices
            with span(SERVE_INFER, batch=batch):
                ring_gd = self._try_ring_plan(g)
                if ring_gd is not None:
                    return self._run_subgraph_ring(sub, xs, ring_gd)
                return self._run_subgraph_tiled(sub, xs)
        if not self._can_bucket:
            self.stats["padded_vertices"] += g.num_vertices
            with span(SERVE_INFER, batch=batch):
                src, dst, val, rel, y = self._to_device(
                    g.src, g.dst, g.weights(), g.rel, xs)
                gd = self._graph_dict(g.num_vertices, src, dst, val, rel)
                for layer, p in zip(self.layers, self.params):
                    y = layer.apply(p, gd, y)
                return np.asarray(y[:sub.num_seeds])

        # pow2-bucketed shapes, best-fit reuse: prefer the smallest
        # already-compiled bucket that fits (padded compute is cheaper
        # than a fresh XLA compile); floored so small miss-sets (cache
        # hot) share one bucket instead of compiling per shrinking shape
        n_need, e_need = g.num_vertices + 1, max(g.num_edges, 1)
        fits = [(n, e) for (n, e, r) in self._compiled
                if r == resident and n >= n_need and e >= e_need]
        if fits:
            n_pad, e_pad = min(fits, key=lambda ne: ne[0] * ne[1])
        else:
            n_pad = max(_next_pow2(n_need), 256)
            e_pad = max(_next_pow2(e_need), 1024)
        self.stats["padded_vertices"] += n_pad
        with span(SERVE_PAD, batch=batch):
            dummy = n_pad - 1
            src = np.full(e_pad, dummy, np.int32)
            dst = np.full(e_pad, dummy, np.int32)
            val = np.zeros(e_pad, np.float32)    # padding edges weigh 0
            src[:g.num_edges] = g.src
            dst[:g.num_edges] = g.dst
            val[:g.num_edges] = g.weights()
            rel = None
            if g.rel is not None:
                # padding edges are rel 0 at the dummy vertex: with
                # weight 0 they add nothing, and the typed in-trace
                # normalisation only pollutes the dummy row the slice
                # below discards
                rel = np.zeros(e_pad, np.int32)
                rel[:g.num_edges] = g.rel
            if resident:
                # the device gathers the rows: the host stages their
                # ids, then -1s for the padding rows the program zeroes
                vids = np.full(n_pad, -1, np.int32)
                vids[:g.num_vertices] = sub.vertices
                inputs = (vids,)
            else:
                xf = np.zeros((n_pad, xs.shape[1]), np.float32)
                xf[:xs.shape[0]] = xs
                inputs = (xf,)

        key = (n_pad, e_pad, resident)
        fn = self._compiled.get(key)
        if fn is None:
            fn = jax.jit(partial(self._resident_fn if resident
                                 else self._stack_fn, n_pad))
            self._compiled[key] = fn
            self.stats["compiles"] += 1
        with span(SERVE_INFER, batch=batch):
            args = self._to_device(src, dst, val, rel, *inputs)
            if resident:
                args.append(self.x_device)
                self.stats["resident_batches"] += 1
            y = np.asarray(fn(*args))
        return y[:sub.num_seeds]

    def _to_device(self, *arrays) -> list:
        """The host arrays on the device (None stays None), counted in
        `stats["h2d_bytes"]`."""
        self.stats["h2d_bytes"] += sum(
            a.nbytes for a in arrays if a is not None)
        return [None if a is None else jnp.asarray(a) for a in arrays]

    def _graph_dict(self, n, src, dst, val, rel) -> Dict:
        gd = {"n": n, "src": src, "dst": dst, "val": val}
        if rel is not None:
            gd["rel"] = rel
            gd["num_relations"] = self.graph.num_relations
        return gd

    def _stack_fn(self, n_pad, src, dst, val, rel, xf):
        gd = self._graph_dict(n_pad, src, dst, val, rel)
        y = xf
        for layer, p in zip(self.layers, self.params):
            y = layer.apply(p, gd, y)
        return y

    def _resident_fn(self, n_pad, src, dst, val, rel, vids, x):
        """`_stack_fn` over the input `x[vids]` (zero where `vids` is
        -1), with layer 0's extraction applied block by block as the
        rows are gathered (`gather_extract`).  `x` is `x_device`, an
        argument, not a constant of the program."""
        gd = self._graph_dict(n_pad, src, dst, val, rel)
        layer, p = self.layers[0], self.params[0]
        f = layer.cfg.in_dim                  # x's columns past f are padding

        def extract(xb):
            return layer.feature_extraction(p, xb[:, :f])
        with scope(EXTRACT):
            h = gather_extract(extract, x, vids,
                               block_rows(n_pad, x.shape[1]))
        y = layer.aggregate_update(p, gd, None, h)   # x_self is not read
        for layer, p in zip(self.layers[1:], self.params[1:]):
            y = layer.apply(p, gd, y)
        return y

    # -- out-of-core fallback (DESIGN.md C7) -------------------------------
    def _subgraph_footprint(self, g: COOGraph) -> int:
        """Device bytes the dense segment path would need for this
        subgraph, at the widest layer of the stack — priced at the
        pow2-bucketed shapes the bucketed path actually allocates, so
        padding cannot overshoot the budget undetected.  Serving is
        inference-only, so the gate prices forward buffers alone
        (training=False): a training-capable plan would carry the
        cotangent twins and the transposed-store backward streams
        (DESIGN.md C9), which `prepare_graph` prices when
        `EnGNConfig.training` is set — the per-batch executors built
        here never grow a transposed view."""
        n, e = g.num_vertices, g.num_edges
        if self._can_bucket:
            n = max(_next_pow2(n + 1), 256)
            e = max(_next_pow2(max(e, 1)), 1024)
        return max(dense_footprint_bytes(
            n, e, self._staged_feat_dim(layer), layer.cfg.out_dim,
            "segment", training=False)
            for layer in self.layers)

    @staticmethod
    def _staged_feat_dim(layer) -> int:
        """The widest per-vertex stream the layer stages (DESIGN.md
        C10): typed models carry the (N, R*H) stacked payload, gated
        ones the (pc || x) 2F stream — both wider than in_dim."""
        f = layer.cfg.in_dim
        if layer.cfg.stage_contract == "typed":
            f = max(f, layer.cfg.num_relations * layer.cfg.out_dim)
        elif layer.cfg.stage_contract == "gated":
            f = max(f, 2 * layer.cfg.in_dim)
        return f

    def _try_ring_plan(self, g: COOGraph):
        """Shard-aware footprint gate (DESIGN.md C2): price the actual
        per-shard ring-tiled plan for this batch's subgraph and return
        a prepared ring graph dict when it fits the per-shard budget,
        else None (the batch then falls back to host streaming).  The
        ring aggregate is built per aggregation op, so mixed-op stacks
        skip the ring path."""
        p = self.config.engn.ring_shards
        if not p:
            return None
        ops = {ly.cfg.aggregate_op for ly in self.layers}
        contracts = {ly.cfg.stage_contract for ly in self.layers}
        if len(ops) != 1 or len(contracts) != 1:
            return None
        contract = contracts.pop()
        from repro.core.dataflow import (build_packed_ring_shards,
                                         build_ring_tile_shards,
                                         ring_stripe_bytes)
        from repro.core.engn import (EnGNConfig, fold_rel_norm,
                                     prepare_ring)
        from repro.distributed.sharding import ring_mesh
        try:
            mesh = ring_mesh(p)
        except ValueError:
            return None                       # fewer devices than shards
        # typed contract: fold the per-(dst, rel) normalisation into the
        # edge weights BEFORE the plan build, so the stripes carry the
        # normalised coefficients (prepare_ring is told not to re-fold)
        rel_normed = False
        if (g.rel is not None and g.num_relations > 1
                and any(ly.cfg.rel_normalize for ly in self.layers)):
            g = fold_rel_norm(g)
            rel_normed = True
        # price both stripe carriers (dense tiles vs packed entries,
        # DESIGN.md C8) before building — an over-budget batch pays
        # nothing, and the cheaper format is built exactly once and
        # handed to prepare_ring (which then re-checks nothing twice)
        dims = ([self._staged_feat_dim(self.layers[0])]
                + [ly.cfg.out_dim for ly in self.layers])
        dense_b = ring_stripe_bytes(g, p, tile=self.config.ring_tile,
                                    in_dim=max(dims), out_dim=max(dims),
                                    tile_format="dense")
        packed_b = ring_stripe_bytes(g, p, tile=self.config.ring_tile,
                                     in_dim=max(dims),
                                     out_dim=max(dims),
                                     tile_format="packed")
        if min(dense_b, packed_b) > self.config.engn.device_budget_bytes:
            return None
        if packed_b <= dense_b:
            plan = build_packed_ring_shards(g, p)
        else:
            plan = build_ring_tile_shards(g, p,
                                          tile=self.config.ring_tile)
        cfg = EnGNConfig(in_dim=self.layers[0].cfg.in_dim,
                         out_dim=self.layers[-1].cfg.out_dim,
                         aggregate_op=ops.pop(), backend="ring",
                         tile=self.config.ring_tile, ring_shards=p,
                         stage_contract=contract,
                         num_relations=max(ly.cfg.num_relations
                                           for ly in self.layers),
                         rel_normalize=any(ly.cfg.rel_normalize
                                           for ly in self.layers))
        return prepare_ring(g, cfg, plan=plan, mesh=mesh,
                            rel_normed=rel_normed)

    def _run_subgraph_ring(self, sub, xs: np.ndarray, gd) -> np.ndarray:
        """Run the stack over the subgraph on the ring mesh: each device
        holds one shard's tile stripe, feature shards rotate with
        ppermute — the per-shard budget admits subgraphs ~P x larger
        than one device before host streaming is needed."""
        y = jnp.asarray(np.asarray(xs, np.float32))
        for layer, p in zip(self.layers, self.params):
            y = layer.apply(p, gd, y)
        self.stats["ring_batches"] += 1
        return np.asarray(y[:sub.num_seeds])

    def _run_subgraph_tiled(self, sub, xs: np.ndarray) -> np.ndarray:
        """Run the stack through the streamed tiled executor: the
        subgraph's edge tiles stay in host memory and stream through
        the device under the budget (instead of OOMing on hub seeds).
        The tile store is rebuilt per batch — O(E log E) host work on
        sparse edge lists (layer jit caches are shared across batches,
        so only the store build recurs)."""
        g = sub.graph
        if (g.rel is not None and g.num_relations > 1
                and any(ly.cfg.rel_normalize for ly in self.layers)):
            # typed sums stream as plain sums: the per-(dst, rel) mean
            # is folded into the tile weights before the store build
            from repro.core.engn import fold_rel_norm
            g = fold_rel_norm(g)
        dims = ([self._staged_feat_dim(layer) for layer in self.layers]
                + [layer.cfg.out_dim for layer in self.layers])
        ex = TiledExecutor(g, tile=self.config.tiled_tile,
                           budget_bytes=self.config.engn.device_budget_bytes,
                           dim_hint=max(dims),
                           streaming_mode=self.config.engn.streaming_mode,
                           value_dtype=self.config.engn.tile_value_dtype)
        gd = {"n": g.num_vertices, "backend": "tiled", "tiled_exec": ex}
        y = np.asarray(xs, np.float32)
        for layer, p in zip(self.layers, self.params):
            y = layer.apply(p, gd, y)
        self.stats["tiled_batches"] += 1
        return np.asarray(y[:sub.num_seeds])
