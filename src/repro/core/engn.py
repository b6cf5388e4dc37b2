"""The EnGN processing model (paper S2.2, Algorithm 1).

Every GNN is expressed as three stage functions over an edge-centric graph:

    feature_extraction(prop_src, prop_dst, W_feat) -> tmp       (per edge)
    aggregate(acc, tmp)                            -> acc       (reduce @ dst)
    update(prop_dst, acc, W_update)                -> prop'     (per vertex)

`EnGNLayer` is the composable module: it owns the stage functions, the
DASR decision (S5.2) and the aggregation backend (segment reference,
device-resident blocked Pallas kernel, fused extract+aggregate, the
sharded ring-tiled device mesh, or the out-of-core streamed tiled
executor).  Models in core/models.py are instances of this class per
Table 1.

Device-memory budget: when `EnGNConfig.device_budget_bytes` is set,
`prepare_graph` estimates the device footprint of the requested backend
and either spills to the streamed "tiled" backend (`auto_spill=True`,
the default) or raises `DeviceBudgetExceeded` — graphs larger than one
device run via core/tiled.py instead of OOMing.

The streamed backend is trainable (DESIGN.md C9): under a jit/grad
trace the layer routes the aggregate through a `jax.custom_vjp`
wrapper whose backward re-streams the same host tiles in transposed
(src <-> dst) order, so the budget-dominating graph payloads (tiles,
edge entries, the (E, d)-scale intermediates) stay streamed in the
reverse pass too.  Features and their cotangents remain device-
resident in training — extraction/update are ordinary traced ops —
and `EnGNConfig.training=True` prices exactly those resident
activation twins into the budget gate (`dense_footprint_bytes`
doubles the activation terms; `tiled_meta["resident_feature_bytes"]`
records what training keeps resident).
"""
from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor
from contextlib import nullcontext
from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.plan import PreparedPlan, plan_carrier, wrap_plan
from repro.core.tiled import (DeviceBudgetExceeded, TiledExecutor,
                              dense_footprint_bytes,
                              make_streamed_aggregate)
from repro.graphs.format import COOGraph, coo_to_blocked
from repro.graphs.partition import tile_schedule_order
from repro.trace import (AGGREGATE, DST_ROWS, EXTRACT, LANE_PACKED, UPDATE,
                         scope)


AggregateOp = str  # "sum" | "max" | "mean"


def _is_traced(*vals) -> bool:
    """True when any leaf of the given pytrees is a jax tracer — i.e.
    we are inside a jit/grad trace and host-loop paths cannot run."""
    return any(isinstance(leaf, jax.core.Tracer)
               for v in vals for leaf in jax.tree_util.tree_leaves(v))


def segment_aggregate(edge_vals: jnp.ndarray, dst: jnp.ndarray, n: int,
                      op: AggregateOp) -> jnp.ndarray:
    """Edge-centric reduce at destination vertices — the reference path
    (Algorithm 1 lines 2-5 literally)."""
    if op == "sum":
        return jax.ops.segment_sum(edge_vals, dst, num_segments=n)
    if op == "max":
        m = jax.ops.segment_max(edge_vals, dst, num_segments=n,
                                indices_are_sorted=False)
        # empty segments come back -inf; the kernel convention is 0
        return jnp.where(jnp.isneginf(m), 0.0, m)
    if op == "mean":
        s = jax.ops.segment_sum(edge_vals, dst, num_segments=n)
        c = jax.ops.segment_sum(jnp.ones_like(dst, jnp.float32), dst,
                                num_segments=n)
        return s / jnp.maximum(c, 1.0)[:, None]
    raise ValueError(op)


# The segment paths gather one row per edge.  Where those rows, counted
# at the TPU's 128-lane width, would take more than EDGE_CHUNK_BYTES,
# the edges are walked in chunks that stay under it.  The cap lies above
# the largest serving bucket's gather (524,288 edges at width 64, 256
# MiB), so every graph below it keeps the one-shot program.
EDGE_CHUNK_BYTES = 512 << 20
LANES = 128
# A chunk loop's (N, d) operands, the table it gathers from and the
# accumulator it adds into, narrower than this are held lane-packed
# (`lane_pack`).  The v5e compile lays an (N, d) float32 buffer out
# column-major ({0,1}) up to 48 columns, so that a row's d values lie N
# apart and its scatter-add takes d strided writes; from 64 columns on
# it is row-major.
LANE_DENSE_BELOW = 64
# Where prepare_graph cuts the default contract's sum into chunks, it
# lays the edges out as destination rows (`dst_row_layout`): each
# vertex's in-edges in rows of SLOT_WIDTH slots, so that a chunk adds one
# row per destination row into the accumulator, not one per edge.
# Chosen on a v5e over Synthetic-A's 71.3M edges (4.19M vertices,
# chunks of 1,048,576 slots), seconds of the width-64 forward and
# backward loops, then the width-16 (packed) ones, and their sum:
#   edges in their order 2.220 2.220 2.134 2.136  8.710
#   W = 1 (sorted only)  1.460 2.619 1.740 2.604  8.423
#   W = 2                1.584 2.288 1.428 2.004  7.304
#   W = 4                1.591 2.154 1.379 1.810  6.934
#   W = 8                1.816 2.292 1.541 1.862  7.511
SLOT_WIDTH = 4
EDGE_KEYS = ("src", "dst", "val", "rel")


def gathered_row_bytes(width: int) -> int:
    """Device bytes of one gathered float32 row of `width` columns,
    the columns rounded up to whole 128-lane rows."""
    return 4 * LANES * -(-max(int(width), 1) // LANES)


def edge_chunk(num_edges: int, width: int) -> int:
    """Edges per chunk of a segment aggregate whose edges gather rows of
    `width` columns: all of them when their rows fit under
    EDGE_CHUNK_BYTES, else the largest power of two that does."""
    rows = max(EDGE_CHUNK_BYTES // gathered_row_bytes(width), 1)
    if num_edges <= rows:
        return num_edges
    return 1 << (rows.bit_length() - 1)


def cut_edges(edges: Dict[str, Any], chunk: int, drop: int, xp=jnp
              ) -> Dict[str, Any]:
    """The per-edge arrays (`EDGE_KEYS`) cut into (chunks, chunk) rows.
    The last row is padded with edges from vertex 0 to `drop`, a
    segment past the last, of weight 0 and relation 0: the reductions
    drop them, so they reach no vertex.  `xp` is numpy on the host."""
    e = int(edges["src"].shape[0])
    chunks = -(-e // chunk)
    fill = {"src": 0, "dst": drop, "val": 0, "rel": 0}
    return {k: xp.concatenate([a, xp.full(chunks * chunk - e, fill[k],
                                          a.dtype)]).reshape(chunks, chunk)
            for k, a in edges.items()}


def _by_destination(dst: np.ndarray) -> np.ndarray:
    """The edge order that sorts `dst` (non-negative int32), stably: a
    radix pass over its low 16 bits, then one over its high 16 bits
    where there are any (numpy sorts 16-bit keys by radix)."""
    order = np.argsort((dst & 0xFFFF).astype(np.uint16), kind="stable")
    if dst.size and int(dst.max()) >> 16:
        hi = (dst[order] >> 16).astype(np.uint16)
        order = order[np.argsort(hi, kind="stable")]
    return order


def dst_row_layout(src: np.ndarray, dst: np.ndarray,
                   val: Optional[np.ndarray], n: int, width: int,
                   slots: int) -> tuple:
    """Host arrays of the edges as destination rows, for `reduce_edges`:
    sorted by destination, each vertex's in-edges packed into rows of
    `slots` slots that hold only that vertex's edges, in their order in
    `dst`.  A real slot carries its edge's weight (1 where `val` is
    None), a padded one reads vertex 0 with weight 0.  The rows are cut
    into chunks of about `edge_chunk(slots in all, width)` slots, each
    chunk's slots stored slot-major, (slots, rows): the rows' last
    dimension fills the 128 lanes, where a minor dimension of `slots`
    would be padded to them.  The last chunk is padded with rows to `n`,
    a segment past the last.  Returns ({"src": (chunks, slots, rows a
    chunk), "val": the same, "dst": (chunks, rows a chunk)}, the real
    rows, slots a chunk).

    The edges are placed in blocks, one thread each (numpy's sorts and
    gathers release the interpreter lock): a block sorts its own edges
    by destination, and the edge counts of the blocks before it place
    them after theirs."""
    e = int(dst.size)
    bounds = np.linspace(0, e, min(8, os.cpu_count() or 1) + 1).astype(int)
    blocks = list(zip(bounds[:-1], bounds[1:]))
    with ThreadPoolExecutor(len(blocks)) as pool:
        counts = list(pool.map(
            lambda b: np.bincount(dst[b[0]:b[1]], minlength=n), blocks))
        before = [np.zeros(n, np.int64)]      # in-edges in earlier blocks
        for c in counts:
            before.append(before[-1] + c)
        rows_of = -(-before.pop() // slots)
        rows = int(rows_of.sum())
        chunk = edge_chunk(rows * slots, width)
        per = max(chunk // slots, 1)
        chunks = -(-rows // per)
        first = np.cumsum(rows_of) - rows_of
        out = {"src": np.zeros((chunks, slots, per), np.int32),
               "val": np.zeros((chunks, slots, per), np.float32),
               "dst": np.full((chunks, per), n, np.int32)}

        def place(i):
            lo, hi = blocks[i]
            order = _by_destination(dst[lo:hi])
            d = dst[lo:hi][order]
            # the rank of each edge among its vertex's in-edges, those
            # of the blocks before this one first, sets its row and slot
            shift = before[i] - (np.cumsum(counts[i]) - counts[i])
            rank = shift[d] + np.arange(hi - lo)
            c, r = np.divmod(first[d] + rank // slots, per)
            at = (c * slots + rank % slots) * per + r
            out["src"].reshape(-1)[at] = src[lo:hi][order]
            out["val"].reshape(-1)[at] = (1.0 if val is None
                                          else val[lo:hi][order])
        list(pool.map(place, range(len(blocks))))
    out["dst"].reshape(-1)[:rows] = np.repeat(
        np.arange(n, dtype=np.int32), rows_of)
    return out, rows, per * slots


def dst_rows(graph: Dict[str, Any]) -> bool:
    """Whether a segment carrier holds destination rows
    (`dst_row_layout`), which only the default contract's sum reduces."""
    return getattr(graph.get("src"), "ndim", 0) == 3


def lane_pack(width: int) -> int:
    """Vertices a chunk loop holds in one 128-lane row of an (N, width)
    operand: LANES // width below LANE_DENSE_BELOW columns, else 1, the
    (N, width) layout itself."""
    return LANES // width if width < LANE_DENSE_BELOW else 1


def _pack(a: jnp.ndarray, k: int) -> jnp.ndarray:
    """(n, d) rows as a lane-packed (ceil(n / k), k * d) buffer: row v
    lies in row v // k, lanes (v % k) * d to (v % k + 1) * d."""
    n, d = a.shape
    rows = -(-n // k)
    return jnp.pad(a, ((0, rows * k - n), (0, 0))).reshape(rows, k * d)


def _slots(ids: jnp.ndarray, k: int) -> jnp.ndarray:
    """(c, k, 1) mask of the slot each of `ids` takes in its packed row."""
    return (ids % k)[:, None, None] == jnp.arange(k)[:, None]


def _spread(r: jnp.ndarray, ids: jnp.ndarray, k: int, fill) -> jnp.ndarray:
    """(c, d) rows as (c, k * d) packed rows: each in the slot of its id,
    `fill` in the others."""
    return jnp.where(_slots(ids, k), r[:, None, :], fill).reshape(
        r.shape[0], -1)


def _take(p: jnp.ndarray, ids: jnp.ndarray, k: int) -> jnp.ndarray:
    """Rows `ids` of a buffer laid out by `lane_pack` (k > 1: packed).
    A packed row's other slots are masked to zeros before the slots are
    summed, so each id reads its own values exactly."""
    if k == 1:
        return p[ids]
    rows = p[ids // k].reshape(ids.shape[0], k, -1)
    return jnp.where(_slots(ids, k), rows, 0.0).sum(1)


def reduce_edges(edges: Dict[str, Any], message: Callable,
                 num_segments: int, width: int, op: AggregateOp = "sum",
                 keys: Optional[Callable] = None,
                 scope_name: Optional[str] = None,
                 table: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Reduce one message per edge at the edge's segment, as
    `segment_aggregate` does, in edge chunks where the gathered rows
    would pass EDGE_CHUNK_BYTES.

    `edges` holds per-edge arrays, either flat (E,), already cut into
    (chunks, chunk) rows by `cut_edges`, or destination rows from
    `dst_row_layout` (as `prepare_graph` lays out a graph that needs
    chunks).  `message(e, rows)` makes the rows of the edges in `e`, a
    dict of the same keys, where `rows(ids)` are the rows `ids` of
    `table` (an (N, d) array, or None where the message gathers
    nothing); `keys(e)` their segment ids (default `e["dst"]`); `width`
    is the columns one edge gathers, which sizes the chunk.  Where the
    flat edges fit in one chunk this emits exactly
    `segment_aggregate(message(edges, table.__getitem__), keys(edges),
    ...)`, with the reduction in `scope_name`.  Otherwise a `lax.scan`
    over the chunks adds (sum, mean) or maxes (max) each chunk's rows
    into one accumulator, under `scope_name`.  The backward is autodiff
    through the loop; each chunk's step runs again there, so the loop
    keeps no per-edge rows or indices for it.

    Destination rows reduce by sum at `e["dst"]` alone: the message sees
    a chunk's slots as flat `src` and `val` (no `dst`); each row's slots
    are summed, then one row per destination row is added into the
    accumulator, by indices sorted within the chunk, in scope DST_ROWS.
    Padded slots carry weight 0, so the sums are the segment sums where
    the rows they read are finite.

    The loop lays out the table and each accumulator by `lane_pack` of
    its width: narrow ones lane-packed, so that an edge's gather and its
    add touch one lane row; the backward, their transposes, is packed
    with them.  The loop then runs in scope LANE_PACKED, and the
    (num_segments, d) result is sliced out once, after it."""
    slots = edges["src"].shape[1] if dst_rows(edges) else 0
    if slots and (op != "sum" or keys is not None):
        raise ValueError(
            f"destination rows reduce by sum at each row's vertex, not by "
            f"op {op!r}" + (" at other keys" if keys is not None else "")
            + ": prepare the graph for this layer's own config, which "
            "lays it out as edges")
    keys = keys or (lambda e: e["dst"])
    ctx = (lambda: scope(scope_name)) if scope_name else nullcontext
    plain = None if table is None else table.__getitem__
    total = int(edges["src"].size)
    chunk = edge_chunk(total, width)
    if edges["src"].ndim == 1:
        if chunk >= total:
            ev = message(edges, plain)
            with ctx():
                return segment_aggregate(ev, keys(edges), num_segments, op)
        # a carrier prepare_graph did not lay out (a serving batch's):
        # cut it here
        edges = cut_edges(edges, chunk, num_segments)
    elif edges["src"][0].size > chunk:
        # rows wider than the layout was sized for: split its rows
        per = max(chunk // max(slots, 1), 1)
        edges = {k: a.reshape(-1, per) if a.ndim == 2 else a.reshape(
                     a.shape[:2] + (-1, per)).swapaxes(1, 2).reshape(
                         -1, slots, per)
                 for k, a in edges.items()}

    def view(e):
        """The edges a message sees of chunk `e`: a chunk of destination
        rows as its flat slots."""
        return ({k: a.reshape(-1) for k, a in e.items() if k != "dst"}
                if slots else e)

    kt = 1 if table is None else lane_pack(table.shape[1])
    ident = {"sum": 0, "max": -jnp.inf}
    one_chunk = view({k: a[0] for k, a in edges.items()})

    def shapes(fn):
        """fn's row arrays' shapes, each with its `lane_pack` (1 for a
        row of scalars)."""
        return [(s, lane_pack(s.shape[1]) if s.ndim == 2 else 1)
                for s in jax.eval_shape(fn, one_chunk)]

    def loop(fn, op):
        """Reduce fn's rows (a tuple of arrays) over every chunk, each
        into an accumulator laid out by its `lane_pack`: (accumulators,
        their packs).  The chunk's step is run again in the backward
        rather than keeping its rows or indices."""
        first = shapes(fn)

        @jax.checkpoint
        def body(acc, e):
            seg, rs = (e["dst"] if slots else keys(e)), fn(view(e))
            out = []
            with scope(DST_ROWS) if slots else nullcontext():
                for a, r, (_, k) in zip(acc, rs, first):
                    if slots:
                        r = r.reshape((slots,) + seg.shape
                                      + r.shape[1:]).sum(0)
                    if k > 1:
                        a, r = a.at[seg // k], _spread(r, seg, k, ident[op])
                    else:
                        a = a.at[seg]
                    out.append(a.max(r, mode="drop") if op == "max"
                               else a.add(r, mode="drop",
                                          indices_are_sorted=bool(slots)))
            return tuple(out), None
        init = tuple(jnp.full((num_segments,) + s.shape[1:] if k == 1
                              else (-(-num_segments // k), k * s.shape[1]),
                              ident[op], s.dtype)
                     for s, k in first)
        return jax.lax.scan(body, init, edges)[0], [k for _, k in first]

    def unpacked(acc, ks):
        """The (num_segments, ...) results of accumulators and packs."""
        return [a if k == 1 else jax.lax.optimization_barrier(
                    a.reshape(-1, a.shape[1] // k)[:num_segments])
                for a, k in zip(acc, ks)]

    lane_packed = kt > 1 or any(
        k > 1 for _, k in shapes(lambda e: (message(e, plain),)))
    # The packed region meets the rest of the program at (N, d) arrays
    # held whole by optimization barriers, forward and backward: the v5e
    # compiler otherwise folds the relayout into the ops beyond, and
    # folded into a mask (a ReLU's) it takes minutes to compile.
    with ctx(), scope(LANE_PACKED) if lane_packed else nullcontext():
        if kt > 1:
            table = _pack(jax.lax.optimization_barrier(table), kt)
        rows = None if table is None else partial(_take, table, k=kt)
        if op == "sum":
            return unpacked(*loop(lambda e: (message(e, rows),), "sum"))[0]
        if op == "mean":
            s, c = unpacked(*loop(lambda e: (message(e, rows),
                                             jnp.ones(e["dst"].shape,
                                                      jnp.float32)),
                                  "sum"))
            return s / jnp.maximum(c, 1.0)[:, None]
        if op != "max":
            raise ValueError(op)
        # the maxima first, then their gradient: each edge that attains
        # its segment's maximum takes an equal share, as segment_max's
        # own derivative gives it
        (mp,), (km,) = loop(
            lambda e: (jax.lax.stop_gradient(message(e, rows)),), "max")

        def attained(e):
            r = message(e, rows)
            hit = r == _take(mp, keys(e), km)
            return (jnp.where(hit, r - jax.lax.stop_gradient(r), 0.0),
                    hit.astype(r.dtype))
        (zero, ties), kz = loop(attained, "sum")
        m, zero, ties = unpacked((mp, zero, ties), [km] + kz)
        y = m + zero / jnp.maximum(ties, 1.0)
        return jnp.where(jnp.isneginf(m), 0.0, y)


def edge_arrays(graph: Dict[str, Any]) -> Dict[str, Any]:
    """The per-edge arrays of a segment carrier, flat or in chunks."""
    return {k: graph[k] for k in EDGE_KEYS if graph.get(k) is not None}


def segment_gather_width(cfg: "EnGNConfig", out_dim: int) -> int:
    """Columns of the rows each edge gathers on the segment backend for
    a layer of `cfg`: the aggregated width of the default contract (the
    extracted `out_dim` when extraction comes first, else `in_dim`), or
    both endpoints' `in_dim` rows of the typed and gated contracts (the
    typed contract's aggregate-first order gathers the source alone)."""
    f = cfg.in_dim
    order = (cfg.stage_order if cfg.stage_order != "auto"
             else "fau" if out_dim <= f else "afu")
    if cfg.stage_contract in ("typed", "gated"):
        return f if (cfg.stage_contract == "typed"
                     and order == "afu") else 2 * f
    return out_dim if order == "fau" else f


@dataclasses.dataclass
class EnGNConfig:
    in_dim: int
    out_dim: int
    aggregate_op: AggregateOp = "sum"
    # DASR: "auto" picks per Observation 1 / Eq. 6-7; "fau" forces
    # feature-extraction->aggregate->update; "afu" forces aggregate-first.
    stage_order: str = "auto"
    # "segment"  edge-centric reference (Algorithm 1)
    # "blocked"  device-resident blocked RER-SpMM (Pallas on TPU)
    # "fused"    blocked + extraction fused into the aggregate sweep
    # "ring"     sharded ring-tiled RER over a device mesh: per-shard
    #            sparse tile stripes + ppermute feature rotation (C2)
    # "tiled"    out-of-core streamed executor (core/tiled.py, C7)
    backend: str = "segment"
    tile: int = 256                   # T for the blocked/tiled/ring backends
    # How the tile-carrying backends (blocked / tiled / ring) carry
    # their tiles (DESIGN.md C8): "dense" T x T blocks (the bit-for-bit
    # oracle), "packed" pow2-nnz-bucketed (row, col, val) entries, or
    # "auto" — ask kernels/autotune.py per (graph, backend).
    tile_format: str = "auto"
    packed_bucket_floor: int = 8      # smallest packed nnz bucket
    ring_shards: Optional[int] = None  # ring: devices in the ring (default all)
    ring_axis: str = "ring"            # ring: mesh axis name
    # device-memory budget for the dense paths; prepare_graph spills to
    # the streamed tiled backend (auto_spill) or raises when exceeded.
    # For the ring backend the budget is PER SHARD: each ring device
    # must hold its tile stripe + feature shard, not the whole graph.
    device_budget_bytes: Optional[int] = None
    auto_spill: bool = True
    tiled_chunk: int = 8              # tiles per streamed device step
    # How the tiled backend streams (DESIGN.md C11): "auto" stages the
    # whole packed stream as a device-resident chunk queue when it fits
    # the budget (zero per-chunk host round trips — the ~10x train-step
    # win), falling back to the per-chunk callback loop; "callback"
    # forces the loop; "chunk_queue" demands the queue or raises.
    streaming_mode: str = "auto"
    # "fp32" | "int8": int8 ships streamed tile values quantised with
    # error feedback (distributed/compression.py) — 4x fewer value
    # bytes per sweep, bounded per-sweep rounding error, unbiased in
    # time average (DESIGN.md C11).  Applies to the tiled backend's
    # packed staging and chunk queue.
    tile_value_dtype: str = "fp32"
    # training=True prices the budget gate for forward AND backward
    # (cotangent twins double the activation terms; the streamed tiled
    # executor pre-sizes its step for the wider backward streams) —
    # set by training entry points (launch/train.py --gnn), left False
    # for inference/serving.
    training: bool = False
    # Stage contract (DESIGN.md C10): models whose messages need more
    # than the default single src projection declare it here (on their
    # own *copy* of the config), so `prepare_graph` builds the matching
    # typed/gated carriers per backend.  None = default contract;
    # "typed" = per-relation messages (R-GCN), with `num_relations`
    # edge types and, when `rel_normalize`, the per-(dst, rel) mean
    # normalisation 1/|N_r(dst)| folded into the edge weights host-side
    # (feature-independent, so every backend's typed aggregate is a
    # plain sum); "gated" = dst+src sigmoid-gated messages (Gated-GCN).
    stage_contract: Optional[str] = None
    num_relations: int = 1
    rel_normalize: bool = False
    dtype: Any = jnp.float32


class EnGNLayer:
    """One GNN propagation layer on the EnGN processing model."""

    def __init__(self, cfg: EnGNConfig, name: str = "engn"):
        self.cfg = cfg
        self.name = name

    # -- parameters ------------------------------------------------------
    def init(self, key: jax.Array) -> Dict[str, jnp.ndarray]:
        cfg = self.cfg
        k1, _ = jax.random.split(key)
        scale = 1.0 / np.sqrt(cfg.in_dim)
        return {"w": jax.random.normal(k1, (cfg.in_dim, cfg.out_dim),
                                       cfg.dtype) * scale}

    # -- stage functions (overridden per model) ---------------------------
    def feature_extraction(self, params, x_src: jnp.ndarray) -> jnp.ndarray:
        """Default: linear condense XW (GCN-style)."""
        return x_src @ params["w"]

    def update(self, params, x_self: jnp.ndarray, agg: jnp.ndarray) -> jnp.ndarray:
        """Default: ReLU activation."""
        return jax.nn.relu(agg)

    def update_reads_self(self) -> bool:
        """Whether `update` reads `x_self` beside the aggregate: not the
        default's; assumed of any override that does not say otherwise."""
        return type(self).update is not EnGNLayer.update

    # -- stage contract (DESIGN.md C10) -----------------------------------
    def stage_spec(self) -> Optional[Dict[str, Any]]:
        """The model's per-stage contract, or None for the default
        (message = edge_val * feature_extraction(x_src), which the
        historical fast paths serve unchanged).  Models whose messages
        read the destination endpoint or the edge type return a spec
        every backend dispatches on:

          {"kind": "typed", "num_relations": R, "channels": H,
           "normalize": bool}   — per-relation messages (R-GCN): the
              layer also provides `src_payload(params, x) -> (N, R*H)`,
              the stacked per-relation projections each typed tile /
              stripe / edge selects its slice of;
          {"kind": "gated"}     — dst+src sigmoid-gated messages
              (Gated-GCN): the layer provides `gate_dst` / `gate_src`
              projections; the message source payload is x itself.

        Both kinds aggregate by sum (Eq. 3-4) and keep `update` as the
        vertex-wise stage."""
        return None

    def extract(self, params, x_src: jnp.ndarray, x_dst: jnp.ndarray,
                edge_val: jnp.ndarray, rel) -> jnp.ndarray:
        """The canonical per-edge message function (the C10 stage
        contract): given both endpoints' features, the edge weight and
        the edge type, produce the message the aggregate reduces.  The
        segment reference consumes this literally; the tiled / ring /
        blocked backends consume the factored per-vertex forms
        (`feature_extraction`, `src_payload`, `gate_dst`/`gate_src`)
        that make the same messages without edge-shaped weights.
        Default: edge_val * feature_extraction(x_src)."""
        return edge_val[:, None] * self.feature_extraction(params, x_src)

    # -- DASR (S5.2): choose sigma(A(XW)) vs sigma((AX)W) -----------------
    def dasr_order(self) -> str:
        cfg = self.cfg
        if cfg.stage_order != "auto":
            return cfg.stage_order
        # aggregate cost is E*H if extraction first (Eq. 6) vs E*F if
        # aggregation first (Eq. 7): extract first iff H <= F.
        return "fau" if cfg.out_dim <= cfg.in_dim else "afu"

    def dasr_op_counts(self, num_edges: int) -> Dict[str, float]:
        f, h = self.cfg.in_dim, self.cfg.out_dim
        return {
            "fau_aggregate_ops": float(num_edges) * h,
            "afu_aggregate_ops": float(num_edges) * f,
        }

    # -- forward ----------------------------------------------------------
    def apply(self, params, graph, x: jnp.ndarray,
              aggregate_fn: Optional[Callable] = None) -> jnp.ndarray:
        """graph: a `PreparedPlan` from `prepare_graph`, or its raw
        carrier dict (device arrays, or the host tile store when the
        effective backend is the streamed "tiled")."""
        graph = plan_carrier(graph)
        spec = self.stage_spec()
        if spec is not None:
            if aggregate_fn is not None:
                # a custom reduce cannot see the typed/gated message
                # structure — refusing beats silently ignoring it
                raise ValueError(
                    f"{type(self).__name__} aggregates through its "
                    f"{spec['kind']!r} stage contract; a custom "
                    f"aggregate_fn is not supported")
            return self._apply_staged(params, graph, x, spec)
        backend = graph.get("backend", self.cfg.backend)
        if backend == "tiled" and aggregate_fn is None:
            # under a jit/grad trace (training, or a jitted caller) the
            # host streaming loop cannot run on tracers: route through
            # the custom_vjp wrapper (C9) instead of the eager host path
            if _is_traced(params, x):
                return self._apply_tiled_diff(params, graph, x)
            return self._apply_tiled(params, graph, x)
        agg = aggregate_fn or partial(self._aggregate, graph)
        linear_sum = (self.cfg.aggregate_op == "sum"
                      and type(self).feature_extraction
                      is EnGNLayer.feature_extraction)
        if (linear_sum and backend == "fused"
                and self.dasr_order() == "fau"):
            # Fig. 8 stage overlap: extraction fused into the aggregate
            # sweep (P = X@W lives only in VMEM per tile), so the one
            # kernel is scoped as the aggregate
            from repro.kernels.fused_engn import fused_engn_layer
            n = graph["n"]
            pad_n = graph["blocks_meta"]["padded"]
            with scope(AGGREGATE):
                xf = jnp.zeros((pad_n, x.shape[1]), x.dtype).at[:n].set(x)
                y = fused_engn_layer(graph["blocks"], graph["block_row"],
                                     graph["block_col"], xf, params["w"],
                                     q=graph["blocks_meta"]["q"])[:n]
        elif linear_sum and self.dasr_order() == "afu":
            with scope(AGGREGATE):
                ax = agg(x)                             # (AX)
            with scope(EXTRACT):
                y = self.feature_extraction(params, ax)  # (AX)W
        else:
            with scope(EXTRACT):
                tmp = self.feature_extraction(params, x)  # XW (per src)
            return self.aggregate_update(params, graph, x, tmp, agg)
        with scope(UPDATE):
            return self.update(params, x, y)

    def aggregate_update(self, params, graph, x, h,
                         aggregate_fn: Optional[Callable] = None
                         ) -> jnp.ndarray:
        """The stages after extraction first: aggregate the extracted
        `h` (A(XW)) and update.  `x` is the layer's input, which `update`
        reads as `x_self`; it may be None where `update_reads_self()` is
        False (the serving engine extracts from rows it never holds
        whole)."""
        agg = aggregate_fn or partial(self._aggregate, plan_carrier(graph))
        with scope(AGGREGATE):
            y = agg(h)                                  # A(XW)
        with scope(UPDATE):
            return self.update(params, x, y)

    # -- staged models on every backend (DESIGN.md C10) -------------------
    def _apply_staged(self, params, graph, x, spec) -> jnp.ndarray:
        cfg = self.cfg
        backend = graph.get("backend", cfg.backend)
        if cfg.aggregate_op != "sum":
            raise ValueError(
                f"the {spec['kind']!r} stage contract aggregates by sum "
                f"(Eq. 3-4); got aggregate_op={cfg.aggregate_op!r}")
        if backend == "fused":
            raise ValueError(
                "the fused Fig. 8 kernel serves the default contract "
                "only; use blocked/tiled/ring for staged models")
        if backend == "segment" and dst_rows(graph):
            raise ValueError(
                f"the {spec['kind']!r} stage contract reduces edges, not "
                f"the destination rows prepare_graph lays out for the "
                f"default contract's sum: prepare the graph for this "
                f"layer's own config")
        if spec["kind"] == "typed":
            return self._staged_typed(params, graph, x, spec, backend)
        if spec["kind"] == "gated":
            return self._staged_gated(params, graph, x, backend)
        raise ValueError(spec["kind"])

    def _staged_typed(self, params, graph, x, spec, backend):
        """Relation-typed messages (R-GCN, Eq. 3) on every backend: the
        per-vertex payload is the (N, R*H) stack of all relations'
        projections; each typed edge carrier (tile, stripe, flat entry)
        selects its own relation's H-wide slice, and the aggregate is a
        plain sum — the per-(dst, rel) normalisation is either folded
        into the carrier weights at prepare time (`rel_normed`) or, on
        raw segment dicts, computed in-trace here."""
        n = graph["n"]
        r = spec["num_relations"]
        h = spec["channels"]
        if backend == "tiled" and not _is_traced(params, x):
            ex = graph["tiled_exec"]
            fns = self._tiled_stage_fns()
            xh = np.asarray(x, np.float32)
            agg = ex.aggregate(xh, "sum", order="auto",
                               extract_fn=partial(fns["src_payload"],
                                                  params),
                               extract_dim=r * h, out_dim_hint=h,
                               rel_channels=h)
            return ex.stream_map(partial(fns["update"], params), xh, agg)
        x = jnp.asarray(x, jnp.float32 if backend == "tiled"
                        else self.cfg.dtype)
        if backend == "segment":
            edges = self._weighted_edges(graph)
            f = x.shape[1]

            def by_rel(e):
                return e["dst"] * r + e["rel"]
            if spec.get("normalize") and not graph.get("rel_normed"):
                with scope(AGGREGATE):
                    cnt = reduce_edges(
                        edges, lambda e, rows: jnp.ones_like(e["val"]),
                        n * r, 1, keys=by_rel)
                    edges["val"] = edges["val"] / jnp.maximum(
                        cnt[by_rel(edges)], 1.0)
            if self.dasr_order() == "afu":
                # aggregate per (dst, rel) first, then one batched
                # projection — Eq. 7's cheaper order when F < H
                with scope(AGGREGATE):
                    agg_r = reduce_edges(
                        edges,
                        lambda e, rows: rows(e["src"]) * e["val"][:, None],
                        n * r, f, keys=by_rel, table=x)
                with scope(EXTRACT):
                    agg = jnp.einsum("nrf,rfh->nh",
                                     agg_r.reshape(n, r, f), params["wr"])
            else:
                def message(e, rows):
                    with scope(EXTRACT):
                        return self.extract(params, rows(e["src"]),
                                            rows(e["dst"]), e["val"],
                                            e["rel"])
                agg = reduce_edges(edges, message, n, 2 * f,
                                   scope_name=AGGREGATE, table=x)
        elif backend in ("tiled", "blocked", "ring"):
            with scope(EXTRACT):
                xw = self.src_payload(params, x)          # (n, r*h)
            with scope(AGGREGATE):
                agg = self._typed_sum(graph, xw, backend, n, r, h, x.dtype)
        else:
            raise ValueError(backend)
        with scope(UPDATE):
            return self.update(params, x, agg)

    @staticmethod
    def _weighted_edges(graph) -> Dict[str, Any]:
        """The staged contracts' segment edges, with float32 weights
        (1 where the graph has none)."""
        edges = edge_arrays(graph)
        val = edges.get("val")
        edges["val"] = (jnp.ones(edges["src"].shape, jnp.float32)
                        if val is None else jnp.asarray(val, jnp.float32))
        return edges

    @staticmethod
    def _typed_sum(graph, xw, backend, n, r, h, dtype):
        """The typed aggregate of the stacked payload `xw` (n, r*h); the
        blocked kernels run at the layer's `dtype`."""
        if backend == "tiled":
            from repro.core.tiled import make_streamed_typed_sum
            return make_streamed_typed_sum(graph["tiled_exec"])(xw)
        if backend == "ring":
            pad_n = graph["ring_meta"]["padded"]
            xf = jnp.zeros((pad_n, r * h), jnp.float32).at[:n].set(xw)
            y = graph["ring_fn"](*graph["ring_operands"], xf,
                                 graph["ring_counts"])
            return y[:n]
        if "typed_flat" in graph:
            gsrc, gdst, gval, grel = graph["typed_flat"]
            ev = gval[:, None] * xw.reshape(n * r, h)[gsrc * r + grel]
            return jax.ops.segment_sum(ev, gdst, num_segments=n)
        from repro.kernels.rer_spmm import ops as spmm_ops
        pad_n = graph["blocks_meta"]["padded"]
        xf = jnp.zeros((pad_n, r * h), dtype).at[:n].set(xw)
        y = None
        for blk in graph["typed_blocks"]:
            rr = blk["rel"]
            part = spmm_ops.blocked_spmm(
                blk["blocks"], blk["block_row"], blk["block_col"],
                xf[:, rr * h:(rr + 1) * h], q=blk["q"], op="sum")
            y = part if y is None else y + part
        return y[:n] if y is not None else jnp.zeros((n, h), dtype)

    def _staged_gated(self, params, graph, x, backend):
        """Dst+src sigmoid-gated messages (Gated-GCN, Eq. 4) on every
        backend: message = val * sigma(ph[dst] + pc[src]) * x[src] with
        ph = gate_dst(x), pc = gate_src(x).  The projections are
        per-vertex, so the gate rides the carriers — ph on the resident
        destination side (tiled) or the stationary shard (ring), pc and
        x on the streamed/rotating source side."""
        n = graph["n"]
        if backend == "tiled" and not _is_traced(params, x):
            ex = graph["tiled_exec"]
            fns = self._tiled_stage_fns()
            xh = np.asarray(x, np.float32)
            ph = ex.stream_map(partial(fns["gate_dst"], params), xh)
            pc = ex.stream_map(partial(fns["gate_src"], params), xh)
            agg = ex.gated_aggregate(ph, pc, xh)
            return ex.stream_map(partial(fns["update"], params), xh, agg)
        x = jnp.asarray(x, jnp.float32 if backend == "tiled"
                        else self.cfg.dtype)
        if backend == "segment":
            def message(e, rows):
                with scope(EXTRACT):
                    return self.extract(params, rows(e["src"]),
                                        rows(e["dst"]), e["val"], None)
            agg = reduce_edges(self._weighted_edges(graph), message, n,
                               2 * x.shape[1], scope_name=AGGREGATE,
                               table=x)
        elif backend in ("tiled", "blocked", "ring"):
            with scope(EXTRACT):
                ph = self.gate_dst(params, x)
                pc = self.gate_src(params, x)
            with scope(AGGREGATE):
                agg = self._gated_sum(graph, ph, pc, x, backend, n)
        else:
            raise ValueError(backend)
        with scope(UPDATE):
            return self.update(params, x, agg)

    @staticmethod
    def _gated_sum(graph, ph, pc, x, backend, n):
        """The gated aggregate of x under the endpoint projections."""
        if backend == "tiled":
            from repro.core.tiled import make_streamed_gated
            return make_streamed_gated(graph["tiled_exec"])(ph, pc, x)
        pad_n = graph["ring_meta" if backend == "ring"
                     else "blocks_meta"]["padded"]

        def pad(a):
            return jnp.zeros((pad_n, a.shape[1]), jnp.float32).at[:n].set(a)
        if backend == "ring":
            pcx = jnp.concatenate([pad(pc), pad(x)], axis=1)
            y = graph["ring_fn"](*graph["ring_operands"], pad(ph), pcx,
                                 graph["ring_counts"])
            return y[:n]
        if "packed_flat" in graph:
            gsrc, gdst, gval = graph["packed_flat"]
            xf, phf, pcf = pad(x), pad(ph), pad(pc)
            z = jax.nn.sigmoid(phf[gdst] + pcf[gsrc])
            ev = gval[:, None] * z * xf[gsrc]
            return jax.ops.segment_sum(ev, gdst, num_segments=pad_n)[:n]
        if "packed_groups" in graph:
            raise ValueError(
                "the gated contract needs the flat packed carrier "
                "(XLA gather); the Mosaic bucket-group layout does "
                "not carry endpoint projections — use "
                "tile_format='dense' on TPU")
        meta = graph["blocks_meta"]
        q, t = meta["q"], meta["tile"]
        blocks = graph["blocks"]
        brow, bcol = graph["block_row"], graph["block_col"]
        xt = pad(x).reshape(q, t, -1)
        pht = pad(ph).reshape(q, t, -1)
        pct = pad(pc).reshape(q, t, -1)
        z = jax.nn.sigmoid(pht[brow][:, :, None, :]
                           + pct[bcol][:, None, :, :])
        contrib = jnp.where(
            blocks[..., None] != 0.0,
            blocks[..., None] * z * xt[bcol][:, None, :, :], 0.0)
        part = jnp.sum(contrib, axis=2)               # (nnzb, t, f)
        return jax.ops.segment_sum(
            part, brow, num_segments=q).reshape(pad_n, -1)[:n]

    # -- streamed out-of-core path, differentiable (DESIGN.md C9) ---------
    def _apply_tiled_diff(self, params, graph, x) -> jnp.ndarray:
        """The trainable twin of `_apply_tiled`: extraction and update
        are ordinary traced jax ops (their VJPs come from XLA), while
        the aggregate runs through `make_streamed_aggregate` — a
        `jax.custom_vjp`-wrapped host callback whose backward
        re-streams the transposed tile store.  Features are device-
        resident here (they already are in any training step); only
        the graph stays out-of-core."""
        cfg = self.cfg
        ex: TiledExecutor = graph["tiled_exec"]
        agg = make_streamed_aggregate(ex, cfg.aggregate_op)
        x = jnp.asarray(x, jnp.float32)
        linear_sum = (cfg.aggregate_op == "sum"
                      and type(self).feature_extraction
                      is EnGNLayer.feature_extraction)
        if linear_sum and self.dasr_order() == "afu":
            with scope(AGGREGATE):
                ax = agg(x)                              # (AX)
            with scope(EXTRACT):
                y = self.feature_extraction(params, ax)
        else:
            with scope(EXTRACT):
                tmp = self.feature_extraction(params, x)  # XW
            with scope(AGGREGATE):
                y = agg(tmp)                             # A(XW)
        with scope(UPDATE):
            return self.update(params, x, y)

    # -- streamed out-of-core path (core/tiled.py, DESIGN.md C7) ----------
    def _tiled_stage_fns(self):
        """Jitted stage functions, cached per layer instance so repeated
        tiled batches (serving fallback) re-trace nothing: the jit cache
        is keyed on these stable callables + the streamed shapes."""
        fns = getattr(self, "_tiled_jit", None)
        if fns is None:
            fns = {
                "extract": jax.jit(
                    lambda p, xb: self.feature_extraction(p, xb)),
                "update": jax.jit(
                    lambda p, xb, ab: self.update(p, xb, ab)),
                "extract_update": jax.jit(
                    lambda p, xb, ab: self.update(
                        p, xb, self.feature_extraction(p, ab))),
            }
            # staged models (C10) add their per-vertex projections: the
            # typed src payload and the gated endpoint projections ride
            # the same per-interval streaming as "extract"
            for extra in ("src_payload", "gate_dst", "gate_src"):
                fn = getattr(self, extra, None)
                if fn is not None:
                    fns[extra] = jax.jit(fn)
            self._tiled_jit = fns
        return fns

    def _apply_tiled(self, params, graph, x) -> np.ndarray:
        """Run the layer through the streamed executor: extraction rides
        on the source-interval loads, aggregation follows the adaptive
        tile schedule, and update streams per destination interval.
        Operates on (and returns) host arrays by construction."""
        cfg = self.cfg
        ex: TiledExecutor = graph["tiled_exec"]
        x = np.asarray(x, np.float32)
        order = tile_schedule_order(cfg.in_dim, cfg.out_dim)
        fns = self._tiled_stage_fns()
        linear_sum = (cfg.aggregate_op == "sum"
                      and type(self).feature_extraction
                      is EnGNLayer.feature_extraction)
        if linear_sum and self.dasr_order() == "afu":
            ax = ex.aggregate(x, "sum", order=order,
                              out_dim_hint=cfg.out_dim)       # (AX)
            return ex.stream_map(
                partial(fns["extract_update"], params), x, ax)
        agg = ex.aggregate(
            x, cfg.aggregate_op, order=order,
            extract_fn=partial(fns["extract"], params),
            extract_dim=cfg.out_dim, out_dim_hint=cfg.out_dim)
        return ex.stream_map(partial(fns["update"], params), x, agg)

    # -- aggregation backends ---------------------------------------------
    def _aggregate(self, graph, feat: jnp.ndarray) -> jnp.ndarray:
        cfg = self.cfg
        graph = plan_carrier(graph)   # stage entry point: plan or dict
        backend = graph.get("backend", cfg.backend)
        if backend == "segment":
            def message(e, rows):
                ev = rows(e["src"])
                if "val" in e:
                    ev = ev * e["val"][:, None]
                return ev
            return reduce_edges(edge_arrays(graph), message, graph["n"],
                                feat.shape[1], cfg.aggregate_op, table=feat)
        if backend in ("blocked", "fused"):
            n = graph["n"]
            pad_n = graph["blocks_meta"]["padded"]
            # mean rides the sum machinery: blocked-sum then divide by
            # the in-edge counts (the exact floats segment mean divides
            # by), so every tile carrier supports all three ops
            base_op = "sum" if cfg.aggregate_op == "mean" else cfg.aggregate_op

            def _finish(y):
                if cfg.aggregate_op != "mean":
                    return y[:n]
                return (y[:n]
                        / jnp.maximum(graph["in_counts"], 1.0)[:, None])
            xf = jnp.zeros((pad_n, feat.shape[1]), feat.dtype).at[:n].set(feat)
            if "packed_flat" in graph:
                # off-TPU: one flat gather+segment launch beats a
                # per-bucket-group loop (each launch pays dispatch)
                from repro.kernels.rer_gather import ops as gather_ops
                gsrc, gdst, gval = graph["packed_flat"]
                scale = graph.get("packed_val_scale")
                if scale is not None:
                    # int8 residency (C11): dequantise in-trace
                    gval = gval.astype(jnp.float32) * scale
                y = gather_ops.packed_flat_xla(
                    gsrc, gdst, gval, xf, n=xf.shape[0], op=base_op)
                return _finish(y)
            if "packed_groups" in graph:
                from repro.kernels.rer_gather import ops as gather_ops
                q = graph["blocks_meta"]["q"]
                y = None
                # TPU: one Mosaic launch per pow2 nnz-bucket group; raw
                # partials merge by + / maximum, -inf finished once
                for gr in graph["packed_groups"]:
                    part = gather_ops.packed_spmm(
                        gr["rows"], gr["cols"], gr["vals"],
                        gr["block_row"], gr["block_col"], xf, q=q,
                        op=base_op, finish=False)
                    if y is None:
                        y = part
                    elif base_op == "sum":
                        y = y + part
                    else:
                        y = jnp.maximum(y, part)
                if base_op == "max":
                    y = jnp.where(jnp.isneginf(y), 0.0, y)
                return _finish(y)
            from repro.kernels.rer_spmm import ops as spmm_ops
            y = spmm_ops.blocked_spmm(graph["blocks"], graph["block_row"],
                                      graph["block_col"], xf,
                                      q=graph["blocks_meta"]["q"],
                                      op=base_op)
            return _finish(y)
        if backend == "tiled":
            # unreachable from apply() (it routes to _apply_tiled before
            # binding _aggregate); a direct caller would get host arrays
            # where every other backend returns device arrays
            raise RuntimeError(
                "the streamed tiled backend runs through "
                "EnGNLayer._apply_tiled, not _aggregate")
        if backend == "ring":
            n = graph["n"]
            pad_n = graph["ring_meta"]["padded"]
            xf = jnp.zeros((pad_n, feat.shape[1]),
                           jnp.float32).at[:n].set(feat)
            y = graph["ring_fn"](*graph["ring_operands"], xf,
                                 graph["ring_counts"])
            return y[:n]
        raise ValueError(backend)


def fold_rel_norm(g: COOGraph) -> COOGraph:
    """Fold R-GCN's per-(dst, rel) mean normalisation 1/|N_r(dst)| into
    the edge weights (Eq. 3).  The count is feature-independent, so
    folding it host-side turns the typed aggregate into a plain sum on
    every backend — tiles, ring stripes and flat entries all carry the
    already-normalised coefficients."""
    if g.rel is None:
        raise ValueError("fold_rel_norm needs a relation-typed graph")
    key = g.dst.astype(np.int64) * g.num_relations + g.rel
    cnt = np.bincount(key, minlength=g.num_vertices * g.num_relations)
    val = (g.weights() / np.maximum(cnt[key], 1)).astype(np.float32)
    return COOGraph(g.num_vertices, g.src, g.dst, val, g.rel,
                    g.num_relations)


def _maybe_fold_rel_norm(g: COOGraph, cfg: EnGNConfig, rel_normed: bool):
    """(graph, rel_normed) after applying the config's normalisation at
    most once across the prepare_* call chain."""
    if (cfg.rel_normalize and not rel_normed and g.rel is not None
            and g.num_relations > 1):
        return fold_rel_norm(g), True
    return g, rel_normed


def prepare_tiled(g: COOGraph, cfg: EnGNConfig,
                  out_dim: Optional[int] = None,
                  impl: Optional[str] = None,
                  rel_normed: bool = False) -> PreparedPlan:
    """Build the `PreparedPlan` for the streamed out-of-core backend:
    the Q x Q edge-tile store stays in host memory; tile/chunk sizes
    are fitted to the device budget for the layer's wider feature dim."""
    h = out_dim if out_dim is not None else cfg.out_dim
    g, _ = _maybe_fold_rel_norm(g, cfg, rel_normed)
    # training pre-sizes the streaming step for the backward sweeps:
    # the max VJP streams a (y, g/cnt) stack twice as wide as the
    # forward activations (DESIGN.md C9); the typed contract streams
    # the (N, R*H) stacked payload, the gated one a 2F-wide stream
    dim_hint = max(cfg.in_dim, h) * (2 if cfg.training else 1)
    if cfg.stage_contract == "typed":
        dim_hint = max(dim_hint, cfg.num_relations * h)
    elif cfg.stage_contract == "gated":
        dim_hint = max(dim_hint, 2 * cfg.in_dim)
    ex = TiledExecutor(g, tile=cfg.tile, chunk=cfg.tiled_chunk,
                       budget_bytes=cfg.device_budget_bytes, impl=impl,
                       dim_hint=dim_hint,
                       tile_format=cfg.tile_format,
                       bucket_floor=cfg.packed_bucket_floor,
                       streaming_mode=cfg.streaming_mode,
                       value_dtype=(cfg.tile_value_dtype
                                    if cfg.tile_format != "dense"
                                    else "fp32"))
    # which streaming regime this config/graph pair actually lands in
    # (the plan is per feature dim; h is the layer's streamed width)
    qplan = ex.queue_plan(max(cfg.in_dim, h), "sum")
    return wrap_plan(
        {"n": g.num_vertices, "backend": "tiled", "tiled_exec": ex,
            "tiled_meta": {"q": ex.store.q, "tile": ex.store.tile,
                           "chunk": ex.chunk,
                           "order": tile_schedule_order(cfg.in_dim, h),
                           "host_bytes": ex.store.nbytes(),
                           "tile_format": ex.tile_format,
                           "format_choice": ex.format_choice,
                           "streaming_mode": ex.streaming_mode,
                           "value_dtype": ex.value_dtype,
                           "queue_plan": (dataclasses.asdict(qplan)
                                          if qplan else None),
                           # reverse path (C9): every tileable model
                           # can now train through the streamed
                           # executor via the custom_vjp wrapper
                           "trainable": True,
                           "training": cfg.training,
                           # what a training step keeps device-resident
                           # (features + their cotangents; the graph
                           # itself streams) — callers can check this
                           # against their real device memory
                           "resident_feature_bytes":
                               (2 if cfg.training else 1) * 4
                               * g.num_vertices * (cfg.in_dim + h)}})


def update_plan(plan: PreparedPlan, snapshot, cfg: EnGNConfig,
                out_dim: Optional[int] = None) -> PreparedPlan:
    """Re-price a `PreparedPlan` for one `EpochSnapshot` of graph
    updates (DESIGN.md C14).

    The streamed tiled backend absorbs the delta in place: the
    executor's stores merge incrementally (`TiledExecutor.
    apply_updates`, bitwise-equal to a fresh build), then the budget
    gate re-fits the streaming step and re-prices the chunk-queue plan
    for the *grown* store (queue pricing is n- and nnz-dependent, so
    growth can demote a chunk-queue plan to the callback loop).  If the
    update-time dim no longer fits the fitted step — e.g. the plan was
    priced for inference and the update arrives under a training config
    whose backward streams double the width — the plan falls back to a
    full `prepare_tiled`, which re-fits the tile for the wider dim:
    a re-plan, never a silent overflow.

    Device-resident backends (segment / blocked / fused / ring) keep no
    mergeable host store — their carriers are uploaded arrays — so the
    epoch graph re-runs `prepare_graph`, which re-prices the dense
    footprint and spills to tiled exactly as it would at cold start.
    """
    plan = wrap_plan(plan)
    h = out_dim if out_dim is not None else cfg.out_dim
    if plan.backend != "tiled":
        return prepare_graph(snapshot.graph, cfg, out_dim)
    if (cfg.rel_normalize and snapshot.graph.rel is not None
            and snapshot.graph.num_relations > 1):
        # folded relation norms are global (degree-dependent): an edge
        # delta invalidates every folded weight, so merge has nothing
        # to reuse — rebuild from the re-folded epoch graph
        return prepare_tiled(snapshot.graph, cfg, out_dim)
    ex: TiledExecutor = plan.carrier["tiled_exec"]
    ex.apply_updates(snapshot)
    dim = max(cfg.in_dim, h)
    try:
        ex.effective_chunk(dim * (2 if cfg.training else 1))
    except DeviceBudgetExceeded:
        # grown graph broke the fitted step: full re-plan re-fits
        # tile/chunk (and the spill chain) for the new size
        stats = ex.stats
        new = prepare_tiled(snapshot.graph, cfg, out_dim)
        nex: TiledExecutor = new.carrier["tiled_exec"]
        nex.stats.delta_merges = stats.delta_merges
        nex.stats.store_builds += stats.store_builds
        return new
    qplan = ex.queue_plan(dim, "sum")
    meta = plan.carrier["tiled_meta"]
    meta.update(q=ex.store.q, host_bytes=ex.store.nbytes(),
                queue_plan=(dataclasses.asdict(qplan)
                            if qplan else None),
                resident_feature_bytes=(2 if cfg.training else 1) * 4
                * snapshot.graph.num_vertices * (cfg.in_dim + h))
    plan.carrier["n"] = snapshot.graph.num_vertices
    # re-derive the typed summary over the refreshed carrier
    return wrap_plan(dict(plan.carrier))


def prepare_ring(g: COOGraph, cfg: EnGNConfig,
                 out_dim: Optional[int] = None, plan=None, mesh=None,
                 rel_normed: bool = False) -> PreparedPlan:
    """Build the `PreparedPlan` for the sharded ring backend (C2):
    destination vertices (and their stripe of edges) are partitioned
    across a ring mesh; each device keeps its stripe and accumulator
    resident while source-feature shards rotate with ppermute.

    `cfg.tile_format` picks the stripe carrier (C8): dense T x T tiles,
    packed (row, col, val) entries at pow2 nnz buckets, or "auto" —
    whichever stages fewer bytes (priced by `ring_stripe_bytes` before
    any build).  A prebuilt `plan` (either class) pins the format.

    `device_budget_bytes` is per shard and is checked against the
    *actually built* plan (the a-priori closed form in
    `dense_footprint_bytes` is an upper bound): over-budget plans spill
    to the streamed tiled executor or raise."""
    from repro.core.dataflow import (PackedRingShards,
                                     build_packed_ring_shards,
                                     build_ring_tile_shards,
                                     make_ring_gated_packed,
                                     make_ring_gated_tiled,
                                     make_ring_packed_aggregate,
                                     make_ring_tiled_aggregate,
                                     make_ring_typed_sum_packed,
                                     make_ring_typed_sum_tiled,
                                     ring_feature_bytes,
                                     ring_stripe_bytes)
    from repro.distributed.sharding import ring_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P
    h = out_dim if out_dim is not None else cfg.out_dim
    g, rel_normed = _maybe_fold_rel_norm(g, cfg, rel_normed)
    typed = (cfg.stage_contract == "typed" and g.rel is not None
             and g.num_relations > 1)
    if mesh is None:
        mesh = ring_mesh(cfg.ring_shards, cfg.ring_axis)
    p = int(mesh.devices.size)
    if plan is None:
        fmt = cfg.tile_format
        if fmt == "auto":
            dense_b = ring_stripe_bytes(g, p, tile=cfg.tile,
                                        tile_format="dense")
            packed_b = ring_stripe_bytes(
                g, p, tile=cfg.tile, tile_format="packed",
                bucket_floor=cfg.packed_bucket_floor,
                value_dtype=cfg.tile_value_dtype)
            fmt = "packed" if packed_b < dense_b else "dense"
        if fmt == "packed":
            plan = build_packed_ring_shards(
                g, p, bucket_floor=cfg.packed_bucket_floor)
        else:
            plan = build_ring_tile_shards(g, p, tile=cfg.tile)
    packed = isinstance(plan, PackedRingShards)
    # the staged contracts widen the rotating shard: typed rotates the
    # (N, R*H) stacked payload, gated rotates the (pc || x) 2F stream
    feat_f = cfg.in_dim
    if typed:
        feat_f = max(feat_f, g.num_relations * h)
    elif cfg.stage_contract == "gated":
        feat_f = max(feat_f, 2 * cfg.in_dim)
    feat_need = ring_feature_bytes(plan.n_loc, feat_f, h)
    if cfg.training:
        feat_need *= 2          # cotangent twins of the rotating shards
    need = plan.device_bytes() + feat_need
    if cfg.device_budget_bytes and need > cfg.device_budget_bytes:
        if not cfg.auto_spill:
            raise DeviceBudgetExceeded(
                f"ring backend needs ~{need} device bytes per shard "
                f"({p} shards), budget is {cfg.device_budget_bytes} "
                f"per shard (more shards shrink the stripe; "
                f"auto_spill=True streams tiles out-of-core instead)")
        return prepare_tiled(g, cfg, out_dim, rel_normed=rel_normed)
    spec = NamedSharding(mesh, P(cfg.ring_axis))
    if packed:
        operands = [plan.rows, plan.cols, plan.vals]
        if typed:
            if plan.rels is None:
                raise ValueError(
                    "typed stage contract needs a relation-typed ring "
                    "plan (build from the typed COOGraph)")
            operands.append(plan.rels)
            ring_fn = make_ring_typed_sum_packed(
                mesh, cfg.ring_axis, plan.n_loc, g.num_relations)
        elif cfg.stage_contract == "gated":
            ring_fn = make_ring_gated_packed(mesh, cfg.ring_axis,
                                             plan.n_loc)
        else:
            ring_fn = make_ring_packed_aggregate(mesh, cfg.ring_axis,
                                                 cfg.aggregate_op,
                                                 plan.n_loc)
    else:
        operands = [plan.blocks, plan.tile_row, plan.tile_col]
        if typed:
            if plan.tile_rel is None:
                raise ValueError(
                    "typed stage contract needs a relation-typed ring "
                    "plan (build from the typed COOGraph)")
            operands.append(plan.tile_rel)
            ring_fn = make_ring_typed_sum_tiled(
                mesh, cfg.ring_axis, plan.q_loc, plan.tile,
                g.num_relations)
        elif cfg.stage_contract == "gated":
            ring_fn = make_ring_gated_tiled(mesh, cfg.ring_axis,
                                            plan.q_loc, plan.tile)
        else:
            ring_fn = make_ring_tiled_aggregate(mesh, cfg.ring_axis,
                                                cfg.aggregate_op,
                                                plan.q_loc, plan.tile)
    operands = tuple(jax.device_put(a, spec) for a in operands)
    d: Dict[str, Any] = {
        "n": g.num_vertices, "backend": "ring",
        "ring_operands": operands,
        "ring_counts": jax.device_put(plan.in_counts, spec),
        "ring_fn": ring_fn,
        "ring_meta": {"shards": p, "padded": plan.padded_vertices,
                      "mesh": mesh, "tile": plan.tile,
                      "q_loc": plan.q_loc, "s_max": plan.s_max,
                      "nnzb": plan.nnzb, "device_bytes": need,
                      "tile_format": "packed" if packed else "dense",
                      "stats": plan.stats(cfg.in_dim, h)},
    }
    return wrap_plan(d)


def prepare_graph(g: COOGraph, cfg: EnGNConfig,
                  out_dim: Optional[int] = None) -> PreparedPlan:
    """Host-side 'format converter': build the `PreparedPlan` (typed
    attributes + the device-side carrier dict) for the chosen backend,
    including the adaptive tile-schedule decision and the device-budget
    spill to the streamed tiled backend."""
    backend = cfg.backend
    h = out_dim if out_dim is not None else cfg.out_dim
    g, rel_normed = _maybe_fold_rel_norm(g, cfg, False)
    if cfg.device_budget_bytes and backend not in ("tiled", "ring"):
        # (the ring gate lives in prepare_ring: it prices the actual
        # per-shard plan, not the closed-form upper bound)
        need = dense_footprint_bytes(g.num_vertices, g.num_edges,
                                     cfg.in_dim, h, backend,
                                     tile=cfg.tile,
                                     has_val=g.val is not None,
                                     tile_format=cfg.tile_format,
                                     training=cfg.training,
                                     value_dtype=cfg.tile_value_dtype)
        if need > cfg.device_budget_bytes:
            if not cfg.auto_spill:
                raise DeviceBudgetExceeded(
                    f"backend {backend!r} needs ~{need} device bytes, "
                    f"budget is {cfg.device_budget_bytes} (set "
                    f"auto_spill=True or backend='tiled' to stream "
                    f"tiles out-of-core)")
            backend = "tiled"
    if backend == "tiled":
        return prepare_tiled(g, cfg, out_dim, rel_normed=rel_normed)
    d: Dict[str, Any] = {"n": g.num_vertices, "backend": backend}
    if backend == "segment":
        edges = {"src": g.src, "dst": g.dst, "val": g.val, "rel": g.rel}
        edges = {k: a for k, a in edges.items() if a is not None}
        # a graph whose gather would pass EDGE_CHUNK_BYTES is laid out
        # in chunks here, once, for reduce_edges to walk: the default
        # contract's sum as destination rows, the rest as edges
        width = segment_gather_width(cfg, h)
        e = g.num_edges
        chunk = edge_chunk(e, width)
        layout, slots, rows = "edges", 1, e
        if (chunk < e and cfg.stage_contract is None
                and cfg.aggregate_op == "sum"):
            layout, slots = "dst_rows", SLOT_WIDTH
            edges, rows, chunk = dst_row_layout(
                g.src, g.dst, g.val, g.num_vertices, width, slots)
        elif chunk < e:
            edges = cut_edges(edges, chunk, g.num_vertices, np)
        d.update((k, jnp.asarray(a)) for k, a in edges.items())
        if g.rel is not None:
            d["num_relations"] = g.num_relations
            d["rel_normed"] = rel_normed
        d["segment_meta"] = {
            "layout": layout, "edge_chunk": chunk,
            "chunks": (edges["src"].shape[0] if layout == "dst_rows"
                       else -(-e // max(chunk, 1))),
            "slot_width": slots, "rows": rows,
            "slot_fill": e / (rows * slots) if rows else 1.0,
            "device_bytes": (sum(a.nbytes for a in edges.values())
                             + chunk * gathered_row_bytes(width))}
        return wrap_plan(d)
    if (backend == "blocked" and cfg.stage_contract == "typed"
            and g.rel is not None and g.num_relations > 1):
        return _prepare_blocked_typed(g, cfg, d, h)
    if backend in ("blocked", "fused"):
        # The adaptive order (Table 3) is recorded for the I/O analysis;
        # on TPU the kernel itself mandates the dst-stationary layout
        # (output tiles must be revisited consecutively), so the blocks
        # are always dst-sorted before upload — see rer_spmm docstring.
        order = tile_schedule_order(cfg.in_dim, h)
        # mean = blocked sum + divide by the in-edge counts (the exact
        # floats segment mean divides by) — _aggregate finishes with
        # them, so every tile carrier supports all three ops; sum/max
        # never read the counts, so they skip the build and upload
        if cfg.aggregate_op == "mean":
            d["in_counts"] = jnp.asarray(
                np.bincount(g.dst, minlength=g.num_vertices)
                .astype(np.float32))
        # Tile format (C8): the fused kernel mandates dense tiles and
        # pins dense, as does an explicit tile_format="dense" (no store
        # build at all in that case); otherwise the autotuner prices
        # packed entries vs dense blocks (mean rides the sum carrier).
        choice = None
        if backend == "blocked" and cfg.tile_format != "dense":
            from repro.graphs.partition import (build_tile_store,
                                                pack_tile_store)
            from repro.kernels.autotune import choose_tile_format
            store = build_tile_store(g, cfg.tile)
            packed = pack_tile_store(store)
            choice = choose_tile_format(
                cfg.tile_format, packed, backend="blocked",
                bucket_floor=cfg.packed_bucket_floor)
            if choice.fmt == "packed":
                from repro.kernels.rer_gather import ops as gather_ops
                # upload only the representation _aggregate will use:
                # pow2-bucket groups feed the Mosaic kernel on TPU, the
                # flat entry arrays feed the one-launch XLA path.  The
                # gated contract always takes flat entries — its sigmoid
                # gate needs per-entry endpoint gathers the bucket-group
                # layout does not carry (DESIGN.md C10).
                if (gather_ops.default_impl() == "xla"
                        or cfg.stage_contract == "gated"):
                    flat = gather_ops.flat_entries(packed)
                    if (cfg.tile_value_dtype == "int8"
                            and cfg.stage_contract != "gated"):
                        # int8 residency (C11): the flat value plane
                        # stays quantised on device (one f32 scale for
                        # the whole graph — it is uploaded once, so
                        # there is no re-streaming for error feedback
                        # to correct) and dequantises in-trace in
                        # _aggregate.  The gated contract keeps fp32:
                        # its per-entry gate products compound the
                        # rounding error.
                        from repro.distributed.compression import (
                            quantize_int8_np)
                        qv, sc, _ = quantize_int8_np(flat[2])
                        d["packed_flat"] = (jnp.asarray(flat[0]),
                                            jnp.asarray(flat[1]),
                                            jnp.asarray(qv))
                        d["packed_val_scale"] = sc
                        tile_bytes = (flat[0].nbytes + flat[1].nbytes
                                      + qv.nbytes + 4)
                    else:
                        d["packed_flat"] = tuple(jnp.asarray(a)
                                                 for a in flat)
                        tile_bytes = sum(a.nbytes for a in flat)
                else:
                    groups = gather_ops.prepare_packed_groups(
                        packed, cfg.packed_bucket_floor)
                    d["packed_groups"] = [
                        {"rows": jnp.asarray(gr.rows),
                         "cols": jnp.asarray(gr.cols),
                         "vals": jnp.asarray(gr.vals),
                         "block_row": jnp.asarray(gr.block_row),
                         "block_col": jnp.asarray(gr.block_col)}
                        for gr in groups]
                    tile_bytes = sum(gr.nbytes() for gr in groups)
                # re-check the *actually built* plan against the budget
                # (the closed-form gate above prices nnz bounds, not the
                # per-group interval padding) — mirror prepare_ring,
                # with the training cotangent twins doubling the
                # feature term exactly as dense_footprint_bytes does
                act = 2 if cfg.training else 1
                need = (tile_bytes
                        + act * 4 * g.num_vertices * (cfg.in_dim + h))
                if (cfg.device_budget_bytes
                        and need > cfg.device_budget_bytes):
                    d.pop("packed_flat", None)
                    d.pop("packed_groups", None)
                    if not cfg.auto_spill:
                        raise DeviceBudgetExceeded(
                            f"packed blocked plan needs ~{need} device "
                            f"bytes, budget is "
                            f"{cfg.device_budget_bytes} (auto_spill="
                            f"True streams tiles out-of-core instead)")
                    return prepare_tiled(g, cfg, out_dim)
                d["blocks_meta"] = {
                    "q": store.q, "padded": store.padded_vertices,
                    "order": order, "tile": store.tile,
                    "tile_format": "packed", "format_choice": choice,
                    "device_bytes": tile_bytes,
                    "value_dtype": ("int8" if "packed_val_scale" in d
                                    else "fp32")}
                return wrap_plan(d)
        from repro.kernels.rer_spmm.ops import prepare_blocks
        b = coo_to_blocked(g, cfg.tile, order="column")
        blocks, brow, bcol = prepare_blocks(b.blocks, b.block_row,
                                            b.block_col, b.q)
        d["blocks"] = jnp.asarray(blocks)
        d["block_row"] = jnp.asarray(brow)
        d["block_col"] = jnp.asarray(bcol)
        d["blocks_meta"] = {"q": b.q, "padded": b.padded_vertices,
                            "order": order, "tile": b.tile,
                            "tile_format": "dense",
                            "format_choice": choice}
        return wrap_plan(d)
    if backend == "ring":
        return prepare_ring(g, cfg, out_dim, rel_normed=rel_normed)
    raise ValueError(backend)


def _prepare_blocked_typed(g: COOGraph, cfg: EnGNConfig,
                           d: Dict[str, Any], h: int) -> PreparedPlan:
    """Device carriers for the typed contract on the blocked backend
    (DESIGN.md C10).  tile_format "dense" keeps one blocked-SpMM plan
    *per relation* (each contracts its own H-wide slice of the stacked
    src payload — the bitwise dense oracle); "packed"/"auto" carries the
    flat merged entries with a per-entry rel column, one gather +
    segment launch total."""
    from repro.graphs.partition import build_tile_store, pack_tile_store
    n = g.num_vertices
    r = g.num_relations
    order = tile_schedule_order(cfg.in_dim, h)
    t = cfg.tile
    q = -(-n // t)
    if cfg.tile_format == "dense":
        from repro.kernels.rer_spmm.ops import prepare_blocks
        d["typed_blocks"] = []
        for rr in range(r):
            m = g.rel == rr
            if not m.any():
                continue
            sub = COOGraph(n, g.src[m], g.dst[m], g.weights()[m])
            b = coo_to_blocked(sub, t, order="column")
            blocks, brow, bcol = prepare_blocks(b.blocks, b.block_row,
                                                b.block_col, b.q)
            d["typed_blocks"].append(
                {"rel": rr, "q": b.q, "blocks": jnp.asarray(blocks),
                 "block_row": jnp.asarray(brow),
                 "block_col": jnp.asarray(bcol)})
        d["blocks_meta"] = {"q": q, "padded": q * t, "order": order,
                            "tile": t, "tile_format": "dense",
                            "format_choice": None, "num_relations": r}
        return wrap_plan(d)
    store = build_tile_store(g, t)
    ps = pack_tile_store(store)
    from repro.kernels.rer_gather import ops as gather_ops
    gsrc, gdst, gval = gather_ops.flat_entries(ps)
    tile_of = np.repeat(np.arange(ps.nnzb, dtype=np.int64),
                        np.diff(ps.entry_ptr))
    grel = ps.block_rel[tile_of].astype(np.int32)
    d["typed_flat"] = tuple(jnp.asarray(a)
                            for a in (gsrc, gdst, gval, grel))
    d["blocks_meta"] = {"q": store.q, "padded": store.padded_vertices,
                        "order": order, "tile": store.tile,
                        "tile_format": "packed", "format_choice": None,
                        "num_relations": r}
    return wrap_plan(d)
