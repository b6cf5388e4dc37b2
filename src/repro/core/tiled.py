"""Out-of-core tiled propagation executor (paper S5.1-S5.3, DESIGN.md C7).

Every other aggregation backend materialises the full graph (or its
blocked form) on device, which caps the graph size at device memory.
This module is the paper's actual scalability story: the adjacency is
grid-partitioned into a Q x Q grid of edge tiles that live in *host*
memory (`graphs.partition.EdgeTileStore`), and the executor streams them
host->device following the adaptive tile schedule (Table 3 / Eq. 8),
accumulating partial destination results exactly as the RER array does:

  * column-major (dst-stationary): the (T, d) accumulator for one
    destination interval stays on device across its whole tile-row sweep
    and is flushed to the host exactly once — the paper's Q x H writes;
  * row-major (src-stationary): one source interval stays resident while
    partial accumulators spill to the host after every tile — the
    paper's Q^2 x H write term, reproduced as real D2H transfers.

Double buffering (the C7 adaptation): while the device reduces chunk k,
the host has already issued `jax.device_put` for chunk k+1, so on real
hardware the tile DMA overlaps the MXU work (NeuraChip's decoupled
fetch/compute, PAPERS.md).  `double_buffer=False` serialises the two, as
an overlap ablation.

Tile format (DESIGN.md C8): with `tile_format="packed"` (or "auto", the
default, when the autotuner picks it) the executor streams *packed*
tiles — per-tile (row_local, col_local, val) entries padded to a pow2
nnz bucket — instead of densifying each tile to T x T.  Host->device
traffic and per-chunk MACs both drop by the tile fill factor (>95% of a
power-law graph's dense tile slots are structural zeros);
`TiledStats.fill_factor` reports how much padding remains.  The dense
path is kept bit-for-bit intact as the oracle (`tile_format="dense"`).

Chunk-queue streaming (DESIGN.md C11): the callback loop above pays one
host dispatch per staged chunk.  When the packed entries and the
feature matrix both fit the device budget, `streaming_mode="auto"` (the
default) stages the whole stream *once* as a device-resident
`kernels.chunk_queue` slab queue and the aggregate becomes a single
traced computation — zero per-chunk host round-trips, plain jax AD
through the queue sweep (no custom_vjp), and the Mosaic persistent
walker with explicit double-buffered DMA on TPU.  The callback loop
remains the true out-of-core path (`streaming_mode="callback"` forces
it; "chunk_queue" demands the queue and raises if it cannot fit).
`value_dtype="int8"` quantises the streamed tile values (queue slabs
and per-chunk packed staging alike) with error feedback
(`distributed.compression`), cutting the value plane's H2D bytes 4x;
`TiledStats.quant_val_bytes` vs `raw_val_bytes` records the saving.

Duplicate-edge caveat (shared with the blocked backends): tiles are
built with add-at, so multi-edges merge by summation before a max
aggregation sees them; dedup edges first if exact multi-edge max
semantics matter.
"""
from __future__ import annotations

import copy
import dataclasses
from collections import OrderedDict
from functools import partial
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.graphs.format import COOGraph
from repro.graphs.partition import (EdgeTileStore, PackedTileStore,
                                    build_tile_store, chunk_tile_row,
                                    pack_tile_store, tile_schedule_order,
                                    transpose_packed_store,
                                    transpose_tile_store)


class DeviceBudgetExceeded(RuntimeError):
    """A dense execution path needs more device memory than the budget."""


# ----------------------------------------------------------------------
# Footprint model: what each backend would place on device
# ----------------------------------------------------------------------

def dense_footprint_bytes(num_vertices: int, num_edges: int, in_dim: int,
                          out_dim: int, backend: str = "segment",
                          tile: int = 256, has_val: bool = True,
                          num_shards: int = 1,
                          tile_format: str = "dense",
                          training: bool = False,
                          value_dtype: str = "fp32") -> int:
    """Device bytes a graph-resident backend needs — the gate that
    decides when to spill to the streamed tiled executor.

    `training=True` prices the reverse pass too: every activation-
    shaped term doubles (each forward buffer has a cotangent twin under
    reverse-mode AD) while the graph structure (edge lists, tiles) is a
    constant with no gradient — so a graph can fit for inference yet
    spill to the streamed executor for training, which now has a
    reverse path of its own (DESIGN.md C9).

    `tile_format` prices the tile-carrying backends in the bytes they
    actually stage: "dense" is the historical 4 T^2 per tile, "packed"
    prices pow2-bucketed (row, col, val) entries (12 B each at fp32
    values, 9 B + per-tile scales with `value_dtype="int8"` — bucket
    padding bounded by 2x + the bucket floor per tile, DESIGN.md
    C8/C11), and "auto" takes the cheaper of the two (what the
    autotuner would pick on byte cost).

    For the ring-tiled backend the estimate is *per shard* of a
    `num_shards`-device ring (the budget is per device): one feature
    shard plus its ppermute double buffer and accumulator, and an upper
    bound on the device-resident stripe (`prepare_ring` refines the
    stripe term with the actually-built plan before deciding to
    spill — this closed form is for sizing without a build)."""
    from repro.kernels.autotune import packed_entry_bytes
    n, e, f, h = num_vertices, num_edges, in_dim, out_dim
    act = 2 if training else 1                # cotangent twin per buffer
    feat = act * 4 * n * (f + h)              # resident X and H
    scale_b = 4 if value_dtype == "int8" else 0   # f32 scale per group
    if backend == "segment":
        from repro.core.engn import edge_chunk, gathered_row_bytes
        edges = e * (8 + (4 if has_val else 0))
        # one edge chunk's gathered rows, at the 128-lane row width
        w = max(f, h)
        return feat + edges + act * edge_chunk(e, w) * gathered_row_bytes(w)
    if backend in ("blocked", "fused"):
        q = -(-n // tile)
        nnzb_ub = min(q * q, max(e, 1))
        dense = feat + 4 * nnzb_ub * tile * tile
        # merged entries <= E; pow2 bucket padding < 2x nnz + floor/tile
        packed = (feat
                  + packed_entry_bytes(2 * e + 8 * nnzb_ub, value_dtype)
                  + (8 + scale_b) * nnzb_ub)
        if tile_format == "dense" or backend == "fused":
            return dense              # the fused kernel eats dense tiles
        return packed if tile_format == "packed" else min(dense, packed)
    if backend == "ring":
        p = max(num_shards, 1)
        n_loc_raw = -(-n // p)
        t = max(1, min(tile, n_loc_raw))
        q_loc = -(-n_loc_raw // t)
        n_loc = q_loc * t
        q = p * q_loc
        # stripe upper bound: min(dense stripe, every edge in its own
        # tile, padding replicating the worst (dst, src) pair P times)
        per_dev_tiles = min(q_loc * q, p * max(e, 1))
        feat_ring = act * 4 * n_loc * (2 * f + h)
        dense = feat_ring + 4 * per_dev_tiles * t * t + 8 * per_dev_tiles
        packed = (feat_ring
                  + packed_entry_bytes(2 * e + 8 * p, value_dtype)
                  + scale_b * p + 4 * n_loc)
        if tile_format == "dense":
            return dense
        return packed if tile_format == "packed" else min(dense, packed)
    raise ValueError(backend)


def _step_bytes(tile: int, chunk: int, dim: int, x_cache: int) -> int:
    """Device bytes one streaming step holds: double-buffered tile
    chunks + the source-interval cache + the destination accumulator."""
    return 4 * (2 * (chunk * tile * tile + chunk * tile * dim)
                + x_cache * tile * dim
                + 2 * tile * dim)


def fit_tile_plan(budget_bytes: Optional[int], dim: int, tile: int = 256,
                  chunk: int = 8, x_cache: int = 2) -> Tuple[int, int]:
    """Largest (tile, chunk) whose streaming step footprint fits the
    device budget."""
    if not budget_bytes:
        return tile, chunk
    while _step_bytes(tile, chunk, dim, x_cache) > budget_bytes:
        if chunk > 1:
            chunk = chunk // 2
        elif tile > 8:
            tile = tile // 2
        else:
            raise DeviceBudgetExceeded(
                f"budget {budget_bytes}B cannot hold even a single "
                f"8x8 tile step at feature dim {dim}")
    return tile, chunk


# ----------------------------------------------------------------------
# Per-chunk device kernels (einsum path; `impl` can route through the
# Pallas rer_spmm kernel for TPU parity)
# ----------------------------------------------------------------------

@jax.jit
def _chunk_step_sum(acc, blocks, xs):
    # blocks (C, T, T) @ xs (C, T, d), reduced over the chunk -> (T, d)
    return acc + jnp.einsum("ktu,kuf->tf", blocks, xs,
                            preferred_element_type=jnp.float32)


@jax.jit
def _chunk_step_max(acc, blocks, xs):
    vals = jnp.where(blocks[..., None] != 0.0,
                     blocks[..., None] * xs[:, None, :, :], -jnp.inf)
    return jnp.maximum(acc, jnp.max(vals, axis=(0, 2)))


@jax.jit
def _finish_max(acc):
    return jnp.where(jnp.isneginf(acc), 0.0, acc)


@jax.jit
def _acc_add(acc, part):
    return acc + part


@jax.jit
def _acc_max(acc, part):
    # packed max partials keep -inf for uncovered rows: a no-op merge
    return jnp.maximum(acc, part)


@jax.jit
def _merge_max_count(acc_val, acc_cnt, m, c):
    """Associative merge of (running max, tie count) pairs: a strictly
    better chunk replaces the count, an exact tie adds to it (the -inf
    'no edges yet' state never ties thanks to the isfinite mask)."""
    better = m > acc_val
    ties = (m == acc_val) & jnp.isfinite(m)
    return (jnp.maximum(acc_val, m),
            jnp.where(better, c, acc_cnt + jnp.where(ties, c, 0.0)))


@jax.jit
def _chunk_step_max_count(acc_val, acc_cnt, blocks, xs):
    """Max chunk step that also counts, per (dst row, feature), how
    many edge products achieve the maximum — the residual the streamed
    VJP needs to split the cotangent evenly among tied winners
    (DESIGN.md C9), bitwise the convention of jax's segment_max grad."""
    vals = jnp.where(blocks[..., None] != 0.0,
                     blocks[..., None] * xs[:, None, :, :], -jnp.inf)
    m = jnp.max(vals, axis=(0, 2))
    c = jnp.sum(jnp.where((vals == m[None, :, None, :])
                          & jnp.isfinite(vals), 1.0, 0.0), axis=(0, 2))
    return _merge_max_count(acc_val, acc_cnt, m, c)


@jax.jit
def _packed_step_max_count(acc_val, acc_cnt, rows, cols, vals, xs):
    """Packed-format twin of `_chunk_step_max_count`: the products are
    the exact floats `packed_tile_part` computes, so the captured max
    and counts are consistent with the packed forward bit-for-bit."""
    c, s = rows.shape
    t, f = xs.shape[1], xs.shape[2]
    gcols = (jnp.arange(c, dtype=jnp.int32)[:, None] * t
             + cols).reshape(c * s)
    gathered = jnp.take(xs.reshape(c * t, f), gcols, axis=0)
    v = vals.reshape(c * s)
    scaled = jnp.where((v != 0.0)[:, None], v[:, None] * gathered,
                       -jnp.inf)
    seg = rows.reshape(c * s)
    m = jax.ops.segment_max(scaled, seg, num_segments=t)
    cnt = jax.ops.segment_sum(
        jnp.where((scaled == m[seg]) & (v != 0.0)[:, None], 1.0, 0.0),
        seg, num_segments=t)
    return _merge_max_count(acc_val, acc_cnt, m, cnt)


@jax.jit
def _chunk_maxbwd_dense(acc, xv, blocks, ygs):
    """One transposed backward chunk for max (dense tiles): `blocks`
    are the TRANSPOSED tiles (rows = src-local u, cols = dst-local t),
    `xv` the resident source interval, `ygs` the streamed (y, g/cnt)
    destination-interval stack.  Each edge product is recomputed with
    the exact operands of the forward (B^T[u, t] == B[t, u], same
    float), so the winner test is a bitwise equality, never a
    tolerance."""
    d = ygs.shape[-1] // 2
    ys, gs = ygs[..., :d], ygs[..., d:]
    prod = jnp.where(blocks[..., None] != 0.0,
                     blocks[..., None] * xv[None, :, None, :], jnp.inf)
    match = prod == ys[:, None, :, :]
    return acc + jnp.sum(
        jnp.where(match, blocks[..., None] * gs[:, None, :, :], 0.0),
        axis=(0, 2))


@jax.jit
def _chunk_maxbwd_packed(acc, xv, rows, cols, vals, ygs):
    """Packed twin of `_chunk_maxbwd_dense`: rows/cols come from the
    transposed packed store, so `rows` index the resident source
    interval (and the gx accumulator) and `cols` the streamed (y,
    g/cnt) stack."""
    c, s = rows.shape
    t = xv.shape[0]
    d = ygs.shape[-1] // 2
    v = vals.reshape(c * s)
    srcl = rows.reshape(c * s)
    gdst = (jnp.arange(c, dtype=jnp.int32)[:, None] * t
            + cols).reshape(c * s)
    flat = ygs.reshape(c * t, 2 * d)
    y_at = jnp.take(flat[:, :d], gdst, axis=0)
    g_at = jnp.take(flat[:, d:], gdst, axis=0)
    prod = v[:, None] * jnp.take(xv, srcl, axis=0)
    match = (v != 0.0)[:, None] & (prod == y_at)
    return acc + jax.ops.segment_sum(
        jnp.where(match, v[:, None] * g_at, 0.0), srcl, num_segments=t)


@partial(jax.jit, static_argnames=("r", "h"))
def _select_rel(xs, rels, *, r, h):
    """Per-tile relation slice of a stacked source payload: xs is the
    (C, T, R*H) interval stack (every relation's extracted messages for
    every source vertex), rels the chunk's per-tile edge types; returns
    the (C, T, H) stack each tile's reduction actually consumes.  This
    is the whole trick of the relation-typed tile layout (DESIGN.md
    C10): rel never rides the inner loop — it picks the slice once per
    staged tile."""
    c, t, ds = xs.shape
    assert ds == r * h, (ds, r, h)
    sel = jnp.take_along_axis(xs.reshape(c, t, r, h),
                              rels[:, None, None, None], axis=2)
    return sel[:, :, 0, :]


@partial(jax.jit, static_argnames=("r",))
def _chunk_step_sum_relscatter(acc, blocks, xs, rels, *, r):
    """Backward chunk step of the typed streamed sum (runs on the
    TRANSPOSED store): each tile's partial lands in its own relation's
    column block of the (T, R, H) accumulator — the exact adjoint of
    `_select_rel`'s per-tile slice."""
    part = jnp.einsum("ktu,kuf->ktf", blocks, xs,
                      preferred_element_type=jnp.float32)
    onehot = jax.nn.one_hot(rels, r, dtype=jnp.float32)
    return acc + jnp.einsum("ktf,kr->trf", part, onehot)


@partial(jax.jit, static_argnames=("r",))
def _packed_step_sum_relscatter(acc, rows, cols, vals, xs, rels, *, r):
    """Packed twin of `_chunk_step_sum_relscatter`: per-tile partials
    via a (tile, row) segment sum, then the same one-hot rel scatter."""
    c, s = rows.shape
    t, f = xs.shape[1], xs.shape[2]
    gcols = (jnp.arange(c, dtype=jnp.int32)[:, None] * t
             + cols).reshape(c * s)
    gathered = jnp.take(xs.reshape(c * t, f), gcols, axis=0)
    v = vals.reshape(c * s)
    seg = (jnp.arange(c, dtype=jnp.int32)[:, None] * t
           + rows).reshape(c * s)
    part = jax.ops.segment_sum(v[:, None] * gathered, seg,
                               num_segments=c * t).reshape(c, t, f)
    onehot = jax.nn.one_hot(rels, r, dtype=jnp.float32)
    return acc + jnp.einsum("ktf,kr->trf", part, onehot)


@partial(jax.jit, static_argnames=("mode",))
def _chunk_step_gated(acc, blocks, stream, res, *, mode):
    """Edgewise gated-message chunk step (dense tiles), one of three
    passes sharing the same sweep (DESIGN.md C10):

      * 'fwd': stream = (pc || x) source stacks, res = resident ph for
        the destination interval; accumulates y = sum val*sigma(a)*x
        with a = ph[dst] + pc[src];
      * 'dst': same operands, accumulates sum val*sigma'(a)*x — the
        dst-side gate gradient before the elementwise g multiply (the
        forward activations are *recomputed*, like the max path);
      * 'src': runs on the TRANSPOSED store — stream = (ph || g)
        destination stacks, res = resident pc for the source interval;
        accumulates [sum val*sigma(a)*g, sum val*sigma'(a)*g], the gx
        half and the gpc half (before its x multiply)."""
    f = res.shape[-1]
    mask = blocks[..., None] != 0.0
    if mode in ("fwd", "dst"):
        pc, xs = stream[..., :f], stream[..., f:]
        z = jax.nn.sigmoid(res[None, :, None, :] + pc[:, None, :, :])
        w = z if mode == "fwd" else z * (1.0 - z)
        contrib = jnp.where(mask, blocks[..., None] * w
                            * xs[:, None, :, :], 0.0)
        return acc + jnp.sum(contrib, axis=(0, 2))
    ph, g = stream[..., :f], stream[..., f:]
    z = jax.nn.sigmoid(ph[:, None, :, :] + res[None, :, None, :])
    wg = jnp.where(mask, blocks[..., None] * g[:, None, :, :], 0.0)
    gx = jnp.sum(wg * z, axis=(0, 2))
    s2 = jnp.sum(wg * z * (1.0 - z), axis=(0, 2))
    return acc + jnp.concatenate([gx, s2], axis=1)


@partial(jax.jit, static_argnames=("mode",))
def _packed_step_gated(acc, rows, cols, vals, stream, res, *, mode):
    """Packed twin of `_chunk_step_gated`: gather both streamed halves
    at the entry coordinates, recompute the gate, segment-reduce over
    the resident-interval rows."""
    c, s = rows.shape
    t, f = res.shape[0], res.shape[-1]
    gcols = (jnp.arange(c, dtype=jnp.int32)[:, None] * t
             + cols).reshape(c * s)
    flat = stream.reshape(c * t, stream.shape[-1])
    a_at = jnp.take(flat[:, :f], gcols, axis=0)
    b_at = jnp.take(flat[:, f:], gcols, axis=0)
    rowsf = rows.reshape(c * s)
    res_at = jnp.take(res, rowsf, axis=0)
    v = vals.reshape(c * s)
    live = (v != 0.0)[:, None]
    z = jax.nn.sigmoid(res_at + a_at)
    if mode in ("fwd", "dst"):
        w = z if mode == "fwd" else z * (1.0 - z)
        contrib = jnp.where(live, v[:, None] * w * b_at, 0.0)
        return acc + jax.ops.segment_sum(contrib, rowsf, num_segments=t)
    wg = jnp.where(live, v[:, None] * b_at, 0.0)
    gx = jax.ops.segment_sum(wg * z, rowsf, num_segments=t)
    s2 = jax.ops.segment_sum(wg * z * (1.0 - z), rowsf, num_segments=t)
    return acc + jnp.concatenate([gx, s2], axis=1)


@jax.jit
def _dequant_tiles(q, s):
    """(C, S) int8 values + (C,) per-tile scales -> f32 values, on
    device right after upload (the packed chunk kernels stay fp32)."""
    return q.astype(jnp.float32) * s[:, None]


@partial(jax.jit, static_argnames=("op", "impl", "q"))
def _chunk_step_kernel(acc, blocks, xs, *, op, impl, q):
    """Same chunk reduction expressed through the RER-SpMM kernel
    dispatcher (Mosaic on TPU, tiled XLA elsewhere): the chunk is a
    1-destination-interval block-sparse SpMM."""
    from repro.kernels.rer_spmm import ops as spmm_ops
    t = blocks.shape[1]
    rows = jnp.zeros(q, jnp.int32)
    cols = jnp.arange(q, dtype=jnp.int32)
    y = spmm_ops.blocked_spmm(blocks, rows, cols,
                              xs.reshape(q * t, xs.shape[-1]),
                              q=q, op=op, impl=impl)[:t]
    if op == "sum":
        return acc + y
    covered = (blocks != 0.0).any(axis=(0, 2))
    return jnp.where(covered[:, None], jnp.maximum(acc, y), acc)


# ----------------------------------------------------------------------
# The executor
# ----------------------------------------------------------------------

@dataclasses.dataclass
class TiledStats:
    steps: int = 0
    tiles: int = 0
    h2d_tile_bytes: int = 0
    h2d_x_bytes: int = 0
    d2h_bytes: int = 0
    x_loads: int = 0
    x_reuse_hits: int = 0
    # staged-payload accounting (both formats): real edge entries vs
    # the padded slots actually uploaded — dense slots are T^2 per
    # tile, packed slots are the pow2 nnz bucket (DESIGN.md C8)
    staged_nnz: int = 0
    staged_slots: int = 0
    packed_tile_bytes: int = 0        # h2d tile bytes when packed
    dense_tile_bytes: int = 0         # h2d tile bytes when dense
    # backward-pass traffic (DESIGN.md C9): the streamed VJP re-streams
    # the transposed tile store, so its transfers are accounted here
    # separately from the forward counters above
    bwd_steps: int = 0
    bwd_tiles: int = 0
    bwd_h2d_tile_bytes: int = 0
    bwd_h2d_x_bytes: int = 0
    bwd_d2h_bytes: int = 0
    # chunk-queue streaming (DESIGN.md C11): the queue stages once and
    # launches traced sweeps, so per-launch H2D/D2H counters above stay
    # quiet on this path — these record the build-time staging instead
    queue_builds: int = 0             # device queues staged
    queue_steps: int = 0              # slabs across all staged queues
    queue_launches: int = 0           # eager queue aggregates dispatched
    queue_h2d_bytes: int = 0          # one-time queue staging bytes
    # value-plane accounting (int8 tile values, DESIGN.md C11): bytes
    # the edge-weight plane actually travelled as vs its f32 size —
    # equal in fp32 mode, ~4x apart in int8 mode (scales included)
    quant_val_bytes: int = 0
    raw_val_bytes: int = 0
    # dynamic-graph accounting (DESIGN.md C14): full tile-store builds
    # vs incremental epoch merges — a healthy update loop holds
    # store_builds at 1 while delta_merges grows with the epochs
    store_builds: int = 0
    delta_merges: int = 0

    def add_backward(self, other: "TiledStats"):
        """Fold one backward sweep's forward-shaped counters (the
        transposed executor counts its own streaming as 'forward')
        into this executor's bwd_* accumulators."""
        self.bwd_steps += other.steps
        self.bwd_tiles += other.tiles
        self.bwd_h2d_tile_bytes += other.h2d_tile_bytes
        self.bwd_h2d_x_bytes += other.h2d_x_bytes
        self.bwd_d2h_bytes += other.d2h_bytes

    def fill_factor(self) -> float:
        """Real entries / padded slots staged so far (1.0 = no padding
        moved) — how much of the upload was useful work."""
        if not self.staged_slots:
            return 1.0
        return self.staged_nnz / self.staged_slots

    def value_compression(self) -> float:
        """Value-plane bytes moved / their f32 equivalent (1.0 in fp32
        mode, ~0.26 with int8 values + per-group scales)."""
        if not self.raw_val_bytes:
            return 1.0
        return self.quant_val_bytes / self.raw_val_bytes

    def as_dict(self) -> Dict[str, float]:
        d = dataclasses.asdict(self)
        d["fill_factor"] = self.fill_factor()
        d["value_compression"] = self.value_compression()
        return d


@dataclasses.dataclass(frozen=True)
class QueuePlan:
    """A feasible chunk-queue staging: `steps` slabs of `slab` entries,
    `device_bytes` total resident footprint (queue + resident x + the
    sweep's working set) under the executor's budget."""
    slab: int
    steps: int
    device_bytes: int


class TiledExecutor:
    """Streamed aggregate over a host-resident `EdgeTileStore`.

    graph:        the COO graph to partition (tiles are built once and
                  shared across layers / calls).
    tile, chunk:  interval size T and tiles per device step; both are
                  shrunk by `fit_tile_plan` when `budget_bytes` is set.
    budget_bytes: device-memory budget the streaming step must respect
                  (priced at the dense staging shapes for both formats —
                  a conservative bound for packed streaming).
    impl:         None -> fused einsum step; "xla"/"pallas" -> route each
                  chunk through the rer_spmm / rer_gather dispatchers.
    tile_format:  "dense" | "packed" | "auto" (DESIGN.md C8).  "auto"
                  asks `kernels.autotune.choose_tile_format`; pass
                  `autotune_measure=True` to decide by timed sample
                  chunks instead of the byte cost model.
    streaming_mode: "auto" | "callback" | "chunk_queue" (DESIGN.md
                  C11).  "auto" stages the whole packed stream as a
                  device-resident chunk queue whenever `queue_plan`
                  says it fits the budget (zero per-chunk host round
                  trips) and falls back to the per-chunk callback loop
                  otherwise; "callback" forces the loop (the true
                  out-of-core path); "chunk_queue" demands the queue
                  and raises `DeviceBudgetExceeded` when it cannot.
    value_dtype:  "fp32" | "int8" — how the packed tile *values*
                  travel.  int8 quantises per staged tile / per queue
                  slab with an error-feedback residual buffer
                  (`distributed.compression.StreamingTileQuantizer`);
                  indices always stay int32.  Requires a packed store
                  (tile_format != "dense").
    """

    def __init__(self, graph: COOGraph, tile: int = 256, chunk: int = 8,
                 budget_bytes: Optional[int] = None,
                 impl: Optional[str] = None, double_buffer: bool = True,
                 x_cache: int = 2, dim_hint: Optional[int] = None,
                 tile_format: str = "auto", bucket_floor: int = 8,
                 autotune_measure: bool = False,
                 streaming_mode: str = "auto",
                 value_dtype: str = "fp32"):
        from repro.kernels.autotune import choose_tile_format
        if streaming_mode not in ("auto", "callback", "chunk_queue"):
            raise ValueError(streaming_mode)
        if value_dtype not in ("fp32", "int8"):
            raise ValueError(value_dtype)
        dim = dim_hint if dim_hint is not None else 128
        tile, chunk = fit_tile_plan(budget_bytes, dim, tile, chunk, x_cache)
        self.store: EdgeTileStore = build_tile_store(graph, tile)
        self.packed: Optional[PackedTileStore] = None
        if tile_format != "dense":
            self.packed = pack_tile_store(self.store)
        self.format_choice = choose_tile_format(
            tile_format, self.packed, backend="tiled",
            bucket_floor=bucket_floor, measure=autotune_measure,
            store=self.store, dim=dim, value_dtype=value_dtype)
        self.tile_format = self.format_choice.fmt
        self.bucket_floor = self.format_choice.bucket_floor
        if value_dtype == "int8" and self.packed is None:
            raise ValueError(
                "value_dtype='int8' quantises packed tile values; "
                "tile_format='dense' has no packed value plane")
        self.chunk = chunk
        self.budget_bytes = budget_bytes
        self.impl = impl
        self.double_buffer = double_buffer
        self.x_cache_cap = max(2, x_cache)
        self.streaming_mode = streaming_mode
        self.value_dtype = value_dtype
        self.stats = TiledStats(store_builds=1)
        self._xcache: OrderedDict = OrderedDict()
        self._transposed: Optional["TiledExecutor"] = None
        self._diff_cache: Dict[str, Callable] = {}
        self._rel_select: Optional[int] = None
        self._init_queue_state()

    def _init_queue_state(self):
        """Fresh chunk-queue caches + error-feedback quantiser (called
        at construction and by `_from_stores` for derived views)."""
        self._queue_cache: Dict[int, object] = {}
        self._queue_max_diff: Dict[int, Callable] = {}
        self._tq = None
        self._counts_dev = None
        self.quantizer = None
        if self.value_dtype == "int8" and self.packed is not None:
            from repro.distributed.compression import StreamingTileQuantizer
            self.quantizer = StreamingTileQuantizer(self.packed.nnz)

    @classmethod
    def _from_stores(cls, store: EdgeTileStore,
                     packed: Optional[PackedTileStore], *,
                     like: "TiledExecutor") -> "TiledExecutor":
        """An executor over prebuilt stores, inheriting every streaming
        parameter from `like` (the transposed backward view shares the
        forward executor's tile/chunk/budget/format decisions)."""
        # shallow copy so any future __init__ attribute is inherited by
        # construction; only the stores and the mutable per-executor
        # state are replaced
        ex = copy.copy(like)
        ex.store = store
        ex.packed = packed
        ex.stats = TiledStats()
        ex._xcache = OrderedDict()
        ex._transposed = None
        ex._diff_cache = {}
        ex._rel_select = None
        ex._init_queue_state()
        return ex

    def transposed(self) -> "TiledExecutor":
        """The A^T view of this executor (cached): same host edge
        arrays (zero copy — see `transpose_tile_store`), same streaming
        parameters, its own stats.  The streamed VJP re-streams these
        transposed tiles instead of keeping forward activations
        resident (DESIGN.md C9)."""
        if self._transposed is None:
            tst = transpose_tile_store(self.store)
            tps = (transpose_packed_store(self.packed)
                   if self.packed is not None else None)
            self._transposed = TiledExecutor._from_stores(tst, tps,
                                                          like=self)
        return self._transposed

    def apply_updates(self, snapshot):
        """Merge one `EpochSnapshot` delta into this executor's stores
        in place — no full rebuild (`stats.store_builds` stays put,
        `stats.delta_merges` counts the epochs).  The merged stores are
        bitwise-equal to building fresh from `snapshot.graph`, so every
        aggregate after the merge matches a from-scratch executor
        exactly; all derived device state (staged queues, transposed
        views, jitted closures, x-cache) is dropped and re-stages
        lazily against the new stores.  Returns the `StoreDelta`."""
        from repro.graphs.updates import (update_packed_store,
                                          update_tile_store)
        new_store, delta = update_tile_store(
            self.store, snapshot.batch, snapshot.graph.num_vertices)
        if self.packed is not None:
            self.packed = update_packed_store(self.packed, new_store,
                                              delta)
        self.store = new_store
        self._xcache = OrderedDict()
        self._transposed = None
        self._diff_cache = {}
        self._rel_select = None
        self._init_queue_state()
        self.stats.delta_merges += 1
        return delta

    # -- public API ----------------------------------------------------
    def reset_stats(self):
        self.stats = TiledStats(store_builds=self.stats.store_builds,
                                delta_merges=self.stats.delta_merges)

    def effective_chunk(self, dim: int) -> int:
        """Re-fit the chunk for this call's feature dim.  The tile is
        fixed by the store, so only the chunk can shrink; if even a
        single tile per step exceeds the budget the executor refuses
        rather than silently overshooting — rebuild with a smaller tile
        (or a wider `dim_hint`) in that case."""
        if not self.budget_bytes:
            return self.chunk
        t, c = self.store.tile, self.chunk
        while (c > 1 and _step_bytes(t, c, dim, self.x_cache_cap)
                > self.budget_bytes):
            c = c // 2
        if _step_bytes(t, c, dim, self.x_cache_cap) > self.budget_bytes:
            raise DeviceBudgetExceeded(
                f"store tile {t} at feature dim {dim} exceeds the "
                f"{self.budget_bytes}B budget even with chunk=1; "
                f"rebuild the executor with dim_hint>={dim}")
        return c

    # -- chunk-queue streaming (DESIGN.md C11) -------------------------
    def queue_plan(self, d: int,
                   op: str = "sum") -> Optional[QueuePlan]:
        """Can this aggregate run as a device-resident chunk queue?
        Prices the queue itself (`kernels.chunk_queue.queue_bytes`) plus
        the sweep's working set — the resident (N, d) features, the
        (N+1, d) accumulator and per-slab segment output, and one
        (slab, d) gather intermediate — against the budget, halving the
        slab (floor 256) until it fits.  Returns None when the callback
        loop must run instead: streaming_mode="callback", no packed
        store, or over budget at the floor slab.
        streaming_mode="chunk_queue" raises instead of returning None
        for the budget case.  Differentiable max no longer constrains
        the slab count: multi-slab max routes through
        `make_queue_max_diff`, whose (max, tie-count) scan carry keeps
        `segment_max`'s even tie-split convention across slabs."""
        if self.streaming_mode == "callback" or self.packed is None:
            return None
        from repro.kernels.chunk_queue.ops import queue_bytes
        m = max(self.packed.nnz, 1)
        n = self.store.num_vertices
        d = max(int(d), 1)

        def total(slab: int) -> Tuple[int, int, int]:
            slab = min(slab, m)
            steps = -(-m // slab)
            work = 4 * d * (slab + 2 * (n + 1)) + 4 * n * d
            return queue_bytes(m, slab, self.value_dtype) + work, slab, steps

        slab = m
        b, slab, steps = total(slab)
        if self.budget_bytes:
            while b > self.budget_bytes and slab > 256:
                b, slab, steps = total(max(slab // 2, 256))
            if b > self.budget_bytes:
                if self.streaming_mode == "chunk_queue":
                    raise DeviceBudgetExceeded(
                        f"chunk queue needs {b}B at the floor slab, "
                        f"budget is {self.budget_bytes}B")
                return None
        return QueuePlan(slab, steps, b)

    def _device_queue(self, slab: int):
        """Build (once per slab size) and cache the device-resident
        queue; accounts the one-time staging in the queue/value-plane
        stat counters.  Built under `ensure_compile_time_eval`: the
        first build may happen while tracing (`_queue_traced` runs at
        trace time), and caching trace-scoped arrays would leak tracers
        into every later trace that hits the cache."""
        q = self._queue_cache.get(slab)
        if q is None:
            from repro.kernels.chunk_queue.ops import build_chunk_queue
            with jax.ensure_compile_time_eval():
                q = build_chunk_queue(self.packed, slab=slab,
                                      value_dtype=self.value_dtype,
                                      quantizer=self.quantizer)
            self._queue_cache[slab] = q
            st = self.stats
            st.queue_builds += 1
            st.queue_steps += q.steps
            st.queue_h2d_bytes += q.device_bytes()
            vb = int(q.vals.nbytes)
            if q.value_dtype == "int8":
                vb += int(q.scales.nbytes)
            st.quant_val_bytes += vb
            st.raw_val_bytes += q.raw_value_bytes()
        return q

    def _tile_queue(self):
        """The dst-sorted tile layout for the persistent Mosaic walker
        (built lazily, fp32 values only — the int8 queue keeps the XLA
        slab formulation so values stay quantised end to end)."""
        from repro.kernels.chunk_queue import ops as cq_ops
        if self.value_dtype != "fp32":
            return None
        if (self.impl or cq_ops.default_impl()) != "pallas":
            return None
        if self._tq is None:
            with jax.ensure_compile_time_eval():
                self._tq = cq_ops.build_tile_queue(self.packed,
                                                   self.bucket_floor)
            self.stats.queue_h2d_bytes += self._tq.device_bytes()
        return self._tq

    def _counts_col(self):
        if self._counts_dev is None:
            with jax.ensure_compile_time_eval():
                self._counts_dev = jnp.asarray(
                    np.maximum(self.store.in_counts, 1.0))[:, None]
        return self._counts_dev

    def _queue_eager(self, x: np.ndarray, op: str,
                     plan: QueuePlan) -> np.ndarray:
        """One queue launch for an eager aggregate: device-put x once,
        run the staged sweep, pull the result back."""
        from repro.kernels.chunk_queue import ops as cq_ops
        q = self._device_queue(plan.slab)
        self.stats.h2d_x_bytes += x.nbytes
        self.stats.x_loads += 1
        y = cq_ops.chunk_queue_aggregate(
            q, jax.device_put(x), op=op, impl=self.impl,
            tile_queue=self._tile_queue() if op == "sum" else None)
        self.stats.queue_launches += 1
        out = np.asarray(y)
        self.stats.d2h_bytes += out.nbytes
        return out

    def _queue_traced(self, x, op: str, plan: QueuePlan):
        """The traced formulation `make_streamed_aggregate` routes to
        when a queue plan exists: plain jax for sum/mean and
        single-slab max — jit fuses it, plain AD differentiates it, no
        host callbacks.  Multi-slab max swaps in `make_queue_max_diff`
        (forward bitwise the plain scan, custom backward carrying the
        cross-slab tie counts) so its gradient keeps `segment_max`'s
        even tie split."""
        from repro.kernels.chunk_queue.ops import (make_queue_max_diff,
                                                   queue_sweep_xla)
        q = self._device_queue(plan.slab)
        base = "sum" if op == "mean" else op
        if base == "max" and q.steps > 1:
            fn = self._queue_max_diff.get(plan.slab)
            if fn is None:
                fn = make_queue_max_diff(q)
                self._queue_max_diff[plan.slab] = fn
            y = fn(x)
        else:
            y = queue_sweep_xla(q.gsrc, q.gdst, q.vals, q.scales, x,
                                n=q.n, op=base)
        if op == "mean":
            y = y / self._counts_col()
        return y

    def aggregate(self, x: np.ndarray, op: str, order: str = "auto",
                  extract_fn: Optional[Callable] = None,
                  extract_dim: Optional[int] = None,
                  out_dim_hint: Optional[int] = None,
                  rel_channels: Optional[int] = None) -> np.ndarray:
        """A(x) (or A(extract(x))) streamed tile-by-tile; returns host
        (N, d).  `order` follows the adaptive scheduler when "auto":
        column-major iff F < 2H (Eq. 8), with F/H taken as the streamed
        dim and `out_dim_hint`.

        `rel_channels=H` turns on the relation-typed path (DESIGN.md
        C10): the streamed payload (x, or extract's output) is a
        (N, R*H) stack of per-relation messages, and every staged tile
        consumes the H-wide slice of its own `block_rel` — so a typed
        aggregate costs one sweep, not R.  Requires a store built from
        a typed graph."""
        x = np.ascontiguousarray(np.asarray(x, np.float32))
        if x.shape[0] != self.store.num_vertices:
            raise ValueError((x.shape, self.store.num_vertices))
        d = extract_dim if extract_fn is not None else x.shape[1]
        if rel_channels is not None:
            if self.store.block_rel is None:
                raise ValueError(
                    "rel_channels needs a relation-typed tile store "
                    "(graph built with rel ids and num_relations > 1)")
            if d != self.store.num_relations * rel_channels:
                raise ValueError((d, self.store.num_relations,
                                  rel_channels))
            d = rel_channels
        if order == "auto":
            h = out_dim_hint if out_dim_hint is not None else d
            order = tile_schedule_order(x.shape[1], h)
        base_op = "sum" if op == "mean" else op
        if base_op not in ("sum", "max"):
            raise ValueError(op)
        if extract_fn is None and rel_channels is None:
            plan = self.queue_plan(d, base_op)
            if plan is not None:
                out = self._queue_eager(x, base_op, plan)
                if op == "mean":
                    out = out / np.maximum(self.store.in_counts,
                                           1.0)[:, None]
                return out
        # extract_fn is called as-is: pass an already-jitted callable to
        # avoid re-tracing per aggregate() call (EnGNLayer caches its
        # jitted stage functions per layer instance)
        ext = extract_fn
        self._xcache = OrderedDict()
        self._rel_select = rel_channels
        try:
            if order == "column":
                out = self._sweep_column(x, base_op, ext, d)
            elif order == "row":
                out = self._sweep_row(x, base_op, ext, d)
            else:
                raise ValueError(order)
        finally:
            self._rel_select = None
        if op == "mean":
            out = out / np.maximum(self.store.in_counts, 1.0)[:, None]
        return out

    def stream_map(self, fn: Callable, *arrays: np.ndarray) -> np.ndarray:
        """Apply `fn` interval-by-interval on device (the update stage of
        a tiled layer): slices of the host arrays stream through, results
        stream back; only one interval is device-resident at a time.
        Pass an already-jitted `fn` — it is invoked as-is."""
        st = self.store
        jfn = fn
        outs: List[np.ndarray] = []
        staged = tuple(jax.device_put(self._interval(a, 0)) for a in arrays)
        for i in range(st.q):
            cur = staged
            if self.double_buffer and i + 1 < st.q:
                staged = tuple(jax.device_put(self._interval(a, i + 1))
                               for a in arrays)
            y = jfn(*cur)
            outs.append(np.asarray(y))
            self.stats.d2h_bytes += outs[-1].nbytes
            if not self.double_buffer and i + 1 < st.q:
                staged = tuple(jax.device_put(self._interval(a, i + 1))
                               for a in arrays)
        return np.concatenate(outs)[:st.num_vertices]

    # -- internals -----------------------------------------------------
    def _interval(self, a: np.ndarray, j: int) -> np.ndarray:
        t = self.store.tile
        blk = a[j * t:(j + 1) * t]
        if blk.shape[0] < t:
            out = np.zeros((t,) + a.shape[1:], a.dtype)
            out[:blk.shape[0]] = blk
            return out
        return blk

    def _src_interval(self, x: np.ndarray, j: int, ext):
        dev = self._xcache.get(j)
        if dev is not None:
            self.stats.x_reuse_hits += 1
            return dev
        hb = self._interval(x, j)
        self.stats.h2d_x_bytes += hb.nbytes
        self.stats.x_loads += 1
        dev = jax.device_put(hb)
        if ext is not None:
            dev = ext(dev)
        self._xcache[j] = dev
        while len(self._xcache) > self.x_cache_cap:
            self._xcache.popitem(last=False)
        return dev

    def _stage_packed(self, idx, width: int, bucket: int):
        """Upload one group of packed tiles as device (rows, cols, vals)
        at the given bucket; returns (payload, host bytes moved).  With
        `value_dtype="int8"` the value plane travels quantised (one f32
        scale per tile, error feedback through `self.quantizer`) and
        dequantises on device, so downstream chunk kernels are unchanged
        (DESIGN.md C11); the quant/raw value-byte counters record the
        saving."""
        ps = self.packed
        if self.value_dtype == "int8":
            rows, cols, qv, sc = ps.pack_quantized(idx, width, bucket,
                                                   self.quantizer)
            tb = rows.nbytes + cols.nbytes + qv.nbytes + sc.nbytes
            self.stats.quant_val_bytes += qv.nbytes + sc.nbytes
            self.stats.raw_val_bytes += 4 * qv.size
            payload = (jax.device_put(rows), jax.device_put(cols),
                       _dequant_tiles(jax.device_put(qv),
                                      jax.device_put(sc)))
        else:
            rows, cols, vals = ps.pack(idx, width, bucket)
            tb = rows.nbytes + cols.nbytes + vals.nbytes
            self.stats.quant_val_bytes += vals.nbytes
            self.stats.raw_val_bytes += vals.nbytes
            payload = (jax.device_put(rows), jax.device_put(cols),
                       jax.device_put(vals))
        return payload, tb

    def _stage_chunk(self, idx: np.ndarray, x: np.ndarray, ext, chunk: int):
        """Host->device for one chunk of tiles: the tile payload —
        dense (C, T, T) stack, or packed (C, S) entry arrays at the
        chunk's pow2 nnz bucket — plus the (C, T, d) stack of their
        source intervals (chunk width fixed so one program compiles)."""
        st = self.store
        t = st.tile
        k = idx.size
        assert k > 0, "chunks are built from non-empty tile lists"
        nnz = int((st.edge_ptr[idx + 1] - st.edge_ptr[idx]).sum())
        if self.tile_format == "packed":
            ps = self.packed
            bucket = ps.bucket_of(idx, self.bucket_floor)
            payload, tb = self._stage_packed(idx, chunk, bucket)
            self.stats.packed_tile_bytes += tb
            self.stats.staged_nnz += int(
                (ps.entry_ptr[idx + 1] - ps.entry_ptr[idx]).sum())
            self.stats.staged_slots += chunk * bucket
        else:
            # fresh buffer per stage: device_put may be zero-copy on
            # CPU, so the staged chunk must not be overwritten while in
            # flight
            blocks = np.zeros((chunk, t, t), np.float32)
            st.densify(idx, blocks)
            tb = blocks.nbytes
            self.stats.dense_tile_bytes += tb
            self.stats.staged_nnz += nnz
            self.stats.staged_slots += chunk * t * t
            payload = jax.device_put(blocks)
        self.stats.h2d_tile_bytes += tb
        self.stats.tiles += k
        xs = [self._src_interval(x, int(j), ext) for j in st.block_col[idx]]
        # pad with a repeat of the first interval: its tiles are zero, so
        # it contributes nothing, and the chunk shape stays compile-stable
        xs.extend(xs[0] for _ in range(chunk - k))
        xs_dev = jnp.stack(xs)
        if self._rel_select is not None:
            # typed store: each tile picks its relation's H-wide slice
            # of the (C, T, R*H) stack once per staging (padding tiles
            # are all-zero, so their rel-0 slice contributes nothing)
            rels = np.zeros(chunk, np.int32)
            rels[:k] = st.block_rel[idx]
            xs_dev = _select_rel(xs_dev, jnp.asarray(rels),
                                 r=st.num_relations, h=self._rel_select)
        return payload, xs_dev

    def _chunk_step(self, acc, payload, xs_dev, op: str, chunk: int):
        if self.tile_format == "packed":
            from repro.kernels.rer_gather import ops as gather_ops
            rows, cols, vals = payload
            part = gather_ops.packed_tile_part(rows, cols, vals, xs_dev,
                                               op=op, impl=self.impl)
            return (_acc_add(acc, part) if op == "sum"
                    else _acc_max(acc, part))
        if self.impl in ("xla", "pallas"):
            return _chunk_step_kernel(acc, payload, xs_dev, op=op,
                                      impl=self.impl, q=chunk)
        if op == "sum":
            return _chunk_step_sum(acc, payload, xs_dev)
        return _chunk_step_max(acc, payload, xs_dev)

    def _sweep_column(self, x, op, ext, d) -> np.ndarray:
        """dst-stationary: accumulator resident per destination interval,
        source tiles stream in S-shape chunks."""
        st = self.store
        t, q = st.tile, st.q
        chunk = self.effective_chunk(d)
        out = np.zeros((st.padded_vertices, d), np.float32)
        steps: List[Tuple[int, np.ndarray]] = []
        for i in range(q):
            for c in chunk_tile_row(st.row_tiles(i), chunk,
                                    snake=(i % 2 == 1)):
                steps.append((i, c))
        if not steps:
            return out[:st.num_vertices]

        def init_acc():
            if op == "max":
                return jnp.full((t, d), -jnp.inf, jnp.float32)
            return jnp.zeros((t, d), jnp.float32)

        def flush(i, acc):
            y = _finish_max(acc) if op == "max" else acc
            h = np.asarray(y)
            self.stats.d2h_bytes += h.nbytes
            out[i * t:(i + 1) * t] = h

        staged = self._stage_chunk(steps[0][1], x, ext, chunk)
        acc = None
        cur_row: Optional[int] = None
        for s, (i, idx) in enumerate(steps):
            payload, xs_dev = staged
            if i != cur_row:
                if cur_row is not None:
                    flush(cur_row, acc)
                acc = init_acc()
                cur_row = i
            if self.double_buffer and s + 1 < len(steps):
                # issue the next H2D before dispatching compute: the
                # transfer overlaps the reduction below (C7)
                staged = self._stage_chunk(steps[s + 1][1], x, ext, chunk)
            acc = self._chunk_step(acc, payload, xs_dev, op, chunk)
            self.stats.steps += 1
            if not self.double_buffer and s + 1 < len(steps):
                jax.block_until_ready(acc)
                staged = self._stage_chunk(steps[s + 1][1], x, ext, chunk)
        flush(cur_row, acc)
        return out[:st.num_vertices]

    def _sweep_row(self, x, op, ext, d) -> np.ndarray:
        """src-stationary: one source interval resident per column sweep;
        each tile's partial accumulator spills to the host (the paper's
        Q^2 x H write traffic, as real D2H transfers)."""
        st = self.store
        t, q = st.tile, st.q
        fill = -np.inf if op == "max" else 0.0
        out = np.full((st.padded_vertices, d), fill, np.float32)
        steps: List[Tuple[int, int]] = []
        for j in range(q):
            tiles = st.col_tiles(j)
            if j % 2 == 1:
                tiles = tiles[::-1]
            steps.extend((j, int(k)) for k in tiles)
        if not steps:
            return np.zeros((st.num_vertices, d), np.float32)

        def stage(step):
            j, k = step
            self.stats.tiles += 1
            if self.tile_format == "packed":
                ps = self.packed
                bucket = ps.bucket_of([k], self.bucket_floor)
                payload, tb = self._stage_packed([k], 1, bucket)
                self.stats.packed_tile_bytes += tb
                self.stats.staged_nnz += int(ps.entry_ptr[k + 1]
                                             - ps.entry_ptr[k])
                self.stats.staged_slots += bucket
            else:
                blk_host = st.densify([k],
                                      np.zeros((1, t, t), np.float32))[0]
                tb = blk_host.nbytes
                self.stats.dense_tile_bytes += tb
                self.stats.staged_nnz += int(st.edge_ptr[k + 1]
                                             - st.edge_ptr[k])
                self.stats.staged_slots += t * t
                payload = jax.device_put(blk_host)
            self.stats.h2d_tile_bytes += tb
            x_dev = self._src_interval(x, j, ext)
            if self._rel_select is not None:
                h = self._rel_select
                r_k = int(st.block_rel[k])
                x_dev = x_dev[:, r_k * h:(r_k + 1) * h]
            return (payload, x_dev)

        staged = stage(steps[0])
        for s, (j, k) in enumerate(steps):
            blk_dev, x_dev = staged
            if self.double_buffer and s + 1 < len(steps):
                staged = stage(steps[s + 1])
            part = self._tile_part(blk_dev, x_dev, op)
            self.stats.steps += 1
            hp = np.asarray(part)                 # partial spill (D2H)
            self.stats.d2h_bytes += hp.nbytes
            i = int(st.block_row[k])
            rows = slice(i * t, (i + 1) * t)
            if op == "sum":
                out[rows] += hp
            else:
                out[rows] = np.maximum(out[rows], hp)
            if not self.double_buffer and s + 1 < len(steps):
                staged = stage(steps[s + 1])
        if op == "max":
            out = np.where(np.isneginf(out), 0.0, out)
        return out[:st.num_vertices]

    # -- reverse path (DESIGN.md C9) -----------------------------------
    def aggregate_max_forward(self, x: np.ndarray
                              ) -> Tuple[np.ndarray, np.ndarray]:
        """Streamed max that also captures the backward residual:
        returns (y, counts), counts[i, f] = how many edge products
        achieved y[i, f].  The streamed VJP splits the cotangent evenly
        among tied winners — the same convention as jax's segment_max
        gradient, so streamed and device-resident grads agree on ties.
        Column (dst-stationary) order only: the (max, count) pair
        merges associatively per destination interval."""
        x = np.ascontiguousarray(np.asarray(x, np.float32))
        if x.shape[0] != self.store.num_vertices:
            raise ValueError((x.shape, self.store.num_vertices))
        d = x.shape[1]
        st = self.store
        t, q = st.tile, st.q
        chunk = self.effective_chunk(d)
        y = np.zeros((st.num_vertices, d), np.float32)
        cnt = np.zeros((st.num_vertices, d), np.float32)
        steps: List[Tuple[int, np.ndarray]] = []
        for i in range(q):
            for c in chunk_tile_row(st.row_tiles(i), chunk,
                                    snake=(i % 2 == 1)):
                steps.append((i, c))
        if not steps:
            return y, cnt
        self._xcache = OrderedDict()

        def flush(i, acc_v, acc_c):
            hv = np.asarray(_finish_max(acc_v))
            hc = np.asarray(acc_c)
            self.stats.d2h_bytes += hv.nbytes + hc.nbytes
            lo = i * t
            m = min((i + 1) * t, st.num_vertices) - lo
            if m > 0:
                y[lo:lo + m] = hv[:m]
                cnt[lo:lo + m] = hc[:m]

        staged = self._stage_chunk(steps[0][1], x, None, chunk)
        acc_v = acc_c = None
        cur_row: Optional[int] = None
        for s, (i, idx) in enumerate(steps):
            payload, xs_dev = staged
            if i != cur_row:
                if cur_row is not None:
                    flush(cur_row, acc_v, acc_c)
                acc_v = jnp.full((t, d), -jnp.inf, jnp.float32)
                acc_c = jnp.zeros((t, d), jnp.float32)
                cur_row = i
            if self.double_buffer and s + 1 < len(steps):
                staged = self._stage_chunk(steps[s + 1][1], x, None, chunk)
            if self.tile_format == "packed":
                rows, cols, vals = payload
                acc_v, acc_c = _packed_step_max_count(acc_v, acc_c, rows,
                                                      cols, vals, xs_dev)
            else:
                acc_v, acc_c = _chunk_step_max_count(acc_v, acc_c,
                                                     payload, xs_dev)
            self.stats.steps += 1
            if not self.double_buffer and s + 1 < len(steps):
                jax.block_until_ready(acc_v)
                staged = self._stage_chunk(steps[s + 1][1], x, None, chunk)
        flush(cur_row, acc_v, acc_c)
        return y, cnt

    def max_vjp(self, x: np.ndarray, y: np.ndarray, cnt: np.ndarray,
                g: np.ndarray) -> np.ndarray:
        """Backward of the streamed max: re-stream the same tiles in
        transposed (src <-> dst) order, recompute every edge product
        against the saved forward max, and scatter g/cnt to each tied
        winner — tile *recomputation* instead of keeping the forward
        activations resident, so the device budget holds for backward
        too.  Traffic lands in `stats.bwd_*`."""
        tex = self.transposed()
        tex.reset_stats()
        gn = (np.asarray(g, np.float32)
              / np.maximum(np.asarray(cnt, np.float32), 1.0))
        yg = np.ascontiguousarray(
            np.concatenate([np.asarray(y, np.float32), gn], axis=1))
        gx = tex._sweep_max_backward(
            np.ascontiguousarray(np.asarray(x, np.float32)), yg)
        self.stats.add_backward(tex.stats)
        return gx

    def _sweep_max_backward(self, x: np.ndarray,
                            yg: np.ndarray) -> np.ndarray:
        """Runs on the TRANSPOSED executor: accumulate gx per source
        interval (this store's rows), streaming the (y, g/cnt)
        destination-interval stacks through the tile chunks exactly as
        the forward streams x (same `_stage_chunk`, same S-shape)."""
        st = self.store
        t, q = st.tile, st.q
        d = yg.shape[1] // 2
        chunk = self.effective_chunk(2 * d)
        gx = np.zeros((st.padded_vertices, d), np.float32)
        steps: List[Tuple[int, np.ndarray]] = []
        for i in range(q):
            for c in chunk_tile_row(st.row_tiles(i), chunk,
                                    snake=(i % 2 == 1)):
                steps.append((i, c))
        if not steps:
            return gx[:st.num_vertices]
        self._xcache = OrderedDict()

        def flush(i, acc):
            h = np.asarray(acc)
            self.stats.d2h_bytes += h.nbytes
            gx[i * t:(i + 1) * t] = h

        staged = self._stage_chunk(steps[0][1], yg, None, chunk)
        acc = None
        xv = None
        cur_row: Optional[int] = None
        for s, (i, idx) in enumerate(steps):
            payload, ygs_dev = staged
            if i != cur_row:
                if cur_row is not None:
                    flush(cur_row, acc)
                acc = jnp.zeros((t, d), jnp.float32)
                hb = self._interval(x, i)
                self.stats.h2d_x_bytes += hb.nbytes
                self.stats.x_loads += 1
                xv = jax.device_put(hb)
                cur_row = i
            if self.double_buffer and s + 1 < len(steps):
                staged = self._stage_chunk(steps[s + 1][1], yg, None,
                                           chunk)
            if self.tile_format == "packed":
                rows, cols, vals = payload
                acc = _chunk_maxbwd_packed(acc, xv, rows, cols, vals,
                                           ygs_dev)
            else:
                acc = _chunk_maxbwd_dense(acc, xv, payload, ygs_dev)
            self.stats.steps += 1
            if not self.double_buffer and s + 1 < len(steps):
                jax.block_until_ready(acc)
                staged = self._stage_chunk(steps[s + 1][1], yg, None,
                                           chunk)
        flush(cur_row, acc)
        return gx[:st.num_vertices]

    # -- typed + gated passes (DESIGN.md C10) --------------------------
    def typed_sum_vjp(self, g: np.ndarray) -> np.ndarray:
        """Backward of the relation-typed streamed sum: re-stream the
        TRANSPOSED typed tiles (rel rides each tile unchanged — a
        tile's edge type is symmetric under src<->dst swap) and scatter
        each tile's partial into its relation's column block, giving
        the (N, R*H) cotangent of the stacked message payload."""
        if self.store.block_rel is None:
            raise ValueError("typed_sum_vjp needs a relation-typed store")
        tex = self.transposed()
        tex.reset_stats()
        gx = tex._sweep_relscatter(
            np.ascontiguousarray(np.asarray(g, np.float32)))
        self.stats.add_backward(tex.stats)
        return gx

    def _sweep_relscatter(self, g: np.ndarray) -> np.ndarray:
        """Runs on the TRANSPOSED executor: column-order sweep whose
        (T, R, H) accumulator receives each tile's partial in its own
        relation's block — the adjoint of `_select_rel`."""
        st = self.store
        t, q = st.tile, st.q
        r = st.num_relations
        h = g.shape[1]
        chunk = self.effective_chunk(r * h)
        gx = np.zeros((st.padded_vertices, r * h), np.float32)
        steps: List[Tuple[int, np.ndarray]] = []
        for i in range(q):
            for c in chunk_tile_row(st.row_tiles(i), chunk,
                                    snake=(i % 2 == 1)):
                steps.append((i, c))
        if not steps:
            return gx[:st.num_vertices]
        self._xcache = OrderedDict()

        def flush(i, acc):
            hb = np.asarray(acc).reshape(t, r * h)
            self.stats.d2h_bytes += hb.nbytes
            gx[i * t:(i + 1) * t] = hb

        staged = self._stage_chunk(steps[0][1], g, None, chunk)
        acc = None
        cur_row: Optional[int] = None
        for s, (i, idx) in enumerate(steps):
            payload, gs_dev = staged
            if i != cur_row:
                if cur_row is not None:
                    flush(cur_row, acc)
                acc = jnp.zeros((t, r, h), jnp.float32)
                cur_row = i
            rels = np.zeros(chunk, np.int32)
            rels[:idx.size] = st.block_rel[idx]
            rels_dev = jnp.asarray(rels)
            if self.double_buffer and s + 1 < len(steps):
                staged = self._stage_chunk(steps[s + 1][1], g, None, chunk)
            if self.tile_format == "packed":
                rows, cols, vals = payload
                acc = _packed_step_sum_relscatter(acc, rows, cols, vals,
                                                  gs_dev, rels_dev, r=r)
            else:
                acc = _chunk_step_sum_relscatter(acc, payload, gs_dev,
                                                 rels_dev, r=r)
            self.stats.steps += 1
            if not self.double_buffer and s + 1 < len(steps):
                jax.block_until_ready(acc)
                staged = self._stage_chunk(steps[s + 1][1], g, None, chunk)
        flush(cur_row, acc)
        return gx[:st.num_vertices]

    def gated_aggregate(self, ph: np.ndarray, pc: np.ndarray,
                        x: np.ndarray) -> np.ndarray:
        """Streamed gated sum (Eq. 4): y[d] = sum over edges (s -> d) of
        val * sigma(ph[d] + pc[s]) * x[s].  The dst-side gate input ph
        is the *resident* interval of the column sweep, so the gate
        costs no extra streaming beyond doubling the source payload
        (pc || x)."""
        stream = np.ascontiguousarray(
            np.concatenate([pc, x], axis=1).astype(np.float32))
        return self._sweep_gated(
            stream, np.ascontiguousarray(np.asarray(ph, np.float32)),
            "fwd")

    def gated_vjp(self, ph: np.ndarray, pc: np.ndarray, x: np.ndarray,
                  g: np.ndarray) -> Tuple[np.ndarray, np.ndarray,
                                          np.ndarray]:
        """Backward of the streamed gated sum: two recompute sweeps
        (the gate recomputes its forward activations like the max path
        — no edge-shaped residuals).  A forward-oriented sweep gives
        the dst-side sum val*sigma'(a)*x (gph = g ⊙ that); the
        transposed sweep streams (ph || g) against the resident pc and
        yields both gx = A_sigma^T g and the pc half of the gate grad.
        Traffic from both sweeps lands in `stats.bwd_*`."""
        ph = np.ascontiguousarray(np.asarray(ph, np.float32))
        pc = np.ascontiguousarray(np.asarray(pc, np.float32))
        x = np.ascontiguousarray(np.asarray(x, np.float32))
        g = np.ascontiguousarray(np.asarray(g, np.float32))
        saved = self.stats
        self.stats = TiledStats()
        u = self._sweep_gated(
            np.ascontiguousarray(np.concatenate([pc, x], axis=1)), ph,
            "dst")
        dst_stats = self.stats
        self.stats = saved
        self.stats.add_backward(dst_stats)
        gph = g * u
        tex = self.transposed()
        tex.reset_stats()
        both = tex._sweep_gated(
            np.ascontiguousarray(np.concatenate([ph, g], axis=1)), pc,
            "src")
        self.stats.add_backward(tex.stats)
        f = x.shape[1]
        return gph, x * both[:, f:], both[:, :f]

    def _sweep_gated(self, stream: np.ndarray, resident: np.ndarray,
                     mode: str) -> np.ndarray:
        """Column-order edgewise sweep shared by the three gated passes
        (`_chunk_step_gated` documents the modes): `stream` is the
        two-half source-side payload staged per tile chunk, `resident`
        the per-row-interval device-resident half (ph forward, pc on
        the transposed src-backward)."""
        st = self.store
        t, q = st.tile, st.q
        f = resident.shape[1]
        d_out = 2 * f if mode == "src" else f
        chunk = self.effective_chunk(max(stream.shape[1], d_out))
        out = np.zeros((st.padded_vertices, d_out), np.float32)
        steps: List[Tuple[int, np.ndarray]] = []
        for i in range(q):
            for c in chunk_tile_row(st.row_tiles(i), chunk,
                                    snake=(i % 2 == 1)):
                steps.append((i, c))
        if not steps:
            return out[:st.num_vertices]
        self._xcache = OrderedDict()

        def flush(i, acc):
            hb = np.asarray(acc)
            self.stats.d2h_bytes += hb.nbytes
            out[i * t:(i + 1) * t] = hb

        staged = self._stage_chunk(steps[0][1], stream, None, chunk)
        acc = None
        res_dev = None
        cur_row: Optional[int] = None
        for s, (i, idx) in enumerate(steps):
            payload, xs_dev = staged
            if i != cur_row:
                if cur_row is not None:
                    flush(cur_row, acc)
                acc = jnp.zeros((t, d_out), jnp.float32)
                hb = self._interval(resident, i)
                self.stats.h2d_x_bytes += hb.nbytes
                self.stats.x_loads += 1
                res_dev = jax.device_put(hb)
                cur_row = i
            if self.double_buffer and s + 1 < len(steps):
                staged = self._stage_chunk(steps[s + 1][1], stream, None,
                                           chunk)
            if self.tile_format == "packed":
                rows, cols, vals = payload
                acc = _packed_step_gated(acc, rows, cols, vals, xs_dev,
                                         res_dev, mode=mode)
            else:
                acc = _chunk_step_gated(acc, payload, xs_dev, res_dev,
                                        mode=mode)
            self.stats.steps += 1
            if not self.double_buffer and s + 1 < len(steps):
                jax.block_until_ready(acc)
                staged = self._stage_chunk(steps[s + 1][1], stream, None,
                                           chunk)
        flush(cur_row, acc)
        return out[:st.num_vertices]

    def _tile_part(self, blk_dev, x_dev, op: str):
        if self.tile_format == "packed":
            from repro.kernels.rer_gather import ops as gather_ops
            rows, cols, vals = blk_dev
            return gather_ops.packed_tile_part(rows, cols, vals,
                                               x_dev[None], op=op,
                                               impl=self.impl)
        if self.impl in ("xla", "pallas"):
            # single-tile chunk through the rer_spmm dispatcher; the
            # -inf/zero init makes the result exactly the raw partial
            t, d = blk_dev.shape[0], x_dev.shape[1]
            init = (jnp.full((t, d), -jnp.inf, jnp.float32) if op == "max"
                    else jnp.zeros((t, d), jnp.float32))
            return _chunk_step_kernel(init, blk_dev[None], x_dev[None],
                                      op=op, impl=self.impl, q=1)
        if op == "sum":
            return _tile_part_sum(blk_dev, x_dev)
        return _tile_part_max(blk_dev, x_dev)


# ----------------------------------------------------------------------
# Differentiable wrapper: the streamed aggregate inside jit/grad (C9)
# ----------------------------------------------------------------------

def make_streamed_aggregate(ex: TiledExecutor, op: str) -> Callable:
    """A jax-traceable, reverse-differentiable view of the streamed
    aggregate (DESIGN.md C9) — what makes the out-of-core backend
    *trainable*.  The host streaming loop runs inside
    `jax.pure_callback`, so it composes with jit/vjp while the graph
    stays host-resident; `jax.custom_vjp` supplies the reverse rule the
    callback lacks:

      * sum:  gx = A^T g — the cotangent re-streams the TRANSPOSED
        tile store (`TiledExecutor.transposed()`, a zero-copy src<->dst
        swap of the same host tiles); no residuals at all;
      * mean: streamed sum + a traced divide by in-counts (the
        divide's VJP is XLA's, the sum's is ours);
      * max:  forward captures (y, tie counts); backward re-streams
        transposed tiles, recomputes each edge product against y, and
        scatters g/count to every tied winner — the same even-split
        convention as jax's segment_max gradient.

    Chunk-queue route (DESIGN.md C11): when `ex.queue_plan` finds a
    device-resident staging that fits, the returned callable skips the
    callback machinery entirely and runs `ex._queue_traced` — a plain
    traced lax.scan over the prestaged slabs that jit fuses into the
    surrounding layer and plain jax AD differentiates (sum backward is
    the same gather/scatter scan transposed by AD; multi-slab max
    routes through `make_queue_max_diff`, whose (max, tie-count) carry
    keeps segment_max's even tie-split convention across slabs).  The
    routing happens per call
    at trace time, so one wrapper serves both regimes: a model traced
    under a tight budget streams through callbacks, the same model
    under a roomy budget runs queue-resident with zero host round
    trips.

    Results are cached per (executor, op) so repeated traces reuse one
    custom_vjp callable.  Gradients flow only to x (the adjacency is a
    constant of the graph)."""
    if op not in ("sum", "max", "mean"):
        raise ValueError(op)
    fn = ex._diff_cache.get(op)
    if fn is not None:
        return fn
    n = ex.store.num_vertices

    def _shape(a):
        return jax.ShapeDtypeStruct((n, a.shape[1]), jnp.float32)

    def _np(a):
        return np.ascontiguousarray(np.asarray(a, np.float32))

    def _host_sum_fwd(xh):
        return ex.aggregate(_np(xh), "sum", order="column")

    def _host_sum_bwd(gh):
        tex = ex.transposed()
        tex.reset_stats()
        gx = tex.aggregate(_np(gh), "sum", order="column")
        ex.stats.add_backward(tex.stats)
        return gx

    if op in ("sum", "mean"):
        @jax.custom_vjp
        def agg_sum(x):
            return jax.pure_callback(_host_sum_fwd, _shape(x), x)

        agg_sum.defvjp(
            lambda x: (agg_sum(x), None),
            lambda _, g: (jax.pure_callback(_host_sum_bwd, _shape(g),
                                            g),))
        if op == "sum":
            cb_fn = agg_sum
        else:
            counts = jnp.asarray(
                np.maximum(ex.store.in_counts, 1.0))[:, None]

            def cb_fn(x):
                return agg_sum(x) / counts
    else:
        def _host_max_fwd(xh):
            return ex.aggregate_max_forward(_np(xh))

        def _host_max_bwd(xh, yh, ch, gh):
            return ex.max_vjp(_np(xh), _np(yh), _np(ch), _np(gh))

        @jax.custom_vjp
        def agg_max(x):
            # primal (non-differentiated jitted forward): plain streamed
            # max — the tie counts are only captured in agg_max_fwd,
            # where a backward pass will actually consume them
            return jax.pure_callback(
                lambda xh: ex.aggregate(_np(xh), "max", order="column"),
                _shape(x), x)

        def agg_max_fwd(x):
            y, cnt = jax.pure_callback(_host_max_fwd,
                                       (_shape(x), _shape(x)), x)
            return y, (x, y, cnt)

        def agg_max_bwd(res, g):
            x, y, cnt = res
            gx = jax.pure_callback(_host_max_bwd, _shape(g), x, y, cnt,
                                   g)
            return (gx,)

        agg_max.defvjp(agg_max_fwd, agg_max_bwd)
        cb_fn = agg_max

    base_op = "sum" if op == "mean" else op

    def fn(x):
        # trace-time routing: shapes are concrete under jit, so the
        # plan (and thus which formulation lands in the jaxpr) is
        # decided per trace, not per run
        plan = ex.queue_plan(int(x.shape[1]), base_op)
        if plan is None:
            return cb_fn(x)
        return ex._queue_traced(x, op, plan)

    ex._diff_cache[op] = fn
    return fn


def make_streamed_typed_sum(ex: TiledExecutor) -> Callable:
    """Differentiable relation-typed streamed sum (DESIGN.md C10): the
    input is the (N, R*H) stack of per-relation messages (e.g. R-GCN's
    x @ W_r for every r), each typed tile consumes its own relation's
    slice, and the output is the plain (N, H) sum over all typed edges.
    Backward re-streams the TRANSPOSED typed tiles with `rel` riding
    each tile unchanged and scatters partials into the stacked
    cotangent — so per-relation weight gradients flow out-of-core with
    no edge-shaped residuals (like the untyped sum, the adjacency is a
    constant)."""
    if ex.store.block_rel is None:
        raise ValueError("typed streamed sum needs a relation-typed "
                         "tile store")
    fn = ex._diff_cache.get("typed_sum")
    if fn is not None:
        return fn
    n = ex.store.num_vertices
    r = ex.store.num_relations

    def _np(a):
        return np.ascontiguousarray(np.asarray(a, np.float32))

    @jax.custom_vjp
    def agg_typed(x):
        h = x.shape[1] // r
        return jax.pure_callback(
            lambda xh: ex.aggregate(_np(xh), "sum", order="column",
                                    rel_channels=h),
            jax.ShapeDtypeStruct((n, h), jnp.float32), x)

    agg_typed.defvjp(
        lambda x: (agg_typed(x), None),
        lambda _, g: (jax.pure_callback(
            lambda gh: ex.typed_sum_vjp(_np(gh)),
            jax.ShapeDtypeStruct((n, r * g.shape[1]), jnp.float32),
            g),))
    ex._diff_cache["typed_sum"] = agg_typed
    return agg_typed


def make_streamed_gated(ex: TiledExecutor) -> Callable:
    """Differentiable streamed gated sum (Eq. 4, DESIGN.md C10):
    `gated(ph, pc, x)` with ph = x @ W_H (dst-side gate input),
    pc = x @ W_C, returns sum_e val * sigma(ph[dst] + pc[src]) * x[src].
    The projections stay traced outside the callback, so W_H / W_C
    gradients flow through XLA's matmul VJP; the callback's own VJP is
    two recompute sweeps (`TiledExecutor.gated_vjp`) that rebuild the
    gate activations tile-by-tile instead of keeping edge-shaped
    residuals resident — the same recompute discipline as the streamed
    max."""
    fn = ex._diff_cache.get("gated")
    if fn is not None:
        return fn
    n = ex.store.num_vertices

    def _np(a):
        return np.ascontiguousarray(np.asarray(a, np.float32))

    def _shape(d):
        return jax.ShapeDtypeStruct((n, d), jnp.float32)

    def _host_fwd(ph, pc, x):
        return ex.gated_aggregate(_np(ph), _np(pc), _np(x))

    def _host_bwd(ph, pc, x, g):
        return ex.gated_vjp(_np(ph), _np(pc), _np(x), _np(g))

    @jax.custom_vjp
    def gated(ph, pc, x):
        return jax.pure_callback(_host_fwd, _shape(x.shape[1]),
                                 ph, pc, x)

    def gated_fwd(ph, pc, x):
        return gated(ph, pc, x), (ph, pc, x)

    def gated_bwd(res, g):
        ph, pc, x = res
        f = x.shape[1]
        return jax.pure_callback(_host_bwd,
                                 (_shape(f), _shape(f), _shape(f)),
                                 ph, pc, x, g)

    gated.defvjp(gated_fwd, gated_bwd)
    ex._diff_cache["gated"] = gated
    return gated


@jax.jit
def _tile_part_sum(blk, xj):
    return jnp.dot(blk, xj, preferred_element_type=jnp.float32)


@jax.jit
def _tile_part_max(blk, xj):
    vals = jnp.where(blk[:, :, None] != 0.0,
                     blk[:, :, None] * xj[None, :, :], -jnp.inf)
    return jnp.max(vals, axis=1)     # keeps -inf: host merge is a max
