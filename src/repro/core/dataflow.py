"""Ring-Edge-Reduce at pod scale (paper S4.1.2, adapted per DESIGN.md C2).

The ASIC connects PEs in a column into a ring; vertex properties flow
around the ring and every PE reduces the edges it owns.  The TPU analogue
lives one level up: *devices* form the ring (ICI torus), vertex-feature
shards rotate with `lax.ppermute`, and each device reduces the adjacency
tiles it owns against whichever shard is currently resident.  Each hop's
permute is issued before the tile contraction so XLA's latency-hiding
scheduler overlaps communication with the MXU work — the same
keep-the-ring-busy property the paper gets from edge reorganisation.

Two implementations share the dataflow:

* `ring_aggregate_dense` / `make_ring_aggregate` — the original dense
  reference: each device holds its (P, n_loc, n_loc) stripe of the full
  adjacency.  O(N^2 / P) device bytes per shard; oracle for tests and
  for `bench_scaling`.
* the **sharded ring-tiled backend** (`build_ring_tile_shards` /
  `make_ring_tiled_aggregate`) — the dense-tile path behind
  `EnGNConfig(backend="ring", tile_format="dense")`.  Destination
  vertices are partitioned into P shards; each device keeps only the
  *non-empty* T x T edge tiles of its stripe (the same sparse per-tile
  edge lists as `graphs.partition.EdgeTileStore`, densified once at
  build), its accumulator stays resident, and source-feature shards
  rotate around the ring.  No dense A, no full-graph replication:
  per-shard device bytes are O(nnzb_stripe * T^2 + n_loc * (F + H)).

* the **packed ring backend** (`build_packed_ring_shards` /
  `make_ring_packed_aggregate`, DESIGN.md C8) — what
  `tile_format="auto"` picks on sparse graphs.  Each (dst, src) shard
  pair carries its merged edge entries `(row_local, col_local, val)`
  directly, padded to the pow2 nnz bucket `l_max` instead of `s_max`
  zero *tiles*: per-shard device bytes drop from O(P s_max T^2) to
  O(P l_max * 12 B), and each ring step is a gather + segment reduce
  over real edges rather than dense T x T contractions over >95%
  structural zeros.

Zero-weight caveat (shared with every dense-tile backend): tiles are
dense scatter-adds, so an explicit 0.0-weight edge is indistinguishable
from no edge — max aggregation masks it out, where the segment
reference would include its 0*x term.  Drop or epsilon explicit zero
weights if that distinction matters.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.graphs.format import COOGraph
from repro.graphs.partition import (build_tile_store, merge_by_key,
                                    pow2_bucket)


def _ring_step_perm(p: int):
    # receive from the southern neighbour: (i+1) % p sends to i
    return [((i + 1) % p, i) for i in range(p)]


# ----------------------------------------------------------------------
# Dense reference ring (oracle; bench_scaling / small graphs only)
# ----------------------------------------------------------------------

def ring_aggregate_dense(a_blocks: jnp.ndarray, x_shard: jnp.ndarray,
                         axis_name: str, op: str = "sum") -> jnp.ndarray:
    """One RER rotation.  Must run inside shard_map over `axis_name`.

    a_blocks: (P, n_loc, n_loc) — this device's dst rows of A, split by
              source shard (a_blocks[s] multiplies the shard owned by
              device s).
    x_shard:  (n_loc, F) — this device's vertex features.
    Returns (n_loc, F): aggregated features for this device's vertices.
    """
    p = jax.lax.psum(1, axis_name)
    me = jax.lax.axis_index(axis_name)
    init_acc = (jnp.zeros(x_shard.shape, jnp.float32) if op == "sum"
                else jnp.full(x_shard.shape, -jnp.inf, jnp.float32))
    # mark the carry as device-varying so the fori_loop carry types match
    # after the ppermute
    init_acc = jax.lax.pcast(init_acc, axis_name, to="varying")

    def body(k, carry):
        x_rot, acc = carry
        src_shard = jax.lax.rem(me + k, p)
        blk = jax.lax.dynamic_index_in_dim(a_blocks, src_shard, 0,
                                           keepdims=False)
        # issue the hop first so it overlaps the contraction below
        x_next = jax.lax.ppermute(x_rot, axis_name, _ring_step_perm(p))
        if op == "sum":
            contrib = jnp.dot(blk, x_rot,
                              preferred_element_type=jnp.float32)
            acc = acc + contrib
        else:
            # max: elementwise per-edge, non-edges contribute -inf
            vals = jnp.where(blk[:, :, None] != 0.0,
                             blk[:, :, None] * x_rot[None, :, :], -jnp.inf)
            acc = jnp.maximum(acc, jnp.max(vals, axis=1))
        return (x_next, acc)

    _, acc = jax.lax.fori_loop(0, p, body, (x_shard, init_acc))
    if op == "max":
        acc = jnp.where(jnp.isinf(acc), 0.0, acc)
    return acc


def pad_ring_features(x, num_shards: int):
    """Pad vertex-feature rows up to a multiple of `num_shards` (the
    companion of `shard_adjacency_for_ring`, which pads A the same way:
    padded rows are zero and contribute nothing)."""
    n = x.shape[0]
    pad = (-n) % num_shards
    if pad == 0:
        return np.asarray(x)
    return np.concatenate(
        [np.asarray(x), np.zeros((pad,) + x.shape[1:], x.dtype)])


def make_ring_aggregate(mesh: Mesh, axis: str, op: str = "sum") -> Callable:
    """shard_map wrapper: (A_blocks_global, X_global) -> AX.

    A_blocks_global: (P, P, n_loc, n_loc) with A_blocks_global[d, s] the
    block of A mapping shard s sources to shard d destinations.
    X_global: (N, F) row-sharded over `axis` — N must be a multiple of
    the ring size (pad with `pad_ring_features`; a non-multiple would
    otherwise fail deep inside shard_map with an opaque sharding error).
    """
    fn = partial(ring_aggregate_dense, axis_name=axis, op=op)
    p = int(mesh.devices.size)

    def inner(a_blocks, x):
        # a_blocks arrives as (1, P, n_loc, n_loc) per device; squeeze.
        return fn(a_blocks[0], x)

    sm = jax.shard_map(inner, mesh=mesh,
                       in_specs=(P(axis, None, None, None), P(axis, None)),
                       out_specs=P(axis, None))

    def call(a_blocks, x):
        if a_blocks.shape[0] != p or a_blocks.shape[1] != p:
            raise ValueError(
                f"a_blocks must be (P, P, n_loc, n_loc) with P={p} ring "
                f"shards, got {a_blocks.shape} (build it with "
                f"shard_adjacency_for_ring(a, {p}))")
        if x.shape[0] != p * a_blocks.shape[2]:
            raise ValueError(
                f"X has {x.shape[0]} rows but the ring blocks expect "
                f"{p} shards of {a_blocks.shape[2]} vertices — pad the "
                f"features to {p * a_blocks.shape[2]} rows with "
                f"pad_ring_features (shard_adjacency_for_ring already "
                f"pads A the same way)")
        return sm(a_blocks, x)

    return call


def shard_adjacency_for_ring(a_dense, num_shards: int):
    """Host-side: dense A (N, N) -> (P, P, n_loc, n_loc) ring blocks,
    padding N up to a multiple of P."""
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    a_dense = np.asarray(a_dense)
    if a_dense.ndim != 2 or a_dense.shape[0] != a_dense.shape[1]:
        raise ValueError(f"adjacency must be square, got {a_dense.shape}")
    n = a_dense.shape[0]
    n_loc = -(-n // num_shards)
    pad = num_shards * n_loc - n
    if pad:
        a_dense = np.pad(a_dense, ((0, pad), (0, pad)))
    a = a_dense.reshape(num_shards, n_loc, num_shards, n_loc)
    return np.ascontiguousarray(a.transpose(0, 2, 1, 3))


# ----------------------------------------------------------------------
# Sharded ring-tiled backend (the "ring" backend of EnGNConfig)
# ----------------------------------------------------------------------

@dataclasses.dataclass
class RingStats:
    """Analytic traffic counters for one ring-tiled aggregate call,
    mirroring `core.tiled.TiledStats` (the device mesh has no host
    streaming, so the counters are computed from the plan, not
    measured)."""
    shards: int = 0
    ring_steps: int = 0        # ppermute hops per aggregate (= P)
    tiles: int = 0             # non-empty tiles reduced across the mesh
    padded_tiles: int = 0      # tiles staged after S_max padding
    block_bytes: int = 0       # device-resident tile/entry bytes per shard
    ppermute_bytes: int = 0    # feature bytes rotated per aggregate
    x_shard_bytes: int = 0     # one resident feature shard
    acc_bytes: int = 0         # the resident destination accumulator
    tile_format: str = "dense"
    # real edge entries vs device-resident padded slots (dense: T^2 per
    # staged tile; packed: the pow2 nnz bucket) — DESIGN.md C8
    nnz: int = 0
    padded_slots: int = 0

    def fill_factor(self) -> float:
        if not self.padded_slots:
            return 1.0
        return self.nnz / self.padded_slots

    def as_dict(self) -> Dict[str, float]:
        d = dataclasses.asdict(self)
        d["fill_factor"] = self.fill_factor()
        return d


@dataclasses.dataclass(frozen=True)
class RingTileShards:
    """Host-built, device-sharded form of the Q x Q edge-tile grid for
    the ring dataflow: destination vertices are split into P contiguous
    shards of `n_loc` (= q_loc * tile) vertices; each shard owns the
    row-stripe of tiles whose destination interval it contains, grouped
    by the source shard the ring rotation will deliver.

    blocks[d, s, i] is the i-th non-empty dense tile mapping sources of
    shard s to destinations of shard d; (tile_row, tile_col)[d, s, i]
    are its *local* destination/source interval indices.  Pairs are
    padded to `s_max` tiles with all-zero tiles (they contribute nothing
    to sum and are masked out of max).
    """
    num_shards: int
    tile: int
    q_loc: int                  # tile intervals per shard
    n_loc: int                  # padded vertices per shard (q_loc * tile)
    s_max: int                  # padded tiles per (dst, src) shard pair
    nnzb: int                   # non-empty tiles (unpadded)
    num_vertices: int
    blocks: np.ndarray          # (P, P, s_max, T, T) float32
    tile_row: np.ndarray        # (P, P, s_max) int32, local dst interval
    tile_col: np.ndarray        # (P, P, s_max) int32, local src interval
    in_counts: np.ndarray       # (P, n_loc) float32 in-edge counts
    # relation-typed stripes (DESIGN.md C10): every entry of a tile
    # shares its tile's relation id, so one (P, P, s_max) column covers
    # the whole stripe; None on untyped graphs
    tile_rel: Optional[np.ndarray] = None    # (P, P, s_max) int32
    num_relations: int = 1

    @property
    def padded_vertices(self) -> int:
        return self.num_shards * self.n_loc

    def device_bytes(self) -> int:
        """Device-resident bytes per shard: the tile stripe + indices +
        the in-count shard (feature/accumulator bytes are priced by
        `ring_feature_bytes` — they depend on the layer dims)."""
        p = self.num_shards
        per_dev_tiles = p * self.s_max
        rel = 4 * per_dev_tiles if self.tile_rel is not None else 0
        return int(4 * per_dev_tiles * self.tile * self.tile
                   + 2 * 4 * per_dev_tiles
                   + 4 * self.n_loc + rel)

    def stats(self, feat_dim: int, out_dim: Optional[int] = None) -> RingStats:
        p = self.num_shards
        h = out_dim if out_dim is not None else feat_dim
        return RingStats(
            shards=p,
            ring_steps=p,
            tiles=self.nnzb,
            padded_tiles=p * p * self.s_max,
            block_bytes=4 * p * self.s_max * self.tile * self.tile,
            ppermute_bytes=4 * p * p * self.n_loc * feat_dim,
            x_shard_bytes=4 * self.n_loc * feat_dim,
            acc_bytes=4 * self.n_loc * h,
            tile_format="dense",
            nnz=int((self.blocks != 0.0).sum()),
            padded_slots=p * p * self.s_max * self.tile * self.tile,
        )


def ring_feature_bytes(n_loc: int, in_dim: int, out_dim: int) -> int:
    """Per-shard bytes of the rotating feature buffers: the resident
    shard, the in-flight ppermute double buffer, and the accumulator."""
    return int(4 * n_loc * (2 * in_dim + out_dim))


def _ring_geometry(num_vertices: int, num_shards: int, tile: int):
    """(t, q_loc, n_loc): shard-aligned tile geometry shared by the
    builder and the cheap sizing pass."""
    n_loc_raw = -(-num_vertices // num_shards)
    t = max(1, min(tile, n_loc_raw))
    q_loc = -(-n_loc_raw // t)
    return t, q_loc, q_loc * t


def ring_stripe_bytes(g: COOGraph, num_shards: int, tile: int = 256,
                      in_dim: int = 0, out_dim: int = 0,
                      tile_format: str = "dense",
                      bucket_floor: int = 8,
                      value_dtype: str = "fp32") -> int:
    """Exact per-shard device bytes of the ring plan for `g` — one
    O(E log E) binning pass, no tile densification.  Matches
    `RingTileShards.device_bytes()` (dense) or
    `PackedRingShards.device_bytes()` (packed), + `ring_feature_bytes`
    when dims are given, so gates can price a batch before paying the
    build; "auto" returns the cheaper of the two (the format
    `prepare_ring` would pick).  `value_dtype="int8"` prices the packed
    stripes' value plane quantised — 9 B per entry slot plus one f32
    scale per stripe (DESIGN.md C11); ring execution itself stays fp32,
    this parameter only keeps budget comparisons honest against a
    quantised tiled/blocked alternative."""
    p = num_shards
    t, q_loc, n_loc = _ring_geometry(g.num_vertices, p, tile)
    feat = ring_feature_bytes(n_loc, in_dim, out_dim)

    def dense_bytes() -> int:
        q = p * q_loc
        key = (g.dst // t).astype(np.int64) * q + (g.src // t)
        uniq = np.unique(key)
        pair = (uniq // q) // q_loc * p + (uniq % q) // q_loc
        counts = np.bincount(pair, minlength=p * p)
        s_max = int(max(counts.max() if counts.size else 0, 1))
        per_dev = p * s_max
        return int(4 * per_dev * t * t + 8 * per_dev + 4 * n_loc)

    def packed_bytes() -> int:
        n_loc_p = -(-g.num_vertices // p)
        n_pad = p * n_loc_p
        uniq = np.unique(g.dst.astype(np.int64) * n_pad + g.src)
        pair = (uniq // n_pad) // n_loc_p * p + (uniq % n_pad) // n_loc_p
        counts = np.bincount(pair, minlength=p * p)
        l_max = pow2_bucket(int(counts.max()) if counts.size else 0,
                            bucket_floor)
        from repro.kernels.autotune import packed_entry_bytes
        scale_b = 4 if value_dtype == "int8" else 0
        return int(packed_entry_bytes(p * l_max, value_dtype)
                   + scale_b * p + 4 * n_loc_p)

    if tile_format == "dense":
        return dense_bytes() + feat
    if tile_format == "packed":
        return packed_bytes() + feat
    return min(dense_bytes(), packed_bytes()) + feat


def build_ring_tile_shards(g: COOGraph, num_shards: int,
                           tile: int = 256) -> RingTileShards:
    """Partition a COO graph into the per-shard sparse tile stripes the
    ring-tiled backend keeps device-resident.

    One `EdgeTileStore` build over the shard-aligned padded vertex space
    (O(E log E) host work), then the non-empty tiles are densified once
    and grouped by (dst shard, src shard).  Vertex counts that do not
    divide `num_shards` are padded up — padded rows have no edges and
    zero features, so they contribute nothing.
    """
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    p = num_shards
    n = g.num_vertices
    t, q_loc, n_loc = _ring_geometry(n, p, tile)
    n_pad = p * n_loc
    store = build_tile_store(
        dataclasses.replace(g, num_vertices=n_pad), t)
    assert store.q == p * q_loc

    d_of = store.block_row // q_loc            # dst shard per tile
    s_of = store.block_col // q_loc            # src shard per tile
    pair = d_of.astype(np.int64) * p + s_of
    order = np.argsort(pair, kind="stable").astype(np.int64)
    pair_sorted = pair[order]
    counts = np.bincount(pair_sorted, minlength=p * p)
    s_max = int(max(counts.max() if counts.size else 0, 1))
    starts = np.searchsorted(pair_sorted, np.arange(p * p))
    slot = np.arange(order.size) - starts[pair_sorted]

    blocks = np.zeros((p, p, s_max, t, t), np.float32)
    tile_row = np.zeros((p, p, s_max), np.int32)
    tile_col = np.zeros((p, p, s_max), np.int32)
    tile_rel = (np.zeros((p, p, s_max), np.int32)
                if store.block_rel is not None else None)
    if order.size:
        buf = np.zeros((order.size, t, t), np.float32)
        store.densify(order, buf)
        di, si = d_of[order], s_of[order]
        blocks[di, si, slot] = buf
        tile_row[di, si, slot] = (store.block_row[order] % q_loc)
        tile_col[di, si, slot] = (store.block_col[order] % q_loc)
        if tile_rel is not None:
            tile_rel[di, si, slot] = store.block_rel[order]

    return RingTileShards(
        num_shards=p, tile=t, q_loc=q_loc, n_loc=n_loc, s_max=s_max,
        nnzb=int(store.nnzb), num_vertices=n,
        blocks=blocks, tile_row=tile_row, tile_col=tile_col,
        in_counts=store.in_counts.reshape(p, n_loc).astype(np.float32),
        tile_rel=tile_rel, num_relations=store.num_relations)


# ----------------------------------------------------------------------
# Packed ring stripes (DESIGN.md C8): nnz-bucket padding, no dense tiles
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PackedRingShards:
    """Host-built, device-sharded *packed* form of the ring stripes:
    destination vertices split into P contiguous shards of `n_loc`
    vertices; each (dst shard d, src shard s) pair carries its merged
    edge entries directly — `rows[d, s, i]` / `cols[d, s, i]` are the
    shard-local destination / source vertex of entry i, `vals` its
    merged weight.  Pairs pad to the pow2 nnz bucket `l_max` with
    (0, 0, 0.0) entries (a no-op for sum, masked out of max by the
    val != 0 convention) — the nnz-bucket replacement for the dense
    plan's `s_max` zero-tile padding."""
    num_shards: int
    n_loc: int                  # padded vertices per shard
    l_max: int                  # pow2 padded entries per shard pair
    nnz: int                    # merged edge entries (unpadded)
    num_vertices: int
    rows: np.ndarray            # (P, P, L) int32 local dst vertex
    cols: np.ndarray            # (P, P, L) int32 local src vertex
    vals: np.ndarray            # (P, P, L) float32 (0.0 = padding)
    in_counts: np.ndarray       # (P, n_loc) float32 in-edge counts
    tile: int = 0               # no tiles in this form (meta compat)
    q_loc: int = 1
    s_max: int = 0              # = l_max (meta compat with the dense plan)
    nnzb: int = 0               # = nnz  (meta compat with the dense plan)
    # relation-typed stripes (DESIGN.md C10): per-entry relation id (the
    # packed carrier has no tile grouping to hang a shared id off);
    # None on untyped graphs.  Typed graphs merge multi-edges per
    # (dst, src, rel) so distinct relations never collapse.
    rels: Optional[np.ndarray] = None        # (P, P, L) int32
    num_relations: int = 1

    @property
    def padded_vertices(self) -> int:
        return self.num_shards * self.n_loc

    def device_bytes(self) -> int:
        """Device-resident bytes per shard: the packed stripe (12 B per
        entry slot across the P source pairs, +4 B with a rel column) +
        the in-count shard."""
        per_slot = 16 if self.rels is not None else 12
        return int(per_slot * self.num_shards * self.l_max
                   + 4 * self.n_loc)

    def stats(self, feat_dim: int, out_dim: Optional[int] = None) -> RingStats:
        p = self.num_shards
        h = out_dim if out_dim is not None else feat_dim
        return RingStats(
            shards=p,
            ring_steps=p,
            tiles=self.nnz,
            padded_tiles=p * p * self.l_max,
            block_bytes=12 * p * self.l_max,
            ppermute_bytes=4 * p * p * self.n_loc * feat_dim,
            x_shard_bytes=4 * self.n_loc * feat_dim,
            acc_bytes=4 * self.n_loc * h,
            tile_format="packed",
            nnz=self.nnz,
            padded_slots=p * p * self.l_max,
        )


def _merge_edges(g: COOGraph, n_pad: int):
    """Merge multi-edges by summation over the padded vertex space —
    the same coefficients the dense tiles' scatter-add produces
    (`graphs.partition.merge_by_key` is the shared merge core).
    Relation-typed graphs merge per (dst, src, rel), exactly like the
    rel-split tile stores, so typed packed and dense stripes carry the
    same coefficients.  Returns (dst, src, val, rel-or-None)."""
    typed = g.rel is not None and g.num_relations > 1
    r = int(g.num_relations) if typed else 1
    key = (g.dst.astype(np.int64) * n_pad + g.src) * r
    if typed:
        key = key + g.rel.astype(np.int64)
    ku, val = merge_by_key(key, g.weights())
    cell = ku // r
    rel = (ku % r).astype(np.int32) if typed else None
    return (cell // n_pad).astype(np.int64), \
        (cell % n_pad).astype(np.int64), val, rel


def build_packed_ring_shards(g: COOGraph, num_shards: int,
                             bucket_floor: int = 8) -> PackedRingShards:
    """Partition a COO graph into per-(dst, src)-shard-pair packed edge
    lists: one argsort to merge multi-edges, one binning pass to group
    by shard pair — O(E log E) host work, no T^2 anywhere."""
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    p = num_shards
    n = g.num_vertices
    n_loc = -(-n // p)
    n_pad = p * n_loc
    dst, src, val, rel = _merge_edges(g, n_pad)
    d_of = dst // n_loc
    s_of = src // n_loc
    pair = d_of * p + s_of
    order = np.argsort(pair, kind="stable")
    pair_sorted = pair[order]
    counts = np.bincount(pair_sorted, minlength=p * p)
    l_max = pow2_bucket(int(counts.max()) if counts.size else 0,
                        bucket_floor)
    starts = np.searchsorted(pair_sorted, np.arange(p * p))
    slot = np.arange(order.size) - starts[pair_sorted]

    rows = np.zeros((p, p, l_max), np.int32)
    cols = np.zeros((p, p, l_max), np.int32)
    vals = np.zeros((p, p, l_max), np.float32)
    rels = np.zeros((p, p, l_max), np.int32) if rel is not None else None
    if order.size:
        di, si = d_of[order], s_of[order]
        rows[di, si, slot] = (dst[order] % n_loc)
        cols[di, si, slot] = (src[order] % n_loc)
        vals[di, si, slot] = val[order]
        if rels is not None:
            rels[di, si, slot] = rel[order]
    in_counts = np.bincount(g.dst, minlength=n_pad).astype(np.float32)
    return PackedRingShards(
        num_shards=p, n_loc=n_loc, l_max=l_max, nnz=int(dst.size),
        num_vertices=n, rows=rows, cols=cols, vals=vals,
        in_counts=in_counts.reshape(p, n_loc),
        s_max=l_max, nnzb=int(dst.size),
        rels=rels, num_relations=int(g.num_relations))


def _ring_packed_shard(rows, cols, vals, x_shard, counts, *,
                       axis_name: str, op: str, n_loc: int,
                       num_shards: int):
    """Per-device body (inside shard_map): gather + segment-reduce this
    device's packed stripe against each rotating source shard.

    rows/cols/vals: (P, L) — this shard's entries, by source shard.
    x_shard:        (n_loc, F) — the resident feature shard (rotates).
    counts:         (n_loc,) — in-edge counts (mean divides by them).
    """
    p = num_shards
    me = jax.lax.axis_index(axis_name)
    f = x_shard.shape[1]
    base_op = "sum" if op == "mean" else op
    if base_op == "sum":
        init_acc = jnp.zeros((n_loc, f), jnp.float32)
    else:
        init_acc = jnp.full((n_loc, f), -jnp.inf, jnp.float32)
    init_acc = jax.lax.pcast(init_acc, axis_name, to="varying")

    def step(carry, k):
        x_rot, acc = carry
        s = jax.lax.rem(me + k, p)
        r = jax.lax.dynamic_index_in_dim(rows, s, 0, keepdims=False)
        c = jax.lax.dynamic_index_in_dim(cols, s, 0, keepdims=False)
        v = jax.lax.dynamic_index_in_dim(vals, s, 0, keepdims=False)
        # issue the hop before the gather/reduce: the collective-permute
        # overlaps the edge work below (C2)
        x_next = jax.lax.ppermute(x_rot, axis_name, _ring_step_perm(p))
        gathered = jnp.take(x_rot, c, axis=0)              # (L, F)
        if base_op == "sum":
            acc = acc + jax.ops.segment_sum(v[:, None] * gathered, r,
                                            num_segments=n_loc)
        else:
            scaled = jnp.where((v != 0.0)[:, None],
                               v[:, None] * gathered, -jnp.inf)
            acc = jnp.maximum(
                acc, jax.ops.segment_max(scaled, r, num_segments=n_loc))
        return (x_next, acc), None

    (_, acc), _ = jax.lax.scan(step, (x_shard, init_acc),
                               jnp.arange(p, dtype=jnp.int32))
    y = acc
    if base_op == "max":
        y = jnp.where(jnp.isneginf(y), 0.0, y)
    if op == "mean":
        y = y / jnp.maximum(counts, 1.0)[:, None]
    return y


def make_ring_packed_aggregate(mesh: Mesh, axis: str, op: str,
                               n_loc: int) -> Callable:
    """shard_map wrapper over `_ring_packed_shard`:

        (rows, cols, vals, X_padded, in_counts) -> A(X)

    with rows/cols/vals (P, P, L), X_padded (P * n_loc, F) row-sharded
    over `axis`, in_counts (P, n_loc)."""
    if op not in ("sum", "max", "mean"):
        raise ValueError(op)
    p = int(mesh.shape[axis])
    body = partial(_ring_packed_shard, axis_name=axis, op=op,
                   n_loc=n_loc, num_shards=p)

    def inner(rows, cols, vals, x, counts):
        # leading P dim arrives size-1 per device; squeeze it
        return body(rows[0], cols[0], vals[0], x, counts[0])

    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(axis, None, None), P(axis, None, None),
                  P(axis, None, None), P(axis, None), P(axis, None)),
        out_specs=P(axis, None))


def _ring_tiled_shard(blocks, tile_row, tile_col, x_shard, counts, *,
                      axis_name: str, op: str, q_loc: int, tile: int,
                      num_shards: int):
    """Per-device body (inside shard_map): reduce this device's sparse
    tile stripe against each rotating source shard.

    blocks:   (P, s_max, T, T) — this shard's tiles, by source shard.
    x_shard:  (n_loc, F) — the resident feature shard (rotates).
    counts:   (n_loc,) — in-edge counts (mean divides by them).

    `num_shards` is static (the mesh size): the ring schedule is a
    length-P scan, which keeps the loop reverse-differentiable for
    training (fori_loop with a traced bound would not be).
    """
    p = num_shards
    me = jax.lax.axis_index(axis_name)
    f = x_shard.shape[1]
    base_op = "sum" if op == "mean" else op
    if base_op == "sum":
        init_acc = jnp.zeros((q_loc, tile, f), jnp.float32)
    else:
        init_acc = jnp.full((q_loc, tile, f), -jnp.inf, jnp.float32)
    init_acc = jax.lax.pcast(init_acc, axis_name, to="varying")

    def step(carry, k):
        x_rot, acc = carry
        s = jax.lax.rem(me + k, p)
        blk = jax.lax.dynamic_index_in_dim(blocks, s, 0, keepdims=False)
        trow = jax.lax.dynamic_index_in_dim(tile_row, s, 0, keepdims=False)
        tcol = jax.lax.dynamic_index_in_dim(tile_col, s, 0, keepdims=False)
        # issue the hop before the contraction: the collective-permute
        # overlaps the tile reduction below (C2)
        x_next = jax.lax.ppermute(x_rot, axis_name, _ring_step_perm(p))
        xs = jnp.take(x_rot.reshape(q_loc, tile, f), tcol, axis=0)
        if base_op == "sum":
            part = jnp.einsum("ktu,kuf->ktf", blk, xs,
                              preferred_element_type=jnp.float32)
            acc = acc + jax.ops.segment_sum(part, trow, num_segments=q_loc)
        else:
            # padded (all-zero) tiles contribute -inf rows: a no-op max
            vals = jnp.where(blk[..., None] != 0.0,
                             blk[..., None] * xs[:, None, :, :], -jnp.inf)
            part = jnp.max(vals, axis=2)                   # (s_max, T, F)
            acc = jnp.maximum(
                acc, jax.ops.segment_max(part, trow, num_segments=q_loc))
        return (x_next, acc), None

    (_, acc), _ = jax.lax.scan(step, (x_shard, init_acc),
                               jnp.arange(p, dtype=jnp.int32))
    y = acc.reshape(q_loc * tile, f)
    if base_op == "max":
        y = jnp.where(jnp.isneginf(y), 0.0, y)
    if op == "mean":
        y = y / jnp.maximum(counts, 1.0)[:, None]
    return y


def make_ring_tiled_aggregate(mesh: Mesh, axis: str, op: str,
                              q_loc: int, tile: int) -> Callable:
    """shard_map wrapper over `_ring_tiled_shard`:

        (blocks, tile_row, tile_col, X_padded, in_counts) -> A(X)

    with blocks (P, P, s_max, T, T), X_padded (P * n_loc, F) row-sharded
    over `axis`, in_counts (P, n_loc).  `op` is "sum" | "max" | "mean"
    (mean = ring sum, then divide by the resident in-count shard).
    """
    if op not in ("sum", "max", "mean"):
        raise ValueError(op)
    p = int(mesh.shape[axis])
    body = partial(_ring_tiled_shard, axis_name=axis, op=op,
                   q_loc=q_loc, tile=tile, num_shards=p)

    def inner(blocks, tile_row, tile_col, x, counts):
        # leading P dim arrives size-1 per device; squeeze it
        return body(blocks[0], tile_row[0], tile_col[0], x, counts[0])

    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(axis, None, None, None, None), P(axis, None, None),
                  P(axis, None, None), P(axis, None), P(axis, None)),
        out_specs=P(axis, None))


# ----------------------------------------------------------------------
# Staged-contract ring bodies (DESIGN.md C10): relation-typed sums and
# dst+src gated messages ride the same rotation — typed stripes carry a
# rel per tile/entry selecting its slice of the rotating (N, R*H)
# payload; the gate keeps ph resident on the destination shard while
# (pc || x) rotates.  All bodies are lax.scan over the P hops, so
# jax.grad differentiates straight through the ring (no custom VJP).
# ----------------------------------------------------------------------

def _ring_typed_tiled_shard(blocks, tile_row, tile_col, tile_rel,
                            x_shard, counts, *, axis_name: str,
                            q_loc: int, tile: int, num_shards: int,
                            num_relations: int):
    """Typed sum: x_shard is the rotating (n_loc, R*H) stacked payload;
    each tile contracts the H-wide slice of its own relation."""
    p, r = num_shards, num_relations
    me = jax.lax.axis_index(axis_name)
    h = x_shard.shape[1] // r
    init_acc = jax.lax.pcast(jnp.zeros((q_loc, tile, h), jnp.float32),
                             axis_name, to="varying")

    def step(carry, k):
        x_rot, acc = carry
        s = jax.lax.rem(me + k, p)
        blk = jax.lax.dynamic_index_in_dim(blocks, s, 0, keepdims=False)
        trow = jax.lax.dynamic_index_in_dim(tile_row, s, 0,
                                            keepdims=False)
        tcol = jax.lax.dynamic_index_in_dim(tile_col, s, 0,
                                            keepdims=False)
        trel = jax.lax.dynamic_index_in_dim(tile_rel, s, 0,
                                            keepdims=False)
        x_next = jax.lax.ppermute(x_rot, axis_name, _ring_step_perm(p))
        xs = jnp.take(x_rot.reshape(q_loc, tile, r * h), tcol, axis=0)
        sel = jnp.take_along_axis(
            xs.reshape(-1, tile, r, h),
            trel[:, None, None, None], axis=2)[:, :, 0, :]
        part = jnp.einsum("ktu,kuf->ktf", blk, sel,
                          preferred_element_type=jnp.float32)
        acc = acc + jax.ops.segment_sum(part, trow, num_segments=q_loc)
        return (x_next, acc), None

    (_, acc), _ = jax.lax.scan(step, (x_shard, init_acc),
                               jnp.arange(p, dtype=jnp.int32))
    return acc.reshape(q_loc * tile, h)


def make_ring_typed_sum_tiled(mesh: Mesh, axis: str, q_loc: int,
                              tile: int, num_relations: int) -> Callable:
    """shard_map wrapper over `_ring_typed_tiled_shard`:

        (blocks, tile_row, tile_col, tile_rel, X_payload, in_counts)
            -> sum_r A_r X[:, rH:(r+1)H]

    with X_payload (P * n_loc, R*H) row-sharded over `axis`."""
    p = int(mesh.shape[axis])
    body = partial(_ring_typed_tiled_shard, axis_name=axis, q_loc=q_loc,
                   tile=tile, num_shards=p, num_relations=num_relations)

    def inner(blocks, tile_row, tile_col, tile_rel, x, counts):
        return body(blocks[0], tile_row[0], tile_col[0], tile_rel[0],
                    x, counts[0])

    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(axis, None, None, None, None), P(axis, None, None),
                  P(axis, None, None), P(axis, None, None),
                  P(axis, None), P(axis, None)),
        out_specs=P(axis, None))


def _ring_typed_packed_shard(rows, cols, vals, rels, x_shard, counts, *,
                             axis_name: str, n_loc: int,
                             num_shards: int, num_relations: int):
    """Typed sum on packed stripes: per-entry rel selects the slice of
    the gathered (L, R*H) payload rows."""
    p, r = num_shards, num_relations
    me = jax.lax.axis_index(axis_name)
    h = x_shard.shape[1] // r
    init_acc = jax.lax.pcast(jnp.zeros((n_loc, h), jnp.float32),
                             axis_name, to="varying")

    def step(carry, k):
        x_rot, acc = carry
        s = jax.lax.rem(me + k, p)
        rw = jax.lax.dynamic_index_in_dim(rows, s, 0, keepdims=False)
        c = jax.lax.dynamic_index_in_dim(cols, s, 0, keepdims=False)
        v = jax.lax.dynamic_index_in_dim(vals, s, 0, keepdims=False)
        re = jax.lax.dynamic_index_in_dim(rels, s, 0, keepdims=False)
        x_next = jax.lax.ppermute(x_rot, axis_name, _ring_step_perm(p))
        gathered = jnp.take(x_rot, c, axis=0)          # (L, R*H)
        sel = jnp.take_along_axis(gathered.reshape(-1, r, h),
                                  re[:, None, None], axis=1)[:, 0, :]
        acc = acc + jax.ops.segment_sum(v[:, None] * sel, rw,
                                        num_segments=n_loc)
        return (x_next, acc), None

    (_, acc), _ = jax.lax.scan(step, (x_shard, init_acc),
                               jnp.arange(p, dtype=jnp.int32))
    return acc


def make_ring_typed_sum_packed(mesh: Mesh, axis: str, n_loc: int,
                               num_relations: int) -> Callable:
    """shard_map wrapper over `_ring_typed_packed_shard`:

        (rows, cols, vals, rels, X_payload, in_counts)
            -> sum_r A_r X[:, rH:(r+1)H]"""
    p = int(mesh.shape[axis])
    body = partial(_ring_typed_packed_shard, axis_name=axis,
                   n_loc=n_loc, num_shards=p,
                   num_relations=num_relations)

    def inner(rows, cols, vals, rels, x, counts):
        return body(rows[0], cols[0], vals[0], rels[0], x, counts[0])

    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(axis, None, None), P(axis, None, None),
                  P(axis, None, None), P(axis, None, None),
                  P(axis, None), P(axis, None)),
        out_specs=P(axis, None))


def _ring_gated_tiled_shard(blocks, tile_row, tile_col, ph_shard,
                            pcx_shard, counts, *, axis_name: str,
                            q_loc: int, tile: int, num_shards: int):
    """Gated sum: message = val * sigmoid(ph[dst] + pc[src]) * x[src].
    ph stays resident on the destination shard; the (pc || x) stack
    rotates.  val == 0 slots (structural zeros and tile padding) are
    masked out — the shared no-edge convention."""
    p = num_shards
    me = jax.lax.axis_index(axis_name)
    f = pcx_shard.shape[1] // 2
    ph_t = ph_shard.reshape(q_loc, tile, f)
    init_acc = jax.lax.pcast(jnp.zeros((q_loc, tile, f), jnp.float32),
                             axis_name, to="varying")

    def step(carry, k):
        x_rot, acc = carry
        s = jax.lax.rem(me + k, p)
        blk = jax.lax.dynamic_index_in_dim(blocks, s, 0, keepdims=False)
        trow = jax.lax.dynamic_index_in_dim(tile_row, s, 0,
                                            keepdims=False)
        tcol = jax.lax.dynamic_index_in_dim(tile_col, s, 0,
                                            keepdims=False)
        x_next = jax.lax.ppermute(x_rot, axis_name, _ring_step_perm(p))
        st = jnp.take(x_rot.reshape(q_loc, tile, 2 * f), tcol, axis=0)
        pc_s, x_s = st[..., :f], st[..., f:]           # (s_max, T, F)
        ph_k = jnp.take(ph_t, trow, axis=0)            # (s_max, T, F)
        z = jax.nn.sigmoid(ph_k[:, :, None, :] + pc_s[:, None, :, :])
        contrib = jnp.where(blk[..., None] != 0.0,
                            blk[..., None] * z * x_s[:, None, :, :], 0.0)
        part = jnp.sum(contrib, axis=2)                # (s_max, T, F)
        acc = acc + jax.ops.segment_sum(part, trow, num_segments=q_loc)
        return (x_next, acc), None

    (_, acc), _ = jax.lax.scan(step, (pcx_shard, init_acc),
                               jnp.arange(p, dtype=jnp.int32))
    return acc.reshape(q_loc * tile, f)


def make_ring_gated_tiled(mesh: Mesh, axis: str, q_loc: int,
                          tile: int) -> Callable:
    """shard_map wrapper over `_ring_gated_tiled_shard`:

        (blocks, tile_row, tile_col, PH, PCX, in_counts) -> agg

    with PH (P * n_loc, F) the resident dst-gate projection and PCX
    (P * n_loc, 2F) the rotating (pc || x) stack, both row-sharded."""
    p = int(mesh.shape[axis])
    body = partial(_ring_gated_tiled_shard, axis_name=axis, q_loc=q_loc,
                   tile=tile, num_shards=p)

    def inner(blocks, tile_row, tile_col, ph, pcx, counts):
        return body(blocks[0], tile_row[0], tile_col[0], ph, pcx,
                    counts[0])

    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(axis, None, None, None, None), P(axis, None, None),
                  P(axis, None, None), P(axis, None), P(axis, None),
                  P(axis, None)),
        out_specs=P(axis, None))


def _ring_gated_packed_shard(rows, cols, vals, ph_shard, pcx_shard,
                             counts, *, axis_name: str, n_loc: int,
                             num_shards: int):
    p = num_shards
    me = jax.lax.axis_index(axis_name)
    f = pcx_shard.shape[1] // 2
    init_acc = jax.lax.pcast(jnp.zeros((n_loc, f), jnp.float32),
                             axis_name, to="varying")

    def step(carry, k):
        x_rot, acc = carry
        s = jax.lax.rem(me + k, p)
        rw = jax.lax.dynamic_index_in_dim(rows, s, 0, keepdims=False)
        c = jax.lax.dynamic_index_in_dim(cols, s, 0, keepdims=False)
        v = jax.lax.dynamic_index_in_dim(vals, s, 0, keepdims=False)
        x_next = jax.lax.ppermute(x_rot, axis_name, _ring_step_perm(p))
        st = jnp.take(x_rot, c, axis=0)                # (L, 2F)
        pc_at, x_at = st[:, :f], st[:, f:]
        ph_at = jnp.take(ph_shard, rw, axis=0)         # (L, F)
        z = jax.nn.sigmoid(ph_at + pc_at)
        contrib = jnp.where((v != 0.0)[:, None],
                            v[:, None] * z * x_at, 0.0)
        acc = acc + jax.ops.segment_sum(contrib, rw, num_segments=n_loc)
        return (x_next, acc), None

    (_, acc), _ = jax.lax.scan(step, (pcx_shard, init_acc),
                               jnp.arange(p, dtype=jnp.int32))
    return acc


def make_ring_gated_packed(mesh: Mesh, axis: str, n_loc: int) -> Callable:
    """shard_map wrapper over `_ring_gated_packed_shard`:

        (rows, cols, vals, PH, PCX, in_counts) -> agg"""
    p = int(mesh.shape[axis])
    body = partial(_ring_gated_packed_shard, axis_name=axis,
                   n_loc=n_loc, num_shards=p)

    def inner(rows, cols, vals, ph, pcx, counts):
        return body(rows[0], cols[0], vals[0], ph, pcx, counts[0])

    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(axis, None, None), P(axis, None, None),
                  P(axis, None, None), P(axis, None), P(axis, None),
                  P(axis, None)),
        out_specs=P(axis, None))
