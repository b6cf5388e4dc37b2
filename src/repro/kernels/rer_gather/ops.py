"""jit'd public wrappers around the packed-tile RER-Gather kernel.

Same dispatcher split as rer_spmm: the Mosaic Pallas kernel on TPU
(interpret mode on CPU is correctness-only), an XLA formulation of the
identical dataflow — flat `take` gather of exactly the referenced rows
+ `segment_sum`/`segment_max` scatter — as the CPU/GPU execution path.

Host-side invariants for the Pallas path mirror `prepare_blocks`:
tiles dst-sorted, every destination interval present (padded with
empty packed tiles), feature dim padded to the chunk multiple.
`prepare_packed_groups` additionally groups tiles by their pow2 nnz
bucket so each jitted program sees one of a log-bounded set of (K, S)
shapes instead of one shape per graph.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.graphs.partition import PackedTileStore, pow2_bucket
from repro.kernels.reference_vjp import with_reference_vjp
from repro.kernels.rer_gather.rer_gather import rer_gather


def _is_cpu() -> bool:
    return jax.default_backend() == "cpu"


def default_impl() -> str:
    """The execution path `packed_spmm`/`packed_tile_part` pick when
    `impl` is not forced: XLA gather+segment off-TPU, Mosaic on TPU."""
    return "xla" if _is_cpu() else "pallas"


# ----------------------------------------------------------------------
# Host-side preparation: pow2 nnz-bucket groups, dst-sorted + padded
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PackedGroup:
    """One nnz-bucket's worth of packed tiles, ready for upload:
    (K, S) entry arrays, dst-sorted, every dst interval present."""
    bucket: int                  # S — pow2 entry slots per tile
    rows: np.ndarray             # (K, S) int32 row_local
    cols: np.ndarray             # (K, S) int32 col_local
    vals: np.ndarray             # (K, S) float32 (0.0 = padding)
    block_row: np.ndarray        # (K,) int32 dst interval, non-decreasing
    block_col: np.ndarray        # (K,) int32 src interval
    real_tiles: int              # tiles before interval padding

    def nbytes(self) -> int:
        return int(self.rows.nbytes + self.cols.nbytes + self.vals.nbytes
                   + self.block_row.nbytes + self.block_col.nbytes)


def prepare_packed_groups(packed: PackedTileStore,
                          bucket_floor: int = 8) -> List[PackedGroup]:
    """Group the store's tiles by pow2 nnz bucket; within each group,
    dst-sort and pad missing destination intervals with empty tiles
    (one sort per group — the same single-pass discipline as the fixed
    `prepare_blocks`)."""
    q = packed.q
    nnz = packed.tile_nnz()
    buckets = np.array([pow2_bucket(int(m), bucket_floor) for m in nnz],
                       np.int64)
    groups: List[PackedGroup] = []
    for b in sorted(set(buckets.tolist())) or [pow2_bucket(0, bucket_floor)]:
        idx = np.nonzero(buckets == b)[0].astype(np.int64)
        brow = packed.block_row[idx]
        present = np.zeros(q, bool)
        present[brow] = True
        missing = np.nonzero(~present)[0].astype(np.int32)
        tiles = np.concatenate([idx, np.full(missing.size, -1, np.int64)])
        brow = np.concatenate([brow, missing]).astype(np.int32)
        bcol = np.concatenate([packed.block_col[idx], missing]
                              ).astype(np.int32)
        order = np.argsort(brow, kind="stable")
        tiles, brow, bcol = tiles[order], brow[order], bcol[order]
        rows, cols, vals = packed.pack(tiles, tiles.size, int(b))
        groups.append(PackedGroup(int(b), rows, cols, vals, brow, bcol,
                                  real_tiles=int(idx.size)))
    return groups


def flat_entries(packed: PackedTileStore):
    """Host-side: the store's merged entries as flat *global* vertex
    indices `(gsrc, gdst, gval)` — the one-launch CPU/GPU layout for a
    device-resident packed graph (`packed_flat_xla`).  The per-tile
    grouping only buys anything on TPU, where the Mosaic kernel needs
    rectangular (K, S) blocks; off-TPU, per-group launches pay one
    dispatch each while a single flat gather+segment pays one total."""
    t = packed.tile
    counts = np.diff(packed.entry_ptr)
    tile_of = np.repeat(np.arange(packed.nnzb, dtype=np.int64), counts)
    gsrc = (packed.block_col[tile_of].astype(np.int64) * t
            + packed.col_local)
    gdst = (packed.block_row[tile_of].astype(np.int64) * t
            + packed.row_local)
    return (gsrc.astype(np.int32), gdst.astype(np.int32),
            packed.val.copy())


@partial(jax.jit, static_argnames=("n", "op", "finish"))
def packed_flat_xla(gsrc, gdst, gval, x, *, n, op="sum", finish=True):
    """Flat merged-entry aggregate: y[gdst] (+)= gval * x[gsrc] — the
    RER dataflow processing edges directly (EnGN Sec. IV), one gather +
    one segment reduce, no padding at all."""
    gathered = jnp.take(x, gsrc, axis=0)
    if op == "sum":
        return jax.ops.segment_sum(gval[:, None] * gathered, gdst,
                                   num_segments=n)
    scaled = jnp.where((gval != 0.0)[:, None],
                       gval[:, None] * gathered, -jnp.inf)
    y = jax.ops.segment_max(scaled, gdst, num_segments=n)
    if finish:
        y = jnp.where(jnp.isneginf(y), 0.0, y)
    return y


# ----------------------------------------------------------------------
# XLA execution path (CPU/GPU): gather + segment reduce
# ----------------------------------------------------------------------

@partial(jax.jit, static_argnames=("q", "t", "op", "finish"))
def packed_spmm_xla(rows, cols, vals, block_row, block_col, x, *, q, t,
                    op="sum", finish=True):
    """The packed-tile dataflow in XLA ops: one flat gather of exactly
    the source rows the entries reference (never a whole T-row
    interval), scale by the merged edge weight, segment-reduce at the
    global (dst interval, row_local) vertex — O(K*S) work, the packed
    format's whole point."""
    k, s = rows.shape
    f = x.shape[1]
    gcols = (block_col[:, None] * t + cols).reshape(k * s)
    gathered = jnp.take(x, gcols, axis=0)                  # (K*S, F)
    seg = (block_row[:, None] * t + rows).reshape(k * s)
    v = vals.reshape(k * s)
    if op == "sum":
        y = jax.ops.segment_sum(v[:, None] * gathered, seg,
                                num_segments=q * t)
    else:
        scaled = jnp.where((v != 0.0)[:, None],
                           v[:, None] * gathered, -jnp.inf)
        y = jax.ops.segment_max(scaled, seg, num_segments=q * t)
        if finish:
            y = jnp.where(jnp.isneginf(y), 0.0, y)
    return y


@partial(jax.jit, static_argnames=("t", "op"))
def _packed_tile_part_xla(rows, cols, vals, xs, *, t, op):
    c, s = rows.shape
    f = xs.shape[-1]
    gcols = (jnp.arange(c, dtype=jnp.int32)[:, None] * t
             + cols).reshape(c * s)
    gathered = jnp.take(xs.reshape(c * t, f), gcols, axis=0)
    seg = rows.reshape(c * s)
    v = vals.reshape(c * s)
    if op == "sum":
        return jax.ops.segment_sum(v[:, None] * gathered, seg,
                                   num_segments=t)
    scaled = jnp.where((v != 0.0)[:, None],
                       v[:, None] * gathered, -jnp.inf)
    return jax.ops.segment_max(scaled, seg, num_segments=t)


@partial(jax.jit, static_argnames=("q", "t", "op", "feature_chunk",
                                   "interpret", "finish"))
def _packed_spmm_pallas(rows, cols, vals, block_row, block_col, x, *, q,
                        t, op, feature_chunk, interpret, finish):
    f = x.shape[1]
    chunk = min(feature_chunk, f)
    pad_f = (-f) % chunk
    if pad_f:
        x = jnp.pad(x, ((0, 0), (0, pad_f)))
    y = rer_gather(rows, cols, vals, block_row, block_col, x, t=t,
                   q_dst=q, op=op, feature_chunk=chunk,
                   interpret=interpret, finish_max=finish)
    return y[:, :f]


# ----------------------------------------------------------------------
# Dispatchers
# ----------------------------------------------------------------------

def packed_spmm(rows, cols, vals, block_row, block_col, x, *, q: int,
                op: str = "sum", feature_chunk: int = 512,
                interpret: bool | None = None, impl: str | None = None,
                finish: bool = True):
    """Full-graph packed SpMM: x (q*T, F) -> y (q*T, F).  Mosaic Pallas
    kernel on TPU, XLA gather+segment elsewhere; `finish=False` keeps
    -inf in uncovered max rows (for callers that merge partials).  The
    kernel's reverse pass is the VJP of `packed_spmm_xla`."""
    t = x.shape[0] // q
    reference = partial(packed_spmm_xla, q=q, t=t, op=op, finish=finish)
    if impl is None:
        impl = "xla" if _is_cpu() else "pallas"
    if impl == "xla":
        return reference(rows, cols, vals, block_row, block_col, x)
    if interpret is None:
        interpret = _is_cpu()
    kernel = partial(_packed_spmm_pallas, q=q, t=t, op=op,
                     feature_chunk=feature_chunk, interpret=interpret,
                     finish=finish)
    return with_reference_vjp(kernel, reference, 5)(
        rows, cols, vals, block_row, block_col, x)


def packed_tile_part(rows, cols, vals, xs, *, op: str = "sum",
                     interpret: bool | None = None,
                     impl: str | None = None):
    """One streamed chunk: (C, S) packed entries against the (C, T, F)
    stack of their source intervals -> (T, F) raw partial for a single
    destination interval (sum from zero; max keeps -inf so the caller's
    accumulator merge is a plain maximum)."""
    c, t, f = xs.shape
    if impl is None:
        impl = "xla" if _is_cpu() else "pallas"
    if impl == "xla":
        return _packed_tile_part_xla(rows, cols, vals, xs, t=t, op=op)
    if interpret is None:
        interpret = _is_cpu()
    y = _packed_spmm_pallas(
        rows, cols, vals,
        jnp.zeros(c, jnp.int32), jnp.arange(c, dtype=jnp.int32),
        xs.reshape(c * t, f), q=1, t=t, op=op, feature_chunk=512,
        interpret=interpret, finish=False)
    return y
