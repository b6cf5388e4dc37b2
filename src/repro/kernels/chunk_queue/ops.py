"""Device-resident chunk queue: build + sweep dispatcher (DESIGN.md C11).

The streamed tiled executor's callback loop pays one host dispatch per
staged chunk — ~0.5 ms each on CPU, which is why its train step ran
~10x the blocked backend's.  When the graph's packed entries and the
feature matrix *both* fit the device budget, the whole stream can be
staged once as a device-resident queue and the entire aggregate becomes
a single traced computation with zero host round-trips:

* **XLA path (CPU/GPU, and the differentiable path everywhere)**:
  the merged entries are reshaped into fixed `(steps, slab)` slabs —
  the "prestaged chunks" — and a `lax.scan` walks them, one gather +
  segment-reduce per slab, accumulating into the destination buffer.
  `slab` bounds the (slab, d) gather intermediate, so the sweep runs
  under budgets where the segment backend's (E, d) intermediate would
  not fit; with a single slab it degenerates to one fused launch
  (bitwise `packed_flat_xla`).  Plain jax AD differentiates the scan
  for sum/mean (and single-slab max); multi-slab max differentiates
  through `make_queue_max_diff`, whose `lax.scan` carries the
  `(max, tie count)` pair across slabs so the cotangent splits evenly
  among ALL tied winners — `segment_max`'s convention — instead of the
  50/50-per-merge split a plain `jnp.maximum` scan would produce.

* **Mosaic path (TPU)**: `chunk_queue.chunk_queue_spmm`, the
  persistent per-interval walker with explicit double-buffered DMA;
  `build_tile_queue` lays the same packed tiles out for it.

Quantised values (`value_dtype="int8"`): the queue's value plane is
int8 with one f32 scale per slab (`distributed.compression`), cutting
its resident + H2D bytes 4x; slabs dequantise on device in-trace.
Padding entries point at the sacrificial destination row `n` (the
output is sliced back to n rows), so padding is exact for sum and max
alike — no bitwise caveats from `0.0 * x` accumulation.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.graphs.partition import PackedTileStore, pow2_bucket
from repro.kernels.rer_gather.ops import flat_entries


def default_impl() -> str:
    """Execution path when `impl` is not forced: the XLA scan off-TPU,
    the persistent Mosaic walker on TPU."""
    return "xla" if jax.default_backend() == "cpu" else "pallas"


# ----------------------------------------------------------------------
# The flat slab queue (XLA scan path)
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ChunkQueue:
    """Device-resident staged stream: the packed store's merged entries
    as `(steps, slab)` global-index slabs, padding routed to the
    sacrificial row `n`."""
    n: int                     # real vertices (output rows)
    entries: int               # real merged entries (pre-padding)
    steps: int
    slab: int
    gsrc: jnp.ndarray          # (steps, slab) int32 global src vertex
    gdst: jnp.ndarray          # (steps, slab) int32 global dst vertex
    vals: jnp.ndarray          # (steps, slab) float32 or int8
    scales: jnp.ndarray        # (steps,) float32 (all-ones when fp32)
    value_dtype: str           # "fp32" | "int8"

    def device_bytes(self) -> int:
        """Resident device bytes of the queue itself."""
        return int(self.gsrc.nbytes + self.gdst.nbytes
                   + self.vals.nbytes + self.scales.nbytes)

    def raw_value_bytes(self) -> int:
        """What the value plane would cost unquantised (f32)."""
        return int(4 * self.steps * self.slab)


def queue_bytes(entries: int, slab: int, value_dtype: str = "fp32") -> int:
    """Closed-form device bytes of a queue before building it — the
    budget gate's pricing twin of `ChunkQueue.device_bytes`."""
    slab = max(int(slab), 1)
    steps = max(-(-int(entries) // slab), 1)
    vb = 1 if value_dtype == "int8" else 4
    return steps * slab * (8 + vb) + 4 * steps


def build_chunk_queue(packed: PackedTileStore, *, slab: Optional[int] = None,
                      value_dtype: str = "fp32",
                      quantizer=None) -> ChunkQueue:
    """Stage a packed store's merged entries as a device-resident slab
    queue.  `slab=None` takes the whole stream as one slab (a single
    fused launch); otherwise entries pad up to `steps * slab`.  With
    `value_dtype="int8"` the values quantise per slab through
    `distributed.compression.quantize_stream_np` (an error-feedback
    `StreamingTileQuantizer` carries residuals across rebuilds)."""
    n = packed.num_vertices
    gsrc, gdst, gval = flat_entries(packed)
    m = int(gsrc.size)
    if slab is None or slab >= max(m, 1):
        slab = max(m, 1)
    slab = int(slab)
    steps = max(-(-m // slab), 1)
    total = steps * slab
    pad = total - m
    if pad:
        gsrc = np.concatenate([gsrc, np.zeros(pad, np.int32)])
        # padding targets the sacrificial row n: exact for sum AND max
        gdst = np.concatenate([gdst, np.full(pad, n, np.int32)])
        gval = np.concatenate([gval, np.zeros(pad, np.float32)])
    gsrc = gsrc.reshape(steps, slab)
    gdst = gdst.reshape(steps, slab)
    gval = gval.reshape(steps, slab)
    if value_dtype == "int8":
        from repro.distributed.compression import quantize_stream_np
        qv, scales = quantize_stream_np(gval, quantizer)
        vals_dev = jnp.asarray(qv)
        scales_dev = jnp.asarray(scales)
    elif value_dtype == "fp32":
        vals_dev = jnp.asarray(gval)
        scales_dev = jnp.ones((steps,), jnp.float32)
    else:
        raise ValueError(value_dtype)
    return ChunkQueue(n, m, steps, slab, jnp.asarray(gsrc),
                      jnp.asarray(gdst), vals_dev, scales_dev,
                      value_dtype)


def _slab_vals(vals_row, scale_row):
    # fp32 slabs carry scale 1.0: v * 1.0 is bitwise v, so the fp32
    # queue stays bit-for-bit the unscaled formulation
    return vals_row.astype(jnp.float32) * scale_row


@partial(jax.jit, static_argnames=("n", "op"))
def queue_sweep_xla(gsrc, gdst, vals, scales, x, *, n: int,
                    op: str = "sum") -> jnp.ndarray:
    """The lax.scan-over-prestaged-chunks aggregate: one gather + one
    segment reduce per slab, accumulated into the (n+1, d) destination
    buffer (row n swallows padding; the result is sliced to n rows).
    A single-slab queue skips the scan — one fused launch, bitwise
    `packed_flat_xla` modulo the sacrificial row."""
    steps = gsrc.shape[0]
    rows = n + 1

    def slab_part(src, dst, v):
        gathered = jnp.take(x, src, axis=0)
        if op == "sum":
            return jax.ops.segment_sum(v[:, None] * gathered, dst,
                                       num_segments=rows)
        scaled = jnp.where((v != 0.0)[:, None],
                           v[:, None] * gathered, -jnp.inf)
        return jax.ops.segment_max(scaled, dst, num_segments=rows)

    if steps == 1:
        y = slab_part(gsrc[0], gdst[0], _slab_vals(vals[0], scales[0]))
    else:
        init = (jnp.zeros((rows, x.shape[1]), jnp.float32) if op == "sum"
                else jnp.full((rows, x.shape[1]), -jnp.inf, jnp.float32))

        def body(acc, sl):
            src, dst, v, s = sl
            part = slab_part(src, dst, _slab_vals(v, s))
            acc = acc + part if op == "sum" else jnp.maximum(acc, part)
            return acc, None

        y, _ = jax.lax.scan(body, init, (gsrc, gdst, vals, scales))
    if op == "max":
        y = jnp.where(jnp.isneginf(y), 0.0, y)
    return y[:n]


def make_queue_max_diff(queue: ChunkQueue):
    """Differentiable multi-slab max sweep over a staged queue.

    The non-differentiable scan in `queue_sweep_xla` merges slabs with
    `jnp.maximum`, whose gradient splits a cross-slab tie 50/50 per
    merge — two winners in slab 1 and one in slab 2 would receive
    g/4, g/4, g/2 instead of `segment_max`'s even g/3 each.  This
    custom_vjp keeps the forward bitwise identical (the value carry is
    the same `maximum` chain) while ALSO carrying the per-row tie count
    across slabs, the same `(max, count)` merge the streamed callback
    VJP uses (`core/tiled.py::_merge_max_count`): a strictly better
    slab replaces the count, an exact finite tie adds to it.  The
    backward re-walks the slabs, recomputes each edge product with the
    forward's exact operands, and scatters g/count to every entry whose
    product equals the global max — `segment_max`'s even-split
    convention, now independent of how ties distribute over slabs.

    Gradients flow to x only; the queue is a constant of the graph.
    """
    n = queue.n
    rows = n + 1
    gsrc, gdst, vals, scales = (queue.gsrc, queue.gdst, queue.vals,
                                queue.scales)

    def _fwd_scan(x):
        d = x.shape[1]
        init = (jnp.full((rows, d), -jnp.inf, jnp.float32),
                jnp.zeros((rows, d), jnp.float32))

        def body(carry, sl):
            acc_v, acc_c = carry
            src, dst, v, s = sl
            vv = _slab_vals(v, s)
            gathered = jnp.take(x, src, axis=0)
            scaled = jnp.where((vv != 0.0)[:, None],
                               vv[:, None] * gathered, -jnp.inf)
            m = jax.ops.segment_max(scaled, dst, num_segments=rows)
            c = jax.ops.segment_sum(
                jnp.where((scaled == m[dst]) & (vv != 0.0)[:, None],
                          1.0, 0.0), dst, num_segments=rows)
            better = m > acc_v
            ties = (m == acc_v) & jnp.isfinite(m)
            acc_v = jnp.maximum(acc_v, m)
            acc_c = jnp.where(better, c,
                              acc_c + jnp.where(ties, c, 0.0))
            return (acc_v, acc_c), None

        (yv, yc), _ = jax.lax.scan(body, init,
                                   (gsrc, gdst, vals, scales))
        return yv, yc

    @jax.custom_vjp
    def sweep(x):
        yv, _ = _fwd_scan(x)
        return jnp.where(jnp.isneginf(yv), 0.0, yv)[:n]

    def sweep_fwd(x):
        yv, yc = _fwd_scan(x)
        y = jnp.where(jnp.isneginf(yv), 0.0, yv)[:n]
        # residuals keep the RAW running max (with -inf for uncovered
        # rows): the backward's bitwise product match must compare
        # against the true max, not the 0.0 the output substitutes
        return y, (x, yv, yc)

    def sweep_bwd(res, g):
        x, yv, yc = res
        gn = (jnp.zeros((rows, g.shape[1]), jnp.float32).at[:n].set(g)
              / jnp.maximum(yc, 1.0))

        def body(gx, sl):
            src, dst, v, s = sl
            vv = _slab_vals(v, s)
            prod = vv[:, None] * jnp.take(x, src, axis=0)
            match = ((vv != 0.0)[:, None]
                     & (prod == jnp.take(yv, dst, axis=0)))
            contrib = jnp.where(match,
                                vv[:, None] * jnp.take(gn, dst, axis=0),
                                0.0)
            gx = gx + jax.ops.segment_sum(contrib, src,
                                          num_segments=x.shape[0])
            return gx, None

        gx, _ = jax.lax.scan(body, jnp.zeros_like(x),
                             (gsrc, gdst, vals, scales))
        return (gx,)

    sweep.defvjp(sweep_fwd, sweep_bwd)
    return sweep


def chunk_queue_aggregate(queue: ChunkQueue, x, *, op: str = "sum",
                          impl: Optional[str] = None,
                          tile_queue: Optional["TileQueue"] = None,
                          interpret: Optional[bool] = None):
    """Dispatch the staged-queue aggregate: XLA scan (CPU/GPU and any
    differentiated call), or the persistent Mosaic walker when a
    `tile_queue` layout is supplied on TPU (sum only — max keeps the
    XLA formulation for its -inf masking)."""
    if impl is None:
        impl = default_impl()
    if impl == "pallas" and tile_queue is not None and op == "sum":
        return tile_queue_aggregate(tile_queue, x, interpret=interpret)
    return queue_sweep_xla(queue.gsrc, queue.gdst, queue.vals,
                           queue.scales, x, n=queue.n, op=op)


# ----------------------------------------------------------------------
# The per-interval tile queue (Mosaic persistent-walker layout)
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TileQueue:
    """The same packed tiles laid out for `chunk_queue_spmm`: dst-sorted
    at one uniform pow2 nnz bucket, with the per-interval span pointers
    the persistent kernel walks."""
    n: int
    tile: int
    q: int
    bucket: int
    tile_ptr: jnp.ndarray      # (q+1,) int32
    tile_src: jnp.ndarray      # (K,) int32
    rows: jnp.ndarray          # (K, S) int32
    cols: jnp.ndarray          # (K, S) int32
    vals: jnp.ndarray          # (K, S) float32

    def device_bytes(self) -> int:
        return int(self.tile_ptr.nbytes + self.tile_src.nbytes
                   + self.rows.nbytes + self.cols.nbytes
                   + self.vals.nbytes)


def build_tile_queue(packed: PackedTileStore,
                     bucket_floor: int = 8) -> TileQueue:
    """Host-side layout for the persistent walker: dst-sort the store's
    tiles, pad every tile to the store-wide pow2 nnz bucket (one shape
    for the whole queue — the walker's fori_loop needs a uniform slab),
    and record each destination interval's span."""
    q = packed.q
    nnz = packed.tile_nnz()
    bucket = pow2_bucket(int(nnz.max()) if nnz.size else 0, bucket_floor)
    order = np.argsort(packed.block_row, kind="stable").astype(np.int64)
    brow = packed.block_row[order]
    tile_ptr = np.searchsorted(brow, np.arange(q + 1)).astype(np.int32)
    rows, cols, vals = packed.pack(order, max(order.size, 1), bucket)
    tile_src = np.zeros(max(order.size, 1), np.int32)
    tile_src[:order.size] = packed.block_col[order]
    return TileQueue(packed.num_vertices, packed.tile, q, bucket,
                     jnp.asarray(tile_ptr), jnp.asarray(tile_src),
                     jnp.asarray(rows), jnp.asarray(cols),
                     jnp.asarray(vals))


def tile_queue_aggregate(tq: TileQueue, x, *,
                         feature_chunk: int = 128,
                         interpret: Optional[bool] = None,
                         activation: Optional[str] = None):
    """Run the persistent Mosaic walker over a built tile queue: pads
    x to the q*T rows the kernel wants and slices the result back to n
    rows."""
    from repro.kernels.chunk_queue.chunk_queue import chunk_queue_spmm
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    n = x.shape[0]
    pad_n = tq.q * tq.tile - n
    if pad_n:
        x = jnp.pad(x, ((0, pad_n), (0, 0)))
    y = chunk_queue_spmm(tq.tile_ptr, tq.tile_src, tq.rows, tq.cols,
                         tq.vals, x, t=tq.tile, q_dst=tq.q,
                         feature_chunk=feature_chunk,
                         interpret=interpret, activation=activation)
    return y[:n]
