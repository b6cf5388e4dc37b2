"""Persistent chunk-queue walker: one program per destination interval,
explicit double-buffered DMA (Pallas TPU, DESIGN.md C11).

`rer_gather` streams packed tiles through the BlockSpec pipeline — one
grid step per tile, the output block revisited across consecutive
steps.  This kernel is the *persistent* formulation of the same RER
dataflow, modelled on EnGN's on-chip result banks: each program owns
one destination interval's (T, Fc) accumulator in VMEM for its whole
lifetime and walks that interval's span of the device-resident tile
queue itself, issuing `pltpu.make_async_copy` for the next tile's
entry slab and source-feature block while the MXU reduces the current
one (two VMEM slots + per-slot DMA semaphores — the C7 double-buffer
discipline moved on chip).  Because the accumulator never leaves VMEM
until the interval is done, the vertex-wise activation of the update
stage folds into the same kernel (`activation="relu"`), the way
`fused_engn` folds extraction into the blocked sweep.

Queue layout (built host-side by `ops.build_tile_queue`): tiles are
dst-sorted and padded to one uniform pow2 nnz bucket S, with

  tile_ptr (q+1,) int32   — interval i owns tiles [ptr[i], ptr[i+1])
  tile_src (K,)   int32   — each tile's source interval
  rows/cols/vals (K, S)   — packed entries (pad val = 0.0), handed to
                            the kernel as lane-major (K, R, L) slabs
                            of whole 128-lane rows
                            (`rer_gather.entry_slabs`): a whole-slab DMA
                            per tile is legal for every bucket S, where
                            a one-row slice of a tiled (K, S) array is
                            not, and the one-hot selectors stay (T, 128)
                            however heavy the hub tile

Scalar-prefetched `tile_ptr`/`tile_src` drive the walk; the entry
arrays and the feature matrix stay in HBM (`pl.ANY`) and are DMA'd
slab-by-slab.  Sum only: the one-hot MXU gather/scatter spelling needs
no (S, T, Fc) candidate tensor for sum, and the streamed max keeps its
own residual-capturing path (DESIGN.md C9).  On CPU the kernel runs in
interpret mode for correctness tests; the production CPU/GPU path is
the `lax.scan` slab formulation in ops.py (same dispatcher split as
rer_spmm / rer_gather).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.rer_gather.rer_gather import LANES, entry_slabs, slab_sum


def _queue_kernel(tile_ptr, tile_src, rows_hbm, cols_hbm, vals_hbm,
                  x_hbm, o_ref, rrows, rcols, rvals, rx, sems, *,
                  t: int, fc: int, activation):
    i = pl.program_id(0)
    j = pl.program_id(1)
    lo, hi = tile_ptr[i], tile_ptr[i + 1]

    def copies(k, slot):
        """The four async copies that stage tile k into VMEM slot
        `slot`: its entry slab and its source-feature block."""
        return (
            pltpu.make_async_copy(rows_hbm.at[k], rrows.at[slot],
                                  sems.at[slot, 0]),
            pltpu.make_async_copy(cols_hbm.at[k], rcols.at[slot],
                                  sems.at[slot, 1]),
            pltpu.make_async_copy(vals_hbm.at[k], rvals.at[slot],
                                  sems.at[slot, 2]),
            pltpu.make_async_copy(
                x_hbm.at[pl.ds(tile_src[k] * t, t),
                         pl.ds(j * fc, fc)],
                rx.at[slot], sems.at[slot, 3]),
        )

    def start(k, slot):
        for c in copies(k, slot):
            c.start()

    def wait(k, slot):
        for c in copies(k, slot):
            c.wait()

    @pl.when(lo < hi)
    def _warm_up():
        start(lo, 0)

    def body(k, acc):
        slot = jax.lax.rem(k - lo, 2)

        @pl.when(k + 1 < hi)
        def _prefetch():
            # issue tile k+1's DMA into the other slot before touching
            # tile k: the transfer overlaps the MXU contraction below
            start(k + 1, 1 - slot)

        wait(k, slot)
        return slab_sum(rrows.at[slot], rcols.at[slot], rvals.at[slot],
                        rx[slot], acc)

    acc = jax.lax.fori_loop(lo, hi, body,
                            jnp.zeros((t, fc), jnp.float32))
    if activation == "relu":
        acc = jnp.maximum(acc, 0.0)
    o_ref[...] = acc


@partial(jax.jit, static_argnames=("t", "q_dst", "feature_chunk",
                                   "interpret", "activation"))
def chunk_queue_spmm(tile_ptr: jnp.ndarray, tile_src: jnp.ndarray,
                     rows: jnp.ndarray, cols: jnp.ndarray,
                     vals: jnp.ndarray, x: jnp.ndarray, *, t: int,
                     q_dst: int, feature_chunk: int = 128,
                     interpret: bool = False,
                     activation: str | None = None) -> jnp.ndarray:
    """Y[i*T:(i+1)*T] = act(sum over the queue span of interval i of
    scatter(rows, vals * X[src*T + cols])) — the persistent sum sweep.

    x must be (q_src*T, F).  Feature chunks are whole 128-lane
    multiples (the per-tile x DMA refuses a narrower column slice), so
    F is zero-padded up to the chunk and the result sliced back.
    """
    n_src, f = x.shape
    assert n_src % t == 0, (n_src, t)
    fc = -(-min(feature_chunk, f) // LANES) * LANES
    f_pad = -(-f // fc) * fc
    if f_pad != f:
        x = jnp.pad(x, ((0, 0), (0, f_pad - f)))
    rows, cols, vals = (entry_slabs(a) for a in (rows, cols, vals))
    slab = rows.shape[1:]
    grid = (q_dst, f_pad // fc)
    y = pl.pallas_call(
        partial(_queue_kernel, t=t, fc=fc, activation=activation),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),   # rows (K, R, L)
                pl.BlockSpec(memory_space=pl.ANY),   # cols (K, R, L)
                pl.BlockSpec(memory_space=pl.ANY),   # vals (K, R, L)
                pl.BlockSpec(memory_space=pl.ANY),   # x (q*T, F)
            ],
            out_specs=pl.BlockSpec((t, fc),
                                   lambda i, j, ptr, src: (i, j)),
            scratch_shapes=[
                pltpu.VMEM((2,) + slab, jnp.int32),     # rows slab x2
                pltpu.VMEM((2,) + slab, jnp.int32),     # cols slab x2
                pltpu.VMEM((2,) + slab, jnp.float32),   # vals slab x2
                pltpu.VMEM((2, t, fc), jnp.float32),    # x block x2
                pltpu.SemaphoreType.DMA((2, 4)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((q_dst * t, f_pad), jnp.float32),
        name="chunk_queue",
        interpret=interpret,
    )(tile_ptr, tile_src, rows, cols, vals, x)
    return y[:, :f]
