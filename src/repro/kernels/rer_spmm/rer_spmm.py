"""RER-SpMM: the aggregate stage as a block-sparse tiled SpMM Pallas kernel.

TPU adaptation of the paper's RER PE array (DESIGN.md S2/S3): vertex
properties do not flow through a ring of registers; instead the adjacency
is grid-partitioned into dense T x T tiles (paper S5.3), only non-empty
tiles are visited (edge reorganisation at block granularity), and each
tile is reduced on the MXU.  The tile visit order is destination-stationary
(the paper's column-major schedule): the output tile Y[dst, fc] stays
resident in VMEM across the inner sweep, exactly like the dst vertices
pinned in the ASIC's result banks.

Hardware constraint note: Pallas/TPU requires an output block to be
revisited only on *consecutive* grid steps, so the kernel mandates
dst-sorted tiles — the TPU analogue of the paper's observation that
row-major scheduling pays Q^2 accumulator spills (Table 3).

Grid: (F / Fc, nnzb) with the feature chunk outer so that each feature
chunk sweeps the dst-sorted block list.  Block indices are scalar-prefetch
operands so BlockSpec index_maps can follow the block-sparse structure.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _spmm_kernel_sum(block_row_ref, block_col_ref, blocks_ref, x_ref, y_ref):
    k = pl.program_id(1)
    first = jnp.logical_or(
        k == 0, block_row_ref[k] != block_row_ref[jnp.maximum(k - 1, 0)])
    prev = jnp.where(first, jnp.zeros_like(y_ref), y_ref[...])
    contrib = jnp.dot(blocks_ref[0], x_ref[...],
                      preferred_element_type=jnp.float32)
    y_ref[...] = prev + contrib


def _spmm_kernel_max(block_row_ref, block_col_ref, blocks_ref, x_ref, y_ref):
    k = pl.program_id(1)
    first = jnp.logical_or(
        k == 0, block_row_ref[k] != block_row_ref[jnp.maximum(k - 1, 0)])
    neg = jnp.full(y_ref.shape, -jnp.inf, jnp.float32)
    prev = jnp.where(first, neg, y_ref[...])
    blk = blocks_ref[0]                             # (T, T)
    x = x_ref[...]                                  # (T, Fc)
    # masked max over sources: non-edges contribute -inf
    vals = jnp.where(blk[:, :, None] != 0.0,
                     blk[:, :, None] * x[None, :, :], -jnp.inf)
    contrib = jnp.max(vals, axis=1)                 # (T, Fc)
    y_ref[...] = jnp.maximum(prev, contrib)


def rer_spmm(blocks: jnp.ndarray, block_row: jnp.ndarray,
             block_col: jnp.ndarray, x: jnp.ndarray, *, q: int,
             op: str = "sum", feature_chunk: int = 512,
             interpret: bool = False) -> jnp.ndarray:
    """Y[br*T:(br+1)*T] (+)= blocks[k] @ X[bc*T:(bc+1)*T] for every tile k.

    blocks:    (nnzb, T, T) dense tiles, **sorted by block_row**
    block_row: (nnzb,) int32 dst interval per tile (non-decreasing, and
               every interval 0..q-1 must appear; pad with zero tiles)
    block_col: (nnzb,) int32 src interval per tile
    x:         (q*T, F) padded vertex features
    """
    nnzb, t, _ = blocks.shape
    n_pad, f = x.shape
    assert n_pad == q * t, (n_pad, q, t)
    fc = min(feature_chunk, f)
    assert f % fc == 0, (f, fc)
    kernel = _spmm_kernel_sum if op == "sum" else _spmm_kernel_max

    grid = (f // fc, nnzb)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, t, t), lambda j, k, br, bc: (k, 0, 0)),
                pl.BlockSpec((t, fc), lambda j, k, br, bc: (bc[k], j)),
            ],
            out_specs=pl.BlockSpec((t, fc), lambda j, k, br, bc: (br[k], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_pad, f), jnp.float32),
        name="rer_spmm_blocked_spmm",
        interpret=interpret,
    )(block_row, block_col, blocks, x)
    if op == "max":
        out = jnp.where(jnp.isneginf(out), 0.0, out)
    return out
