"""Fused feature-extraction + RER aggregate kernel (paper Fig. 8).

The paper overlaps the feature-extraction and aggregate stages: as soon
as a batch of vertices finishes extraction, aggregation starts.  The TPU
realisation fuses them in one Pallas kernel computing

    Y[dst_tile] += A[dst_tile, src_tile] @ (X[src_tile] @ W[:, fc])

tile-by-tile: the extracted features P = X@W for the current source tile
live only in VMEM (per grid step), never making the HBM round trip that
a separate extraction pass would pay.  This is DASR's FAU order (extract
before aggregate, the F >= H case) with stage overlap.

Grid: (H / Hc, nnzb), dst-sorted tiles (same invariants as rer_spmm).
For each step: P = X[bc[k]] @ W[:, j] on the MXU (T x F @ F x Hc), then
Y[br[k], j] += A_tile @ P (T x T @ T x Hc).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _fused_kernel(block_row_ref, block_col_ref, blocks_ref, x_ref, w_ref,
                  y_ref):
    k = pl.program_id(1)
    first = jnp.logical_or(
        k == 0, block_row_ref[k] != block_row_ref[jnp.maximum(k - 1, 0)])
    prev = jnp.where(first, jnp.zeros_like(y_ref), y_ref[...])
    # stage 1 (extraction) — in VMEM only
    p = jnp.dot(x_ref[...], w_ref[...],
                preferred_element_type=jnp.float32)          # (T, Hc)
    # stage 2 (aggregate) — reduce into the dst-stationary output tile
    y_ref[...] = prev + jnp.dot(blocks_ref[0], p,
                                preferred_element_type=jnp.float32)


def fused_extract_aggregate(blocks: jnp.ndarray, block_row: jnp.ndarray,
                            block_col: jnp.ndarray, x: jnp.ndarray,
                            w: jnp.ndarray, *, q: int,
                            h_chunk: int = 256,
                            interpret: bool = False) -> jnp.ndarray:
    """Y = A @ (X @ W) with A given as dst-sorted dense tiles.

    blocks:    (nnzb, T, T) sorted by block_row, every interval present
    x:         (q*T, F) padded vertex features
    w:         (F, H) extraction weights
    Returns (q*T, H) float32.
    """
    nnzb, t, _ = blocks.shape
    n_pad, f = x.shape
    f2, h = w.shape
    assert n_pad == q * t and f == f2, (n_pad, q, t, f, f2)
    hc = min(h_chunk, h)
    assert h % hc == 0, (h, hc)

    grid = (h // hc, nnzb)
    return pl.pallas_call(
        _fused_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, t, t), lambda j, k, br, bc: (k, 0, 0)),
                pl.BlockSpec((t, f), lambda j, k, br, bc: (bc[k], 0)),
                pl.BlockSpec((f, hc), lambda j, k, br, bc: (0, j)),
            ],
            out_specs=pl.BlockSpec((t, hc), lambda j, k, br, bc: (br[k], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_pad, h), jnp.float32),
        name="fused_engn",
        interpret=interpret,
    )(block_row, block_col, blocks, x, w)
