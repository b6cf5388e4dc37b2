"""RER-Gather: the aggregate stage over *packed* edge tiles (Pallas).

The sparsity-aware sibling of `rer_spmm` (DESIGN.md C8): instead of a
dense T x T tile on the MXU, each grid step consumes one packed tile —
S `(row_local, col_local, val)` entries, S being the tile's pow2 nnz
bucket — and

  1. gathers the referenced rows of the resident source-feature block
     (a one-hot selector contracted on the MXU, the TPU-friendly
     spelling of a vector gather),
  2. scales by the edge weight, and
  3. scatter-accumulates into the destination interval (the transposed
     one-hot contraction).

Work and bytes are O(S) per tile instead of O(T^2) — on power-law
graphs that removes the >95% structural zeros every dense-tile backend
pays for (EnGN Sec. IV processes edges, not tile slots; VersaGNN /
NeuraChip in PAPERS.md make the same case).

Same hardware constraint as rer_spmm: the output block is revisited
only on consecutive grid steps, so tiles must be dst-sorted with every
destination interval present (`prepare_packed_groups` pads empty
tiles).  Padding entries are (0, 0, 0.0): sum ignores them via the 0.0
weight, max masks them with the val != 0 convention.

Mosaic layout: a (1, S) block of a (K, S) array breaks the TPU's (8,
128) tiling rule for any K > 1, so the wrapper hands the kernel each
tile's entries as an (R, 128) slab of a (K, R, 128) array (S padded to
whole lane rows; the MXU pads a narrower contraction to 128 anyway),
whose last two block dims are the full array dims.  The kernel walks
the slab one lane row at a time, so its one-hot selectors stay
(T, 128) whatever the bucket, and both one-hot contractions run at
HIGHEST precision: a selector must reproduce x and the edge weights
exactly, not at bf16.

The max variant densifies the tile in VMEM (entries are merged per
(row, col), so each slot of the rebuilt (T, T) block holds exactly one
weight) and reduces it as rer_spmm's max kernel does.  The production
CPU/GPU path is the XLA take+segment formulation in ops.py (the same
dispatcher split as rer_spmm).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


LANES = 128
_HI = jax.lax.Precision.HIGHEST


def entry_slabs(a: jnp.ndarray) -> jnp.ndarray:
    """(K, S) entry plane -> (K, R, 128) lane-major slabs, R = ceil(S /
    128), S zero-padded to whole lane rows (padding entries carry val
    0.0, so they add nothing)."""
    k, s = a.shape
    r = -(-s // LANES)
    if r * LANES != s:
        a = jnp.pad(a, ((0, 0), (0, r * LANES - s)))
    return a.reshape(k, r, LANES)


def slab_sum(rows_ref, cols_ref, vals_ref, x, acc):
    """acc (T, Fc) + scatter(rows, vals * x[cols]) over an (R, 128)
    entry slab held in VMEM refs, one lane row per loop step."""
    t = x.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, (t, LANES), 0)

    def body(r, acc):
        sel = (iota == cols_ref[pl.ds(r, 1), :]).astype(jnp.float32)
        gathered = jax.lax.dot_general(
            sel, x, (((0,), (0,)), ((), ())), precision=_HI,
            preferred_element_type=jnp.float32)                 # (L, Fc)
        scat = jnp.where(iota == rows_ref[pl.ds(r, 1), :],
                         vals_ref[pl.ds(r, 1), :], 0.0)          # (T, L)
        return acc + jnp.dot(scat, gathered, precision=_HI,
                             preferred_element_type=jnp.float32)

    return jax.lax.fori_loop(0, rows_ref.shape[0], body, acc)


def _dense_tile(rows_ref, cols_ref, vals_ref, t: int) -> jnp.ndarray:
    """Rebuild the (T, T) weight block from an (R, 128) entry slab."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (t, LANES), 0)

    def body(r, blk):
        scat = jnp.where(iota == rows_ref[pl.ds(r, 1), :],
                         vals_ref[pl.ds(r, 1), :], 0.0)          # (T, L)
        sel = (iota == cols_ref[pl.ds(r, 1), :]).astype(jnp.float32)
        return blk + jax.lax.dot_general(
            scat, sel, (((1,), (1,)), ((), ())), precision=_HI,
            preferred_element_type=jnp.float32)                 # (T, T)

    return jax.lax.fori_loop(0, rows_ref.shape[0], body,
                             jnp.zeros((t, t), jnp.float32))


def _gather_kernel_sum(br_ref, bc_ref, rows_ref, cols_ref, vals_ref,
                       x_ref, y_ref):
    k = pl.program_id(1)
    first = jnp.logical_or(
        k == 0, br_ref[k] != br_ref[jnp.maximum(k - 1, 0)])
    prev = jnp.where(first, jnp.zeros_like(y_ref), y_ref[...])
    y_ref[...] = slab_sum(rows_ref.at[0], cols_ref.at[0], vals_ref.at[0],
                          x_ref[...], prev)


def _gather_kernel_max(br_ref, bc_ref, rows_ref, cols_ref, vals_ref,
                       x_ref, y_ref):
    k = pl.program_id(1)
    first = jnp.logical_or(
        k == 0, br_ref[k] != br_ref[jnp.maximum(k - 1, 0)])
    neg = jnp.full(y_ref.shape, -jnp.inf, jnp.float32)
    prev = jnp.where(first, neg, y_ref[...])
    x = x_ref[...]                                              # (T, Fc)
    blk = _dense_tile(rows_ref.at[0], cols_ref.at[0], vals_ref.at[0],
                      x.shape[0])
    vals = jnp.where(blk[:, :, None] != 0.0,
                     blk[:, :, None] * x[None, :, :], -jnp.inf)
    y_ref[...] = jnp.maximum(prev, jnp.max(vals, axis=1))


def rer_gather(rows: jnp.ndarray, cols: jnp.ndarray, vals: jnp.ndarray,
               block_row: jnp.ndarray, block_col: jnp.ndarray,
               x: jnp.ndarray, *, t: int, q_dst: int, op: str = "sum",
               feature_chunk: int = 512, interpret: bool = False,
               finish_max: bool = True) -> jnp.ndarray:
    """Y[br*T:(br+1)*T] (+)= scatter(rows, vals * X[bc*T + cols]) per
    packed tile k.

    rows/cols/vals: (K, S) packed entries per tile (pad val = 0.0)
    block_row:      (K,) int32 dst interval (non-decreasing, every
                    interval 0..q_dst-1 present — prepare_packed_groups)
    block_col:      (K,) int32 src interval into x
    x:              (q_src*T, F) padded source features
    """
    k_tiles = rows.shape[0]
    n_src, f = x.shape
    assert n_src % t == 0, (n_src, t)
    fc = min(feature_chunk, f)
    assert f % fc == 0, (f, fc)
    kernel = _gather_kernel_sum if op == "sum" else _gather_kernel_max
    rows, cols, vals = (entry_slabs(a) for a in (rows, cols, vals))
    slab = pl.BlockSpec((1,) + rows.shape[1:],
                        lambda j, k, br, bc: (k, 0, 0))

    grid = (f // fc, k_tiles)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                slab, slab, slab,
                pl.BlockSpec((t, fc), lambda j, k, br, bc: (bc[k], j)),
            ],
            out_specs=pl.BlockSpec((t, fc),
                                   lambda j, k, br, bc: (br[k], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((q_dst * t, f), jnp.float32),
        interpret=interpret,
    )(block_row, block_col, rows, cols, vals, x)
    if op == "max" and finish_max:
        out = jnp.where(jnp.isneginf(out), 0.0, out)
    return out
