"""jit'd public wrapper around the RER-SpMM Pallas kernel.

Handles host-side invariants the kernel mandates:
  * tiles sorted by destination interval (dst-stationary schedule);
  * every dst interval visited at least once (pad with zero tiles so
    untouched output blocks are well-defined);
  * feature dim padded to the feature-chunk multiple.
On CPU (this container) the kernel runs in interpret mode; on TPU it
compiles to a real Mosaic kernel.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.reference_vjp import with_reference_vjp
from repro.kernels.rer_spmm.rer_spmm import rer_spmm


def _is_cpu() -> bool:
    return jax.default_backend() == "cpu"


def prepare_blocks(blocks: np.ndarray, block_row: np.ndarray,
                   block_col: np.ndarray, q: int):
    """Sort tiles by dst interval and pad so every interval appears.

    Pad tiles are appended *before* the single stable argsort: a
    missing interval has no real tiles to collide with, so one sort
    yields the same order the old sort-pad-resort produced (real tiles
    keep their relative order within an interval) at half the sort
    cost — tests/test_kernels.py::test_prepare_blocks_single_sort_order.
    """
    present = np.zeros(q, bool)
    present[block_row] = True
    missing = np.nonzero(~present)[0].astype(np.int32)
    if missing.size:
        t = blocks.shape[1]
        blocks = np.concatenate(
            [blocks, np.zeros((missing.size, t, t), blocks.dtype)])
        block_row = np.concatenate([block_row, missing])
        block_col = np.concatenate([block_col, missing])
    order = np.argsort(block_row, kind="stable")
    return (blocks[order], block_row[order].astype(np.int32),
            block_col[order].astype(np.int32))


@partial(jax.jit, static_argnames=("q", "op", "feature_chunk", "interpret"))
def _blocked_spmm_jit(blocks, block_row, block_col, x, *, q, op,
                      feature_chunk, interpret):
    f = x.shape[1]
    # pad F to a multiple of the chunk
    chunk = min(feature_chunk, f)
    pad_f = (-f) % chunk
    if pad_f:
        x = jnp.pad(x, ((0, 0), (0, pad_f)))
    y = rer_spmm(blocks, block_row, block_col, x, q=q, op=op,
                 feature_chunk=chunk, interpret=interpret)
    return y[:, :f]


@partial(jax.jit, static_argnames=("q", "op"))
def blocked_spmm_xla(blocks, block_row, block_col, x, *, q, op="sum"):
    """The same tiled dataflow expressed in XLA ops (tile gather +
    batched dense tile matmul + reduce at destination intervals).

    This is the CPU/GPU execution path: Pallas interpret mode executes
    the kernel body step-by-step in Python and is for correctness
    validation only.  On TPU the Mosaic kernel (rer_spmm) is used."""
    nnzb, t, _ = blocks.shape
    x_tiles = x.reshape(q, t, x.shape[1])
    src = x_tiles[block_col]                       # (nnzb, T, F)
    if op == "sum":
        contrib = jnp.einsum("ktu,kuf->ktf", blocks, src,
                             preferred_element_type=jnp.float32)
        y = jax.ops.segment_sum(contrib, block_row, num_segments=q)
    else:
        vals = jnp.where(blocks[..., None] != 0.0,
                         blocks[..., None] * src[:, None, :, :], -jnp.inf)
        contrib = jnp.max(vals, axis=2)            # (nnzb, T, F)
        y = jax.ops.segment_max(contrib, block_row, num_segments=q)
        y = jnp.where(jnp.isneginf(y), 0.0, y)
    return y.reshape(q * t, x.shape[1])


def blocked_spmm(blocks, block_row, block_col, x, *, q: int, op: str = "sum",
                 feature_chunk: int = 512, interpret: bool | None = None,
                 impl: str | None = None):
    """Dispatch: Mosaic Pallas kernel on TPU, XLA tiled path elsewhere.
    Pass impl="pallas" to force the kernel (interpret mode on CPU).  The
    kernel's reverse pass is the VJP of `blocked_spmm_xla`."""
    reference = partial(blocked_spmm_xla, q=q, op=op)
    if impl is None:
        impl = "xla" if _is_cpu() else "pallas"
    if impl == "xla":
        return reference(blocks, block_row, block_col, x)
    if interpret is None:
        interpret = _is_cpu()
    kernel = partial(_blocked_spmm_jit, q=q, op=op,
                     feature_chunk=feature_chunk, interpret=interpret)
    return with_reference_vjp(kernel, reference, 3)(
        blocks, block_row, block_col, x)
