"""jit'd wrapper for the fused extract+aggregate kernel.

Same impl dispatch as rer_spmm: the Mosaic kernel on TPU, an XLA
formulation of the identical tiled dataflow on CPU/GPU (interpret mode
is correctness-only).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.fused_engn.fused_engn import fused_extract_aggregate
from repro.kernels.reference_vjp import with_reference_vjp


def _is_cpu() -> bool:
    return jax.default_backend() == "cpu"


@partial(jax.jit, static_argnames=("q", "h_chunk", "interpret"))
def _fused_jit(blocks, block_row, block_col, x, w, *, q, h_chunk,
               interpret):
    h = w.shape[1]
    hc = min(h_chunk, h)
    pad_h = (-h) % hc
    if pad_h:
        w = jnp.pad(w, ((0, 0), (0, pad_h)))
    y = fused_extract_aggregate(blocks, block_row, block_col, x, w, q=q,
                                h_chunk=hc, interpret=interpret)
    return y[:, :h]


@partial(jax.jit, static_argnames=("q",))
def _fused_xla(blocks, block_row, block_col, x, w, *, q):
    nnzb, t, _ = blocks.shape
    x_tiles = x.reshape(q, t, x.shape[1])
    p = jnp.einsum("ktf,fh->kth", x_tiles[block_col], w,
                   preferred_element_type=jnp.float32)
    contrib = jnp.einsum("ktu,kuh->kth", blocks, p,
                         preferred_element_type=jnp.float32)
    y = jax.ops.segment_sum(contrib, block_row, num_segments=q)
    return y.reshape(q * t, w.shape[1])


def fused_engn_layer(blocks, block_row, block_col, x, w, *, q: int,
                     h_chunk: int = 256, interpret: bool | None = None,
                     impl: str | None = None):
    """Y = A (X W): Mosaic kernel on TPU, `_fused_xla` elsewhere; the
    kernel's reverse pass (to x and w) is the VJP of `_fused_xla`."""
    reference = partial(_fused_xla, q=q)
    if impl is None:
        impl = "xla" if _is_cpu() else "pallas"
    if impl == "xla":
        return reference(blocks, block_row, block_col, x, w)
    if interpret is None:
        interpret = _is_cpu()
    kernel = partial(_fused_jit, q=q, h_chunk=h_chunk, interpret=interpret)
    return with_reference_vjp(kernel, reference, 3)(
        blocks, block_row, block_col, x, w)
