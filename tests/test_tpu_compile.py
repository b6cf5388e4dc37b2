"""Compile the main path's kernels for a described TPU v5e.

No chip is attached: `get_topology_desc` describes a v5e:2x2 slice and
the TPU compiler, which ships with jaxlib, compiles for its first chip.
That catches what interpret mode cannot — a block or slice that breaks
the (8, 128) tiling, a kernel over its VMEM — at the widths
`chip_smoke.py` runs: the pubmed stand-in's tile grid (T = 256, q = 78
destination intervals), its feature width F = 500 and the GCN's
aggregate widths (hidden 32, 3 classes).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and a worker that could not would
otherwise collect different tests from the others.  Dispatchers pick
their path from `jax.default_backend()`, which stays "cpu" here, so the
tests call the Mosaic paths explicitly.
"""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.chunk_queue.chunk_queue import chunk_queue_spmm
from repro.kernels.feature_update.ops import fused_linear_act
from repro.kernels.fused_engn.ops import fused_engn_layer
from repro.kernels.rer_gather.ops import packed_spmm, packed_tile_part
from repro.kernels.rer_gather.rer_gather import rer_gather
from repro.kernels.rer_spmm.ops import blocked_spmm
from repro.kernels.rer_spmm.rer_spmm import rer_spmm

T, Q, K = 256, 78, 512          # tile, dst intervals, tiles per launch
F, HIDDEN, CLASSES = 500, 32, 3
CUSTOM = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory placed on the described first chip."""
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)


def _compile(fn, *args) -> str:
    """Compile for the described chip; the program text must hold the
    Mosaic kernel."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert CUSTOM in text
    return text


@pytest.mark.parametrize("op,width", [("sum", HIDDEN), ("sum", CLASSES),
                                      ("max", HIDDEN)])
def test_rer_spmm_compiles(spec, op, width):
    i32 = jnp.int32
    _compile(partial(rer_spmm, q=Q, op=op), spec((K, T, T)),
             spec((K,), i32), spec((K,), i32), spec((Q * T, width)))


@pytest.mark.parametrize("op,bucket,width", [
    ("sum", 8, HIDDEN), ("sum", 128, HIDDEN), ("sum", 2048, HIDDEN),
    ("sum", 2048, CLASSES), ("max", 8, HIDDEN), ("max", 2048, HIDDEN)])
def test_rer_gather_compiles(spec, op, bucket, width):
    i32 = jnp.int32
    _compile(partial(rer_gather, t=T, q_dst=Q, op=op),
             spec((K, bucket), i32), spec((K, bucket), i32),
             spec((K, bucket)), spec((K,), i32), spec((K,), i32),
             spec((Q * T, width)))


@pytest.mark.parametrize("bucket,width", [(8, 512), (64, 512),
                                          (128, 512), (1024, 512),
                                          (2048, 512), (2048, HIDDEN)])
def test_chunk_queue_walker_compiles(spec, bucket, width):
    """The persistent walker at every bucket class (2048 is the pubmed
    stand-in's hub tile), over the raw F = 500 features padded to four
    128-lane chunks and over the hidden width (the two afu aggregates)."""
    i32 = jnp.int32
    _compile(partial(chunk_queue_spmm, t=T, q_dst=Q, feature_chunk=128,
                     activation="relu"),
             spec((Q + 1,), i32), spec((K,), i32), spec((K, bucket), i32),
             spec((K, bucket), i32), spec((K, bucket)),
             spec((Q * T, width)))


@pytest.mark.parametrize("bucket,width", [(8, HIDDEN), (2048, HIDDEN),
                                          (2048, CLASSES)])
def test_streamed_chunk_step_compiles(spec, bucket, width):
    """The tiled executor's per-chunk step: eight staged packed tiles
    against their stacked source intervals."""
    i32 = jnp.int32
    chunk = 8
    _compile(partial(packed_tile_part, impl="pallas", interpret=False),
             spec((chunk, bucket), i32), spec((chunk, bucket), i32),
             spec((chunk, bucket)), spec((chunk, T, width)))


def test_fused_engn_compiles(spec):
    i32 = jnp.int32
    _compile(partial(fused_engn_layer, q=Q, impl="pallas",
                     interpret=False),
             spec((K, T, T)), spec((K,), i32), spec((K,), i32),
             spec((Q * T, F)), spec((F, HIDDEN)))


def test_feature_update_compiles(spec):
    _compile(partial(fused_linear_act, interpret=False),
             spec((Q * T, F)), spec((F, HIDDEN)), spec((HIDDEN,)))


@pytest.mark.parametrize("fmt", ["dense", "packed"])
def test_grad_of_blocked_aggregate_compiles(spec, fmt):
    """`pallas_call` has no transpose rule: the blocked aggregate's
    reverse pass must come from its custom VJP, with the kernel still
    in the forward program (the squared loss keeps the forward output
    live in the gradient program)."""
    i32 = jnp.int32
    if fmt == "dense":
        def loss(blocks, brow, bcol, x):
            y = blocked_spmm(blocks, brow, bcol, x, q=Q, impl="pallas",
                             interpret=False)
            return jnp.sum(y * y)
        graph = (spec((K, T, T)), spec((K,), i32), spec((K,), i32))
    else:
        def loss(rows, cols, vals, brow, bcol, x):
            y = packed_spmm(rows, cols, vals, brow, bcol, x, q=Q,
                            impl="pallas", interpret=False)
            return jnp.sum(y * y)
        graph = (spec((K, 128), i32), spec((K, 128), i32),
                 spec((K, 128)), spec((K,), i32), spec((K,), i32))
    argnum = len(graph)
    _compile(jax.grad(loss, argnums=argnum), *graph,
             spec((Q * T, HIDDEN)))
