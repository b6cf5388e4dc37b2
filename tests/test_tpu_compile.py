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




@pytest.mark.parametrize("precision", [None, "high"])
def test_resident_serving_bucket_compiles(spec, precision):
    """The serving bucket program that gathers its rows from the
    resident feature matrix, at NELL's size (65,755 x 5,415 features,
    stored as 5,504 zero-padded columns; GCN 64 -> 210) and the
    warm-up's 131,072-row bucket.  The padded matrix takes the row-major
    layout, so no copy of it is made, and the temporaries stay far below
    the 2.84 GB (n_pad, F) input the host path sends: about one
    8,192-row block at "high" (260 MiB on this compiler), and a bfloat16
    copy of x hoisted out of the loop at the default precision."""
    import numpy as np

    from repro.core.models import init_stack, make_gnn_stack
    from repro.graphs.generate import rmat_graph
    from repro.serving.engine import GNNServingEngine

    n, f, n_pad, e_pad = 65755, 5415, 131072, 524288
    layers = make_gnn_stack("gcn", [f, 64, 210])
    params = init_stack(layers, jax.random.key(0))
    eng = GNNServingEngine(rmat_graph(300, 1200, seed=0).gcn_normalized(),
                           np.zeros((300, f), np.float32), layers, params)
    width = eng.x_device.shape[1]
    edges = spec((e_pad,), jnp.int32)
    with jax.default_matmul_precision(precision):
        compiled = jax.jit(partial(eng._resident_fn, n_pad)).lower(
            edges, edges, spec((e_pad,)), None, spec((n_pad,), jnp.int32),
            spec((n, width))).compile()
    assert compiled.input_formats[0][-1].layout.major_to_minor == (0, 1)
    assert not [line for line in compiled.as_text().splitlines()
                if f"f32[{n},{width}]" in line and " copy(" in line]
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < n_pad * f * 4 // 3, temp
    if precision == "high":
        assert temp < 2 * 8192 * width * 4, temp


SYNTH_A_N, SYNTH_A_E = 4_190_000, 67_100_000 + 4_190_000


# Synthetic-A's graph (R-MAT, graph_seed 0, self loops) as destination
# rows of SLOT_WIDTH = 4 slots: 20.23M rows, 78 chunks of 262,144 rows
SYNTH_A_ROW_CHUNKS = 78


@pytest.fixture(scope="module")
def synthetic_a_step(spec):
    """The segment GCN train step at Synthetic-A's full size (4.19M
    vertices, 67.1M edges and a self loop each, 100 features, widths
    64 and 16) as `prepare_graph` lays it out, destination rows in
    chunks of 1,048,576 slots, compiled for the described chip."""
    from repro.core.engn import (SLOT_WIDTH, edge_chunk,
                                 segment_gather_width)
    from repro.core.models import apply_stack, make_gnn_stack
    from repro.training.optimizer import init_opt_state
    from repro.training.train_lib import make_gnn_train_step

    n, e, dims = SYNTH_A_N, SYNTH_A_E, [100, 64, 16]
    layers = make_gnn_stack("gcn", dims)
    for layer in layers:
        layer.cfg.training = True
    chunk = edge_chunk(e, segment_gather_width(layers[0].cfg, dims[1]))
    assert (chunk, SLOT_WIDTH) == (1 << 20, 4)
    rows = (SYNTH_A_ROW_CHUNKS, chunk // SLOT_WIDTH)
    slots = (SYNTH_A_ROW_CHUNKS, SLOT_WIDTH, chunk // SLOT_WIDTH)

    def loss_fn(ps, batch, arrays):
        src, dst, val, x, y = arrays
        carrier = {"n": n, "backend": "segment", "src": src, "dst": dst,
                   "val": val}
        nodes = batch["nodes"]
        logits = apply_stack(layers, ps, carrier, x)[nodes]
        ll = jax.nn.log_softmax(logits, -1)
        return -jnp.mean(jnp.take_along_axis(ll, y[nodes][:, None], 1))

    i32 = jnp.int32
    params = [{"w": spec((a, b))} for a, b in zip(dims[:-1], dims[1:])]
    opt = jax.tree.map(lambda s: spec(s.shape, s.dtype),
                       jax.eval_shape(init_opt_state, params))
    return make_gnn_train_step(loss_fn).lower(
        params, opt, {"nodes": spec((256,), i32)},
        (spec(slots, i32), spec(rows, i32), spec(slots),
         spec((n, dims[0])), spec((n,), i32))).compile()


def _loop_body_lines(text):
    """The lines of the program's while-loop bodies."""
    import re

    bodies = set(re.findall(r"body=%([\w.\-]+)", text))
    lines, comp = [], None
    for line in text.splitlines():
        if line.startswith("%"):
            comp = line[1:].split(" ", 1)[0]
        elif comp in bodies:
            lines.append(line)
    return bodies, lines


def test_segment_train_step_at_synthetic_a_fits(synthetic_a_step):
    """Synthetic-A's step: its arguments and temporaries fit in three
    quarters of the chip's 16 GiB, and no floating-point buffer holds a
    row per edge, neither the (E, d) gather of the one-shot program
    (36.5 GB, which the chip refused) nor chunks' messages stacked for
    the backward."""
    import re

    n, e = SYNTH_A_N, SYNTH_A_E
    compiled = synthetic_a_step
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < 0.75 * (16 << 30), used
    text = compiled.as_text()
    # the chunk loops add into their accumulators in place: no loop
    # body copies an (n, d) buffer
    bodies, lines = _loop_body_lines(text)
    assert len(bodies) == 4, bodies
    copies = [line for line in lines
              if f"f32[{n}," in line and " copy(" in line]
    assert not copies, copies
    for dims_text in re.findall(r"(?:f32|bf16|f16)\[([0-9,]+)\]", text):
        shape = [int(d) for d in dims_text.split(",")]
        assert shape[0] < e, shape
        assert not (len(shape) > 2 and shape[0] * shape[1] >= e), shape


def test_synthetic_a_width_16_loops_are_lane_dense(synthetic_a_step):
    """Synthetic-A's width-16 chunk loops hold their table and their
    accumulator lane-packed, 8 vertices to a 128-lane row of a
    (523,750, 128) row-major buffer, where the (4.19M, 16) buffer they
    replace is laid out column-major: no loop body has a width-16
    operand of 4.19M rows in that layout, none copies a packed buffer,
    and the step still fits in three quarters of the chip."""
    n = SYNTH_A_N
    packed = f"f32[{-(-n // 8)},128]"
    compiled = synthetic_a_step
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 0.75 * (16 << 30))
    _, lines = _loop_body_lines(compiled.as_text())
    assert not [line for line in lines if f"f32[{n},16]{{0,1" in line]
    # the width-16 forward and backward accumulators
    scatters = [line for line in lines
                if line.lstrip().startswith("%fusion")
                and f" = {packed}{{1,0:" in line]
    assert len(scatters) == 2, scatters
    assert not [line for line in lines
                if (packed in line or f"f32[{n}," in line)
                and " copy(" in line]


def test_synthetic_a_loops_add_destination_rows(synthetic_a_step):
    """Each of Synthetic-A's four chunk loops, forward and backward,
    sums its rows' slots and adds (forward) or gathers (backward) one
    row per destination row in scope DST_ROWS, inside engn.aggregate.
    The forward loops add by indices sorted in the layout, so only the
    backward's scatter-adds by source still sort theirs."""
    import re

    from repro.trace import DST_ROWS, LANE_PACKED

    _, lines = _loop_body_lines(synthetic_a_step.as_text())

    def op_name(line):
        m = re.search(r'op_name="([^"]+)"', line)
        return m.group(1) if m else ""
    fwd = "jit(train_step)/jvp(engn.aggregate)"
    bwd = "jit(train_step)/transpose(jvp(engn.aggregate))"
    rows = {op_name(line).split("/while/")[0] for line in lines
            if f"/{DST_ROWS}/" in op_name(line)}
    assert rows == {fwd, bwd, f"{fwd}/{LANE_PACKED}", f"{bwd}/{LANE_PACKED}"}
    sorts = [op_name(line) for line in lines if " sort(" in line]
    assert len(sorts) == 2 and all(a.startswith(bwd) for a in sorts), sorts
