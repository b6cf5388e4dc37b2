"""Fig. 17 — scalability over compute-array size.

ASIC: throughput vs PE-array rows/cols.  TPU analogues:
  (a) tile size T (rows of the array == vertices per tile) — blocked
      SpMM time vs T at fixed graph;
  (b) ring width P (pod-level RER): devices in the rotation — wall time
      of the sharded ring aggregate over the first P devices of this
      process (`ring_mesh(P)`).  One process drives every ring size, so
      the accelerator is opened once."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, pick, scaled, time_fn
from repro.core.dataflow import make_ring_aggregate, shard_adjacency_for_ring
from repro.distributed.sharding import ring_mesh
from repro.graphs.format import coo_to_blocked
from repro.graphs.generate import rmat_graph, random_features
from repro.kernels.rer_spmm import ops as spmm_ops


def run():
    nv, ne = scaled(4096, 60000)
    g = rmat_graph(nv, ne, seed=0).gcn_normalized()
    for t in pick((64, 128, 256, 512), 2):
        b = coo_to_blocked(g, t)
        xp = jnp.asarray(random_features(b.padded_vertices, 64, seed=0))
        blocks, brow, bcol = spmm_ops.prepare_blocks(
            b.blocks, b.block_row, b.block_col, b.q)
        us = time_fn(lambda bl, br, bc, xx: spmm_ops.blocked_spmm(
            bl, br, bc, xx, q=b.q, op="sum", feature_chunk=64),
            jnp.asarray(blocks), jnp.asarray(brow), jnp.asarray(bcol), xp)
        emit(f"fig17a/tile_{t}/spmm_us", round(us, 1),
             f"nnzb={b.nnzb} density={b.density():.3f}")

    n, f = 1024, 64
    rng = np.random.default_rng(0)
    a = (rng.random((n, n)) < 0.05).astype(np.float32)
    x = jnp.asarray(rng.standard_normal((n, f)).astype(np.float32))
    for p in pick((1, 2, 4, 8), 2):
        if p > len(jax.devices()):
            break
        blocks = jnp.asarray(shard_adjacency_for_ring(a, p))
        fn = jax.jit(make_ring_aggregate(ring_mesh(p), "ring"))
        emit(f"fig17b/ring_devices_{p}/us", round(time_fn(fn, blocks, x), 1),
             "")
