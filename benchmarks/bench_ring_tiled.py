"""Sharded ring backend (C2 / C8) — weak/strong scaling across ring
sizes, packed vs dense ring stripes, with the analytic ring-traffic
counters (RingStats, the device-mesh mirror of TiledStats).

Every ring size runs in this process over a prefix of its devices
(`ring_mesh(P)` via `EnGNConfig.ring_shards`): the forced host devices
`benchmarks/run.py` sets up on the CPU, or the chips of a TPU host,
which a child process could not open while this one holds them.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from benchmarks.common import emit, pick
from repro.core.engn import prepare_graph
from repro.core.models import make_gnn
from repro.graphs.format import COOGraph
from repro.graphs.generate import random_features, rmat_graph


def _run_ring(p: int, n: int, e: int, f: int, h: int,
              fmt: str = "auto") -> dict:
    g = rmat_graph(n, e, seed=0)
    # shuffle-relabel: R-MAT hubs cluster in the leading intervals, so
    # the hub-hub (dst, src) pair would dominate the s_max padding; a
    # random relabel is the production hash-partition layout and keeps
    # shard stripes balanced
    perm = np.random.default_rng(0).permutation(n).astype(np.int32)
    g = COOGraph(n, perm[g.src], perm[g.dst], g.val).gcn_normalized()
    x = jnp.asarray(random_features(n, f, seed=1))
    layer = make_gnn("gcn", f, h, backend="ring")
    layer.cfg.tile_format = fmt
    layer.cfg.ring_shards = p
    params = layer.init(jax.random.key(0))
    gd = prepare_graph(g, layer.cfg)
    fn = jax.jit(lambda xx: layer.apply(params, gd, xx))
    jax.block_until_ready(fn(x))                       # compile
    ts = []
    for _ in range(1 if common.SMOKE else 3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        ts.append(time.perf_counter() - t0)
    meta = gd.meta
    return {"us": float(np.median(ts) * 1e6), "edges": g.num_edges,
            "shards": meta["shards"], "tile": meta["tile"],
            "s_max": meta["s_max"], "nnzb": meta["nnzb"],
            "fmt": meta["tile_format"],
            "dev_bytes": meta["device_bytes"],
            **meta["stats"].as_dict()}


def run():
    f, h = (16, 8) if common.SMOKE else (64, 32)
    # the strong-scaling graph must look like a real power-law graph at
    # this tile size (Q x Q grid with sparse tiles), not a 2x2 grid of
    # hub-dense tiles — that is the regime the packed format targets
    n0, e0 = (2048, 9000) if common.SMOKE else (4096, 60_000)
    nw, ew = (512, 3000) if common.SMOKE else (1024, 15_000)
    shard_counts = [p for p in pick([1, 2, 4, 8], 2)
                    if p <= len(jax.devices())]

    # strong scaling: fixed graph, growing ring — dense stripes vs
    # packed stripes (C8) at every ring size
    for p in shard_counts:
        us = {}
        for fmt in ("dense", "packed"):
            r = _run_ring(p, n0, e0, f, h, fmt=fmt)
            us[fmt] = r["us"]
            tag = "" if fmt == "dense" else "packed_"
            emit(f"ring_tiled/strong/p{p}/{tag}us", round(us[fmt], 1),
                 f"tile={r['tile']} s_max={r['s_max']} nnzb={r['nnzb']} "
                 f"fill={r['fill_factor']:.4f} "
                 f"dev_mb={r['dev_bytes'] / 1e6:.2f}")
            emit(f"ring_tiled/strong/p{p}/{tag}edges_per_s",
                 round(r["edges"] / (us[fmt] / 1e6), 1),
                 f"ppermute_mb={r['ppermute_bytes'] / 1e6:.2f} "
                 f"padded_tiles={r['padded_tiles']} tiles={r['tiles']}")
        emit(f"ring_tiled/strong/p{p}/packed_speedup",
             round(us["dense"] / max(us["packed"], 1.0), 3),
             f"dense={us['dense']:.0f}us packed={us['packed']:.0f}us")

    # weak scaling: graph grows with the ring, per-shard work constant
    # (tile_format=auto — the autotuned production configuration)
    for p in shard_counts:
        r = _run_ring(p, nw * p, ew * p, f, h, fmt="auto")
        us = r["us"]
        emit(f"ring_tiled/weak/p{p}/us", round(us, 1),
             f"n={nw * p} e={r['edges']} fmt={r['fmt']} "
             f"dev_mb={r['dev_bytes'] / 1e6:.2f}")
        emit(f"ring_tiled/weak/p{p}/edges_per_s",
             round(r["edges"] / (us / 1e6), 1),
             f"ppermute_mb={r['ppermute_bytes'] / 1e6:.2f} "
             f"fill={r['fill_factor']:.4f}")
