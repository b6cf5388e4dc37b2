"""Sparsity-aware packed tile format (DESIGN.md C8): the packed kernel
vs segment_aggregate, packed streaming/blocked/ring vs their dense
oracles, the autotuner, and the fill-factor accounting.  Property-based
via hypothesis (vendored fallback on clean checkouts)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dataflow import (build_packed_ring_shards,
                                 build_ring_tile_shards,
                                 make_ring_packed_aggregate,
                                 make_ring_tiled_aggregate,
                                 ring_stripe_bytes)
from repro.core.engn import EnGNConfig, prepare_graph, segment_aggregate
from repro.core.tiled import TiledExecutor
from repro.graphs.format import COOGraph
from repro.graphs.generate import rmat_graph
from repro.graphs.partition import (build_tile_store, pack_tile_store,
                                    pow2_bucket)
from repro.kernels.autotune import choose_tile_format
from repro.kernels.rer_gather import ops as gather_ops
from repro.kernels.rer_gather.ref import packed_tile_part_ref


def _int_graph(n, e, seed, dedup=True):
    """Integer-weighted graph: small-int sums are exact in fp32, so the
    packed paths must match the segment reference *bit-for-bit*."""
    g = rmat_graph(n, e, seed=seed)
    src, dst = g.src, g.dst
    if dedup:
        uniq = np.unique(np.stack([src, dst]), axis=1)
        src, dst = uniq[0], uniq[1]
    rng = np.random.default_rng(seed)
    val = rng.integers(1, 4, src.shape[0]).astype(np.float32)
    return COOGraph(n, src.astype(np.int32), dst.astype(np.int32), val)


def _int_features(n, f, seed):
    rng = np.random.default_rng(seed + 17)
    return rng.integers(-3, 4, (n, f)).astype(np.float32)


def _segment_ref(g, x, op):
    ev = jnp.asarray(x)[jnp.asarray(g.src)] * jnp.asarray(g.val)[:, None]
    return np.asarray(segment_aggregate(ev, jnp.asarray(g.dst),
                                        g.num_vertices, op))


# ---------------------------------------------------- store invariants
def test_pack_tile_store_merges_and_matches_densify():
    g = rmat_graph(80, 900, seed=0).gcn_normalized()   # has multi-edges
    st_ = build_tile_store(g, 16)
    ps = pack_tile_store(st_)
    assert ps.nnz <= g.num_edges
    buf = np.zeros((st_.nnzb, 16, 16), np.float32)
    st_.densify(np.arange(st_.nnzb), buf)
    scat = np.zeros_like(buf)
    for k in range(ps.nnzb):
        lo, hi = ps.entry_ptr[k], ps.entry_ptr[k + 1]
        scat[k, ps.row_local[lo:hi], ps.col_local[lo:hi]] = ps.val[lo:hi]
    np.testing.assert_allclose(scat, buf, rtol=1e-6, atol=1e-7)
    # packed carries far fewer bytes than the dense tiles at this fill
    assert ps.nbytes() < buf.nbytes
    assert 0.0 < ps.fill_factor() <= 1.0
    assert ps.dense_fill() < 0.5


def test_pow2_bucket():
    assert pow2_bucket(0) == 8 and pow2_bucket(8) == 8
    assert pow2_bucket(9) == 16 and pow2_bucket(1000) == 1024
    assert pow2_bucket(3, floor=1) == 4


# ---------------------------------------------------- kernel vs segment
# (the packed-blocked and packed-streaming segment-parity properties
# moved to tests/test_backend_matrix.py, which sweeps every backend x
# format x op x graph shape from one set of shared fixtures)
def test_packed_kernel_impls_match_ref_and_each_other():
    """The XLA take+segment formulation, the Pallas kernel (interpret
    mode on CPU) and the numpy oracle agree exactly, chunk and
    full-graph shapes, sum and max."""
    g = _int_graph(60, 400, seed=1)
    st_ = build_tile_store(g, 8)
    ps = pack_tile_store(st_)
    x = _int_features(st_.padded_vertices, 5, 1)
    groups = gather_ops.prepare_packed_groups(ps, bucket_floor=4)
    assert len(groups) > 1          # pow2 buckets actually vary
    for op in ("sum", "max"):
        for gr in groups:
            args = (jnp.asarray(gr.rows), jnp.asarray(gr.cols),
                    jnp.asarray(gr.vals), jnp.asarray(gr.block_row),
                    jnp.asarray(gr.block_col), jnp.asarray(x))
            y_x = gather_ops.packed_spmm(*args, q=st_.q, op=op,
                                         impl="xla", finish=False)
            y_p = gather_ops.packed_spmm(*args, q=st_.q, op=op,
                                         impl="pallas", feature_chunk=5,
                                         finish=False)
            assert np.array_equal(np.asarray(y_x), np.asarray(y_p)), op
    tiles = st_.row_tiles(0)
    rows, cols, vals = ps.pack(tiles, len(tiles), ps.bucket_of(tiles, 4))
    xs = np.stack([x[j * 8:(j + 1) * 8] for j in st_.block_col[tiles]])
    for op in ("sum", "max"):
        want = packed_tile_part_ref(rows, cols, vals, xs, op=op)
        for impl in ("xla", "pallas"):
            got = np.asarray(gather_ops.packed_tile_part(
                jnp.asarray(rows), jnp.asarray(cols), jnp.asarray(vals),
                jnp.asarray(xs), op=op, impl=impl))
            assert np.array_equal(got, want), (op, impl)


def test_multi_edges_merge_by_summation():
    """Duplicate edges merge before max sees them — the same convention
    as the dense tiles (scatter-add at build), checked packed-vs-dense
    bitwise and (for sum, where merging commutes) vs segment."""
    src = np.array([0, 0, 0, 2, 2, 5], np.int32)
    dst = np.array([1, 1, 1, 3, 3, 5], np.int32)
    val = np.array([1.0, 2.0, 1.0, 3.0, -3.0, 2.0], np.float32)
    g = COOGraph(8, src, dst, val)
    x = _int_features(8, 4, 3)
    for op in ("sum", "max"):
        dense = TiledExecutor(g, tile=3, chunk=2, tile_format="dense")
        packed = TiledExecutor(g, tile=3, chunk=2, tile_format="packed")
        a = dense.aggregate(x, op)
        b = packed.aggregate(x, op)
        assert np.array_equal(a, b), op
    # 2->3 merges to weight 0.0 == "no edge" in both forms
    assert np.array_equal(
        TiledExecutor(g, tile=3, chunk=2,
                      tile_format="packed").aggregate(x, "max")[3],
        np.zeros(4, np.float32))
    np.testing.assert_allclose(
        TiledExecutor(g, tile=3, chunk=2,
                      tile_format="packed").aggregate(x, "sum"),
        _segment_ref(g, x, "sum"), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("op", ["sum", "mean"])
def test_packed_matches_dense_on_float_weights(op):
    """On real-valued gcn-normalised weights the two carriers sum in a
    different order, so they agree to fp32 rounding, not bit-for-bit."""
    g = rmat_graph(400, 3000, seed=5).gcn_normalized()
    x = np.random.default_rng(5).normal(0, 1, (400, 8)).astype(np.float32)
    dense = TiledExecutor(g, tile=32, chunk=4, tile_format="dense")
    packed = TiledExecutor(g, tile=32, chunk=4, tile_format="packed")
    np.testing.assert_allclose(packed.aggregate(x, op),
                               dense.aggregate(x, op), rtol=0, atol=1e-5)


def test_packed_empty_tiles_and_all_zero_rows():
    g = COOGraph(10, np.array([0], np.int32), np.array([9], np.int32),
                 np.array([2.0], np.float32))
    x = _int_features(10, 4, 0)
    for op in ("sum", "max", "mean"):
        ex = TiledExecutor(g, tile=3, chunk=2, tile_format="packed")
        got = ex.aggregate(x, op)
        assert np.array_equal(got, _segment_ref(g, x, op)), op
        assert np.array_equal(got[:9], np.zeros((9, 4), np.float32))


# ---------------------------------------------------- ring packed
def _ring(g, x, op, shards, packed):
    from repro.distributed.sharding import ring_mesh
    mesh = ring_mesh(shards)
    if packed:
        plan = build_packed_ring_shards(g, shards)
        fn = make_ring_packed_aggregate(mesh, "ring", op, plan.n_loc)
        pre = (plan.rows, plan.cols, plan.vals)
    else:
        plan = build_ring_tile_shards(g, shards, tile=4)
        fn = make_ring_tiled_aggregate(mesh, "ring", op, plan.q_loc,
                                       plan.tile)
        pre = (plan.blocks, plan.tile_row, plan.tile_col)
    xp = np.zeros((plan.padded_vertices, x.shape[1]), np.float32)
    xp[:g.num_vertices] = x
    y = fn(*(jnp.asarray(a) for a in pre), jnp.asarray(xp),
           jnp.asarray(plan.in_counts))
    return np.asarray(y)[:g.num_vertices]


def test_ring_packed_stripes_match_dense_ring_bitwise():
    """Packed ring stripes == dense ring tiles bitwise (integer
    weights) on whatever mesh is available.  (The random-draw
    segment-parity sweep for both ring formats lives in
    tests/test_backend_matrix.py; this keeps one direct packed-vs-dense
    ring comparison plus the 8-way subprocess below.)"""
    shards = min(len(jax.devices()), 8)
    g = _int_graph(101, 600, 3)
    x = _int_features(101, 6, 3)
    for op in ("sum", "max", "mean"):
        got = _ring(g, x, op, shards, packed=True)
        want = _ring(g, x, op, shards, packed=False)
        assert np.array_equal(got, want), (op, shards)


_SUBPROC_PACKED = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core.dataflow import (build_packed_ring_shards,
                                     build_ring_tile_shards,
                                     make_ring_packed_aggregate,
                                     make_ring_tiled_aggregate)
    from repro.distributed.sharding import ring_mesh
    from repro.graphs.format import COOGraph
    from repro.graphs.generate import rmat_graph

    P_DEV = 8
    rng = np.random.default_rng(5)
    n = 101                      # not a multiple of 8: uneven shards
    g0 = rmat_graph(n, 800, seed=5)
    uniq = np.unique(np.stack([g0.src, g0.dst]), axis=1)
    val = rng.integers(1, 4, uniq.shape[1]).astype(np.float32)
    g = COOGraph(n, uniq[0].astype(np.int32), uniq[1].astype(np.int32),
                 val)
    x = rng.integers(-3, 4, (n, 6)).astype(np.float32)
    mesh = ring_mesh(P_DEV)

    pp = build_packed_ring_shards(g, P_DEV)
    dp = build_ring_tile_shards(g, P_DEV, tile=4)
    for op in ("sum", "max", "mean"):
        fn_p = jax.jit(make_ring_packed_aggregate(mesh, "ring", op,
                                                  pp.n_loc))
        xp = np.zeros((pp.padded_vertices, 6), np.float32); xp[:n] = x
        y = np.asarray(fn_p(jnp.asarray(pp.rows), jnp.asarray(pp.cols),
                            jnp.asarray(pp.vals), jnp.asarray(xp),
                            jnp.asarray(pp.in_counts)))[:n]
        fn_d = jax.jit(make_ring_tiled_aggregate(mesh, "ring", op,
                                                 dp.q_loc, dp.tile))
        xd = np.zeros((dp.padded_vertices, 6), np.float32); xd[:n] = x
        w = np.asarray(fn_d(jnp.asarray(dp.blocks),
                            jnp.asarray(dp.tile_row),
                            jnp.asarray(dp.tile_col), jnp.asarray(xd),
                            jnp.asarray(dp.in_counts)))[:n]
        assert np.array_equal(y, w), op
        print(f"PACKED_RING_{op.upper()}_OK")

    fn_p = jax.jit(make_ring_packed_aggregate(mesh, "ring", "sum",
                                              pp.n_loc))
    args = (jnp.asarray(pp.rows), jnp.asarray(pp.cols),
            jnp.asarray(pp.vals), jnp.asarray(xp),
            jnp.asarray(pp.in_counts))
    txt = fn_p.lower(*args).compile().as_text()
    assert "collective-permute" in txt, "ring hop missing from HLO"
    assert "all-gather" not in txt, "features must rotate, not gather"
    print("PACKED_RING_HLO_OK")
""")


def test_ring_packed_multidevice_subprocess():
    """8-way packed ring == 8-way dense ring bitwise, uneven shards,
    all three ops, plus the collective-permute HLO check — in a
    subprocess so it runs even on a single-device checkout."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SUBPROC_PACKED],
                       cwd=os.getcwd(), env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    for tag in ("SUM", "MAX", "MEAN", "HLO"):
        assert f"PACKED_RING_{tag}_OK" in r.stdout


# ---------------------------------------------------- autotune / pricing
def test_autotuner_picks_packed_on_sparse_dense_on_dense():
    sparse = rmat_graph(400, 1500, seed=0).gcn_normalized()
    ps = pack_tile_store(build_tile_store(sparse, 64))
    c = choose_tile_format("auto", ps, backend="blocked")
    assert c.fmt == "packed" and c.reason == "cost-model"
    assert c.packed_bytes < c.dense_bytes
    # a fully dense tiny-tile graph keeps the MXU-friendly dense form
    n = 12
    src, dst = np.meshgrid(np.arange(n), np.arange(n))
    full = COOGraph(n, src.ravel().astype(np.int32),
                    dst.ravel().astype(np.int32),
                    np.ones(n * n, np.float32))
    pd = pack_tile_store(build_tile_store(full, 4))
    cd = choose_tile_format("auto", pd, backend="blocked", bucket_floor=4)
    assert cd.fmt == "dense"
    forced = choose_tile_format("dense", ps)
    assert forced.fmt == "dense" and forced.reason == "forced"
    with pytest.raises(ValueError, match="tile_format"):
        choose_tile_format("csr", ps)


def test_autotuner_measured_choice_runs_and_caches():
    from repro.kernels.autotune import _MEASURED, measured_choice
    g = rmat_graph(200, 1200, seed=2).gcn_normalized()
    st_ = build_tile_store(g, 32)
    ps = pack_tile_store(st_)
    _MEASURED.clear()
    c1 = measured_choice(st_, ps, dim=8, sample=2, iters=1)
    assert c1.reason == "measured" and c1.fmt in ("packed", "dense")
    assert len(_MEASURED) == 1
    assert measured_choice(st_, ps, dim=8) is c1      # cache hit


def test_ring_stripe_bytes_prices_packed_plan_exactly():
    g = _int_graph(90, 500, seed=3)
    for p in (1, 2):
        plan = build_packed_ring_shards(g, p)
        priced = ring_stripe_bytes(g, p, tile_format="packed")
        assert priced == plan.device_bytes()
        # auto never prices above the cheaper concrete format
        assert (ring_stripe_bytes(g, p, tile_format="auto")
                <= min(priced, ring_stripe_bytes(g, p,
                                                 tile_format="dense")))
        s = plan.stats(6, 6)
        assert s.tile_format == "packed"
        assert 0.0 < s.fill_factor() <= 1.0
        assert s.as_dict()["fill_factor"] == s.fill_factor()


def test_tiled_stats_fill_factor_packed_beats_dense():
    g = _int_graph(150, 700, seed=4)
    x = _int_features(150, 6, 4)
    dense = TiledExecutor(g, tile=32, chunk=2, tile_format="dense")
    # pin the callback loop: the per-chunk staging counters under test
    # (fill_factor, packed_tile_bytes) only move on the C7 path
    packed = TiledExecutor(g, tile=32, chunk=2, tile_format="packed",
                           streaming_mode="callback")
    a = dense.aggregate(x, "sum")
    b = packed.aggregate(x, "sum")
    assert np.array_equal(a, b)
    assert packed.stats.fill_factor() > dense.stats.fill_factor()
    assert packed.stats.h2d_tile_bytes < dense.stats.h2d_tile_bytes
    assert packed.stats.packed_tile_bytes > 0
    assert dense.stats.dense_tile_bytes > 0
    assert "fill_factor" in packed.stats.as_dict()


def test_packed_blocked_budget_rechecks_built_plan():
    """The blocked packed path re-prices the *actually built* arrays
    (per-group interval padding can exceed the closed-form nnz bound)
    and spills to the streamed executor or raises — mirror of the ring
    gate."""
    g = rmat_graph(400, 2500, seed=6).gcn_normalized()
    strict = EnGNConfig(in_dim=8, out_dim=8, backend="blocked", tile=32,
                        tile_format="packed", device_budget_bytes=10_000,
                        auto_spill=False)
    with pytest.raises(Exception) as ei:
        prepare_graph(g, strict)
    assert "DeviceBudgetExceeded" in type(ei.value).__name__
    spill = EnGNConfig(in_dim=8, out_dim=8, backend="blocked", tile=32,
                       tile_format="packed", device_budget_bytes=10_000)
    gd = prepare_graph(g, spill)
    assert gd.backend == "tiled"
    fits = EnGNConfig(in_dim=8, out_dim=8, backend="blocked", tile=32,
                      tile_format="packed",
                      device_budget_bytes=50_000_000)
    gd = prepare_graph(g, fits)
    assert gd.meta["tile_format"] == "packed"
    # exactly one device representation is uploaded (flat off-TPU)
    assert ("packed_flat" in gd.carrier) != ("packed_groups" in gd.carrier)


def test_prepared_plans_record_format_choice():
    g = _int_graph(100, 600, seed=5)
    cfg = EnGNConfig(in_dim=6, out_dim=6, backend="tiled", tile=16)
    gd = prepare_graph(g, cfg)
    meta = gd.meta
    assert meta["tile_format"] in ("packed", "dense")
    assert meta["format_choice"].reason in ("cost-model", "forced")
    rcfg = EnGNConfig(in_dim=6, out_dim=6, backend="ring", tile=16,
                      ring_shards=1)
    rgd = prepare_graph(g, rcfg)
    assert rgd.meta["tile_format"] == "packed"
    assert rgd.meta["stats"].tile_format == "packed"
