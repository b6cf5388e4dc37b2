"""Gradient correctness for the differentiable out-of-core path
(ISSUE 5, DESIGN.md C9): finite-difference checks of the kernel ops
(rer_spmm / rer_gather XLA formulations; the Pallas route is TPU-only),
the streamed tiled VJP against the blocked backend's jax.grad (sum and
mean bitwise on integer data, max allclose), the max tie-breaking
convention (even split among tied winners, like jax's segment_max
grad), backward-traffic accounting, and the end-to-end --gnn training
trajectory on a graph whose dense footprint exceeds the device budget.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engn import (EnGNConfig, EnGNLayer, prepare_graph,
                             segment_aggregate)
from repro.core.tiled import (TiledExecutor, dense_footprint_bytes,
                              make_streamed_aggregate)
from repro.graphs.format import COOGraph
from repro.graphs.generate import rmat_graph
from repro.graphs.partition import build_tile_store, pack_tile_store
from repro.kernels.rer_gather import ops as gather_ops
from repro.kernels.rer_spmm.ops import blocked_spmm_xla


def _int_graph(n, e, seed):
    g = rmat_graph(n, e, seed=seed)
    uniq = np.unique(np.stack([g.src, g.dst]), axis=1)
    rng = np.random.default_rng(seed)
    val = rng.integers(1, 4, uniq.shape[1]).astype(np.float32)
    return COOGraph(n, uniq[0].astype(np.int32), uniq[1].astype(np.int32),
                    val)


def _int_features(n, f, seed):
    rng = np.random.default_rng(seed + 17)
    return rng.integers(-3, 4, (n, f)).astype(np.float32)


def _float_graph(n, e, seed):
    """Float weights and no dedup: the generic case for FD checks
    (random continuous values keep max kinks away from the sample)."""
    return rmat_graph(n, e, seed=seed).gcn_normalized()


def _segment_loss(g, coef, op):
    def f(x):
        ev = x[jnp.asarray(g.src)] * jnp.asarray(g.val)[:, None]
        y = segment_aggregate(ev, jnp.asarray(g.dst), g.num_vertices, op)
        return jnp.sum(y * coef)
    return f


# ---------------------------------------------------- finite differences
def _check_fd(f, x, seed=0, eps=1e-3, directions=4, rtol=5e-2, atol=0.05):
    """Directional central differences vs jax.grad: for random unit-ish
    directions v, (f(x+eps v) - f(x-eps v)) / 2eps must match <grad, v>.
    Tolerances account for fp32 cancellation in the difference and for
    the occasional max kink inside the eps ball; a wrong VJP (missing
    scatter, untransposed tiles, dropped edge weight) is off by O(1)
    factors and still fails loudly.  The median over directions guards
    against a single kink-crossing direction."""
    fj = jax.jit(f)
    g = np.asarray(jax.jit(jax.grad(f))(x))
    rng = np.random.default_rng(seed)
    rel = []
    for k in range(directions):
        v = rng.standard_normal(np.shape(x)).astype(np.float32)
        fd = (float(fj(x + eps * v)) - float(fj(x - eps * v))) / (2 * eps)
        an = float(np.sum(g * v))
        rel.append(abs(fd - an) / (atol + rtol * max(abs(an), abs(fd))))
    assert float(np.median(rel)) <= 1.0, rel


def test_rer_spmm_xla_grad_matches_fd():
    """jax.grad through the blocked RER-SpMM XLA formulation (the
    CPU/GPU execution path) passes directional FD for sum and max."""
    g = _float_graph(40, 250, seed=0)
    cfg = EnGNConfig(in_dim=5, out_dim=5, backend="blocked", tile=8,
                     tile_format="dense")
    gd = prepare_graph(g, cfg)
    q, pad = gd.meta["q"], gd.meta["padded"]
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.uniform(0.5, 1.5, (pad, 5)).astype(np.float32))
    coef = jnp.asarray(rng.uniform(-1, 1, (pad, 5)).astype(np.float32))
    for op in ("sum", "max"):
        def loss(xx, _op=op):
            y = blocked_spmm_xla(gd.carrier["blocks"], gd.carrier["block_row"],
                                 gd.carrier["block_col"], xx, q=q, op=_op)
            return jnp.sum(y * coef)
        _check_fd(loss, x, seed=2)


def test_rer_gather_xla_grad_matches_fd():
    """jax.grad through the packed-tile XLA formulations — the flat
    one-launch gather+segment and the per-group packed_spmm — passes
    directional FD for sum and max."""
    g = _float_graph(48, 300, seed=3)
    st_ = build_tile_store(g, 8)
    ps = pack_tile_store(st_)
    pad = st_.padded_vertices
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.uniform(0.5, 1.5, (pad, 4)).astype(np.float32))
    coef = jnp.asarray(rng.uniform(-1, 1, (pad, 4)).astype(np.float32))
    gsrc, gdst, gval = (jnp.asarray(a) for a in gather_ops.flat_entries(ps))
    groups = gather_ops.prepare_packed_groups(ps, bucket_floor=4)
    for op in ("sum", "max"):
        def loss_flat(xx, _op=op):
            y = gather_ops.packed_flat_xla(gsrc, gdst, gval, xx, n=pad,
                                           op=_op)
            return jnp.sum(y * coef)
        _check_fd(loss_flat, x, seed=5)

        def loss_groups(xx, _op=op):
            y = None
            for gr in groups:
                part = gather_ops.packed_spmm(
                    jnp.asarray(gr.rows), jnp.asarray(gr.cols),
                    jnp.asarray(gr.vals), jnp.asarray(gr.block_row),
                    jnp.asarray(gr.block_col), xx, q=st_.q, op=_op,
                    impl="xla", finish=False)
                y = part if y is None else (
                    y + part if _op == "sum" else jnp.maximum(y, part))
            if _op == "max":
                y = jnp.where(jnp.isneginf(y), 0.0, y)
            return jnp.sum(y * coef)
        _check_fd(loss_groups, x, seed=6)


def test_streamed_vjp_matches_fd():
    """Directional FD through the streamed custom_vjp itself (the host
    callback forward and the transposed re-stream backward), dense and
    packed, all three ops."""
    g = _float_graph(60, 400, seed=7)
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.uniform(0.5, 1.5, (60, 4)).astype(np.float32))
    coef = jnp.asarray(rng.uniform(-1, 1, (60, 4)).astype(np.float32))
    for fmt in ("dense", "packed"):
        for op in ("sum", "max", "mean"):
            ex = TiledExecutor(g, tile=16, chunk=3, tile_format=fmt)
            agg = make_streamed_aggregate(ex, op)

            def loss(xx, _agg=agg):
                return jnp.sum(_agg(xx) * coef)
            _check_fd(loss, x, seed=9)


@pytest.mark.parametrize("kernel,op", [("spmm", "sum"), ("spmm", "max"),
                                       ("gather", "sum"),
                                       ("gather", "max"), ("fused", "sum")])
def test_kernel_dispatch_grad_is_reference_vjp(kernel, op):
    """`pallas_call` has no transpose rule: a dispatcher that picks the
    Pallas kernel (interpret mode here, Mosaic on TPU) differentiates
    through its custom VJP, which must equal AD of the XLA path."""
    from repro.graphs.format import coo_to_blocked
    from repro.kernels.fused_engn.ops import fused_engn_layer
    from repro.kernels.rer_spmm.ops import blocked_spmm, prepare_blocks
    g = _float_graph(100, 600, seed=1)
    t = 32
    b = coo_to_blocked(g, t, order="column")
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(b.q * t, 8)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(8, 4)).astype(np.float32))
    if kernel == "gather":
        gr = gather_ops.prepare_packed_groups(
            pack_tile_store(build_tile_store(g, t)), 8)[-1]
        graph = (gr.rows, gr.cols, gr.vals, gr.block_row, gr.block_col)

        def agg(xx, impl):
            return gather_ops.packed_spmm(*graph, xx, q=b.q, op=op,
                                          impl=impl)
    else:
        graph = prepare_blocks(b.blocks, b.block_row, b.block_col, b.q)
        if kernel == "spmm":
            def agg(xx, impl):
                return blocked_spmm(*graph, xx, q=b.q, op=op, impl=impl)
        else:
            def agg(xx, impl):
                return fused_engn_layer(*graph, xx, w, q=b.q, impl=impl)

    def grad(impl):
        return jax.jit(jax.grad(
            lambda xx: jnp.sum(agg(xx, impl) ** 2)))(x)
    np.testing.assert_allclose(np.asarray(grad("pallas")),
                               np.asarray(grad("xla")),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.skipif(jax.default_backend() == "cpu",
                    reason="Pallas interpret mode is correctness-only "
                           "and has no reverse rules; the kernel grad "
                           "path is exercised on real TPU")
def test_streamed_vjp_with_pallas_impl():
    """On TPU the streamed forward chunks run the Mosaic kernels while
    the custom_vjp backward is the hand-written transposed re-stream —
    no kernel AD needed — so jax.grad must agree with the XLA-impl
    executor."""
    g = _float_graph(60, 400, seed=7)
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.uniform(0.5, 1.5, (60, 4)).astype(np.float32))
    coef = jnp.asarray(rng.uniform(-1, 1, (60, 4)).astype(np.float32))
    for op in ("sum", "max"):
        ex_p = TiledExecutor(g, tile=16, chunk=3, impl="pallas")
        ex_x = TiledExecutor(g, tile=16, chunk=3, impl="xla")

        def loss(xx, _ex=ex_p, _op=op):
            return jnp.sum(make_streamed_aggregate(_ex, _op)(xx) * coef)

        def loss_ref(xx, _ex=ex_x, _op=op):
            return jnp.sum(make_streamed_aggregate(_ex, _op)(xx) * coef)
        np.testing.assert_allclose(np.asarray(jax.grad(loss)(x)),
                                   np.asarray(jax.grad(loss_ref)(x)),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------- streamed vs blocked
def test_streamed_vjp_matches_blocked_grad():
    """Acceptance (ISSUE 5): jax.grad through the streamed tiled
    backend == the blocked backend's grad on a graph whose dense
    footprint exceeds the budget — sum and mean bitwise (integer data;
    the mean cotangent is an exact multiple of the in-counts so the
    even division stays integer), max allclose (tie-free floats would
    be bitwise too, but the reduction orders of tied recomputes may
    differ)."""
    n, d = 300, 6
    g = _int_graph(n, 2500, seed=0)
    x = jnp.asarray(_int_features(n, d, 0))
    r = jnp.asarray(_int_features(n, d, 99))
    counts = jnp.asarray(np.maximum(
        np.bincount(g.dst, minlength=n), 1).astype(np.float32))[:, None]
    budget = 50_000
    for backend in ("segment", "blocked"):
        assert dense_footprint_bytes(n, g.num_edges, d, d,
                                     backend) > budget
    for op in ("sum", "mean", "max"):
        coef = r * counts if op == "mean" else r
        cfg_b = EnGNConfig(in_dim=d, out_dim=d, aggregate_op=op,
                           backend="blocked", tile=32)
        gd_b = prepare_graph(g, cfg_b)
        layer_b = EnGNLayer(cfg_b)

        def loss_b(xx):
            return jnp.sum(layer_b._aggregate(gd_b, xx) * coef)

        cfg_t = EnGNConfig(in_dim=d, out_dim=d, aggregate_op=op,
                           backend="blocked", tile=32, training=True,
                           device_budget_bytes=budget)
        gd_t = prepare_graph(g, cfg_t)
        assert gd_t.backend == "tiled", op
        agg = make_streamed_aggregate(gd_t.carrier["tiled_exec"], op)

        def loss_t(xx):
            return jnp.sum(agg(xx) * coef)

        gb = np.asarray(jax.grad(loss_b)(x))
        gt = np.asarray(jax.jit(jax.grad(loss_t))(x))
        if op == "max":
            np.testing.assert_allclose(gt, gb, rtol=1e-5, atol=1e-6)
        else:
            assert np.array_equal(gt, gb), op


def test_streamed_layer_grads_match_segment_backend():
    """Full-layer gradients (params AND input) through apply():
    the spilled GCN layer under jit+grad routes through the
    differentiable streamed path and matches the segment backend —
    bitwise for the sum aggregate on integer data."""
    n, f, h = 150, 6, 4
    g = _int_graph(n, 900, seed=1)
    x = jnp.asarray(_int_features(n, f, 1))
    r = jnp.asarray(_int_features(n, h, 5))
    from repro.core.models import make_gnn
    seg = make_gnn("gcn", f, h, backend="segment")
    params = seg.init(jax.random.key(0))
    # integer weights so every contraction stays exact in fp32
    params = {"w": jnp.asarray(np.sign(np.asarray(params["w"])) * 1.0)}
    gd_s = prepare_graph(g, seg.cfg, out_dim=h)

    til = make_gnn("gcn", f, h, backend="tiled", tile=32)
    til.cfg.training = True
    gd_t = prepare_graph(g, til.cfg, out_dim=h)

    def loss(layer, gd, ps, xx):
        return jnp.sum(layer.apply(ps, gd, xx) * r)

    gs_p, gs_x = jax.grad(lambda p, xx: loss(seg, gd_s, p, xx),
                          argnums=(0, 1))(params, x)
    gt_p, gt_x = jax.jit(jax.grad(
        lambda p, xx: loss(til, gd_t, p, xx),
        argnums=(0, 1)))(params, x)
    assert np.array_equal(np.asarray(gt_p["w"]), np.asarray(gs_p["w"]))
    assert np.array_equal(np.asarray(gt_x), np.asarray(gs_x))


def test_streamed_max_tie_convention():
    """Ties split the cotangent evenly among all winners — bitwise the
    convention of jax's segment_max gradient — so a deliberate
    two-way tie gets 0.5 of the incoming gradient on each source."""
    # vertices 0 and 1 both feed 2 with weight 1 and equal features
    src = np.array([0, 1, 3], np.int32)
    dst = np.array([2, 2, 4], np.int32)
    val = np.ones(3, np.float32)
    g = COOGraph(5, src, dst, val)
    x = jnp.asarray(np.array([[2.0], [2.0], [0.0], [7.0], [0.0]],
                             np.float32))
    coef = jnp.asarray(np.array([[0.0], [0.0], [4.0], [0.0], [8.0]],
                                np.float32))
    want = np.asarray(jax.grad(
        lambda xx: _segment_loss(g, coef, "max")(xx))(x))
    np.testing.assert_allclose(want[:2, 0], [2.0, 2.0])  # even split
    for fmt in ("dense", "packed"):
        ex = TiledExecutor(g, tile=2, chunk=2, tile_format=fmt)
        agg = make_streamed_aggregate(ex, "max")
        got = np.asarray(jax.grad(
            lambda xx: jnp.sum(agg(xx) * coef))(x))
        assert np.array_equal(got, want), fmt


def test_streamed_backward_stats_and_transposed_sharing():
    """The backward re-stream is accounted in TiledStats.bwd_* and the
    transposed store is a zero-copy view of the forward host arrays."""
    g = _int_graph(120, 800, seed=2)
    x = jnp.asarray(_int_features(120, 5, 2))
    # pin the callback loop: this test is about the bwd_* accounting of
    # the transposed re-stream, which the chunk-queue route never runs
    ex = TiledExecutor(g, tile=16, chunk=2, streaming_mode="callback")
    agg = make_streamed_aggregate(ex, "sum")
    jax.grad(lambda xx: jnp.sum(agg(xx)))(x)
    s = ex.stats
    assert s.bwd_steps > 0 and s.bwd_tiles > 0
    assert s.bwd_h2d_tile_bytes > 0 and s.bwd_d2h_bytes > 0
    assert s.tiles > 0                       # forward counted separately
    d = s.as_dict()
    assert d["bwd_tiles"] == s.bwd_tiles
    tex = ex.transposed()
    assert tex.store.edge_w is ex.store.edge_w
    assert tex.store.edge_li is ex.store.edge_lj
    assert tex is ex.transposed()            # cached


def test_streamed_vjp_respects_budget():
    """Forward AND backward streaming fit the same device budget: a
    max-aggregate grad (the widest backward stream: tiles + the
    (y, g/cnt) stack + the resident source interval) runs under the
    budget the training-priced prepare_graph fitted."""
    n, d = 400, 8
    g = _int_graph(n, 3000, seed=3)
    x = jnp.asarray(_int_features(n, d, 3))
    cfg = EnGNConfig(in_dim=d, out_dim=d, aggregate_op="max",
                     backend="segment", device_budget_bytes=120_000,
                     training=True)
    gd = prepare_graph(g, cfg)
    assert gd.backend == "tiled"
    agg = make_streamed_aggregate(gd.carrier["tiled_exec"], "max")
    gx = jax.grad(lambda xx: jnp.sum(agg(xx)))(x)   # must not raise
    assert np.isfinite(np.asarray(gx)).all()


# ------------------------------------------- staged contracts (ISSUE 6)
_RELS = 3


def _typed_float_graph(n, e, seed, rels=_RELS):
    """Float-weighted relation-typed graph for FD checks: continuous
    values keep ReLU/sigmoid kinks away from the sample points."""
    g = rmat_graph(n, e, seed=seed)
    rng = np.random.default_rng(seed + 31)
    val = rng.uniform(0.5, 1.5, g.num_edges).astype(np.float32)
    rel = ((g.src.astype(np.int64) + g.dst) % rels).astype(np.int32)
    return COOGraph(n, g.src, g.dst, val, rel, rels)


def _uniform(shape, seed, lo=-1.0, hi=1.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.uniform(lo, hi, shape).astype(np.float32))


def test_streamed_typed_rgcn_grads_fd():
    """Per-relation weights AND input features through the streamed
    typed-sum custom_vjp (DESIGN.md C10: the backward rel-scatters the
    dst cotangent into the (T, R, H) payload slices) on a graph whose
    dense footprint exceeds the device budget.  The staged carriers are
    XLA formulations on every backend, so there is no separate Pallas
    variant to skip off-TPU.

    Inputs and weights are drawn from the positive cone so every ReLU
    pre-activation sits strictly above zero: the update stays locally
    smooth and the central difference is well conditioned (signed
    inputs make some FD directions cross ReLU kinks)."""
    from repro.core.models import make_gnn
    n, f, h = 180, 6, 5
    g = _typed_float_graph(n, 1400, seed=7)
    x = _uniform((n, f), seed=8, lo=0.1, hi=1.0)
    r = _uniform((n, h), seed=9)
    til = make_gnn("rgcn", f, h, backend="tiled", tile=32,
                   num_relations=_RELS)
    til.cfg.training = True
    til.cfg.device_budget_bytes = budget = 40_000
    assert dense_footprint_bytes(n, g.num_edges, f, h,
                                 "segment") > budget
    gd = prepare_graph(g, til.cfg)
    assert gd.backend == "tiled"
    shapes = til.init(jax.random.key(2))
    params = {
        "w0": _uniform(shapes["w0"].shape, seed=12, lo=0.1, hi=1.0),
        "wr": _uniform(shapes["wr"].shape, seed=13, lo=0.1, hi=1.0),
    }

    def loss_wr(wr):
        ps = {"w0": params["w0"], "wr": wr.reshape(_RELS, f, h)}
        return jnp.sum(til.apply(ps, gd, x) * r)

    _check_fd(loss_wr, jnp.ravel(params["wr"]), seed=3)
    _check_fd(lambda xx: jnp.sum(til.apply(params, gd, xx) * r), x,
              seed=4)


def test_streamed_typed_grads_match_segment_backend():
    """The streamed typed VJP agrees with plain jax.grad through the
    segment reference — params (both weight groups) and input."""
    from repro.core.models import make_gnn
    n, f, h = 150, 6, 4
    g = _typed_float_graph(n, 1000, seed=11)
    x = _uniform((n, f), seed=12)
    r = _uniform((n, h), seed=13)
    seg = make_gnn("rgcn", f, h, backend="segment", num_relations=_RELS)
    params = seg.init(jax.random.key(4))
    gd_s = prepare_graph(g, seg.cfg)
    til = make_gnn("rgcn", f, h, backend="tiled", tile=32,
                   num_relations=_RELS)
    til.cfg.training = True
    gd_t = prepare_graph(g, til.cfg)

    gs = jax.grad(lambda p, xx: jnp.sum(seg.apply(p, gd_s, xx) * r),
                  argnums=(0, 1))(params, x)
    gt = jax.jit(jax.grad(
        lambda p, xx: jnp.sum(til.apply(p, gd_t, xx) * r),
        argnums=(0, 1)))(params, x)
    np.testing.assert_allclose(np.asarray(gt[0]["w0"]),
                               np.asarray(gs[0]["w0"]),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gt[0]["wr"]),
                               np.asarray(gs[0]["wr"]),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gt[1]), np.asarray(gs[1]),
                               rtol=1e-4, atol=1e-5)


def test_streamed_gated_grads_fd():
    """The gated message val.sigmoid(ph[dst]+pc[src]).x[src] through
    the streamed custom_vjp: the backward recomputes the forward gate
    per tile (like the max path recomputes winners) for d(gate)/d(ph),
    then re-streams transposed for d/d(pc) and d/d(x).  FD-checked for
    both gate projections and the input, on a budget-exceeding graph."""
    from repro.core.models import make_gnn
    n, f, h = 160, 6, 4
    g = _typed_float_graph(n, 1200, seed=17)
    x = _uniform((n, f), seed=18)
    r = _uniform((n, h), seed=19)
    til = make_gnn("gated_gcn", f, h, backend="tiled", tile=32)
    til.cfg.training = True
    til.cfg.device_budget_bytes = budget = 40_000
    assert dense_footprint_bytes(n, g.num_edges, f, h,
                                 "segment") > budget
    gd = prepare_graph(g, til.cfg)
    assert gd.backend == "tiled"
    params = til.init(jax.random.key(6))

    for key in ("w_h", "w_c"):
        def loss_w(w, _key=key):
            ps = dict(params)
            ps[_key] = w
            return jnp.sum(til.apply(ps, gd, x) * r)

        _check_fd(loss_w, jnp.asarray(params[key]), seed=7)
    _check_fd(lambda xx: jnp.sum(til.apply(params, gd, xx) * r), x,
              seed=8)


@pytest.mark.parametrize("fmt", ["dense", "packed"])
@pytest.mark.parametrize("model", ["rgcn", "gated_gcn"])
def test_ring_staged_grads_fd(model, fmt):
    """Gradients straight through the ring scan (jax.grad across
    shard_map + ppermute: the rotation is a lax.scan, so reverse-mode
    AD re-rotates the cotangents) for both staged contracts and both
    stripe carriers: FD on the model's message-defining weights and
    the input features."""
    from repro.core.models import make_gnn
    n, f, h = 90, 6, 4
    shards = min(len(jax.devices()), 8)
    g = _typed_float_graph(n, 700, seed=23)
    x = _uniform((n, f), seed=24)
    r = _uniform((n, h), seed=25)
    ring = make_gnn(model, f, h, backend="ring", tile=8,
                    num_relations=_RELS)
    ring.cfg.ring_shards = shards
    ring.cfg.tile_format = fmt
    gd = prepare_graph(g, ring.cfg)
    assert gd.meta["tile_format"] == fmt
    params = ring.init(jax.random.key(9))
    wkey = "wr" if model == "rgcn" else "w_h"

    def loss_w(w):
        ps = dict(params)
        ps[wkey] = w.reshape(params[wkey].shape)
        return jnp.sum(ring.apply(ps, gd, x) * r)

    _check_fd(loss_w, jnp.ravel(params[wkey]), seed=10)
    _check_fd(lambda xx: jnp.sum(ring.apply(params, gd, xx) * r), x,
              seed=11)


# ---------------------------------------------------- training trajectory
def test_gnn_training_trajectory_tiled_matches_blocked():
    """Acceptance (ISSUE 5): a short --gnn training run on a graph
    whose dense footprint exceeds the budget (so it spills to the
    streamed executor) follows the blocked backend's loss trajectory
    within 1e-4."""
    from repro.launch.train import build_gnn
    kw = dict(model="gcn", dataset="pubmed", steps=6, hidden=16,
              batch=64, max_vertices=300, max_edges=2500,
              feature_dim=128)
    step_b, st_b, data_b, gd_b, _ = build_gnn(backend="blocked",
                                              device_budget_bytes=None,
                                              **kw)
    budget = 300_000
    step_t, st_t, data_t, gd_t, _ = build_gnn(backend="blocked",
                                              device_budget_bytes=budget,
                                              **kw)
    assert gd_b.backend == "blocked"
    assert gd_t.backend == "tiled"
    traj = {}
    for tag, step, state, data in (("blocked", step_b, st_b, data_b),
                                   ("tiled", step_t, st_t, data_t)):
        losses = []
        for _, batch in zip(range(6), data):
            state["params"], state["opt"], m = step(state["params"],
                                                    state["opt"], batch)
            losses.append(float(m["loss"]))
        traj[tag] = losses
    np.testing.assert_allclose(traj["tiled"], traj["blocked"],
                               rtol=0, atol=1e-4)
    st = gd_t.carrier["tiled_exec"].stats
    # callback regime streams transposed tiles backward; the chunk-queue
    # regime (DESIGN.md C11) differentiates the device-resident sweep
    # instead, so no backward tiles move on it
    assert st.bwd_tiles > 0 or st.queue_builds > 0
