"""The segment backend's edge chunks (`core/engn.py::reduce_edges`).

A graph whose per-edge gather would pass `EDGE_CHUNK_BYTES` is laid out
by `prepare_graph` in (chunks, chunk) edge rows, and every segment path
(the default contract's aggregate, the typed and gated contracts) walks
them in a loop.  Here the cap is made small, so that graphs of a thousand
edges take several chunks, the last one part-filled, and each path is
held to the one-shot program it replaces, forward and backward.  Below
the cap the program is the one-shot one, op for op: the NELL-sized train
step and the largest serving bucket lower to the same StableHLO as the
literal one-shot aggregate.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engn
from repro.core.engn import (EnGNConfig, EnGNLayer, prepare_graph,
                             segment_aggregate)
from repro.core.models import (apply_stack, init_stack, make_gnn,
                               make_gnn_stack)
from repro.core.plan import plan_carrier
from repro.core.tiled import dense_footprint_bytes
from repro.graphs.format import COOGraph

N, E, F, H = 120, 1000, 6, 5
ISOLATED = 3                      # the last vertices have no in-edges
SMALL = 64 * engn.gathered_row_bytes(1)   # 64 edges a chunk: 16 chunks


@pytest.fixture
def small_chunks(monkeypatch):
    monkeypatch.setattr(engn, "EDGE_CHUNK_BYTES", SMALL)


def _graph(seed, rels=None, n=N):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, E).astype(np.int32)
    dst = rng.integers(0, n - ISOLATED, E).astype(np.int32)
    val = rng.uniform(0.5, 1.5, E).astype(np.float32)
    rel = None if rels is None else rng.integers(0, rels, E).astype(
        np.int32)
    return COOGraph(n, src, dst, val, rel, rels or 1)


def _uniform(shape, seed, lo=-1.0, hi=1.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.uniform(lo, hi, shape).astype(np.float32))


def _plans(g, cfg, monkeypatch):
    """(one-shot plan, chunked plan) of the same graph."""
    whole = prepare_graph(g, cfg)
    monkeypatch.setattr(engn, "EDGE_CHUNK_BYTES", SMALL)
    cut = prepare_graph(g, cfg)
    assert whole.meta["chunks"] == 1 and whole.carrier["src"].ndim == 1
    assert cut.meta["chunks"] == 16
    assert cut.carrier["src"].shape == (16, 64)
    return whole, cut


def _value_and_grad(fn, x, seed):
    r = _uniform(fn(x).shape, seed)
    return fn(x), jax.grad(lambda xx: jnp.sum(fn(xx) * r))(x)


# rows of 1 to 32 columns are lane-packed in the loop, 128 // width
# vertices to a 128-lane row: 123 vertices leave the last packed row
# part-empty, 120 at width 16 fill it, so that the padding edges' segment
# lies past the last row; 64 and 100 keep the (N, d) layout
WIDTHS = [pytest.param(op, F, N, id=op) for op in ("sum", "max", "mean")] + [
    pytest.param(op, width, n, id=f"{op}-w{width}-n{n}")
    for op in ("sum", "max", "mean")
    for width, n in [(1, N + 3), (3, N + 3), (16, N), (16, N + 3),
                     (32, N + 3), (64, N + 3), (100, N + 3)]]


@pytest.mark.parametrize("op,width,n", WIDTHS)
def test_chunked_aggregate_matches_one_shot(op, width, n, monkeypatch):
    g = _graph(0, n=n)
    cfg = EnGNConfig(in_dim=F, out_dim=F, aggregate_op=op)
    layer = EnGNLayer(cfg)
    whole, cut = _plans(g, cfg, monkeypatch)
    x = _uniform((n, width), 1)
    y1, g1 = _value_and_grad(partial(layer._aggregate, whole), x, 2)
    y2, g2 = _value_and_grad(partial(layer._aggregate, cut), x, 2)
    np.testing.assert_allclose(y2, y1, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(g2, g1, rtol=1e-6, atol=1e-6)


def test_chunked_max_all_negative_and_isolated(monkeypatch):
    """Messages all below zero: the maxima are negative, never the 0 a
    padding edge at vertex 0 would leave; vertices with no in-edges
    read 0, as segment_aggregate finishes them."""
    g = _graph(3)
    cfg = EnGNConfig(in_dim=F, out_dim=F, aggregate_op="max")
    layer = EnGNLayer(cfg)
    whole, cut = _plans(g, cfg, monkeypatch)
    x = _uniform((N, F), 4, lo=-2.0, hi=-0.5)
    y = np.asarray(layer._aggregate(cut, x))
    np.testing.assert_array_equal(y, layer._aggregate(whole, x))
    has_in = np.bincount(g.dst, minlength=N) > 0
    assert not has_in[N - ISOLATED:].any()
    assert (y[has_in] < 0).all() and (y[~has_in] == 0).all()


def test_chunked_max_shares_ties_as_segment_max(monkeypatch):
    """Every edge twice, the copies chunks apart: each of a maximum's
    copies takes half its gradient, as segment_max's own derivative
    gives it."""
    g = _graph(5)
    half = E // 2
    g = COOGraph(N, np.tile(g.src[:half], 2), np.tile(g.dst[:half], 2),
                 np.tile(g.val[:half], 2))
    cfg = EnGNConfig(in_dim=F, out_dim=F, aggregate_op="max")
    layer = EnGNLayer(cfg)
    whole, cut = _plans(g, cfg, monkeypatch)
    x = _uniform((N, F), 6)
    _, g1 = _value_and_grad(partial(layer._aggregate, whole), x, 7)
    _, g2 = _value_and_grad(partial(layer._aggregate, cut), x, 7)
    np.testing.assert_allclose(g2, g1, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("model,f,h", [("rgcn", F, H), ("rgcn", 4, 7),
                                       ("gated_gcn", F, H),
                                       ("rgcn", 64, 16), ("rgcn", 16, 70),
                                       ("gated_gcn", 48, 5)])
def test_staged_contracts_chunked(model, f, h, monkeypatch):
    """The typed (extraction first, and aggregation first when h > f)
    and gated segment paths, forward and their gradients in the
    parameters and the features."""
    g = _graph(8, rels=3)
    layer = make_gnn(model, f, h)
    whole, cut = _plans(g, layer.cfg, monkeypatch)
    params = layer.init(jax.random.key(0))
    x = _uniform((N, f), 9)

    def run(plan):
        r = _uniform((N, h), 10)
        y = layer.apply(params, plan, x)
        grads = jax.grad(lambda p, xx: jnp.sum(layer.apply(p, plan, xx)
                                               * r), (0, 1))(params, x)
        return y, grads
    want, got = run(whole), run(cut)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("width", [1, 3, 16, 64])
def test_chunked_max_ties_negative_and_isolated_at_each_layout(
        width, monkeypatch):
    """Messages all below zero, every edge twice with the copies chunks
    apart, and a vertex count that leaves the last packed row part-empty:
    at each row layout the maxima are negative, vertices with no
    in-edges read 0, and each copy of a maximum takes half its gradient,
    as the one-shot segment_max gives them."""
    n, half = N + 3, E // 2
    g = _graph(16, n=n)
    g = COOGraph(n, np.tile(g.src[:half], 2), np.tile(g.dst[:half], 2),
                 np.tile(g.val[:half], 2))
    cfg = EnGNConfig(in_dim=F, out_dim=F, aggregate_op="max")
    layer = EnGNLayer(cfg)
    whole, cut = _plans(g, cfg, monkeypatch)
    x = _uniform((n, width), 17, lo=-2.0, hi=-0.5)
    y1, g1 = _value_and_grad(partial(layer._aggregate, whole), x, 18)
    y2, g2 = _value_and_grad(partial(layer._aggregate, cut), x, 18)
    np.testing.assert_array_equal(y2, y1)
    np.testing.assert_allclose(g2, g1, rtol=1e-6, atol=1e-6)
    has_in = np.bincount(g.dst, minlength=n) > 0
    assert not has_in[n - ISOLATED:].any()
    y2 = np.asarray(y2)
    assert (y2[has_in] < 0).all() and (y2[~has_in] == 0).all()


@pytest.mark.parametrize("model", ["gcn", "rgcn", "gated_gcn"])
def test_raw_carrier_is_cut_in_the_trace(model, monkeypatch):
    """A carrier prepare_graph did not lay out (a serving batch's flat
    edge arrays; typed, with the per-relation normalisation computed in
    the trace) past the cap is cut where it is reduced, and agrees with
    the one-shot reduction of the same arrays."""
    g = _graph(11, rels=3)
    layer = make_gnn(model, F, H)
    params = layer.init(jax.random.key(1))
    gd = {"n": N, "src": jnp.asarray(g.src), "dst": jnp.asarray(g.dst),
          "val": jnp.asarray(g.val), "rel": jnp.asarray(g.rel),
          "num_relations": 3}
    x = _uniform((N, F), 12)
    monkeypatch.setattr(engn, "EDGE_CHUNK_BYTES", SMALL)
    got = jax.jit(lambda xx: layer.apply(params, gd, xx))(x)
    text = jax.jit(lambda xx: layer.apply(params, gd, xx)).lower(
        x).as_text()
    assert "stablehlo.while" in text
    monkeypatch.setattr(engn, "EDGE_CHUNK_BYTES", 1 << 40)
    want = layer.apply(params, gd, x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_wider_rows_split_the_layout(monkeypatch):
    """A layout sized for 128-lane rows, reduced at 200 columns (two
    lane rows a gathered row), is split into chunks half as long."""
    g = _graph(13)
    cfg = EnGNConfig(in_dim=F, out_dim=F)
    layer = EnGNLayer(cfg)
    whole, cut = _plans(g, cfg, monkeypatch)
    feat = _uniform((N, 200), 14)
    lowered = jax.jit(partial(layer._aggregate, cut)).lower(feat)
    assert "tensor<32x32xi32>" in lowered.as_text()
    np.testing.assert_allclose(layer._aggregate(cut, feat),
                               layer._aggregate(whole, feat),
                               rtol=1e-6, atol=1e-6)


def test_chunk_counters_and_footprint(small_chunks):
    g = _graph(15)
    cfg = EnGNConfig(in_dim=F, out_dim=H)
    plan = prepare_graph(g, cfg)
    edge_bytes = 16 * 64 * (4 + 4 + 4)         # src, dst, val
    assert plan.meta == {"edge_chunk": 64, "chunks": 16,
                         "device_bytes": edge_bytes + 64 * 512}
    assert plan.footprint_bytes == plan.meta["device_bytes"]
    # the gate prices one chunk's lane-padded rows, so a budget that
    # holds the features, the edges and one chunk keeps the segment plan
    need = dense_footprint_bytes(N, E, F, H, "segment")
    assert need == 4 * N * (F + H) + 12 * E + 64 * 512
    budgeted = EnGNConfig(in_dim=F, out_dim=H, device_budget_bytes=need)
    assert prepare_graph(g, budgeted).backend == "segment"
    assert prepare_graph(g, EnGNConfig(
        in_dim=F, out_dim=H, device_budget_bytes=need - 1)
    ).backend == "tiled"


def test_chunk_length_from_the_row_bytes():
    cap = engn.EDGE_CHUNK_BYTES
    rows = cap // 512
    assert engn.edge_chunk(rows, 64) == rows              # fits: one chunk
    assert engn.edge_chunk(rows + 1, 64) == rows          # a power of two
    assert engn.edge_chunk(71_290_000, 64) == 1 << 20
    assert engn.edge_chunk(71_290_000, 129) == 1 << 19    # two lane rows
    # the largest serving bucket gathers 524,288 rows of 64 in one go
    assert engn.edge_chunk(524_288, 64) == 524_288


def _literal_one_shot(self, graph, feat):
    """The segment aggregate as it was before edge chunks."""
    graph = plan_carrier(graph)
    ev = feat[graph["src"]]
    if "val" in graph:
        ev = ev * graph["val"][:, None]
    return segment_aggregate(ev, graph["dst"], graph["n"],
                             self.cfg.aggregate_op)


def _train_step(n, edges, dims):
    """The segment GCN train step (the trainer's loss over its carrier)
    on n vertices of dims[0] features, lowered for edge arrays of shape
    `edges`, flat or in chunks."""
    from repro.training.optimizer import init_opt_state
    from repro.training.train_lib import make_gnn_train_step

    s = jax.ShapeDtypeStruct
    i32, f32 = jnp.int32, jnp.float32
    layers = make_gnn_stack("gcn", dims)
    for layer in layers:
        layer.cfg.training = True

    def loss_fn(ps, batch, arrays):
        src, dst, val, x, y = arrays
        carrier = {"n": n, "backend": "segment", "src": src, "dst": dst,
                   "val": val}
        nodes = batch["nodes"]
        logits = apply_stack(layers, ps, carrier, x)[nodes]
        ll = jax.nn.log_softmax(logits, -1)
        return -jnp.mean(jnp.take_along_axis(ll, y[nodes][:, None], 1))
    params = [{"w": s((a, b), f32)} for a, b in zip(dims[:-1], dims[1:])]
    arrays = (s(edges, i32), s(edges, i32), s(edges, f32),
              s((n, dims[0]), f32), s((n,), i32))
    step = make_gnn_train_step(loss_fn)
    return step.lower(params, jax.eval_shape(init_opt_state, params),
                      {"nodes": s((256,), i32)}, arrays)


def _nell_programs():
    """StableHLO of the NELL-sized segment train step and of the largest
    serving bucket's programs (host rows and resident rows)."""
    from repro.graphs.generate import rmat_graph
    from repro.serving.engine import GNNServingEngine

    n, e, dims = 65755, 251550 + 65755, [5415, 64, 210]
    s = jax.ShapeDtypeStruct
    i32, f32 = jnp.int32, jnp.float32
    train = _train_step(n, (e,), dims).as_text()

    f, n_pad, e_pad = dims[0], 131072, 524288
    sl = make_gnn_stack("gcn", dims)
    eng = GNNServingEngine(rmat_graph(300, 1200, seed=0).gcn_normalized(),
                           np.zeros((300, f), np.float32), sl,
                           init_stack(sl, jax.random.key(0)))
    edges = (s((e_pad,), i32), s((e_pad,), i32), s((e_pad,), f32), None)
    host = jax.jit(partial(eng._stack_fn, n_pad)).lower(
        *edges, s((n_pad, f), f32)).as_text()
    resident = jax.jit(partial(eng._resident_fn, n_pad)).lower(
        *edges, s((n_pad,), i32),
        s((n, eng.x_device.shape[1]), f32)).as_text()
    return train, host, resident


def test_nell_and_serving_programs_are_the_one_shot_ones(monkeypatch):
    got = _nell_programs()
    monkeypatch.setattr(EnGNLayer, "_aggregate", _literal_one_shot)
    want = _nell_programs()
    assert "stablehlo.while" not in got[0]
    for a, b in zip(got, want):
        assert a == b


def test_lane_packed_scope_in_the_synthetic_a_step():
    """The Synthetic-A step (4.19M vertices, widths 64 and 16, edges in
    68 chunks of 1,048,576), lowered: the width-16 chunk loops, forward
    and backward, run in scope LANE_PACKED inside engn.aggregate, which
    the stage split of a trace still counts as the aggregate; the
    width-64 loops keep the (N, d) layout, outside it."""
    import re

    from bench.trace_stages import stage_of
    from repro.trace import LANE_PACKED

    lowered = _train_step(4_190_000, (68, 1 << 20), [100, 64, 16])
    names = set(re.findall(r'loc\("([^"]+)"', lowered.as_text(
        debug_info=True)))
    packed = {a for a in names if f"/{LANE_PACKED}/" in a}
    loops = {a.split(f"/{LANE_PACKED}/")[0] for a in packed
             if a.endswith(f"/{LANE_PACKED}/while")}
    assert loops == {"jit(train_step)/jvp(engn.aggregate)",
                     "jit(train_step)/transpose(jvp(engn.aggregate))"}
    assert {stage_of(a) for a in packed} == {"engn.aggregate"}
    assert {a for a in names - packed
            if re.search(r"engn\.aggregate\)*/while$", a)} == {
        "jit(train_step)/jvp(engn.aggregate)/while",
        "jit(train_step)/transpose(jvp(engn.aggregate))/while"}
