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
    """(one-shot plan, chunked plan) of the same graph: the default
    contract's sum in destination rows of 64 slots a chunk, the rest in
    edges."""
    whole = prepare_graph(g, cfg)
    monkeypatch.setattr(engn, "EDGE_CHUNK_BYTES", SMALL)
    cut = prepare_graph(g, cfg)
    assert whole.meta["chunks"] == 1 and whole.carrier["src"].ndim == 1
    if cut.meta["layout"] == "edges":
        assert cut.meta["chunks"] == 16
        assert cut.carrier["src"].shape == (16, 64)
    else:
        w = engn.SLOT_WIDTH
        chunks = cut.meta["chunks"]
        assert cut.carrier["src"].shape == (chunks, w, 64 // w)
        assert cut.carrier["dst"].shape == (chunks, 64 // w)
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


def _split_at_200_columns(op, monkeypatch):
    """The chunk shape a layout sized for 128-lane rows is walked in at
    200 columns (two lane rows a gathered row), which must be half as
    long, and the chunked aggregate against the one-shot one."""
    g = _graph(13)
    cfg = EnGNConfig(in_dim=F, out_dim=F, aggregate_op=op)
    layer = EnGNLayer(cfg)
    whole, cut = _plans(g, cfg, monkeypatch)
    feat = _uniform((N, 200), 14)
    lowered = jax.jit(partial(layer._aggregate, cut)).lower(feat)
    np.testing.assert_allclose(layer._aggregate(cut, feat),
                               layer._aggregate(whole, feat),
                               rtol=1e-6, atol=1e-6)
    return cut.meta, lowered.as_text()


def test_wider_rows_split_the_layout(monkeypatch):
    """Destination rows of 64 slots a chunk, reduced at 200 columns, are
    walked in chunks of 32 slots: twice as many, each of half the rows."""
    meta, text = _split_at_200_columns("sum", monkeypatch)
    w = engn.SLOT_WIDTH
    assert meta["layout"] == "dst_rows"
    assert f"tensor<{2 * meta['chunks']}x{w}x{32 // w}xi32>" in text


@pytest.mark.parametrize("op", ["max", "mean"])
def test_wider_rows_split_the_edge_layout(op, monkeypatch):
    """16 chunks of 64 edges, reduced at 200 columns, are walked as 32
    chunks of 32 edges."""
    meta, text = _split_at_200_columns(op, monkeypatch)
    assert meta["layout"] == "edges"
    assert "tensor<32x32xi32>" in text


def test_chunk_counters_and_footprint(small_chunks):
    g = _graph(15)
    cfg = EnGNConfig(in_dim=F, out_dim=H)
    plan = prepare_graph(g, cfg)
    # the default contract's sum, in destination rows of 4 slots: each
    # of the 117 destinations takes ceil(in-degree / 4) rows, 16 a chunk
    rows = int((-(-np.bincount(g.dst) // 4)).sum())
    assert (engn.SLOT_WIDTH, rows) == (4, 295)
    chunks = -(-rows // 16)
    row_bytes = chunks * (64 * (4 + 4) + 16 * 4)   # slots' src, val; dst
    assert plan.meta == {"layout": "dst_rows", "edge_chunk": 64,
                         "chunks": chunks, "slot_width": 4, "rows": rows,
                         "slot_fill": E / (4 * rows),
                         "device_bytes": row_bytes + 64 * 512}
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


def test_edge_chunk_counters(small_chunks):
    """Where the edges stay edges (here max), each is its own row."""
    g = _graph(15)
    plan = prepare_graph(g, EnGNConfig(in_dim=F, out_dim=H,
                                       aggregate_op="max"))
    edge_bytes = 16 * 64 * (4 + 4 + 4)         # src, dst, val
    assert plan.meta == {"layout": "edges", "edge_chunk": 64, "chunks": 16,
                         "slot_width": 1, "rows": E, "slot_fill": 1.0,
                         "device_bytes": edge_bytes + 64 * 512}


def _hub_graph(n, extra=150, hub=7):
    """`_graph`'s edges and `extra` more into vertex `hub`, whose rows
    then pass a chunk's 64 slots."""
    g = _graph(19, n=n)
    rng = np.random.default_rng(20)
    src = np.concatenate([g.src, rng.integers(0, n, extra).astype(np.int32)])
    dst = np.concatenate([g.dst, np.full(extra, hub, np.int32)])
    val = np.concatenate([g.val, rng.uniform(0.5, 1.5, extra).astype(
        np.float32)])
    return COOGraph(n, src, dst, val)


@pytest.mark.parametrize("slots", [1, 4])
@pytest.mark.parametrize("width,n", [(16, N), (16, N + 3), (64, N + 3)])
def test_dst_rows_match_one_shot(slots, width, n, monkeypatch):
    """Destination rows of 1 and 4 slots, at a packed width (16) and a
    row-major one (64), forward and gradient, against the one-shot
    segment_aggregate: with vertices that have no in-edges, a hub whose
    rows lie in several chunks, and a last chunk part-filled."""
    monkeypatch.setattr(engn, "SLOT_WIDTH", slots)
    g = _hub_graph(n)
    cfg = EnGNConfig(in_dim=F, out_dim=F)
    layer = EnGNLayer(cfg)
    whole, cut = _plans(g, cfg, monkeypatch)
    assert cut.meta["layout"] == "dst_rows"
    assert cut.meta["slot_width"] == slots
    dst = np.asarray(cut.carrier["dst"])
    assert len(np.unique(np.nonzero(dst == 7)[0])) > 1
    assert cut.meta["rows"] % (64 // slots) and dst[-1, -1] == n
    assert not np.isin(np.arange(n - ISOLATED, n), dst).any()
    x = _uniform((n, width), 21)
    y1, g1 = _value_and_grad(partial(layer._aggregate, whole), x, 22)
    y2, g2 = _value_and_grad(partial(layer._aggregate, cut), x, 22)
    # the hub's 160-odd terms are summed in another order
    np.testing.assert_allclose(y2, y1, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(g2, g1, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("slots", [1, 2, 4, 8])
def test_dst_row_layout_invariants(slots, small_chunks, monkeypatch):
    """Rows sorted by destination, chunk by chunk and across chunks;
    each vertex in ceil(in-degree / slots) rows whose real slots hold
    exactly its in-edges; padded slots of weight 0 from vertex 0; the
    rows past the last real one at vertex n."""
    g = _hub_graph(N)
    arrays, rows, chunk = engn.dst_row_layout(g.src, g.dst, g.val, N, 6,
                                              slots)
    src, val, dst = (arrays[k] for k in ("src", "val", "dst"))
    per = 64 // slots
    assert chunk == 64 and dst.shape == (-(-rows // per), per)
    assert src.shape == val.shape == (dst.shape[0], slots, per)
    flat = dst.reshape(-1)
    assert (np.diff(flat) >= 0).all()
    deg = np.bincount(g.dst, minlength=N)
    assert rows == int((-(-deg // slots)).sum())
    np.testing.assert_array_equal(np.bincount(flat[:rows], minlength=N),
                                  -(-deg // slots))
    assert (flat[rows:] == N).all()
    # (row, slot) order: each row's slots side by side
    rs = src.transpose(0, 2, 1).reshape(-1, slots)
    rv = val.transpose(0, 2, 1).reshape(-1, slots)
    real = rv != 0
    assert not real[rows:].any()
    assert (rs[~real] == 0).all() and (rv[~real] == 0).all()
    # a row's real slots come first, and only a vertex's last row pads
    assert (np.diff(real.astype(np.int8), axis=1) <= 0).all()
    last = np.r_[flat[1:rows] != flat[:rows - 1], True]
    assert real[:rows][~last].all()
    got = sorted(zip(np.repeat(flat, slots)[real.reshape(-1)],
                     rs[real], rv[real]))
    assert got == sorted(zip(g.dst, g.src, g.val))
    # the edges are placed in blocks, a thread each: one block gives the
    # same arrays
    monkeypatch.setattr(engn.os, "cpu_count", lambda: 1)
    one, _, _ = engn.dst_row_layout(g.src, g.dst, g.val, N, 6, slots)
    for k, a in arrays.items():
        np.testing.assert_array_equal(one[k], a)


@pytest.mark.parametrize("model,op", [("rgcn", "sum"), ("gated_gcn", "sum"),
                                      ("gcn", "max"), ("gcn", "mean")])
def test_other_contracts_and_ops_keep_edges(model, op, small_chunks):
    g = _graph(23, rels=3)
    cfg = make_gnn(model, F, H).cfg
    cfg.aggregate_op = op
    plan = prepare_graph(g, cfg)
    assert plan.meta["layout"] == "edges"
    assert plan.carrier["src"].shape == (16, 64)


def test_dst_rows_refuse_other_contracts_and_ops(small_chunks):
    """A carrier of destination rows handed to a layer that does not
    reduce the default contract's sum raises; it never answers."""
    g = _graph(24, rels=3)
    plan = prepare_graph(g, EnGNConfig(in_dim=F, out_dim=F))
    assert plan.meta["layout"] == "dst_rows"
    x = _uniform((N, F), 25)
    for op in ("max", "mean"):
        layer = EnGNLayer(EnGNConfig(in_dim=F, out_dim=F, aggregate_op=op))
        with pytest.raises(ValueError, match="destination rows"):
            layer._aggregate(plan, x)
    for model in ("rgcn", "gated_gcn"):
        layer = make_gnn(model, F, H)
        params = layer.init(jax.random.key(2))
        with pytest.raises(ValueError, match="destination rows"):
            layer.apply(params, plan, x)


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


def _train_step(n, edges, dims, rows=None):
    """The segment GCN train step (the trainer's loss over its carrier)
    on n vertices of dims[0] features, lowered for edge arrays of shape
    `edges`, flat or in chunks, or for destination rows: `src` and `val`
    of shape `edges`, `dst` of shape `rows`."""
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
    arrays = (s(edges, i32), s(rows or edges, i32), s(edges, f32),
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
    78 chunks of 262,144 destination rows of 4 slots), lowered: the
    width-16 chunk loops, forward and backward, run in scope LANE_PACKED
    inside engn.aggregate, which the stage split of a trace still counts
    as the aggregate; the width-64 loops keep the (N, d) layout, outside
    it.  Compiled, each of the four loops sums its slots and adds or
    gathers its rows in scope DST_ROWS, inside the same loops."""
    import re

    from bench.trace_stages import stage_of
    from repro.trace import DST_ROWS, LANE_PACKED

    rows = (78, (1 << 20) // 4)
    lowered = _train_step(4_190_000, (78, 4, rows[1]), [100, 64, 16],
                          rows=rows)
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
    ops = set(re.findall(r'op_name="([^"]+)"', lowered.compile().as_text()))
    in_loops = {a for a in ops if f"/{DST_ROWS}/" in a and "/while/" in a}
    fwd = "jit(train_step)/jvp(engn.aggregate)"
    bwd = "jit(train_step)/transpose(jvp(engn.aggregate))"
    assert {a.split("/while/")[0] for a in in_loops} == {
        fwd, bwd, f"{fwd}/{LANE_PACKED}", f"{bwd}/{LANE_PACKED}"}
    assert {stage_of(a) for a in in_loops} == {"engn.aggregate"}
