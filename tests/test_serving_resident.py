"""Serving with the feature matrix resident on the device: the bucket
program gathers each batch's rows there, a block of rows at a time,
into layer 0's extraction.  It must answer as the host path does, keep
the host path for stacks whose layer 0 cannot be reduced block by
block and for budgeted engines, and never hold the whole padded input."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engn import EnGNConfig
from repro.core.models import init_stack, make_gnn, make_gnn_stack
from repro.graphs.format import COOGraph
from repro.graphs.generate import random_features, rmat_graph
from repro.serving import engine as engine_mod
from repro.serving.engine import (GNNServingEngine, ServingConfig,
                                  block_rows, gather_extract)
from repro.serving.replicate import ReplicatedServer


F = 24           # wider than layer 0's 16 outputs: extraction first


def _gcn_engine(n=2000, e=8000, f=F, **cfg_kw):
    g = rmat_graph(n, e, seed=0).gcn_normalized()
    x = random_features(n, f, seed=1)
    layers = make_gnn_stack("gcn", [f, 16, 4])
    params = init_stack(layers, jax.random.key(0))
    cfg = ServingConfig(batch_size=32, num_hops=2, **cfg_kw)
    return GNNServingEngine(g, x, layers, params, cfg)


def _rel_gap(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


@pytest.mark.parametrize("rows", [None, 64], ids=["one-block", "64-rows"])
@pytest.mark.parametrize("seeds", [3, 40, 150])
def test_resident_rows_match_the_host_path(monkeypatch, seeds, rows):
    """The device gather answers as the host gather, pad and transfer
    do, across buckets; with 64-row blocks the last block is part
    filled and the blocks past the subgraph are skipped."""
    if rows is not None:
        monkeypatch.setattr(engine_mod, "BLOCK_BYTES", rows * 4 * F)
    eng = _gcn_engine()
    assert eng.x_device is not None
    ids = np.random.default_rng(seeds).choice(2000, seeds, replace=False)
    sub, xs = eng._extract_batch(np.sort(ids).astype(np.int32))
    assert xs is None
    got = eng._infer_batch(sub, None)
    want = eng._infer_batch(sub, eng.x[sub.vertices])
    assert eng.stats["resident_batches"] == 1
    assert eng.stats["device_batches"] == 2
    assert _rel_gap(got, want) <= 1e-6
    (n_pad, _, _), = {k for k in eng._compiled if k[2]}
    if rows is not None:
        assert block_rows(n_pad, F) == rows
        assert sub.graph.num_vertices % rows
        assert n_pad > 2 * rows
    else:
        assert block_rows(n_pad, F) == n_pad > sub.graph.num_vertices


def test_gather_extract_zeroes_the_padding_before_extracting():
    """Rows at or past the real count are the extraction of a zero row
    (a bias shows it), whether or not their block is gathered."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(50, 5)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(5, 3)), jnp.float32)

    def extract(xb):
        return xb @ w + 1.0
    ids = rng.integers(0, 50, 64)
    for n_real in (1, 20, 33, 64):
        vids = jnp.asarray(np.where(np.arange(64) < n_real, ids, -1),
                           jnp.int32)
        got = gather_extract(extract, x, vids, 16)
        xf = jnp.where((jnp.arange(64) < n_real)[:, None], x[ids], 0)
        np.testing.assert_allclose(got, extract(xf), rtol=1e-6, atol=1e-6)


def _typed_graph(n=200, e=1200, relations=3):
    g = rmat_graph(n, e, seed=2)
    rel = np.random.default_rng(2).integers(0, relations, g.num_edges)
    return COOGraph(n, g.src, g.dst, g.weights(), rel.astype(np.int32),
                    relations)


def _host_path_case(kind):
    f = 8
    g = rmat_graph(200, 1200, seed=2).gcn_normalized()
    cfg = ServingConfig(batch_size=16)
    if kind == "rgcn":
        g = _typed_graph()
        layers = [make_gnn("rgcn", f, 8, num_relations=3),
                  make_gnn("rgcn", 8, 4, num_relations=3)]
    elif kind == "budgeted":
        layers = make_gnn_stack("gcn", [f, 8, 4])
        cfg = ServingConfig(batch_size=16, engn=EnGNConfig(
            in_dim=0, out_dim=0, device_budget_bytes=1 << 30))
    else:
        dims = [f, f, f] if kind == "grn" else [f, 8, 4]
        layers = make_gnn_stack(kind, dims)
    params = init_stack(layers, jax.random.key(0))
    return GNNServingEngine(g, random_features(200, f, seed=3), layers,
                            params, cfg)


@pytest.mark.parametrize("kind,reads", [
    ("gcn", False), ("gs_pool", True), ("rgcn", True), ("gated_gcn", True),
    ("grn", True)])
def test_layers_say_whether_update_reads_self(kind, reads):
    """The default update reads the aggregate alone; every override is
    taken to read x_self too."""
    dims = [8, 8] if kind == "grn" else [8, 4]
    kw = {"num_relations": 2} if kind == "rgcn" else {}
    assert make_gnn(kind, *dims, **kw).update_reads_self() is reads


@pytest.mark.parametrize("kind", ["gs_pool", "rgcn", "gated_gcn", "grn",
                                  "budgeted"])
def test_other_stacks_keep_the_host_path(kind):
    """GS-Pool (max, update reads x_self), R-GCN and Gated-GCN (staged
    contracts), GRN (update reads x_self) and a budgeted engine gather
    their rows on the host, as before."""
    eng = _host_path_case(kind)
    assert eng.x_device is None
    sub, xs = eng._extract_batch(np.array([3, 50, 120], np.int32))
    np.testing.assert_array_equal(xs, eng.x[sub.vertices])
    eng.submit(0, np.array([3, 50, 120], np.int32))
    (res,) = eng.drain()
    assert res.status == "ok" and np.isfinite(res.outputs).all()
    assert eng.stats["device_batches"] >= 1
    assert eng.stats["resident_batches"] == 0
    assert eng.stats["h2d_bytes"] > 0


def test_apply_updates_serves_the_new_features():
    from repro.graphs.updates import UpdateLog
    eng = _gcn_engine(n=300, e=1500)
    ids = np.arange(10, dtype=np.int32)
    eng.submit(0, ids)
    before = eng.drain()[0].outputs
    old = eng.x_device
    x_new = random_features(300, F, seed=9)
    eng.apply_updates(UpdateLog(eng.graph).snapshot(), x_new=x_new)
    assert eng.x_device is not old
    np.testing.assert_array_equal(np.asarray(eng.x_device)[:, :F], x_new)
    np.testing.assert_array_equal(np.asarray(eng.x_device)[:, F:], 0)
    fresh = GNNServingEngine(eng.graph, x_new, eng.layers, eng.params,
                             ServingConfig(batch_size=32, num_hops=2))
    eng.submit(1, ids)
    fresh.submit(1, ids)
    got, want = eng.drain()[0].outputs, fresh.drain()[0].outputs
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    assert not np.allclose(got, before)
    assert eng.stats["resident_batches"] == 2


def test_apply_updates_serves_features_edited_in_place():
    """The README's usage: edit `engine.x` in place and pass the same
    array back; the device copy is refreshed all the same."""
    from repro.graphs.updates import UpdateLog
    eng = _gcn_engine(n=300, e=1500)
    ids = np.arange(10, dtype=np.int32)
    eng.submit(0, ids)
    before = eng.drain()[0].outputs
    x = eng.x
    x[:50] = random_features(50, F, seed=11)
    eng.apply_updates(UpdateLog(eng.graph).snapshot(), x_new=x)
    assert eng.x is x
    np.testing.assert_array_equal(np.asarray(eng.x_device)[:, :F], x)
    fresh = GNNServingEngine(eng.graph, x.copy(), eng.layers, eng.params,
                             ServingConfig(batch_size=32, num_hops=2))
    eng.submit(1, ids)
    fresh.submit(1, ids)
    got, want = eng.drain()[0].outputs, fresh.drain()[0].outputs
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    assert not np.allclose(got, before)


def test_apply_updates_without_new_features_keeps_the_copy():
    from repro.graphs.updates import UpdateLog
    eng = _gcn_engine(n=300, e=1500)
    old = eng.x_device
    eng.apply_updates(UpdateLog(eng.graph).snapshot())
    assert eng.x_device is old


@pytest.mark.parametrize("limit", [None, 1 << 40, 1 << 18])
def test_features_larger_than_the_device_share_stay_on_the_host(
        monkeypatch, limit):
    """x goes to the device only where its padded copy fits
    `RESIDENT_SHARE` of the device's memory (here 300 x 128 float32,
    150 KiB): under a 256 KiB limit it stays on the host, and the batch
    answers as the resident path does."""
    monkeypatch.setattr(engine_mod, "device_bytes_limit", lambda: limit)
    eng = _gcn_engine(n=300, e=1500)
    assert (eng.x_device is None) == (limit == 1 << 18)
    eng.submit(0, np.array([1, 2, 3], np.int32))
    got = eng.drain()[0].outputs
    assert eng.stats["resident_batches"] == (limit != 1 << 18)
    monkeypatch.setattr(engine_mod, "device_bytes_limit", lambda: None)
    ref = _gcn_engine(n=300, e=1500)
    ref.submit(0, np.array([1, 2, 3], np.int32))
    np.testing.assert_allclose(got, ref.drain()[0].outputs, rtol=1e-6,
                               atol=1e-7)


def test_a_grown_x_past_the_device_share_goes_back_to_the_host(
        monkeypatch):
    from repro.graphs.updates import UpdateLog
    eng = _gcn_engine(n=300, e=1500)
    assert eng.x_device is not None
    monkeypatch.setattr(engine_mod, "device_bytes_limit", lambda: 1 << 18)
    eng.apply_updates(UpdateLog(eng.graph).snapshot(),
                      x_new=random_features(300, F, seed=4))
    assert eng.x_device is None
    eng.submit(0, np.array([5, 6], np.int32))
    assert eng.drain()[0].status == "ok"
    assert eng.stats["resident_batches"] == 0


def test_apply_updates_grows_the_device_copy():
    from repro.graphs.updates import UpdateLog
    eng = _gcn_engine(n=300, e=1500)
    log = UpdateLog(eng.graph)
    log.insert(np.array([300, 301]), np.array([0, 301]),
               np.ones(2, np.float32))
    eng.apply_updates(log.snapshot())
    assert eng.x_device.shape == (302, 128)
    np.testing.assert_array_equal(np.asarray(eng.x_device)[300:], 0)
    eng.submit(0, np.array([0, 301], np.int32))
    assert eng.drain()[0].status == "ok"


def test_replicas_share_one_device_copy():
    g = rmat_graph(300, 1500, seed=0).gcn_normalized()
    x = random_features(300, F, seed=1)
    layers = make_gnn_stack("gcn", [F, 16, 4])
    params = init_stack(layers, jax.random.key(0))
    with ReplicatedServer(g, x, layers, params, replicas=3,
                          config=ServingConfig(batch_size=16)) as srv:
        copies = {id(e.x_device) for e in srv.engines}
        assert len(copies) == 1 and srv.engines[0].x_device is not None
        for rid in range(6):
            srv.submit(rid, np.array([rid, 100 + rid], np.int32))
        assert all(r.status == "ok" for r in srv.drain())
        assert sum(e.stats["resident_batches"] for e in srv.engines) >= 3


def test_reset_telemetry_zeroes_the_new_counters():
    eng = _gcn_engine(n=300, e=1500)
    eng.submit(0, np.array([1, 2, 3], np.int32))
    eng.drain()
    assert eng.stats["resident_batches"] == 1
    # the batch's edges and ids: no feature rows
    assert 0 < eng.stats["h2d_bytes"] < eng.x.nbytes
    eng.reset_telemetry()
    assert eng.stats["resident_batches"] == 0
    assert eng.stats["h2d_bytes"] == 0


def test_bucket_program_never_holds_the_padded_input():
    """At 131,072 rows of 5,415 features the (n_pad, F) float32 input
    would be 2.84 GB; the compiled program's temporaries stay below it
    (about one 8,192-row block)."""
    n, f, n_pad, e_pad = 400, 5415, 131072, 524288
    g = rmat_graph(n, 2000, seed=0).gcn_normalized()
    layers = make_gnn_stack("gcn", [f, 64, 210])
    params = init_stack(layers, jax.random.key(0))
    eng = GNNServingEngine(g, np.zeros((n, f), np.float32), layers, params)
    from functools import partial
    i32, f32 = jnp.int32, jnp.float32
    edges = jax.ShapeDtypeStruct((e_pad,), i32)
    compiled = jax.jit(partial(eng._resident_fn, n_pad)).lower(
        edges, edges, jax.ShapeDtypeStruct((e_pad,), f32), None,
        jax.ShapeDtypeStruct((n_pad,), i32),
        jax.ShapeDtypeStruct((n, eng.x_device.shape[1]), f32)).compile()
    slab = n_pad * f * 4
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert block_rows(n_pad, f) == 8192
    assert temp < slab // 4, (temp, slab)
