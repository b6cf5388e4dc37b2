"""The reference against the program's segment path, and the generators'
determinism, at tiny sizes on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from bench.gen import inputs, traffic
from bench.gen.rmat import rmat_edges

N, E, DIMS = 300, 1800, [12, 8, 5]


def tiny_graph():
    return rmat_edges(N, E, 0)


def test_reference_matches_the_program_forward_and_gradients():
    from repro.core.engn import prepare_graph
    from repro.core.models import apply_stack, make_gnn_stack
    from repro.graphs.format import COOGraph
    from repro.launch.elastic_gnn import ElasticGNNTrainer

    src, dst = tiny_graph()
    g = COOGraph(N, src, dst).gcn_normalized()
    x = inputs.features(N, DIMS[0], 5, 0.1)
    y = inputs.labels(N, DIMS[-1], 5)
    params = inputs.weights(DIMS, 5)
    layers = make_gnn_stack("gcn", DIMS, backend="segment")
    plan = prepare_graph(g, layers[0].cfg)
    got = np.asarray(apply_stack(layers, params, plan, x))
    s, d, v = reference.normalize(src, dst, N)
    blocks = [jnp.asarray(a) for a in reference.edge_blocks(s, d, v, 512)]
    want = np.asarray(reference.forward(params, x, *blocks, N))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    trainer = ElasticGNNTrainer(layers=layers, graph=g, x=x, y_true=y,
                                hidden=DIMS[1], peak_lr=5e-3, steps=10)
    nodes = inputs.node_batch(N, 64, 5, 0)
    lp, gp = jax.value_and_grad(trainer.loss_fn)(
        params, {"nodes": nodes}, *trainer.consts)
    lr, gr = jax.value_and_grad(reference.loss)(
        params, x, *blocks, N, jnp.asarray(nodes), y)
    assert float(lp) == pytest.approx(float(lr), rel=1e-6)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)


def test_normalisation_counts_repeated_edges():
    s, d, v = reference.normalize(np.array([0, 0]), np.array([1, 1]), 2)
    # in-degrees with self loops: vertex 0 -> 1, vertex 1 -> 3
    assert list(s) == [0, 0, 0, 1] and list(d) == [1, 1, 0, 1]
    np.testing.assert_allclose(v, [3 ** -0.5, 3 ** -0.5, 1.0, 1 / 3],
                               rtol=1e-6)


def test_generators_are_deterministic():
    a, b = tiny_graph(), tiny_graph()
    assert all(np.array_equal(p, q) for p, q in zip(a, b))
    assert a[0].min() >= 0 and a[0].max() < N and a[1].max() < N
    big = 2 ** 33 + 7
    for seed in (7, big):
        np.testing.assert_array_equal(inputs.features(N, 4, seed, 0.1),
                                      inputs.features(N, 4, seed, 0.1))
        np.testing.assert_array_equal(inputs.labels(N, 3, seed),
                                      inputs.labels(N, 3, seed))
        np.testing.assert_array_equal(inputs.node_batch(N, 64, seed, 2),
                                      inputs.node_batch(N, 64, seed, 2))
    assert not np.array_equal(inputs.features(N, 4, 7, 0.1),
                              inputs.features(N, 4, big, 0.1))
    w7, wb = inputs.weights(DIMS, 7), inputs.weights(DIMS, big)
    assert not np.array_equal(w7[0]["w"], wb[0]["w"])
    nodes = inputs.node_batch(N, 256, big, 0)
    assert np.unique(nodes).size == 256
    assert not np.array_equal(nodes, inputs.node_batch(N, 256, big, 1))


def test_the_graph_does_not_follow_the_run_seed():
    from bench import harness
    from bench.jobs import train
    c = harness.cell("gcn-nell.train-segment")
    c["config"] = {"model": "gcn", "dims": DIMS, "num_vertices": N,
                   "num_edges": E, "rmat_abc": [0.57, 0.19, 0.19],
                   "graph_seed": 0, "feature_scale": 0.1}
    one, two = train.setup(c, 11), train.setup(c, 2 ** 32 + 11)
    assert np.array_equal(one["src"], two["src"])
    assert np.array_equal(one["dst"], two["dst"])
    assert not np.array_equal(one["p0"][0]["w"], two["p0"][0]["w"])


def test_trace_follows_its_mix():
    src, dst = tiny_graph()
    deg = np.bincount(src, minlength=N) + np.bincount(dst, minlength=N)
    order = traffic.degree_order(deg)
    mix = {"arrivals": "poisson", "rate": 400.0, "mean_size": 4,
           "targets": "zipf", "zipf_a": 1.1}
    trace = traffic.make_trace(mix, order, 5.0, 3)
    due = np.array([t for t, _ in trace])
    assert len(trace) == 2000
    assert np.all(np.diff(due) >= 0) and due[0] >= 0 and due[-1] < 5.0
    # a Poisson process given its count: uniform over the window, gaps
    # exponential with mean 1 / rate
    for k in range(5):
        assert abs(np.mean((due >= k) & (due < k + 1)) - 0.2) < 0.03
    assert np.mean(np.diff(due)) == pytest.approx(1 / 400.0, rel=0.05)
    sizes = np.array([ids.size for _, ids in trace])
    assert sizes.min() >= 1 and sizes.mean() == pytest.approx(4, rel=0.1)
    ids = np.concatenate([ids for _, ids in trace])
    top = np.bincount(ids, minlength=N).argmax()
    assert top == order[0]
    assert traffic.make_trace(mix, order, 5.0, 3)[7][1].tolist() == \
        trace[7][1].tolist()
    uni = traffic.make_trace(dict(mix, targets="uniform"), order, 5.0, 3)
    counts = np.bincount(np.concatenate([i for _, i in uni]), minlength=N)
    assert counts.max() < 5 * counts.mean()



def test_steady_poisson_trace_is_the_sorted_uniform_draw():
    """With no bursts the due times are the window's sorted uniform
    draws, the trace every measured run so far was driven by."""
    order = np.arange(N, dtype=np.int32)
    mix = {"rate": 50.0, "mean_size": 4, "zipf_a": 1.1}
    due = [t for t, _ in traffic.make_trace(mix, order, 20.0, 2 ** 31 + 5)]
    rng = np.random.default_rng((2 ** 31 + 5, 0x7AFF, 1))
    assert due == np.sort(rng.uniform(0.0, 20.0, 1000)).tolist()


def test_burst_puts_its_share_of_arrivals_in_its_span():
    order = np.arange(N, dtype=np.int32)
    mix = {"arrivals": "poisson", "rate": 200.0, "mean_size": 2,
           "targets": "uniform",
           "bursts": [{"start": 0.4, "length": 0.2, "factor": 4.0}]}
    due = np.array([t for t, _ in traffic.make_trace(mix, order, 10.0, 9)])
    # 0.8 of the window at the base rate, 0.2 at four times it
    assert due.size == round(200.0 * 10.0 * 1.6)
    assert np.all(np.diff(due) >= 0) and 0 <= due[0] and due[-1] < 10.0
    in_burst = np.mean((due >= 4.0) & (due < 6.0))
    assert in_burst == pytest.approx(0.5, abs=0.03)
    assert np.mean(due < 4.0) == pytest.approx(0.25, abs=0.03)


@pytest.mark.parametrize("mix", [
    {"arrivals": "no_such_process"},
    {"targets": "no_such_targets"},
])
def test_a_mix_names_files_that_exist(mix):
    order = np.arange(N, dtype=np.int32)
    base = {"rate": 10.0, "mean_size": 2, "zipf_a": 1.1}
    with pytest.raises(FileNotFoundError):
        traffic.make_trace(dict(base, **mix), order, 1.0, 1)


def test_a_burst_must_lie_in_the_window():
    from bench.gen.arrivals import poisson
    with pytest.raises(ValueError):
        poisson.profile([{"start": 0.9, "length": 0.2, "factor": 2.0}])
    with pytest.raises(ValueError):
        poisson.profile([{"start": 0.1, "length": 0.2, "factor": 0.0}])
