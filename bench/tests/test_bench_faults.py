"""A whole run of each job at a tiny size on the CPU, past the harness's
look for a chip: sound, it comes out correct; with the timed path broken
underneath, or with the control in the program's place, it does not."""
import numpy as np
import pytest

from bench import harness, run
from bench.jobs import serve, train

TINY = {"name": "tiny", "model": "gcn", "dims": [24, 16, 6],
        "num_vertices": 600, "num_edges": 4800,
        "rmat_abc": [0.57, 0.19, 0.19], "graph_seed": 0,
        "feature_scale": 0.1}
SEED = 3_000_000_019


@pytest.fixture(autouse=True)
def default_precision():
    """A serving job states HIGHEST for the process; put it back."""
    yield
    import jax
    jax.config.update("jax_default_matmul_precision", None)


def tiny_cell(name):
    c = harness.cell(name)
    c["config"] = dict(TINY)
    if c["workload"]["job"] == "serve":
        # more vertices than the result cache holds, so the window
        # misses it too
        c["config"].update(num_vertices=3000, num_edges=24000)
        c["workload"]["warm_seconds"] = 0.3
    return c


def run_tiny(name, trace=False):
    import jax
    import time
    return run.result(name, SEED, 0.4, trace, devices=jax.devices()[:1],
                      t_start=time.perf_counter(), c=tiny_cell(name))


@pytest.mark.parametrize("name", ["gcn-nell.train-blocked",
                                  "gcn-nell.serve-zipf"])
def test_sound_run_is_correct(name):
    out = run_tiny(name, trace=True)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-2:] == ["checks", "_info"]
    assert out["_info"]["compiles_in_window"] == 0


def test_step_that_keeps_its_state(monkeypatch):
    from repro.launch.elastic_gnn import ElasticGNNTrainer
    step = ElasticGNNTrainer.step

    def unchanged(self, params, opt, batch):
        _, _, m = step(self, params, opt, batch)
        return params, opt, m
    monkeypatch.setattr(ElasticGNNTrainer, "step", unchanged)
    out = run_tiny("gcn-nell.train-segment")
    assert not out["correct"]
    assert out["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_step_over_half_the_batch(monkeypatch):
    from repro.launch.elastic_gnn import ElasticGNNTrainer
    step = ElasticGNNTrainer.step

    def half(self, params, opt, batch):
        nodes = batch["nodes"]
        return step(self, params, opt,
                    {"nodes": nodes[: nodes.size // 2]})
    monkeypatch.setattr(ElasticGNNTrainer, "step", half)
    out = run_tiny("gcn-nell.train-blocked")
    assert not out["correct"], out["checks"]


def test_answer_altered_where_it_is_made(monkeypatch):
    from repro.serving.engine import GNNServingEngine
    infer = GNNServingEngine._infer_batch

    def altered(self, sub, xs):
        rows = np.array(infer(self, sub, xs))
        rows[0] = rows[0] + 1.0
        return rows
    monkeypatch.setattr(GNNServingEngine, "_infer_batch", altered)
    out = run_tiny("gcn-nell.serve-zipf")
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("name", ["gcn-nell.train-blocked",
                                  "gcn-nell.train-segment"])
def test_training_control_fails(name):
    c = tiny_cell(name)
    ctx = train.setup(c, SEED)
    train.release(ctx)
    numbers = train.compare(ctx, train.reference_run(ctx, control=True),
                            train.reference_run(ctx))
    limits = c["workload"]["limits"]
    assert any(v > limits[k] for k, v in numbers.items()), numbers


def test_serving_control_fails():
    c = tiny_cell("gcn-nell.serve-zipf")
    ctx = serve.setup(c, SEED)
    serve.window(ctx, 0.4)
    serve.release(ctx)
    want = serve.reference_logits(ctx)
    ctl = serve.reference_logits(ctx, control=True)
    rows = [(ids, ctl[ids]) for ids, _ in ctx["answered"]]
    assert rows
    assert serve.row_gap(rows, want) > c["workload"]["limits"]["row_gap"]
    assert np.isfinite(want).all()
