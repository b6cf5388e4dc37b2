"""The `gcn-synthA.train-segment` cell run whole at the tiny size on the
CPU, with the program's edge-chunk cap made small so that the tiny graph
is walked in chunks, as Synthetic-A is on the chip: sound, it comes out
correct; with the step broken underneath, or with the control in the
program's place, it does not."""
import pytest

from bench.jobs import train
from bench.tests.test_bench_faults import SEED, run_tiny, tiny_cell

CELL = "gcn-synthA.train-segment"


@pytest.fixture(autouse=True)
def small_chunks(monkeypatch):
    """1,024 edges a chunk: the tiny graph's 5,400 edges (self loops
    included) take 6 chunks, the last part-filled.  Records each plan's
    meta block."""
    from repro.core import engn
    monkeypatch.setattr(engn, "EDGE_CHUNK_BYTES",
                        1024 * engn.gathered_row_bytes(1))
    metas = []
    prepare = engn.prepare_graph

    def recording(*args, **kw):
        plan = prepare(*args, **kw)
        metas.append(plan.meta)
        return plan
    monkeypatch.setattr(engn, "prepare_graph", recording)
    return metas


def test_sound_run_is_correct(small_chunks):
    out = run_tiny(CELL, trace=True)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["_info"]["compiles_in_window"] == 0
    assert out["_info"]["backend"] == "segment"
    assert small_chunks == [{"edge_chunk": 1024, "chunks": 6,
                             "device_bytes": 6 * 1024 * 12 + 1024 * 512}]


def test_step_that_keeps_its_state(monkeypatch):
    from repro.launch.elastic_gnn import ElasticGNNTrainer
    step = ElasticGNNTrainer.step

    def unchanged(self, params, opt, batch):
        _, _, m = step(self, params, opt, batch)
        return params, opt, m
    monkeypatch.setattr(ElasticGNNTrainer, "step", unchanged)
    out = run_tiny(CELL)
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
    out = run_tiny(CELL)
    assert not out["correct"], out["checks"]


def test_training_control_fails():
    c = tiny_cell(CELL)
    ctx = train.setup(c, SEED)
    train.release(ctx)
    numbers = train.compare(ctx, train.reference_run(ctx, control=True),
                            train.reference_run(ctx))
    limits = c["workload"]["limits"]
    assert any(v > limits[k] for k, v in numbers.items()), numbers
