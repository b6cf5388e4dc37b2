"""The program's own tracing (`repro/trace.py`): the EnGN stage scopes
reach every op of a layer's forward and backward on each device
backend, and a traced `ServingPipeline` records one span per serving
stage on the thread that runs it, with the ticket number that joins
the stages of one batch, beside the counters they feed."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import trace
from repro.core.engn import prepare_graph
from repro.core.models import init_stack, make_gnn
from repro.graphs.generate import random_features, rmat_graph
from repro.serving.engine import GNNServingEngine, ServingConfig
from repro.serving.pipeline import ServingPipeline

STAGES = {trace.EXTRACT, trace.AGGREGATE, trace.UPDATE}


def _layer(backend):
    g = rmat_graph(200, 1200, seed=0).gcn_normalized()
    layer = make_gnn("gcn", 24, 8, backend=backend, tile=32)
    plan = prepare_graph(g, layer.cfg)
    params = init_stack([layer], jax.random.key(0))[0]
    x = jnp.asarray(random_features(200, 24, seed=1))
    return layer, plan, params, x


# the fused kernel does the extraction inside its aggregate sweep, so it
# carries the aggregate's scope alone
@pytest.mark.parametrize("backend,stages", [
    ("segment", STAGES), ("blocked", STAGES),
    ("fused", {trace.AGGREGATE, trace.UPDATE})])
def test_stage_scopes_in_forward_and_backward(backend, stages):
    layer, plan, params, x = _layer(backend)

    def fwd(p, x):
        return layer.apply(p, plan, x)

    def loss(p, x):
        return jnp.sum(fwd(p, x) ** 2)

    for fn, wrap in ((fwd, "{}"), (jax.grad(loss), "transpose(jvp({}))")):
        text = jax.jit(fn).lower(params, x).as_text(debug_info=True)
        for stage in STAGES:
            assert (wrap.format(stage) in text) == (stage in stages), (
                backend, stage)


def test_optimizer_scope_in_the_train_step():
    from repro.training.optimizer import init_opt_state
    from repro.training.train_lib import make_gnn_train_step
    layer, plan, params, x = _layer("segment")

    def loss(p, batch):
        return jnp.mean(layer.apply(p, plan, x)[batch] ** 2)
    step = make_gnn_train_step(loss)
    text = step.lower(params, init_opt_state(params),
                      jnp.arange(8)).as_text(debug_info=True)
    assert trace.OPTIMIZER in text and trace.AGGREGATE in text


def _spans(log_dir):
    """Every `engn.` host span of the trace: (name, batch, thread line)."""
    from jax.profiler import ProfileData
    path = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("engn."):
                    out.append((ev.name, dict(ev.stats).get("batch"), i))
    return out


def test_serving_spans_and_counters(tmp_path):
    g = rmat_graph(300, 2400, seed=0).gcn_normalized()
    x = random_features(300, 8, seed=1)
    layers = [make_gnn("gcn", 8, 16), make_gnn("gcn", 16, 4)]
    params = init_stack(layers, jax.random.key(0))
    engine = GNNServingEngine(g, x, layers, params,
                              ServingConfig(batch_size=16,
                                            cache_capacity=256))
    pl = ServingPipeline(engine, extract_workers=1)
    rng = np.random.default_rng(3)
    ids = [rng.integers(0, 300, 4).astype(np.int32) for _ in range(12)]
    jax.profiler.start_trace(str(tmp_path))
    try:
        for rounds in range(2):          # the second round hits the cache
            for rid, v in enumerate(ids):
                pl.submit(rounds * 100 + rid, v)
                pl.drain()
    finally:
        jax.profiler.stop_trace()
        pl.close()
    spans = _spans(tmp_path)
    assert {name for name, _, _ in spans} == set(trace.SERVE_SPANS)
    assert all(batch is not None and batch >= 0 for _, batch, _ in spans)
    # the worker's walk and the completion thread's stages of one batch
    # share its ticket number, on different threads
    worker = {b: line for name, b, line in spans
              if name == trace.SERVE_EXTRACT}
    home = {b: line for name, b, line in spans
            if name == trace.SERVE_INFER}
    assert worker and set(worker) == set(home)
    assert all(worker[b] != home[b] for b in worker)
    tel = pl.telemetry()
    eng, pipe = tel["engine"], tel["pipeline"]
    assert 1 <= pipe["hit_batches"] <= pipe["pumped_batches"]
    assert pipe["hit_batch_wait_s"] >= 0.0
    assert eng["device_batches"] == len(home)
    assert eng["padded_vertices"] >= eng["subgraph_vertices"] > 0
    assert "subgraph_edges" not in eng
