"""bench/trace_stages.py: the program's spans and stage scopes read from
a trace.  On the recorded chip trace of bench/trace_reduce.py it gives
what that module gives.  On two traces it recorded itself on one TPU
v5e (`load()` of a 20-s window, cut to three steps of blocked GCN
training on NELL and to six seconds of zipf serving) it sorts the
device time by stage and names idle gaps by the program's spans.  On
hand-made events it counts a span's time once, names idle gaps by the
window's thread and the others, and sorts device time by stage; on a
tiny serving run on the CPU it reads the program's spans and counters
into the stage metrics."""
import json
from pathlib import Path

import pytest

from bench import harness, trace_reduce, trace_stages

DIR = Path(__file__).resolve().parent / "data"
DATA = DIR / "nell-blocked-trace.json"
BLOCKED = DIR / "nell-blocked-stages.json"
SERVE = DIR / "nell-serve-stages.json"


def _three_fields(events):
    return {"device": {k: [e[:3] for e in v]
                       for k, v in events["device"].items()},
            "modules": events["modules"],
            "host": [e[:3] for e in events["host"]]}


def _read(name, rec):
    return harness.module("metrics", name).read(rec)
MS = 1_000_000
OLD_KEYS = ("window_s", "busy_s", "devices", "ops", "device_ops",
            "idle_gaps", "spans", "modules")


@pytest.mark.parametrize("path", [DATA, BLOCKED])
def test_the_old_reduction_is_unchanged(path):
    events = json.loads(path.read_text())
    old = trace_reduce.reduce(_three_fields(events))
    new = trace_stages.reduce(events)
    for key in OLD_KEYS:
        assert new[key] == old[key], key


def test_the_old_three_field_trace_has_no_scope():
    events = json.loads(DATA.read_text())
    old = trace_reduce.reduce(events)
    new = trace_stages.reduce(events)
    # three-field ops carry no scope
    assert set(new["scope_s"]) == {trace_stages.UNSCOPED}
    assert new["scope_s"]["unscoped"] == pytest.approx(sum(
        old["ops"].values()))
    assert new["span_self_s"]["bench.train.step"] > 0


def test_blocked_training_by_stage_on_the_chip():
    events = json.loads(BLOCKED.read_text())
    red = trace_stages.reduce(events)
    steps = red["modules"]["jit_train_step"]
    assert steps == 3
    scope = red["scope_s"]
    assert set(scope) == {"engn.extract", "engn.aggregate", "engn.update",
                          "engn.optimizer", "unscoped"}
    # the program's scopes cover at least 90% of the device's time
    assert scope["unscoped"] < 0.1 * sum(scope.values())
    # every rer_gather launch, and the backward's scatter-adds, forward
    # and transposed alike, are the aggregate's
    ops = events["device"]["/device:TPU:0"]
    kernels = [o for o in ops if o[0] == "kernel rer_gather_packed_spmm"]
    assert len(kernels) == 18 * steps
    scatters = [o for o in ops if o[0].startswith(("fusion.41 ",
                                                   "fusion.59 "))]
    assert scatters and all("transpose(jvp(engn.aggregate))" in o[3]
                            for o in scatters)
    for o in kernels + scatters:
        assert trace_stages.stage_of(o[3]) == "engn.aggregate"
    rec = {"job": "train", "trace": red}
    aggregate = _read("aggregate_ms_per_step.train", rec)
    extract = _read("extract_ms_per_step.train", rec)
    assert 100 < aggregate < 134 and 0 < extract < 10
    assert aggregate + extract <= 1e3 * red["window_s"] / steps


def test_serving_by_span_on_the_chip():
    events = json.loads(SERVE.read_text())
    red = trace_stages.reduce(events)
    self_s = red["span_self_s"]
    assert {"engn.serve.probe", "engn.serve.extract", "engn.serve.gather",
            "engn.serve.pad", "engn.serve.infer",
            "engn.serve.finish"} <= set(self_s)
    window = next(s for s in events["host"] if s[0] == "bench.window")
    home = [s for s in events["host"] if s[3] == window[3]]
    workers = {s[3] for s in events["host"] if s[0] == "engn.serve.gather"}
    assert workers and window[3] not in workers
    # a thread's self times add up to no more than the window: a
    # child's time is counted once
    w0, w1 = window[1], window[1] + window[2]
    assert sum(trace_stages.span_self_seconds(home, w0, w1).values()) \
        <= red["window_s"]
    poll = sum(min(s[1] + s[2], w1) - max(s[1], w0) for s in home
               if s[0] == "bench.serve.poll" and s[1] + s[2] > w0) * 1e-9
    assert self_s["bench.serve.poll"] < 0.1 * poll
    # no long idle gap is left to the benchmark's spans alone: each names
    # the program's stage, on the main thread or on the workers
    for name, seconds in red["idle_gaps"]:
        assert "engn.serve." in name, name
    assert any(" + engn.serve.gather" in n for n, _ in red["idle_gaps"])
    rec = {"job": "serve", "trace": red}
    assert _read("stage_ms_per_batch.serve", rec) > 0
    assert _read("infer_ms_per_batch.serve", rec) > 0
    assert _read("extract_ms_per_batch.serve", rec) > 0


def _events():
    """A window [0, 20] ms on thread "main", with a worker thread and two
    device ops in each of two stages."""
    return {
        "device": {"/device:TPU:0": [
            ["kernel k", 1 * MS, 2 * MS,
             "jit(step)/jvp(engn.aggregate)/jit(k)/pallas_call"],
            ["fusion.1 f32[4]", 3 * MS, 1 * MS,
             "jit(step)/transpose(jvp(engn.aggregate))/scatter-add"],
            ["fusion.2 f32[4]", 12 * MS, 1 * MS,
             "jit(step)/jvp(engn.extract)/dot_general"],
            ["fusion.3 f32[4]", 14 * MS, 1 * MS, "jit(step)/add"]]},
        "modules": {"/device:TPU:0": [["jit_train_step(1)", 1 * MS, 14 * MS]]},
        "host": [["bench.window", 0, 20 * MS, "python/0"],
                 ["bench.serve.poll", 4 * MS, 8 * MS, "python/0"],
                 ["engn.serve.infer", 5 * MS, 6 * MS, "python/0"],
                 ["engn.serve.pad", 5 * MS, 2 * MS, "python/0"],
                 ["engn.serve.extract", 6 * MS, 10 * MS, "python/1"],
                 ["engn.serve.extract", 16 * MS, 6 * MS, "python/1"]],
    }


def test_self_time_counts_each_span_once():
    red = trace_stages.reduce(_events())
    self_s = red["span_self_s"]
    # poll 8 ms holds infer 6 ms, which holds pad 2 ms
    assert self_s["bench.serve.poll"] == pytest.approx(0.002)
    assert self_s["engn.serve.infer"] == pytest.approx(0.004)
    assert self_s["engn.serve.pad"] == pytest.approx(0.002)
    # the worker's spans are not the main thread's children; the last
    # is cut at the window's end
    assert self_s["engn.serve.extract"] == pytest.approx(0.014)
    assert red["spans"]["engn.serve.extract"] == 2
    assert "bench.window" not in self_s


def test_scope_seconds_by_stage():
    red = trace_stages.reduce(_events())
    assert red["scope_s"] == {"engn.aggregate": pytest.approx(0.003),
                              "engn.extract": pytest.approx(0.001),
                              "unscoped": pytest.approx(0.001)}
    assert trace_stages.stage_of(
        "jit(f)/transpose(jvp(engn.aggregate))/jvp(engn.update)/x") == \
        "engn.update"
    assert trace_stages.stage_of("jit(f)/mul") == "unscoped"


def test_idle_gaps_named_by_window_thread_and_the_others():
    red = trace_stages.reduce(_events())
    assert red["idle_gaps"] == [
        # 4..12 ms: the main thread in infer (pad closed at 7), the
        # worker walking
        ["engn.serve.infer + engn.serve.extract", pytest.approx(0.008)],
        # 15..20 ms: the main thread after poll, the worker walking again
        ["after bench.serve.poll + engn.serve.extract",
         pytest.approx(0.005)],
        ["none", pytest.approx(0.001)],                     # 0..1 ms
        ["after bench.serve.poll + engn.serve.extract",     # 13..14 ms
         pytest.approx(0.001)]]


def test_stage_metrics_on_hand_made_records():
    red = trace_stages.reduce(_events())
    serve = {"job": "serve", "trace": red,
             "engine": {"subgraph_vertices": 300, "padded_vertices": 1024},
             "pipeline": {"hit_batches": 4, "hit_batch_wait_s": 2.0},
             "latency": {"count": 10, "mean_queue_delay_s": 0.25}}

    read = _read
    assert read("extract_ms_per_batch.serve", serve) == pytest.approx(7.0)
    assert read("stage_ms_per_batch.serve", serve) == pytest.approx(2.0)
    assert read("infer_ms_per_batch.serve", serve) == pytest.approx(4.0)
    assert read("bucket_fill.serve", serve) == pytest.approx(29.296875)
    assert read("hit_batch_wait_ms.serve", serve) == pytest.approx(500.0)
    assert read("queue_wait_ms.serve", serve) == pytest.approx(250.0)
    train = {"job": "train", "trace": red}
    assert read("aggregate_ms_per_step.train", train) == pytest.approx(3.0)
    assert read("extract_ms_per_step.train", train) == pytest.approx(1.0)
    # nothing to read in the harness's own reduction, which keeps neither
    # the program's spans nor its scopes, nor in an empty record
    ev = _events()
    old = trace_reduce.reduce({
        "device": {k: [e[:3] for e in v] for k, v in ev["device"].items()},
        "modules": ev["modules"], "host": [e[:3] for e in ev["host"]]})
    for name in trace_stages.STAGE_METRICS:
        if not name.startswith(("bucket", "hit", "queue")):
            assert read(name, dict(serve, trace=old)) is None
            assert read(name, dict(train, trace=old)) is None
        assert read(name, {}) is None


def test_a_tiny_serving_window_on_the_cpu():
    import jax
    from bench.tests.test_bench_faults import SEED, tiny_cell
    out = trace_stages.traced_window(tiny_cell("gcn-nell.serve-zipf"), SEED,
                                     0.4, jax.devices()[:1])
    spans = out["reduced"]["spans"]
    assert spans["engn.serve.infer"] == out["rec"]["engine"][
        "device_batches"]
    assert set(out["metrics"]) >= {
        "queue_wait_ms.serve", "extract_ms_per_batch.serve",
        "stage_ms_per_batch.serve", "infer_ms_per_batch.serve",
        "bucket_fill.serve"}
    assert 0 < out["metrics"]["bucket_fill.serve"] <= 100


def test_op_names_from_the_hlo_protos(tmp_path):
    """The op_name of each instruction, decoded from the HLO proto the
    profiler keeps per module in its metadata plane."""
    import glob

    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x, w):
        with jax.named_scope("engn.extract"):
            return jnp.sum((x @ w) ** 2)
    x, w = jnp.ones((64, 32)), jnp.ones((32, 16))
    step(x, w).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    step(x, w).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    names = trace_stages.op_names(path)
    [module] = [k for k in names if k.startswith("jit_step(")]
    stages = {trace_stages.stage_of(v) for v in names[module].values()}
    assert "engn.extract" in stages
    assert any(v.startswith("jit(step)/") for v in names[module].values())
