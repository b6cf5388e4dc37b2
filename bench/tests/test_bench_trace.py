"""bench/trace_reduce.py on a recorded chip trace (blocked GCN training
on NELL, one TPU v5e, four steps of the window) and on hand-made
events."""
import json
from pathlib import Path

import pytest

from bench import harness, trace_reduce

DATA = Path(__file__).resolve().parent / "data" / "nell-blocked-trace.json"


@pytest.fixture(scope="module")
def chip():
    events = json.loads(DATA.read_text())
    return events, trace_reduce.reduce(events)


def test_busy_and_idle_over_the_window(chip):
    events, red = chip
    w0, w1 = trace_reduce.window_of(events)
    assert red["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    assert red["devices"] == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    # the device ran back to back: under 1% of the window idle
    assert 1 - red["busy_s"] / red["window_s"] < 0.01
    gaps = sum(s for _, s in red["idle_gaps"])
    assert gaps <= red["window_s"] - red["busy_s"] + 1e-9


def test_kernel_time_by_name(chip):
    _, red = chip
    kernel = trace_reduce.op_seconds(red, r"^kernel .*packed_spmm")
    assert red["device_ops"][0][0] == "kernel jvp_jit__packed_spmm_pallas__"
    assert kernel == pytest.approx(red["device_ops"][0][1])
    # four train steps, each two rer_gather aggregates of 77 ms or so
    assert red["modules"] == {"jit_train_step": 4}
    assert 0.25 < kernel < 0.35
    assert kernel < red["busy_s"]
    assert trace_reduce.op_seconds(red, r"^fusion\.227 ") > 0


def test_breakdown_ordering(chip):
    _, red = chip
    ops = [s for _, s in red["device_ops"]]
    gaps = [s for _, s in red["idle_gaps"]]
    assert ops == sorted(ops, reverse=True) and len(ops) == 10
    assert gaps == sorted(gaps, reverse=True) and len(gaps) <= 10
    assert all(isinstance(n, str) for n, _ in red["idle_gaps"])
    assert red["spans"]["bench.train.step"] >= 4


def test_roofline_reader_on_the_recorded_trace(chip):
    _, red = chip
    from bench import cost
    rec = {"job": "train", "trace": red, "peak": cost.peaks("TPU v5 lite"),
           "cost": {"entries": 300000, "n": 65755,
                    "aggregate_widths": [64, 64]}}
    share = harness.module("metrics", "agg_kernel_roofline.train").read(rec)
    assert 0 < share < 1.0
    idle = harness.module("metrics", "device_idle.train").read(rec)
    assert 0 <= idle < 1.0


def test_hand_made_events():
    ms = 1_000_000
    events = {
        "device": {"/device:TPU:0": [["kernel k", 1 * ms, 2 * ms],
                                     ["fusion.1 f32[4]", 2 * ms, 2 * ms],
                                     ["fusion.2 f32[4]", 7 * ms, 1 * ms]]},
        "modules": {"/device:TPU:0": [["jit_step(1)", 1 * ms, 7 * ms]]},
        "host": [["bench.window", 0, 10 * ms],
                 ["bench.a", 4 * ms, 3 * ms],
                 ["bench.b", 8 * ms, 1 * ms]],
    }
    red = trace_reduce.reduce(events)
    assert red["window_s"] == pytest.approx(0.010)
    assert red["busy_s"] == pytest.approx(0.004)       # [1,4] and [7,8]
    assert red["idle_gaps"] == [["bench.a", pytest.approx(0.003)],
                                ["bench.b", pytest.approx(0.002)],
                                ["none", pytest.approx(0.001)]]
    assert red["modules"] == {"jit_step": 1}
    assert trace_reduce.op_seconds(red, "^kernel ") == pytest.approx(0.002)
    assert trace_reduce.label(
        '%fusion.9 = f32[8,128]{1,0} fusion(f32[8] %p), kind=kLoop') == \
        "fusion.9 f32[8,128]"
    assert trace_reduce.label(
        '%jvp_jit__packed_spmm_pallas__.45 = f32[64,64]{1,0} custom-call('
        'f32[1] %a), custom_call_target="tpu_custom_call"') == \
        "kernel jvp_jit__packed_spmm_pallas__"
