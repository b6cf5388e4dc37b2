"""bench/cost.py and bench/peaks.json against hand counts at tiny
shapes."""
import numpy as np
import pytest

from bench import cost


def test_two_layer_gcn_step_by_hand():
    # layer 1, 8 -> 4, extraction first: XW 640, A(XW) 240, backward
    # A^T dH 240 and dW 640, no dX for the input layer -> 1760
    # layer 2, 4 -> 6, aggregation first: AX 240, (AX)W 480, backward
    # dW 480, d(AX) 480 and A^T d(AX) 240 -> 1920
    assert cost.gcn_train_step_flops(10, 30, [8, 4, 6]) == 1760 + 1920
    assert cost.aggregate_widths([8, 4, 6]) == [4, 4]


def test_nell_step():
    flops = cost.gcn_train_step_flops(65755, 317305, [5415, 64, 210])
    assert flops == pytest.approx(96.6e9, rel=2e-3)


def test_three_tile_packed_plan_by_hand():
    from repro.graphs.format import COOGraph
    from repro.graphs.partition import build_tile_store, pack_tile_store
    # tile 2 over 4 vertices: entries fall in tiles (0,0), (0,1), (1,1);
    # the edge 1->0 comes twice and merges into one entry
    src = np.array([0, 1, 1, 2, 3, 3, 2], np.int32)
    dst = np.array([0, 0, 0, 1, 2, 3, 3], np.int32)
    g = COOGraph(4, src, dst, np.ones(7, np.float32))
    packed = pack_tile_store(build_tile_store(g, 2))
    assert packed.nnzb == 3
    assert cost.merged_entries(src, dst, 4) == 6 == int(
        packed.tile_nnz().sum())
    c = cost.aggregate_cost(6, 4, 64)
    assert c["flops"] == 2 * 6 * 64
    assert c["bytes"] == 12 * 6 + 2 * 4 * 4 * 64


def test_roofline_takes_the_larger_bound():
    peak = cost.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    assert peak["hbm_bytes"] == 16 * 2 ** 30
    assert cost.min_time_s(197e12, 1.0, peak) == pytest.approx(1.0)
    assert cost.min_time_s(1.0, 819e9, peak) == pytest.approx(1.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        cost.peaks("TPU v9 imaginary")
