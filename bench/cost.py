"""Operations and bytes the benchmark's work requires, and the peaks of
the chips it runs on.

Counts are of the algorithm, from shapes alone: padding, recomputation
and layout work the program may add do not count.  A multiply-add is two
operations.  The GCN layer order follows the paper's DASR rule (S5.2):
extraction first (XW, then aggregate H wide) when H <= F, aggregation
first (AX, then extract) otherwise; either way the layer computes the
same function, and the cheaper order is the work it requires.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Sequence

import numpy as np

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table row of `device_kind`; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def extraction_first(f_in: int, f_out: int) -> bool:
    return f_out <= f_in


def gcn_train_step_flops(n: int, e: int, dims: Sequence[int]) -> float:
    """One full-graph training step of a GCN stack over n vertices and e
    edges (self loops included): the forward's matmuls and aggregates,
    and the backward's weight gradients and the activation gradients
    they need.  The input features get no gradient."""
    total = 0.0
    for i, (f_in, f_out) in enumerate(zip(dims[:-1], dims[1:])):
        mm = 2.0 * n * f_in * f_out
        if extraction_first(f_in, f_out):
            agg = 2.0 * e * f_out
            # forward XW, A(XW); backward A^T dH (needed for dW), dW,
            # and dX = d(XW) W^T below the first layer
            total += mm + agg + agg + mm + (mm if i else 0.0)
        else:
            agg = 2.0 * e * f_in
            # forward AX, (AX)W; backward dW, and below the first layer
            # d(AX) = dH W^T and dX = A^T d(AX)
            total += agg + mm + mm + ((mm + agg) if i else 0.0)
    return total


def aggregate_widths(dims: Sequence[int]) -> list:
    """The feature width of each layer's forward aggregate."""
    return [f_out if extraction_first(f_in, f_out) else f_in
            for f_in, f_out in zip(dims[:-1], dims[1:])]


def merged_entries(src: np.ndarray, dst: np.ndarray, n: int) -> int:
    """Distinct (dst, src) pairs: the entries a sparse aggregate must
    touch once repeated edges are merged."""
    key = np.asarray(dst, np.int64) * int(n) + np.asarray(src, np.int64)
    return int(np.unique(key).size)


def aggregate_cost(entries: int, n: int, width: int) -> Dict[str, float]:
    """One sum-aggregate y = A x of width `width` over n vertices: a
    multiply-add per entry and feature, and at least each entry (row,
    column, weight: 12 bytes), each source row read once and each
    output row written once."""
    return {"flops": 2.0 * entries * width,
            "bytes": 12.0 * entries + 2.0 * 4.0 * n * width}


def min_time_s(flops: float, nbytes: float, peak: Dict[str, float]
               ) -> float:
    """The roofline: the larger of the compute and the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
