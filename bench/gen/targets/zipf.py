"""Zipf targets: ranks drawn from Zipf(`zipf_a`) truncated at N (a draw
past the last rank is drawn again), mapped to vertices in decreasing
order of degree, so the hubs are the hottest targets.

The program's own `zipf_traffic` clamps a draw past the last rank to it
instead; at a = 1.1 and NELL's 65,755 vertices that puts about 31% of
all targets on the one vertex of lowest degree.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def zipf_ranks(rng, a: float, size: int, n: int) -> np.ndarray:
    """`size` ranks in [0, n) from Zipf(a) truncated at n."""
    ranks = rng.zipf(a, size) - 1
    while True:
        out = ranks >= n
        if not out.any():
            return ranks
        ranks[out] = rng.zipf(a, int(out.sum())) - 1


def draw(rng, mix: Dict, order: np.ndarray, k: int) -> np.ndarray:
    return order[zipf_ranks(rng, float(mix["zipf_a"]), k, int(order.size))]
