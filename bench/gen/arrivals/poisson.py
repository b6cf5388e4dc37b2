"""Poisson arrivals given their count, under a piecewise-constant rate.

    rate     requests per second at the base rate;
    bursts   optional list of {"start": s, "length": l, "factor": f},
             each a share of the window: over [s, s + l) the rate is f
             times what it is elsewhere (a flash crowd is one burst;
             bursts that overlap multiply).

The count over a window is fixed at round(the rate's integral over it),
and the due times are that many independent draws from the rate's
density, sorted (a Poisson process given its count), so every seed
offers the same load.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def profile(bursts: List[Dict]) -> Tuple[np.ndarray, np.ndarray]:
    """(edges, factors): the window's shares [edges[i], edges[i + 1])
    and the rate's factor over each."""
    cuts = {0.0, 1.0}
    for b in bursts:
        start, end, factor = (float(b["start"]),
                              float(b["start"]) + float(b["length"]),
                              float(b["factor"]))
        if not (0.0 <= start < end <= 1.0 and factor > 0.0):
            raise ValueError(f"burst {b!r} is not a share of the window "
                             "with a positive factor")
        cuts |= {start, end}
    edges = np.array(sorted(cuts))
    mids = (edges[:-1] + edges[1:]) / 2
    factors = np.ones(mids.size)
    for b in bursts:
        inside = ((mids >= float(b["start"]))
                  & (mids < float(b["start"]) + float(b["length"])))
        factors[inside] *= float(b["factor"])
    return edges, factors


def due_times(rng, mix: Dict, seconds: float) -> np.ndarray:
    bursts = mix.get("bursts", [])
    edges, factors = profile(bursts)
    mass = float(np.sum(factors * np.diff(edges)))
    count = int(round(float(mix["rate"]) * seconds * mass))
    u = rng.uniform(0.0, seconds, count)
    if bursts:
        cdf = np.concatenate([[0.0], np.cumsum(factors * np.diff(edges))])
        u = np.interp(u / seconds, cdf / mass, edges) * seconds
    return np.sort(u)
