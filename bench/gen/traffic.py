"""Timed request traces for the open-loop serving load generator.

A trace is a list of (due time, vertex ids).  A traffic mix is a data
file of parameters; this one generator reads every mix, and finds the
parts a mix names by their names:

    arrivals   `bench/gen/arrivals/<arrivals>.py`, whose
               `due_times(rng, mix, seconds)` gives the sorted due times
               in [0, seconds) (default "poisson");
    sizes      vertices per request, geometric with mean `mean_size`;
    targets    `bench/gen/targets/<targets>.py`, whose
               `draw(rng, mix, order, k)` gives a request's `k` vertex
               ids; `order` lists the vertices by decreasing degree
               (default "zipf").

A mix with another arrival process or target distribution is a new file
in one of those directories and a workload file that names it.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from bench import harness

_ARRIVALS, _SIZES, _TARGETS = 1, 2, 3


def degree_order(degrees: np.ndarray) -> np.ndarray:
    """Vertex ids by decreasing degree, ties by id."""
    return np.argsort(-np.asarray(degrees), kind="stable").astype(np.int32)


def make_trace(mix: Dict, order: np.ndarray, seconds: float, seed: int
               ) -> List[Tuple[float, np.ndarray]]:
    """The requests due in [0, seconds) under `mix`, sorted by due time."""
    arrivals = harness.module("gen/arrivals", mix.get("arrivals", "poisson"))
    targets = harness.module("gen/targets", mix.get("targets", "zipf"))
    rng = [np.random.default_rng((int(seed), 0x7AFF, s))
           for s in (_ARRIVALS, _SIZES, _TARGETS)]
    due = arrivals.due_times(rng[0], mix, float(seconds))
    sizes = rng[1].geometric(1.0 / float(mix["mean_size"]), due.size)
    return [(t, np.asarray(targets.draw(rng[2], mix, order, k), np.int32))
            for t, k in zip(due.tolist(), sizes.tolist())]
