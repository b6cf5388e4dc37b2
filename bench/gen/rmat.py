"""R-MAT edge lists (Chakrabarti, Zhan and Faloutsos, SDM 2004), drawn on
the device.

Each of ceil(log2 N) levels draws one uniform number per edge and picks a
quadrant with the weights (a, b, c, 1 - a - b - c): quadrants c and d
move the source to the lower half, b and d move the destination to the
right half.  Ids are then reduced modulo N.  The defaults are the
quadrant weights of the program's own generator (0.57, 0.19, 0.19).

The draw uses `jax.random` from the configuration's `graph_seed`, whose
bits are the same on every platform, so every run of a configuration
sees the same graph, and the run's `--seed` never changes it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _rmat(key, num_vertices: int, num_edges: int, a: float, b: float,
          c: float):
    levels = max(1, (num_vertices - 1).bit_length())
    ab, abc = a + b, a + b + c

    def level(i, sd):
        s, d = sd
        r = jax.random.uniform(jax.random.fold_in(key, i), (num_edges,))
        quad = ((r >= a).astype(jnp.int32) + (r >= ab).astype(jnp.int32)
                + (r >= abc).astype(jnp.int32))
        return s * 2 + (quad >= 2), d * 2 + (quad % 2)

    zero = jnp.zeros(num_edges, jnp.int32)
    s, d = jax.lax.fori_loop(0, levels, level, (zero, zero))
    return s % num_vertices, d % num_vertices


def rmat_edges(num_vertices: int, num_edges: int, graph_seed: int,
               a: float = 0.57, b: float = 0.19, c: float = 0.19):
    """(src, dst) int32 host arrays of an R-MAT multigraph: duplicate
    edges and self loops are kept, as the program's generator keeps
    them."""
    if num_vertices < 1 or num_edges < 1:
        raise ValueError("an R-MAT graph needs vertices and edges")
    s, d = _rmat(jax.random.key(int(graph_seed)), int(num_vertices),
                 int(num_edges), float(a), float(b), float(c))
    return np.asarray(s), np.asarray(d)
