"""Production mesh construction.

A function, not a module-level constant, so importing this module never
touches jax device state.  Single pod: (data=16, model=16) = 256 chips
(v5e pod).  Multi-pod adds a leading "pod" axis: (2, 16, 16) = 512 chips.
`make_elastic_mesh` builds the best mesh for whatever devices survive —
the elastic-scaling entry point used by checkpoint/elastic.py.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """`jax.make_mesh` with every axis `Auto`: jax 0.9 defaults to
    `Explicit` axes, which `NamedSharding` constraints cannot refer to."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_elastic_mesh(n_devices: Optional[int] = None, model_parallel: int = 16):
    """Best-effort (data, model) mesh from the available device count —
    used on restart after losing nodes.  model axis shrinks to the largest
    power-of-two divisor <= model_parallel if needed."""
    n = n_devices if n_devices is not None else len(jax.devices())
    mp = min(model_parallel, n)
    while n % mp != 0:
        mp //= 2
    mp = max(mp, 1)
    return make_mesh((n // mp, mp), ("data", "model"))


def single_device_mesh():
    return make_mesh((1, 1), ("data", "model"))
