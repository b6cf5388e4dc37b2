"""Per-run inputs drawn from `--seed`: features, weights, labels and the
training batches.

Each kind of input has a stream of its own, so changing how one is drawn
never moves another.  Seeds may exceed 32 bits: the high word is folded
into the key, since `jax.random.key` keeps only the low one.  Device
inputs are made in one jitted call each, on the device, in float32.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

FEATURES, WEIGHTS, LABELS = 1, 2, 3


def seed_key(seed: int, stream: int) -> jax.Array:
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seeds are whole numbers >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


@partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, n: int, f: int, scale: float):
    return jax.random.normal(key, (n, f), jnp.float32) * scale


def features(n: int, f: int, seed: int, scale: float) -> jax.Array:
    """(n, f) float32 features, N(0, scale^2), on the device."""
    return _normal(seed_key(seed, FEATURES), int(n), int(f), float(scale))


@partial(jax.jit, static_argnums=(1,))
def _weights(key, dims: Sequence[int]):
    keys = jax.random.split(key, len(dims) - 1)
    return [{"w": jax.random.normal(k, (fi, fo), jnp.float32)
             / np.sqrt(fi)}
            for k, fi, fo in zip(keys, dims[:-1], dims[1:])]


def weights(dims: Sequence[int], seed: int) -> List[Dict[str, jax.Array]]:
    """One {"w": (F_in, F_out)} float32 matrix per layer, N(0, 1/F_in)."""
    return _weights(seed_key(seed, WEIGHTS), tuple(int(d) for d in dims))


@partial(jax.jit, static_argnums=(1, 2))
def _labels(key, n: int, classes: int):
    return jax.random.randint(key, (n,), 0, classes, jnp.int32)


def labels(n: int, classes: int, seed: int) -> jax.Array:
    """(n,) int32 class labels, uniform over the classes."""
    return _labels(seed_key(seed, LABELS), int(n), int(classes))


def node_batch(n: int, size: int, seed: int, step: int) -> np.ndarray:
    """The labelled vertices of training step `step`: `size` distinct
    vertex ids, a pure function of (seed, step)."""
    rng = np.random.default_rng((int(seed), 0xBA7C, int(step)))
    return rng.choice(int(n), int(size), replace=False).astype(np.int32)
