"""Uniform targets: every vertex alike."""
from __future__ import annotations

from typing import Dict

import numpy as np


def draw(rng, mix: Dict, order: np.ndarray, k: int) -> np.ndarray:
    return rng.integers(0, int(order.size), k).astype(np.int32)
