"""Uniform u64 values over the whole 64-bit range (record values)."""

from __future__ import annotations

import numpy as np


def draw(n: int, rng: np.random.Generator) -> np.ndarray:
    return rng.integers(0, 2**64, size=n, dtype=np.uint64)
