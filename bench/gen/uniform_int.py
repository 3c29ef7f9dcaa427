"""Uniform integers in [lo, hi], both ends included (YCSB's
``UniformLongGenerator``, used for ``scanlengthdistribution=uniform``)."""

from __future__ import annotations

import numpy as np


def draw(n: int, rng: np.random.Generator, lo: int, hi: int) -> np.ndarray:
    return rng.integers(lo, hi + 1, size=n, dtype=np.int64)
