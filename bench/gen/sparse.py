"""``sparse`` key shape: uniform over the 64-bit key space (SOSD's synthetic
uniform set; the paper's Sec 4.1 default).

The same shape as the program's ``core/datasets.sparse``, drawn in a few
vectorised passes.  2^64-1 is the store's reserved sentinel and is never
drawn.
"""

from __future__ import annotations

import numpy as np

KEY_TOP = 2**64 - 1  # exclusive: the sentinel 2^64-1 is reserved


def keys(n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` sorted unique u64 keys."""
    out = np.unique(rng.integers(0, KEY_TOP, size=n, dtype=np.uint64))
    while out.size < n:  # collisions: ~n^2 / 2^65, almost never
        extra = rng.integers(0, KEY_TOP, size=n - out.size, dtype=np.uint64)
        out = np.unique(np.concatenate([out, extra]))
    return out
