"""Algorithm bytes of the RANGE walk (``lookup.range_batch_loop``), from
shapes alone, in the units of ``bench/cost.py``.

Per client scan of length ``n`` (its own requested length, not the wave's
padded limit, and once however many shards the scan is issued to):

* one descent to its start: ``cost.inner_level_bytes`` per inner level
  (depth - 1 of them) and the leaf's model (slot, count, slope, anchor);
* its ``n`` pairs read (key and value, 16 B each) and the same ``n`` pairs
  written out;
* the start key in (8 B).

The count is the same for whatever implements the walk, so a later kernel
is judged on the same bytes.
"""

from __future__ import annotations

import numpy as np

import cost

LEAF_MODEL_B = cost.I32_B + cost.I32_B + 4 + cost.KEY_B  # slot, count, slope, anchor
PAIR_B = cost.KEY_B + cost.VAL_B


def descent_bytes(depth: int, eps_inner: int) -> int:
    """Bytes of one descent from the root to a scan's start leaf."""
    return (depth - 1) * cost.inner_level_bytes(eps_inner) + LEAF_MODEL_B


def range_bytes(lengths, depth: int, eps_inner: int) -> int:
    """Bytes of the scans whose own lengths are ``lengths``."""
    lengths = np.asarray(lengths, dtype=np.int64)
    per_scan = descent_bytes(depth, eps_inner) + cost.KEY_B
    return int(lengths.size * per_scan + 2 * PAIR_B * lengths.sum())
