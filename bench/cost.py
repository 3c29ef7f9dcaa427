"""Algorithm bytes of the served device programs, from shapes alone.

These count what a walk must read from and write to device memory per
request, whatever implements it, so a later kernel is judged on the same
count.  The lookups are gather-bound: bytes, not operations, bound them,
so a program's roofline share is ``bytes / (device time x HBM peak)``.

Layout (``core/tree.py``): keys and values are u64 (two u32 limbs, 8 B);
an inner node holds ``NODE_SEGS`` segments.

* inner level: the node's segment first-keys (NODE_SEGS x 8 B), the chosen
  segment's model (slope f32, count i32, pivot slot i32: 12 B), the
  +-eps_inner pivot window (2 eps + 2 keys) and one child pointer (4 B);
* leaf: its model (slot, count, slope: 12 B; anchor key 8 B), the
  +-eps_leaf key window (2 eps + 2 keys), one value (8 B) and the insert
  buffer's fill count (4 B);
* the key in (8 B) and the result out (value 8 B, found flag 1 B).
"""

from __future__ import annotations

NODE_SEGS = 7  # core/tree.py
KEY_B = 8
VAL_B = 8
I32_B = 4


def inner_level_bytes(eps_inner: int) -> int:
    seg_model = 4 + I32_B + I32_B
    window = (2 * eps_inner + 2) * KEY_B
    return NODE_SEGS * KEY_B + seg_model + window + I32_B


def leaf_bytes(eps_leaf: int) -> int:
    model = I32_B + I32_B + 4 + KEY_B
    window = (2 * eps_leaf + 2) * KEY_B
    return model + window + VAL_B + I32_B


def get_bytes(n: int, depth: int, eps_inner: int, eps_leaf: int) -> int:
    """Bytes of ``n`` GET walks through a tree of ``depth`` levels."""
    per = (
        (depth - 1) * inner_level_bytes(eps_inner)
        + leaf_bytes(eps_leaf)
        + KEY_B
        + VAL_B
        + 1
    )
    return n * per

