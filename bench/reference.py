"""The plain reference: an ordered u64 -> u64 map over sorted numpy arrays.

It imports nothing of the program.  Lookups are ``searchsorted`` and
slices, as in the oracle of ``chip_smoke.py``; writes are applied in
submission order, the last write to a key within one batch winning, which
is the store's stated semantics (a wave is applied in request order).  New
keys go to a sorted overlay beside the loaded arrays, so a run's inserts do
not copy the 50M-key base on every wave; a RANGE merges the two.
"""

from __future__ import annotations

import numpy as np

KEY_MAX = np.uint64(2**64 - 1)  # never a key: pads rows past their end


def last_wins(k: np.ndarray, v: np.ndarray):
    """Distinct keys of a write batch, each with the value of its last write."""
    k = np.asarray(k, dtype=np.uint64)
    v = np.asarray(v, dtype=np.uint64)
    uk, first_in_reversed = np.unique(k[::-1], return_index=True)
    return uk, v[::-1][first_in_reversed]


class Reference:
    """Sorted unique u64 keys with u64 values; GET, RANGE, upsert."""

    def __init__(self, keys: np.ndarray, vals: np.ndarray):
        self.keys = np.array(keys, dtype=np.uint64)
        self.vals = np.array(vals, dtype=np.uint64)
        self.new_keys = np.zeros(0, dtype=np.uint64)
        self.new_vals = np.zeros(0, dtype=np.uint64)

    @staticmethod
    def _find(keys, q):
        pos = np.searchsorted(keys, q)
        safe = np.minimum(pos, max(keys.size - 1, 0))
        hit = (pos < keys.size) & (keys[safe] == q) if keys.size else np.zeros(q.shape, bool)
        return safe, hit

    def get(self, q):
        """(values, found); absent keys read 0."""
        q = np.asarray(q, dtype=np.uint64)
        pos, hit = self._find(self.keys, q)
        out = np.where(hit, self.vals[pos], np.uint64(0))
        if self.new_keys.size:
            npos, nhit = self._find(self.new_keys, q)
            out = np.where(nhit, self.new_vals[npos], out)
            hit = hit | nhit
        return out, hit

    @staticmethod
    def _window(keys, vals, k_min, limit):
        start = np.searchsorted(keys, k_min)
        idx = start[:, None] + np.arange(limit)
        valid = idx < keys.size
        safe = np.minimum(idx, max(keys.size - 1, 0))
        if not keys.size:
            return np.full(idx.shape, KEY_MAX), np.zeros(idx.shape, np.uint64)
        return np.where(valid, keys[safe], KEY_MAX), np.where(valid, vals[safe], np.uint64(0))

    def range(self, k_min, limit: int):
        """First ``limit`` pairs with key >= k_min, per row: (keys (R, limit),
        vals (R, limit), counts (R,)); zeros past each row's count."""
        k_min = np.asarray(k_min, dtype=np.uint64)
        k, v = self._window(self.keys, self.vals, k_min, limit)
        if self.new_keys.size:
            nk, nv = self._window(self.new_keys, self.new_vals, k_min, limit)
            k = np.concatenate([k, nk], axis=1)
            v = np.concatenate([v, nv], axis=1)
            order = np.argsort(k, axis=1, kind="stable")[:, :limit]
            k = np.take_along_axis(k, order, axis=1)
            v = np.take_along_axis(v, order, axis=1)
        valid = k != KEY_MAX
        return np.where(valid, k, np.uint64(0)), np.where(valid, v, np.uint64(0)), valid.sum(axis=1)

    def put(self, k, v) -> None:
        """Upsert a write batch in request order."""
        k, v = last_wins(k, v)
        pos, hit = self._find(self.keys, k)
        self.vals[pos[hit]] = v[hit]
        k, v = k[~hit], v[~hit]
        if self.new_keys.size:
            npos, nhit = self._find(self.new_keys, k)
            self.new_vals[npos[nhit]] = v[nhit]
            k, v = k[~nhit], v[~nhit]
        at = np.searchsorted(self.new_keys, k)
        self.new_keys = np.insert(self.new_keys, at, k)
        self.new_vals = np.insert(self.new_vals, at, v)
