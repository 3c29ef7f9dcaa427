"""YCSB's ``requestdistribution=zipfian``: ``ScrambledZipfianGenerator``.

A Zipfian rank over YCSB's fixed item space of 10^10 with constant 0.99,
drawn by the closed form of Gray et al. ("Quickly generating billion-record
synthetic databases", SIGMOD 1994) exactly as YCSB's ``ZipfianGenerator``
does with its precomputed zeta, then scattered over the record count by
YCSB's 64-bit FNV-1a hash: the hot ranks land at hashed positions in the
key space.  Returns indices into the sorted array of loaded keys.

YCSB only precomputes zeta for the constant 0.99; any other constant is
refused.
"""

from __future__ import annotations

import numpy as np

ITEM_COUNT = 10_000_000_000  # YCSB ScrambledZipfianGenerator.ITEM_COUNT
ZETAN = 26.46902820178302  # its zeta(ITEM_COUNT, 0.99)
CONSTANT = 0.99
FNV_OFFSET_BASIS_64 = np.uint64(0xCBF29CE484222325)
FNV_PRIME_64 = np.uint64(1099511628211)


def fnvhash64(v: np.ndarray) -> np.ndarray:
    """YCSB ``Utils.fnvhash64`` over u64 values, then its ``Math.abs``
    (read as u64, so the one value Java leaves negative stays in range)."""
    v = np.asarray(v, dtype=np.uint64).copy()
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, dtype=np.uint64)
    for _ in range(8):
        h ^= v & np.uint64(0xFF)
        h *= FNV_PRIME_64
        v >>= np.uint64(8)
    s = h.view(np.int64)
    return np.where(s < 0, -(s + 1), s).astype(np.uint64) + (s < 0).astype(np.uint64)


def ranks(n: int, rng: np.random.Generator, constant: float = CONSTANT) -> np.ndarray:
    """Zipfian ranks in [0, ITEM_COUNT] (YCSB ``ZipfianGenerator.nextLong``)."""
    if constant != CONSTANT:
        raise ValueError(f"scrambled zipfian is defined for 0.99 only, not {constant}")
    theta = constant
    items = ITEM_COUNT + 1  # ZipfianGenerator(0, ITEM_COUNT): max - min + 1
    zeta2 = 1.0 + 0.5**theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / ZETAN)
    u = rng.random(n)
    uz = u * ZETAN
    tail = np.floor(items * np.power(eta * u - eta + 1.0, alpha))
    out = np.where(uz < 1.0, 0.0, np.where(uz < 1.0 + 0.5**theta, 1.0, tail))
    return out.astype(np.uint64)


def draw(n: int, rng: np.random.Generator, n_items: int, constant: float = CONSTANT) -> np.ndarray:
    """``n`` item indices in [0, n_items)."""
    return (fnvhash64(ranks(n, rng, constant)) % np.uint64(n_items)).astype(np.int64)
