"""Pallas kernel: ONE payload-generic cache probe (Bloom + 4-way buckets).

The paper keeps each thread's Bloom filter in the spare bytes of its resident
context cache line, so negative probes are free; bucket hits cost one DPA
memory line.  TPU mapping: the Bloom words and the bucket array are VMEM-
resident (they are tiny: 176 x 8 u32 words + 176 x 24 x 4 entries), probed
lane-parallel across the request tile.

Both caches in the system share this exact structure — they differ only in
what a bucket entry *carries*:

  * the point-GET hot-entry cache (Sec 3.1.2 / Fig 5) carries a 2-word u32
    value payload (``core/hotcache.py``);
  * the scan-anchor cache carries a 1-word leaf-id payload: the leaf where
    the key's descent bottomed out, so a hit lets RANGE skip the whole
    traversal (``core/scancache.py``).

So there is ONE kernel, ``_generic_probe_kernel``, generic over the payload
word count (the payload rides as a ``(T, NB, W, P)`` array and a hit
returns its ``(P,)`` words) and over the hash salts (each cache family
decorrelates with its own).  ``probe_pallas`` and ``anchor_probe_pallas``
are thin payload-packing wrappers kept for the dispatch layer
(``kernels/ops.py``) and the equivalence sweeps.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.hotcache import SALT_BLOOM, SALT_BUCKET, CacheConfig
from repro.core.scancache import SALT_SBLOOM, SALT_SBUCKET, ScanCacheConfig


def _limb_hash(hi, lo, salt: int):
    h = hi ^ (lo * jnp.uint32(0x9E3779B9)) ^ jnp.uint32(
        (salt * 0x85EBCA6B + 0xC2B2AE35) & 0xFFFFFFFF
    )
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x846CA68B)
    h = h ^ (h >> 16)
    return h


def _generic_probe_kernel(
    bloom_ref,  # (T, bits/32) u32   VMEM
    bkey_ref,  # (T, NB, W, 2) u32  VMEM
    bpay_ref,  # (T, NB, W, P)      VMEM — payload words (value / leaf id)
    bvalid_ref,  # (T, NB, W) i32   VMEM (bool widened)
    tid_ref,  # (Bt,)
    khi_ref,
    klo_ref,
    hit_ref,  # (Bt,) i32
    pay_ref,  # (Bt, P) — hit payload, zeros on miss
    *,
    bloom_bits: int,
    n_buckets: int,
    salts_bloom: Sequence[int],
    salt_bucket: int,
):
    tid = tid_ref[...]
    khi = khi_ref[...]
    klo = klo_ref[...]
    may = jnp.ones_like(khi, dtype=bool)
    bloom = bloom_ref[...]
    for s in salts_bloom:
        h = _limb_hash(khi, klo, s) % jnp.uint32(bloom_bits)
        word = jnp.take_along_axis(
            jnp.take(bloom, tid, axis=0), (h // 32).astype(jnp.int32)[:, None], axis=1
        )[:, 0]
        may &= (word >> (h % 32)) & 1 == 1
    bucket = (_limb_hash(khi, klo, salt_bucket) % jnp.uint32(n_buckets)).astype(
        jnp.int32
    )
    rows_k = jnp.take(bkey_ref[...], tid, axis=0)
    bk = jnp.take_along_axis(
        rows_k, bucket[:, None, None, None].repeat(rows_k.shape[2], 2).repeat(2, 3), axis=1
    )[:, 0]
    rows_p = jnp.take(bpay_ref[...], tid, axis=0)
    P = rows_p.shape[3]
    bp = jnp.take_along_axis(
        rows_p, bucket[:, None, None, None].repeat(rows_p.shape[2], 2).repeat(P, 3), axis=1
    )[:, 0]
    rows_val = jnp.take(bvalid_ref[...], tid, axis=0)
    valid = jnp.take_along_axis(
        rows_val, bucket[:, None, None].repeat(rows_val.shape[2], 2), axis=1
    )[:, 0]
    eq = (
        (bk[:, :, 0] == khi[:, None])
        & (bk[:, :, 1] == klo[:, None])
        & (valid != 0)
    )
    way = jnp.argmax(eq, axis=1)
    hit = may & jnp.any(eq, axis=1)
    v = jnp.take_along_axis(bp, way[:, None, None].repeat(P, -1), axis=1)[:, 0]
    hit_ref[...] = hit.astype(jnp.int32)
    pay_ref[...] = jnp.where(hit[:, None], v, 0)


def generic_probe_pallas(
    bloom: jnp.ndarray,
    bkey: jnp.ndarray,
    bpay: jnp.ndarray,  # (T, NB, W, P) payload words
    bvalid: jnp.ndarray,
    tid: jnp.ndarray,
    khi: jnp.ndarray,
    klo: jnp.ndarray,
    *,
    bloom_bits: int,
    n_buckets: int,
    salts_bloom: Sequence[int],
    salt_bucket: int,
    block_requests: int = 128,
    interpret: bool,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Batched payload-generic probe: (hit (B,), payload (B, P)).  The one
    kernel both cache families instantiate (see module docstring).

    Not on the served path (the store probes through ``cacheset.probe_set``),
    and it does not lower for TPU yet: Mosaic refuses its bucket gather
    (``tests/test_tpu_compile.py``)."""
    B = khi.shape[0]
    assert B % block_requests == 0
    P = bpay.shape[3]
    grid = (B // block_requests,)
    kernel = functools.partial(
        _generic_probe_kernel,
        bloom_bits=bloom_bits,
        n_buckets=n_buckets,
        salts_bloom=tuple(salts_bloom),
        salt_bucket=salt_bucket,
    )
    vmem = lambda arr: pl.BlockSpec(arr.shape, lambda i: tuple([0] * arr.ndim))
    tile = pl.BlockSpec((block_requests,), lambda i: (i,))
    tile_p = pl.BlockSpec((block_requests, P), lambda i: (i, 0))
    bvalid_i32 = bvalid.astype(jnp.int32)
    hit, pay = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            vmem(bloom),
            vmem(bkey),
            vmem(bpay),
            vmem(bvalid_i32),
            tile,
            tile,
            tile,
        ],
        out_specs=[tile, tile_p],
        out_shape=[
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B, P), bpay.dtype),
        ],
        interpret=interpret,
    )(bloom, bkey, bpay, bvalid_i32, tid, khi, klo)
    return hit.astype(bool), pay


def probe_pallas(
    cache,
    tid: jnp.ndarray,
    khi: jnp.ndarray,
    klo: jnp.ndarray,
    *,
    cfg: CacheConfig,
    block_requests: int = 128,
    interpret: bool,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Point-GET hot-entry probe: value-payload (P=2) instantiation of the
    generic kernel.  Semantics == hotcache.probe."""
    hit, pay = generic_probe_pallas(
        cache.bloom,
        cache.bkey,
        cache.bval,  # (T, NB, W, 2): the u32 value limbs ARE the payload
        cache.bvalid,
        tid,
        khi,
        klo,
        bloom_bits=cfg.bloom_bits,
        n_buckets=cfg.n_buckets,
        salts_bloom=SALT_BLOOM,
        salt_bucket=SALT_BUCKET,
        block_requests=block_requests,
        interpret=interpret,
    )
    return hit, pay[:, 0], pay[:, 1]


def anchor_probe_pallas(
    cache,
    tid: jnp.ndarray,
    khi: jnp.ndarray,
    klo: jnp.ndarray,
    *,
    cfg: ScanCacheConfig,
    block_requests: int = 128,
    interpret: bool,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scan-anchor probe: leaf-id-payload (P=1) instantiation of the
    generic kernel.  Semantics == scancache.probe."""
    hit, pay = generic_probe_pallas(
        cache.bloom,
        cache.bkey,
        cache.bleaf[..., None],  # (T, NB, W, 1) i32 leaf-id payload
        cache.bvalid,
        tid,
        khi,
        klo,
        bloom_bits=cfg.bloom_bits,
        n_buckets=cfg.n_buckets,
        salts_bloom=SALT_SBLOOM,
        salt_bucket=SALT_SBUCKET,
        block_requests=block_requests,
        interpret=interpret,
    )
    return hit, pay[:, 0]
