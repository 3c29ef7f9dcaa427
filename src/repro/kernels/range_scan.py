"""Pallas kernel: RANGE leaf-chain scan (the DMA-heavy half of RANGE).

The paper's RANGE walks leaves from k_min, scanning the contiguous key/value
arrays in host memory — bulk sequential DMA, the part worth a kernel.  The
small insert-buffer merge (cache-resident on the DPA) happens in the jnp
epilogue of ``ops.range_scan``, which is where the paper's temp-buffer merge
lives too.  To keep the composition exact under buffered deletes, the kernel
over-collects ``limit + max_leaves*ib_cap`` stitched entries so the epilogue
always has enough survivors to fill ``limit`` outputs (equality with the
pure-jnp oracle is asserted in tests).

Memory placement mirrors traverse.py: leaf metadata in VMEM; the key/value
arrays in ``memory_space=ANY`` read with whole-row dynamic copies (the
paper's sequential leaf DMA).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .traverse import ANY, _limb_le


def _range_kernel(
    lnext_ref,  # (Nl,) VMEM
    lcount_ref,  # (Nl,) VMEM
    lslot_ref,  # (Nl,) VMEM
    hk_ref,  # (Ns, 128, 2) ANY
    hv_ref,  # (Ns, 128, 2) ANY
    start_ref,  # (Bt,) start leaf ids
    khi_ref,  # (Bt,) k_min
    klo_ref,
    out_kh_ref,  # (Bt, L)
    out_kl_ref,
    out_vh_ref,
    out_vl_ref,
    out_n_ref,  # (Bt,)
    out_leaf_ref,  # (Bt, max_leaves) leaf ids visited (-1 pad) for the epilogue
    out_next_ref,  # (Bt,) first UNwalked leaf (-1 = chain ended): continuation
    *,
    limit: int,
    max_leaves: int,
):
    bt = start_ref.shape[0]
    width = hk_ref.shape[1]

    def lane(i, _):
        kmin_hi = khi_ref[i]
        kmin_lo = klo_ref[i]
        okh = jnp.zeros((limit,), dtype=jnp.uint32)
        okl = jnp.zeros((limit,), dtype=jnp.uint32)
        ovh = jnp.zeros((limit,), dtype=jnp.uint32)
        ovl = jnp.zeros((limit,), dtype=jnp.uint32)
        cnt = jnp.int32(0)
        leaf = start_ref[i]
        for step in range(max_leaves):
            alive = leaf >= 0
            safe = jnp.maximum(leaf, 0)
            out_leaf_ref[i, step] = jnp.where(alive, leaf, -1)
            slot = lslot_ref[safe]
            lcnt = lcount_ref[safe]
            # sequential leaf DMA: the whole row in one copy
            row_k = hk_ref[pl.ds(slot, 1), slice(None), slice(None)][0]
            row_v = hv_ref[pl.ds(slot, 1), slice(None), slice(None)][0]
            pos = jnp.arange(width, dtype=jnp.int32)
            ge = _limb_le(kmin_hi, kmin_lo, row_k[:, 0], row_k[:, 1])
            mask = ge & (pos < lcnt) & alive
            tgt = cnt + jnp.cumsum(mask.astype(jnp.int32)) - 1
            put = mask & (tgt < limit)
            tgt_safe = jnp.where(put, tgt, limit)  # OOB -> dropped
            okh = okh.at[tgt_safe].set(row_k[:, 0], mode="drop")
            okl = okl.at[tgt_safe].set(row_k[:, 1], mode="drop")
            ovh = ovh.at[tgt_safe].set(row_v[:, 0], mode="drop")
            ovl = ovl.at[tgt_safe].set(row_v[:, 1], mode="drop")
            cnt = jnp.minimum(cnt + jnp.sum(mask.astype(jnp.int32)), limit)
            leaf = jnp.where(alive, lnext_ref[safe], -1)
        out_kh_ref[i, :] = okh
        out_kl_ref[i, :] = okl
        out_vh_ref[i, :] = ovh
        out_vl_ref[i, :] = ovl
        out_n_ref[i] = cnt
        # ``leaf`` after the loop is the first leaf the bounded walk did NOT
        # visit — the device-side continuation cursor (-1 = chain exhausted)
        out_next_ref[i] = leaf
        return 0

    jax.lax.fori_loop(0, bt, lane, 0)


def range_pallas(
    tree,
    start_leaf: jnp.ndarray,
    khi: jnp.ndarray,
    klo: jnp.ndarray,
    *,
    limit: int,
    max_leaves: int = 4,
    block_requests: int = 64,
    interpret: bool,
) -> Tuple[jnp.ndarray, ...]:
    """Returns (keys_hi (B,L), keys_lo, vals_hi, vals_lo, n (B,),
    visited_leaves (B, max_leaves), next_leaf (B,)).  ``next_leaf`` is the
    first unwalked leaf (-1 when the chain ended inside the window) — the
    epilogue combines it with the merged count to derive the ``truncated``
    flag and resume cursor.

    Not on the served path, and it does not lower for TPU yet: a 64-row
    tile breaks Mosaic's rule that a rank-1 block is a multiple of 128, and
    at 128 rows the per-lane scalar stores are refused
    (``tests/test_tpu_compile.py`` pins both).  The served RANGE is
    ``core.lookup.range_batch_loop``."""
    B = khi.shape[0]
    assert B % block_requests == 0
    assert limit >= 1, "0-width output blocks break the kernel; ops.range_scan guards limit=0"
    grid = (B // block_requests,)
    kernel = functools.partial(_range_kernel, limit=limit, max_leaves=max_leaves)
    vmem = lambda arr: pl.BlockSpec(arr.shape, lambda i: tuple([0] * arr.ndim))
    anymem = lambda arr: pl.BlockSpec(
        arr.shape, lambda i: tuple([0] * arr.ndim), memory_space=ANY
    )
    tile1 = pl.BlockSpec((block_requests,), lambda i: (i,))
    tile2 = lambda w: pl.BlockSpec((block_requests, w), lambda i: (i, 0))
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            vmem(tree.leaf_next),
            vmem(tree.leaf_count),
            vmem(tree.leaf_slot),
            anymem(tree.hbm_keys),
            anymem(tree.hbm_vals),
            tile1,
            tile1,
            tile1,
        ],
        out_specs=[
            tile2(limit),
            tile2(limit),
            tile2(limit),
            tile2(limit),
            tile1,
            tile2(max_leaves),
            tile1,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, limit), jnp.uint32),
            jax.ShapeDtypeStruct((B, limit), jnp.uint32),
            jax.ShapeDtypeStruct((B, limit), jnp.uint32),
            jax.ShapeDtypeStruct((B, limit), jnp.uint32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B, max_leaves), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
        ],
        interpret=interpret,
    )(
        tree.leaf_next,
        tree.leaf_count,
        tree.leaf_slot,
        tree.hbm_keys,
        tree.hbm_vals,
        start_leaf,
        khi,
        klo,
    )
    return outs
