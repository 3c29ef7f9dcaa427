"""Dispatch layer over the Pallas kernels, used by the kernel tests.

Nothing on the served path imports this module: the store serves through
the ``jnp`` walks of ``core/lookup.py``, ``core/hotcache.py`` and
``core/scancache.py``.  Every entry point takes a required ``impl`` —
``"ref"`` (those ``jnp`` walks), ``"pallas"`` (the compiled kernel) or
``"pallas_interpret"`` (the kernel body in the Pallas interpreter, which
is what the equivalence sweeps run).  Nothing is chosen by backend: none
of the three kernels lowers for TPU yet (``tests/test_tpu_compile.py``).
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from repro.core import lookup
from repro.core.hotcache import CacheConfig
from repro.core.keys import limb_le
from repro.core.lookup import IB_DEL, IB_EMPTY, InsertBuffers
from repro.core.scancache import ScanCacheConfig
from . import ref as _ref
from .traverse import get_pallas
from .cache_probe import anchor_probe_pallas, probe_pallas
from .range_scan import range_pallas


def _resolve(impl: str) -> str:
    if impl not in ("ref", "pallas", "pallas_interpret"):
        raise ValueError(f"unknown impl {impl!r}")
    return impl


def _pad_to(arr, mult, fill=0):
    b = arr.shape[0]
    rem = (-b) % mult
    if rem == 0:
        return arr, b
    pad = jnp.full((rem,) + arr.shape[1:], fill, dtype=arr.dtype)
    return jnp.concatenate([arr, pad], axis=0), b


def get(
    tree,
    ib,
    khi,
    klo,
    *,
    depth: int,
    eps_inner: int,
    eps_leaf: int,
    impl: str,
    block_requests: int = 128,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.get(
            tree, ib, khi, klo, depth=depth, eps_inner=eps_inner, eps_leaf=eps_leaf
        )
    khi_p, n = _pad_to(khi, block_requests)
    klo_p, _ = _pad_to(klo, block_requests)
    vhi, vlo, found = get_pallas(
        tree,
        ib,
        khi_p,
        klo_p,
        depth=depth,
        eps_inner=eps_inner,
        eps_leaf=eps_leaf,
        block_requests=block_requests,
        interpret=(impl == "pallas_interpret"),
    )
    return vhi[:n], vlo[:n], found[:n]


def cache_probe(
    cache,
    tid,
    khi,
    klo,
    *,
    cfg: CacheConfig,
    impl: str,
    block_requests: int = 128,
):
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.cache_probe(cache, tid, khi, klo, cfg=cfg)
    khi_p, n = _pad_to(khi, block_requests)
    klo_p, _ = _pad_to(klo, block_requests)
    tid_p, _ = _pad_to(tid, block_requests)
    hit, vhi, vlo = probe_pallas(
        cache,
        tid_p,
        khi_p,
        klo_p,
        cfg=cfg,
        block_requests=block_requests,
        interpret=(impl == "pallas_interpret"),
    )
    return hit[:n], vhi[:n], vlo[:n]


def scan_anchor_probe(
    cache,
    tid,
    khi,
    klo,
    *,
    cfg: ScanCacheConfig,
    impl: str,
    block_requests: int = 128,
):
    """Scan-anchor cache probe: (hit, leaf) — the RANGE descent-skip path."""
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.scan_anchor_probe(cache, tid, khi, klo, cfg=cfg)
    khi_p, n = _pad_to(khi, block_requests)
    klo_p, _ = _pad_to(klo, block_requests)
    tid_p, _ = _pad_to(tid, block_requests)
    hit, leaf = anchor_probe_pallas(
        cache,
        tid_p,
        khi_p,
        klo_p,
        cfg=cfg,
        block_requests=block_requests,
        interpret=(impl == "pallas_interpret"),
    )
    return hit[:n], leaf[:n]


def range_scan(
    tree,
    ib: InsertBuffers,
    khi,
    klo,
    *,
    depth: int,
    eps_inner: int,
    limit: int,
    max_leaves: int = 4,
    impl: str,
    block_requests: int = 64,
    start_leaf=None,
):
    """Full RANGE op: traversal to the start leaf (skipped when an anchor /
    continuation ``start_leaf`` is supplied), Pallas leaf-chain scan, jnp
    insert-buffer merge epilogue.  Output layout == ref.range_scan:
    (keys, vals, valid, truncated, cursor)."""
    if limit <= 0:  # degenerate scan: keep 0-width blocks out of the kernel
        B = khi.shape[0]
        empty = jnp.zeros((B, 0, 2), dtype=jnp.uint32)
        return (
            empty,
            empty,
            jnp.zeros((B, 0), dtype=bool),
            jnp.zeros((B,), dtype=bool),
            lookup.ScanCursor(khi, klo, jnp.full((B,), -1, dtype=jnp.int32)),
        )
    impl = _resolve(impl)
    if impl == "ref":
        if start_leaf is not None:
            return _ref.range_scan_from(
                tree, ib, start_leaf, khi, klo, limit=limit, max_leaves=max_leaves
            )
        return _ref.range_scan(
            tree,
            ib,
            khi,
            klo,
            depth=depth,
            eps_inner=eps_inner,
            limit=limit,
            max_leaves=max_leaves,
        )
    khi_p, n = _pad_to(khi, block_requests)
    klo_p, _ = _pad_to(klo, block_requests)
    if start_leaf is None:
        start = lookup.traverse(tree, khi_p, klo_p, depth=depth, eps_inner=eps_inner)
    else:
        start, _ = _pad_to(start_leaf, block_requests, fill=-1)
    cap = ib.keys.shape[1]
    # over-collect so buffered deletes can never starve the final cut
    inner_limit = limit + max_leaves * cap
    kh, kl, vh, vl, cnt, visited, next_leaf = range_pallas(
        tree,
        start,
        khi_p,
        klo_p,
        limit=inner_limit,
        max_leaves=max_leaves,
        block_requests=block_requests,
        interpret=(impl == "pallas_interpret"),
    )
    keys, vals, valid, truncated, cursor = _merge_ib_epilogue(
        ib, khi_p, klo_p, kh, kl, vh, vl, cnt, visited, next_leaf, limit=limit
    )
    return (
        keys[:n],
        vals[:n],
        valid[:n],
        truncated[:n],
        lookup.ScanCursor(cursor.khi[:n], cursor.klo[:n], cursor.leaf[:n]),
    )


def range_scan_loop(
    tree,
    ib: InsertBuffers,
    khi,
    klo,
    *,
    depth: int,
    eps_inner: int,
    limit: int,
    max_leaves: int = 4,
    max_rounds: int = 0,
    impl: str,
    block_requests: int = 64,
    start_leaf=None,
    ub_hi=None,
    ub_lo=None,
):
    """In-mesh RANGE: the multi-round continuation of
    ``lookup.range_batch_loop`` with the per-round walk dispatched to the
    Pallas kernel (``impl='pallas'``/``'pallas_interpret'``) or the jnp
    reference.  The kernel's ``next_leaf`` output is the loop-carried
    cursor state: each ``lax.while_loop`` round feeds it back as the next
    round's ``start_leaf``, so a scan that needs many bounded walks is
    still ONE dispatch.  ``ub_hi``/``ub_lo`` are per-row owned-window
    upper-bound limbs (default: KEY_MAX sentinel = no clip).  Returns
    (keys, vals, valid, truncated, cursor, rounds)."""
    B = khi.shape[0]
    if limit <= 0 or B == 0:
        empty = jnp.zeros((B, 0, 2), dtype=jnp.uint32)
        return (
            empty,
            empty,
            jnp.zeros((B, 0), dtype=bool),
            jnp.zeros((B,), dtype=bool),
            lookup.ScanCursor(khi, klo, jnp.full((B,), -1, dtype=jnp.int32)),
            jnp.int32(0),
        )
    impl = _resolve(impl)
    khi_p, n = _pad_to(khi, block_requests)
    klo_p, _ = _pad_to(klo, block_requests)
    sentinel = jnp.uint32(0xFFFFFFFF)
    ub_hi = jnp.full_like(khi, sentinel) if ub_hi is None else ub_hi
    ub_lo = jnp.full_like(klo, sentinel) if ub_lo is None else ub_lo
    ub_hi_p, _ = _pad_to(ub_hi, block_requests, fill=sentinel)
    ub_lo_p, _ = _pad_to(ub_lo, block_requests, fill=sentinel)
    if start_leaf is None:
        start = lookup.traverse(tree, khi_p, klo_p, depth=depth, eps_inner=eps_inner)
        # pad lanes ride along dead (they would otherwise walk from key 0)
        start = jnp.where(jnp.arange(start.shape[0]) < n, start, -1)
    else:
        start, _ = _pad_to(start_leaf, block_requests, fill=-1)

    if impl == "ref":
        # the jnp device loop IS the reference — dispatch to it wholesale so
        # the hard cap / round invariants live in exactly one place
        keys, vals, valid, truncated, cursor, rounds = lookup.range_batch_loop(
            tree, ib, start, khi_p, klo_p, ub_hi_p, ub_lo_p,
            limit=limit, max_leaves=max_leaves, max_rounds=max_rounds,
        )
    else:
        cap = ib.keys.shape[1]
        inner_limit = limit + max_leaves * cap  # see range_scan

        def round_fn(s, h, l):
            kh, kl, vh, vl, cnt, visited, next_leaf = range_pallas(
                tree,
                s,
                h,
                l,
                limit=inner_limit,
                max_leaves=max_leaves,
                block_requests=block_requests,
                interpret=(impl == "pallas_interpret"),
            )
            return _merge_ib_epilogue(
                ib, h, l, kh, kl, vh, vl, cnt, visited, next_leaf, limit=limit
            )

        n_leaves = tree.leaf_next.shape[0]
        keys, vals, valid, truncated, cursor, rounds = lookup.continuation_loop(
            round_fn,
            start,
            khi_p,
            klo_p,
            ub_hi_p,
            ub_lo_p,
            limit=limit,
            max_rounds=max_rounds,
            hard_cap=n_leaves // max(max_leaves, 1) + 2,
        )
    return (
        keys[:n],
        vals[:n],
        valid[:n],
        truncated[:n],
        lookup.ScanCursor(cursor.khi[:n], cursor.klo[:n], cursor.leaf[:n]),
        rounds,
    )


def _merge_ib_epilogue(
    ib: InsertBuffers, khi, klo, kh, kl, vh, vl, cnt, visited, next_leaf, *, limit: int
):
    """Merge insert-buffer entries of the visited leaves into the stitched
    scan results (newest wins, tombstones delete) — the DPA-side temp-buffer
    merge of the paper, vectorised.  Also derives the continuation outputs:
    ``truncated`` (chain continues at ``next_leaf`` AND the merged row
    under-filled ``limit``) and the resume cursor.  The kernel's over-
    collection bound (``limit + max_leaves*ib_cap``) guarantees a row that
    under-fills after the merge really did emit every survivor of its
    window, so the flag is exact."""
    B, L = kh.shape
    cap = ib.keys.shape[1]
    M = visited.shape[1]
    pad = jnp.uint32(0xFFFFFFFF)

    # stitched part: priority 0
    s_valid = jnp.arange(L)[None, :] < cnt[:, None]
    s_prio = jnp.zeros((B, L), dtype=jnp.int32)
    s_del = jnp.zeros((B, L), dtype=bool)

    # buffered part: gather (B, M*cap)
    leaf_safe = jnp.maximum(visited, 0)  # (B, M)
    bk = ib.keys[leaf_safe]  # (B, M, cap, 2)
    bv = ib.vals[leaf_safe]
    bo = ib.op[leaf_safe]
    bc = ib.count[leaf_safe]
    alive = (visited >= 0)[:, :, None]
    pos = jnp.arange(cap)[None, None, :]
    b_valid = alive & (pos < bc[:, :, None]) & (bo != IB_EMPTY)
    # only keys >= k_min participate
    b_valid &= limb_le(khi[:, None, None], klo[:, None, None], bk[..., 0], bk[..., 1])
    b_prio = jnp.broadcast_to(
        jnp.arange(1, cap + 1, dtype=jnp.int32)[None, None, :], bo.shape
    )
    b_del = bo == IB_DEL

    def flat(x):
        return x.reshape(B, -1)

    keys_h = jnp.concatenate([kh, flat(bk[..., 0])], axis=1)
    keys_l = jnp.concatenate([kl, flat(bk[..., 1])], axis=1)
    vals_h = jnp.concatenate([vh, flat(bv[..., 0])], axis=1)
    vals_l = jnp.concatenate([vl, flat(bv[..., 1])], axis=1)
    valid = jnp.concatenate([s_valid, flat(b_valid)], axis=1)
    prio = jnp.concatenate([s_prio, flat(b_prio)], axis=1)
    is_del = jnp.concatenate([s_del, flat(b_del)], axis=1)

    keys_h = jnp.where(valid, keys_h, pad)
    keys_l = jnp.where(valid, keys_l, pad)
    order = jnp.lexsort((-prio, keys_l, keys_h), axis=-1)
    keys_h = jnp.take_along_axis(keys_h, order, axis=1)
    keys_l = jnp.take_along_axis(keys_l, order, axis=1)
    vals_h = jnp.take_along_axis(vals_h, order, axis=1)
    vals_l = jnp.take_along_axis(vals_l, order, axis=1)
    valid = jnp.take_along_axis(valid, order, axis=1)
    is_del = jnp.take_along_axis(is_del, order, axis=1)
    first = jnp.concatenate(
        [
            jnp.ones((B, 1), dtype=bool),
            (keys_h[:, 1:] != keys_h[:, :-1]) | (keys_l[:, 1:] != keys_l[:, :-1]),
        ],
        axis=1,
    )
    keep = valid & first & ~is_del
    target = jnp.cumsum(keep.astype(jnp.int32), axis=1) - 1
    in_out = keep & (target < limit)
    tgt = jnp.where(in_out, target, limit)
    rows = jnp.arange(B)[:, None]
    out_kh = jnp.full((B, limit + 1), pad, dtype=jnp.uint32).at[rows, tgt].set(
        jnp.where(in_out, keys_h, pad)
    )
    out_kl = jnp.full((B, limit + 1), pad, dtype=jnp.uint32).at[rows, tgt].set(
        jnp.where(in_out, keys_l, pad)
    )
    out_vh = jnp.zeros((B, limit + 1), dtype=jnp.uint32).at[rows, tgt].set(
        jnp.where(in_out, vals_h, 0)
    )
    out_vl = jnp.zeros((B, limit + 1), dtype=jnp.uint32).at[rows, tgt].set(
        jnp.where(in_out, vals_l, 0)
    )
    n_found = jnp.minimum(jnp.sum(keep, axis=1), limit)
    out_valid = jnp.arange(limit)[None, :] < n_found[:, None]
    out_keys = jnp.stack([out_kh[:, :limit], out_kl[:, :limit]], axis=-1)
    out_vals = jnp.stack([out_vh[:, :limit], out_vl[:, :limit]], axis=-1)
    truncated = (next_leaf >= 0) & (n_found < limit)
    cursor = lookup.make_cursor(khi, klo, out_keys, n_found, next_leaf, truncated)
    return out_keys, out_vals, out_valid, truncated, cursor
