"""Pure-jnp batched traversal of the NIC-side learned index (the served path).

This module is the *semantic definition* of the DPA traverser (Sec 3.1) and
the path the store serves on every backend, the TPU included: nothing on the
served path imports ``repro.kernels``.  The Pallas kernels there are
tile-level versions of these functions, tested against them in interpret
mode; they do not lower for TPU yet (``tests/test_tpu_compile.py``).

Access-pattern faithfulness: each inner-node visit touches (1) the segment
first-key line, (2) the segment model, (3) an eps-bounded pivot window, and
(4) one child pointer — the same "few cache lines per level" contract the
paper engineers for the DPA memory (Fig 4).  Each leaf visit touches the
insert buffer, an eps_leaf window of the key array, and one value — the two
"DMA crossings" (here: HBM touches) of the paper.  ``benchmarks/`` counts
these touches and pushes them through the paper's latency constants, so the
structure here *is* the performance model.

All keys are u32 limb pairs; all functions are batched over a request wave.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from .keys import limb_le, limb_eq, limb_sub_to_f32
from .tree import DeviceTree, NODE_SEGS

# insert-buffer op codes
IB_EMPTY = 0
IB_PUT = 1  # INSERT or UPDATE (newest wins)
IB_DEL = 2  # tombstone


class InsertBuffers(NamedTuple):
    """Per-leaf NIC-side insert buffers (Sec 3.1: appended with two atomic
    counters; a wave here is an atomic batch, so visibility is wave-granular)."""

    keys: jnp.ndarray  # (Nl, B, 2) u32
    vals: jnp.ndarray  # (Nl, B, 2) u32
    op: jnp.ndarray  # (Nl, B) i32
    count: jnp.ndarray  # (Nl,) i32


def make_insert_buffers(n_leaves: int, cap: int) -> InsertBuffers:
    return InsertBuffers(
        keys=jnp.zeros((n_leaves, cap, 2), dtype=jnp.uint32),
        vals=jnp.zeros((n_leaves, cap, 2), dtype=jnp.uint32),
        op=jnp.full((n_leaves, cap), IB_EMPTY, dtype=jnp.int32),
        count=jnp.zeros((n_leaves,), dtype=jnp.int32),
    )


# ---------------------------------------------------------------------------
# inner-node routing
# ---------------------------------------------------------------------------


def _predict(slope, anchor_hi, anchor_lo, khi, klo):
    """Clamped-below PLA prediction of a local rank (f32; see keys.py for
    the error-bound argument that makes f32 sufficient)."""
    below = ~limb_le(anchor_hi, anchor_lo, khi, klo)  # key < anchor
    delta = limb_sub_to_f32(khi, klo, anchor_hi, anchor_lo)
    return jnp.where(below, jnp.float32(0.0), slope * delta)


def _window_rank(pool_keys, slot, count, pred, eps, khi, klo):
    """Index of the last key <= k inside the eps window around ``pred``.

    pool_keys: (P, 128, 2); slot/count/pred/khi/klo: (B,).  Returns (B,) rank
    (may be -1 when the key precedes the window, which only happens for keys
    below the segment's first entry) and the window base ``lo``.
    """
    w = 2 * eps + 2  # floor(p) +/- eps plus rounding slack — covers the bound
    lo = jnp.clip(
        jnp.floor(pred).astype(jnp.int32) - eps,
        0,
        jnp.maximum(count - w, 0),
    )
    idx = lo[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]  # (B, w)
    rows = pool_keys[slot]  # (B, 128, 2)
    wk = jnp.take_along_axis(rows, idx[:, :, None], axis=1)  # (B, w, 2)
    le = limb_le(wk[:, :, 0], wk[:, :, 1], khi[:, None], klo[:, None])
    in_range = idx < count[:, None]
    c = jnp.sum((le & in_range).astype(jnp.int32), axis=1)
    return lo + c - 1, lo


def route_one_level(
    tree: DeviceTree, node: jnp.ndarray, khi: jnp.ndarray, klo: jnp.ndarray, eps: int
) -> jnp.ndarray:
    """One inner-node descent step for a wave of requests: node (B,) -> child (B,)."""
    sf = tree.node_seg_first[node]  # (B, 7, 2)
    le = limb_le(sf[:, :, 0], sf[:, :, 1], khi[:, None], klo[:, None])  # (B,7)
    # padded segments hold KEY_MAX -> never <= a real key; segment 0 is the
    # floor for keys below the node's range.
    seg = jnp.maximum(jnp.sum(le[:, 1:].astype(jnp.int32), axis=1), 0)
    bidx = jnp.arange(node.shape[0])
    a_hi = sf[bidx, seg, 0]
    a_lo = sf[bidx, seg, 1]
    slope = tree.node_seg_slope[node, seg]
    count = tree.node_seg_count[node, seg]
    slot = tree.node_seg_slot[node, seg]
    pred = _predict(slope, a_hi, a_lo, khi, klo)
    rank, _ = _window_rank(tree.pivot_keys, slot, count, pred, eps, khi, klo)
    rank = jnp.maximum(rank, 0)
    return jnp.take_along_axis(
        tree.pivot_child[slot], rank[:, None], axis=1
    )[:, 0]


@partial(jax.jit, static_argnames=("depth", "eps_inner"))
def traverse(
    tree: DeviceTree, khi: jnp.ndarray, klo: jnp.ndarray, *, depth: int, eps_inner: int
) -> jnp.ndarray:
    """Descend the learned index: request keys (B,) -> leaf ids (B,)."""
    node = jnp.broadcast_to(tree.root, khi.shape).astype(jnp.int32)
    for _ in range(depth - 1):
        node = route_one_level(tree, node, khi, klo, eps_inner)
    return node


# ---------------------------------------------------------------------------
# leaf access ("the DMA part")
# ---------------------------------------------------------------------------


def leaf_search(
    tree: DeviceTree, leaf: jnp.ndarray, khi: jnp.ndarray, klo: jnp.ndarray, eps_leaf: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Search the leaf's HBM key array.  Returns (rank, found, vhi, vlo);
    rank = index of last key <= k within the leaf (-1 if none)."""
    slot = tree.leaf_slot[leaf]
    count = tree.leaf_count[leaf]
    anchor = tree.leaf_anchor[leaf]
    pred = _predict(tree.leaf_slope[leaf], anchor[:, 0], anchor[:, 1], khi, klo)
    rank, _ = _window_rank(tree.hbm_keys, slot, count, pred, eps_leaf, khi, klo)
    safe = jnp.maximum(rank, 0)
    kk = jnp.take_along_axis(tree.hbm_keys[slot], safe[:, None, None].repeat(2, -1), axis=1)[:, 0]
    found = (rank >= 0) & limb_eq(kk[:, 0], kk[:, 1], khi, klo)
    vv = jnp.take_along_axis(tree.hbm_vals[slot], safe[:, None, None].repeat(2, -1), axis=1)[:, 0]
    return rank, found, vv[:, 0], vv[:, 1]


def ib_search(
    ib: InsertBuffers, leaf: jnp.ndarray, khi: jnp.ndarray, klo: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Scan the leaf's insert buffer, newest entry wins (Sec 3.1: GETs check
    the buffer before the leaf array and may early-exit).

    Returns (present, deleted, vhi, vlo): ``present`` = key has a live PUT as
    its newest entry; ``deleted`` = newest entry is a tombstone.
    """
    bk = ib.keys[leaf]  # (B, cap, 2)
    bv = ib.vals[leaf]
    bop = ib.op[leaf]
    cnt = ib.count[leaf]
    cap = bk.shape[1]
    pos = jnp.arange(cap, dtype=jnp.int32)[None, :]
    match = (
        limb_eq(bk[:, :, 0], bk[:, :, 1], khi[:, None], klo[:, None])
        & (pos < cnt[:, None])
        & (bop != IB_EMPTY)
    )
    newest = jnp.max(jnp.where(match, pos, -1), axis=1)  # (B,)
    has = newest >= 0
    safe = jnp.maximum(newest, 0)
    op = jnp.take_along_axis(bop, safe[:, None], axis=1)[:, 0]
    v = jnp.take_along_axis(bv, safe[:, None, None].repeat(2, -1), axis=1)[:, 0]
    present = has & (op == IB_PUT)
    deleted = has & (op == IB_DEL)
    return present, deleted, v[:, 0], v[:, 1]


@partial(jax.jit, static_argnames=("depth", "eps_inner", "eps_leaf"))
def get_batch(
    tree: DeviceTree,
    ib: InsertBuffers,
    khi: jnp.ndarray,
    klo: jnp.ndarray,
    *,
    depth: int,
    eps_inner: int,
    eps_leaf: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Full GET path (sans hot cache, which store.py layers in front):
    traverse -> insert buffer (newest wins) -> leaf HBM probe."""
    leaf = traverse(tree, khi, klo, depth=depth, eps_inner=eps_inner)
    ib_present, ib_deleted, ib_vhi, ib_vlo = ib_search(ib, leaf, khi, klo)
    _, tree_found, t_vhi, t_vlo = leaf_search(tree, leaf, khi, klo, eps_leaf)
    found = ib_present | (tree_found & ~ib_deleted)
    vhi = jnp.where(ib_present, ib_vhi, t_vhi)
    vlo = jnp.where(ib_present, ib_vlo, t_vlo)
    return vhi, vlo, found


# ---------------------------------------------------------------------------
# range scan (Sec 3.1 RANGE): merge leaf array + insert buffer in key order,
# walking leaf_next across up to ``max_leaves`` leaves.  The walk reports
# whether it was truncated by the leaf bound and where to resume — the
# device-side continuation the scatter-gather epilogue and the host facade
# use to re-issue precisely instead of over-sizing ``max_leaves``.
# ---------------------------------------------------------------------------


class ScanCursor(NamedTuple):
    """Resume point of a bounded RANGE walk — and, representationally, a
    scan anchor: (key limbs, leaf id).  For truncated rows ``leaf`` is the
    first unwalked leaf and ``khi/klo`` the last key emitted (the original
    ``k_min`` when nothing was); for complete rows ``leaf`` is -1.  A
    resumed walk starts at ``leaf`` with the original ``k_min`` — every
    entry of the unwalked suffix is strictly greater than everything
    already emitted (leaf chain is in key order and buffered writes are
    leaf-local), so resuming neither duplicates nor skips.  This is the
    same (key, leaf) pair ``core.scancache`` admits as an anchor.

    ``epoch`` pins the version epoch of an ``as_of`` scan (-1 = a live
    scan): resuming a truncated versioned scan MUST re-read the same
    frozen snapshot, no matter how many flushes/rebalances/reshards landed
    in between — the store validates the pinned epoch is still retained
    and re-resolves leaf versions against it on every resume."""

    khi: jnp.ndarray  # (B,) u32
    klo: jnp.ndarray  # (B,) u32
    leaf: jnp.ndarray  # (B,) i32, -1 = complete
    epoch: int = -1  # pinned as_of epoch; -1 = live (unversioned) scan


def make_cursor(khi, klo, out_keys, n_found, cont_leaf, truncated) -> ScanCursor:
    """Build the resume cursor from a scan's outputs: last emitted key
    (falling back to k_min for empty rows) + the first unwalked leaf."""
    last = jnp.maximum(n_found - 1, 0)
    last_kh = jnp.take_along_axis(out_keys[..., 0], last[:, None], axis=1)[:, 0]
    last_kl = jnp.take_along_axis(out_keys[..., 1], last[:, None], axis=1)[:, 0]
    has = n_found > 0
    return ScanCursor(
        khi=jnp.where(has, last_kh, khi),
        klo=jnp.where(has, last_kl, klo),
        leaf=jnp.where(truncated, cont_leaf, -1).astype(jnp.int32),
    )


@partial(jax.jit, static_argnames=("limit", "max_leaves"))
def range_batch_from(
    tree: DeviceTree,
    ib: InsertBuffers,
    start_leaf: jnp.ndarray,
    khi: jnp.ndarray,
    klo: jnp.ndarray,
    *,
    limit: int,
    max_leaves: int = 4,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, ScanCursor]:
    """RANGE(k_min, limit) for a wave, starting the leaf-chain walk at
    ``start_leaf`` (a descent result, a cached scan anchor, or a
    continuation cursor — all the same representation; ``-1`` marks a dead
    lane that returns empty and untruncated).

    Returns (keys (B,limit,2), vals (B,limit,2), valid (B,limit),
    truncated (B,), cursor): the first ``limit`` live pairs with key >=
    k_min in ascending key order.  The scan walks at most ``max_leaves``
    leaves via ``leaf_next`` — the analogue of the paper's re-descend-and-
    continue loop, bounded like its 64-pairs-per-response packetisation.
    Buffer entries override leaf entries and newer buffer entries override
    older ones (same visibility rule as GET).

    ``truncated`` is True iff the chain continues past the walked window
    AND fewer than ``limit`` entries were returned — i.e. the response is
    genuinely incomplete because of the leaf bound, not because the shard's
    slice ran out (``truncated=False`` with a short row means *exhausted*;
    the scatter-gather epilogue uses exactly this distinction).  A
    truncated row emitted every survivor of its window, so resuming at
    ``cursor.leaf`` with the original ``k_min`` is exact.
    """
    assert limit >= 1, "limit=0 is guarded by the callers"
    cap = ib.keys.shape[1]
    B = khi.shape[0]

    def gather_leaf(leaf, alive):
        """Candidate entries of one leaf (leaf array + insert buffer)."""
        slot = tree.leaf_slot[leaf]
        lk = tree.hbm_keys[slot]  # (B,128,2)
        lv = tree.hbm_vals[slot]
        lcnt = tree.leaf_count[leaf]
        lvalid = (jnp.arange(lk.shape[1])[None, :] < lcnt[:, None]) & alive[:, None]
        bk = ib.keys[leaf]
        bv = ib.vals[leaf]
        bop = ib.op[leaf]
        bcnt = ib.count[leaf]
        bvalid = (
            (jnp.arange(cap)[None, :] < bcnt[:, None])
            & (bop != IB_EMPTY)
            & alive[:, None]
        )
        keys_h = jnp.concatenate([lk[:, :, 0], bk[:, :, 0]], axis=1)
        keys_l = jnp.concatenate([lk[:, :, 1], bk[:, :, 1]], axis=1)
        vals_h = jnp.concatenate([lv[:, :, 0], bv[:, :, 0]], axis=1)
        vals_l = jnp.concatenate([lv[:, :, 1], bv[:, :, 1]], axis=1)
        valid = jnp.concatenate([lvalid, bvalid], axis=1)
        # priority: leaf entries 0; buffer entry j gets j+1 (newest wins).
        prio = jnp.concatenate(
            [
                jnp.zeros((B, lk.shape[1]), dtype=jnp.int32),
                jnp.broadcast_to(jnp.arange(1, cap + 1, dtype=jnp.int32), (B, cap)),
            ],
            axis=1,
        )
        is_del = jnp.concatenate(
            [jnp.zeros((B, lk.shape[1]), dtype=bool), bop == IB_DEL], axis=1
        )
        return keys_h, keys_l, vals_h, vals_l, valid, prio, is_del

    parts = []
    leaf = start_leaf
    alive = start_leaf >= 0
    for _ in range(max_leaves):
        safe = jnp.maximum(leaf, 0)
        parts.append(gather_leaf(safe, alive))
        nxt = tree.leaf_next[safe]
        alive = alive & (nxt >= 0)
        leaf = nxt
    # after the walk: ``alive`` <=> an unwalked successor exists (= ``leaf``)

    keys_h = jnp.concatenate([p[0] for p in parts], axis=1)
    keys_l = jnp.concatenate([p[1] for p in parts], axis=1)
    vals_h = jnp.concatenate([p[2] for p in parts], axis=1)
    vals_l = jnp.concatenate([p[3] for p in parts], axis=1)
    valid = jnp.concatenate([p[4] for p in parts], axis=1)
    prio = jnp.concatenate([p[5] for p in parts], axis=1)
    is_del = jnp.concatenate([p[6] for p in parts], axis=1)

    # drop entries below k_min or invalid by forcing their key to KEY_MAX
    ge_min = limb_le(khi[:, None], klo[:, None], keys_h, keys_l)
    live = valid & ge_min
    pad = jnp.uint32(0xFFFFFFFF)
    keys_h = jnp.where(live, keys_h, pad)
    keys_l = jnp.where(live, keys_l, pad)

    # sort each row by (key asc, priority desc); first occurrence of a key
    # is then its newest version.
    order = jnp.lexsort((-prio, keys_l, keys_h), axis=-1)
    keys_h = jnp.take_along_axis(keys_h, order, axis=1)
    keys_l = jnp.take_along_axis(keys_l, order, axis=1)
    vals_h = jnp.take_along_axis(vals_h, order, axis=1)
    vals_l = jnp.take_along_axis(vals_l, order, axis=1)
    live = jnp.take_along_axis(live, order, axis=1)
    is_del = jnp.take_along_axis(is_del, order, axis=1)

    first = jnp.concatenate(
        [
            jnp.ones((B, 1), dtype=bool),
            (keys_h[:, 1:] != keys_h[:, :-1]) | (keys_l[:, 1:] != keys_l[:, :-1]),
        ],
        axis=1,
    )
    keep = live & first & ~is_del

    # compact kept entries into the first `limit` output columns, in order
    target = jnp.cumsum(keep.astype(jnp.int32), axis=1) - 1  # (B, M)
    in_out = keep & (target < limit)
    tgt = jnp.where(in_out, target, limit)  # overflow -> scratch column
    out_kh = jnp.full((B, limit + 1), pad, dtype=jnp.uint32)
    out_kl = jnp.full((B, limit + 1), pad, dtype=jnp.uint32)
    out_vh = jnp.zeros((B, limit + 1), dtype=jnp.uint32)
    out_vl = jnp.zeros((B, limit + 1), dtype=jnp.uint32)
    rows = jnp.arange(B)[:, None]
    out_kh = out_kh.at[rows, tgt].set(jnp.where(in_out, keys_h, pad))
    out_kl = out_kl.at[rows, tgt].set(jnp.where(in_out, keys_l, pad))
    out_vh = out_vh.at[rows, tgt].set(jnp.where(in_out, vals_h, 0))
    out_vl = out_vl.at[rows, tgt].set(jnp.where(in_out, vals_l, 0))
    n_found = jnp.minimum(jnp.sum(keep, axis=1), limit)
    out_valid = jnp.arange(limit)[None, :] < n_found[:, None]
    out_keys = jnp.stack([out_kh[:, :limit], out_kl[:, :limit]], axis=-1)
    out_vals = jnp.stack([out_vh[:, :limit], out_vl[:, :limit]], axis=-1)
    truncated = alive & (n_found < limit)
    cursor = make_cursor(khi, klo, out_keys, n_found, leaf, truncated)
    return out_keys, out_vals, out_valid, truncated, cursor


# ---------------------------------------------------------------------------
# in-mesh continuation loop: re-walk only truncated lanes from their cursor,
# entirely on device (jax.lax.while_loop), so a multi-round scan costs one
# dispatch — the paper's re-descend-and-continue loop with every host
# round-trip removed (the DPA-to-host hop is what dominates tail latency).
# ---------------------------------------------------------------------------


def continuation_loop(
    round_fn,
    start_leaf: jnp.ndarray,
    khi: jnp.ndarray,
    klo: jnp.ndarray,
    ub_hi: jnp.ndarray,
    ub_lo: jnp.ndarray,
    *,
    limit: int,
    max_rounds: int = 0,
    hard_cap: int,
    advance_kmin: bool = False,
):
    """Drive ``round_fn`` (one bounded walk: ``(start, khi, klo) -> (keys,
    vals, valid, truncated, cursor)``) inside a ``jax.lax.while_loop`` until
    every lane hit ``limit``, exhausted its chain, or ran into its owned
    window — the device-resident analogue of the host re-issue loop.

    ``advance_kmin`` (versioned scans): after each round, a lane's ``k_min``
    moves to its last emitted key + 1.  A versioned round reads each walked
    leaf through its resolved ancestor, whose key range can reach *below*
    the walked window and so re-cover keys an earlier round already emitted
    — the k_min advance is what keeps rounds disjoint.  Correct because an
    active (truncated, under-limit) lane emitted EVERY snapshot key >= its
    k_min inside the walked window, so the next window's survivors are all
    strictly greater.  Live scans keep ``k_min`` fixed (resume-at-cursor is
    already exact for leaf-local buffers).

    Per round, per lane: the walk resumes at the lane's cursor leaf with the
    original ``k_min`` (exact — see :class:`ScanCursor`), its results are
    clipped to the lane's owned window ``[.., ub)`` (clipping proves the
    window is exhausted, so ``truncated`` is cleared — steady-state no-op at
    the KEY_MAX sentinel), and survivors are appended to the lane's
    accumulator row.  Only lanes still ``truncated`` with room left stay
    active; inactive lanes ride along dead (``start=-1`` walks are empty).

    ``max_rounds=0`` loops until quiescence (bounded by ``hard_cap``, the
    chain-length ceiling — each active lane advances >= ``max_leaves``
    leaves per round); ``max_rounds>=1`` stops early and reports the
    leftover lanes ``truncated`` with a live resume cursor, which is what
    keeps the bounded-round contract of ``range_with_state`` intact.

    Returns (keys (B,limit,2), vals, valid, truncated, cursor, rounds) with
    the exact output conventions of :func:`range_batch_from` (pad keys /
    zero vals in dead columns) plus the executed round count (i32 scalar).
    """
    B = khi.shape[0]
    cap_rounds = hard_cap if max_rounds <= 0 else min(max_rounds, hard_cap)
    pad = jnp.uint32(0xFFFFFFFF)
    rows = jnp.arange(B)[:, None]
    cols = jnp.arange(limit, dtype=jnp.int32)[None, :]

    def cond(st):
        return jnp.any(st["active"]) & (st["rounds"] < cap_rounds)

    def body(st):
        start = jnp.where(st["active"], st["cur"], jnp.int32(-1))
        rk, rv, rvalid, rtrunc, cursor = round_fn(start, st["khi"], st["klo"])
        # owned-window clip, per round: entries at/above the lane's ub are
        # dropped and prove the window exhausted (clear ``truncated`` — the
        # continuation belongs to whoever owns the successor window)
        beyond = limb_le(ub_hi[:, None], ub_lo[:, None], rk[..., 0], rk[..., 1])
        clipped = rvalid & beyond
        rvalid = rvalid & ~beyond
        rtrunc = rtrunc & ~jnp.any(clipped, axis=1)
        rc = jnp.sum(rvalid, axis=1)
        # append the round's survivors at each lane's fill level
        tgt = st["acc_n"][:, None] + cols
        put = rvalid & (tgt < limit)
        tgt = jnp.where(put, tgt, limit)  # overflow -> scratch column
        acc_kh = st["acc_kh"].at[rows, tgt].set(jnp.where(put, rk[..., 0], pad))
        acc_kl = st["acc_kl"].at[rows, tgt].set(jnp.where(put, rk[..., 1], pad))
        acc_vh = st["acc_vh"].at[rows, tgt].set(jnp.where(put, rv[..., 0], 0))
        acc_vl = st["acc_vl"].at[rows, tgt].set(jnp.where(put, rv[..., 1], 0))
        acc_n = jnp.minimum(st["acc_n"] + rc, limit)
        active = st["active"] & rtrunc & (acc_n < limit)
        nkhi, nklo = st["khi"], st["klo"]
        if advance_kmin:
            # last emitted key + 1 (u32 limbs with carry); lanes that
            # emitted nothing this round keep their k_min unchanged
            lo1 = cursor.klo + jnp.uint32(1)
            hi1 = cursor.khi + (lo1 == 0).astype(jnp.uint32)
            emitted = rc > 0
            nklo = jnp.where(emitted, lo1, nklo)
            nkhi = jnp.where(emitted, hi1, nkhi)
        return dict(
            acc_kh=acc_kh,
            acc_kl=acc_kl,
            acc_vh=acc_vh,
            acc_vl=acc_vl,
            acc_n=acc_n,
            cur=cursor.leaf,
            khi=nkhi,
            klo=nklo,
            active=active,
            rounds=st["rounds"] + 1,
        )

    st = jax.lax.while_loop(
        cond,
        body,
        dict(
            acc_kh=jnp.full((B, limit + 1), pad, dtype=jnp.uint32),
            acc_kl=jnp.full((B, limit + 1), pad, dtype=jnp.uint32),
            acc_vh=jnp.zeros((B, limit + 1), dtype=jnp.uint32),
            acc_vl=jnp.zeros((B, limit + 1), dtype=jnp.uint32),
            acc_n=jnp.zeros((B,), dtype=jnp.int32),
            cur=start_leaf.astype(jnp.int32),
            khi=khi,
            klo=klo,
            active=jnp.ones((B,), dtype=bool),
            rounds=jnp.int32(0),
        ),
    )
    out_keys = jnp.stack([st["acc_kh"][:, :limit], st["acc_kl"][:, :limit]], axis=-1)
    out_vals = jnp.stack([st["acc_vh"][:, :limit], st["acc_vl"][:, :limit]], axis=-1)
    out_valid = cols < st["acc_n"][:, None]
    truncated = st["active"]  # only a bounded max_rounds leaves lanes active
    cursor = make_cursor(
        khi, klo, out_keys, st["acc_n"], st["cur"], truncated
    )
    return out_keys, out_vals, out_valid, truncated, cursor, st["rounds"]


@partial(jax.jit, static_argnames=("limit", "max_leaves", "max_rounds"))
def range_batch_loop(
    tree: DeviceTree,
    ib: InsertBuffers,
    start_leaf: jnp.ndarray,
    khi: jnp.ndarray,
    klo: jnp.ndarray,
    ub_hi: jnp.ndarray,
    ub_lo: jnp.ndarray,
    *,
    limit: int,
    max_leaves: int = 4,
    max_rounds: int = 0,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, ScanCursor, jnp.ndarray]:
    """Multi-round RANGE in ONE device dispatch: :func:`range_batch_from`
    rounds driven by :func:`continuation_loop`.  ``ub_hi``/``ub_lo`` are
    per-lane exclusive owned-window upper bounds (KEY_MAX limbs = no clip:
    real keys never reach the sentinel); ``start_leaf`` is a descent
    result / cached anchor / resume cursor (-1 = dead lane).  See
    :func:`continuation_loop` for the round invariants and outputs."""
    n_leaves = tree.leaf_next.shape[0]
    hard_cap = n_leaves // max(max_leaves, 1) + 2

    def round_fn(start, h, l):
        return range_batch_from(
            tree, ib, start, h, l, limit=limit, max_leaves=max_leaves
        )

    return continuation_loop(
        round_fn,
        start_leaf,
        khi,
        klo,
        ub_hi,
        ub_lo,
        limit=limit,
        max_rounds=max_rounds,
        hard_cap=hard_cap,
    )


@partial(jax.jit, static_argnames=("depth", "eps_inner", "limit", "max_leaves"))
def range_batch(
    tree: DeviceTree,
    ib: InsertBuffers,
    khi: jnp.ndarray,
    klo: jnp.ndarray,
    *,
    depth: int,
    eps_inner: int,
    limit: int,
    max_leaves: int = 4,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, ScanCursor]:
    """Descend-then-scan RANGE: ``traverse`` to the floor leaf, then the
    bounded walk of :func:`range_batch_from` (see there for the output
    contract incl. ``truncated`` + resume cursor).  The anchor-cached store
    path skips this wrapper and calls ``range_batch_from`` directly with
    cached anchors — that skip IS the cache's payoff.

    Edge cases (exercised in tests/test_range_shard.py): a ``k_min`` above
    the largest key routes to the last leaf and returns an empty window; a
    ``k_min`` inside a gap returns the successor keys; ``limit`` must be
    >= 1 (callers guard ``limit == 0`` — ``store.range`` / ``ops.range_scan``
    short-circuit it host-side to keep the jit cache free of degenerate
    shapes).
    """
    start_leaf = traverse(tree, khi, klo, depth=depth, eps_inner=eps_inner)
    return range_batch_from(
        tree, ib, start_leaf, khi, klo, limit=limit, max_leaves=max_leaves
    )


# ---------------------------------------------------------------------------
# point-in-time reads (as_of=epoch): serve a frozen snapshot through the
# CURRENT tree.  The store builds a host-side *resolve table* for epoch E —
# res_table[l] walks TreeImage.ver_prev while ver_birth > E — so the device
# side is one extra gather per leaf visit: traverse/walk the live structure,
# read each visited leaf's content through its resolved ancestor.  Freed
# leaf/slot rows are never overwritten by stitch COPYs (new ids only) and
# EpochManager.retain keeps every reachable ancestor un-recycled, so the
# ancestor's device rows still hold the epoch-E bytes.  Insert buffers are
# skipped: a version epoch is a *stitched* state (snapshot_epoch flushes).
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("depth", "eps_inner", "eps_leaf"))
def get_batch_versioned(
    tree: DeviceTree,
    res_table: jnp.ndarray,
    khi: jnp.ndarray,
    klo: jnp.ndarray,
    *,
    depth: int,
    eps_inner: int,
    eps_leaf: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """GET against the epoch pinned by ``res_table``: traverse the CURRENT
    index (a replacement leaf's key range is always covered by the leaf it
    replaced, so the live descent lands inside the right ancestor chain),
    resolve the leaf to its epoch-E version, probe that leaf's HBM row."""
    leaf = traverse(tree, khi, klo, depth=depth, eps_inner=eps_inner)
    leaf = res_table[leaf]
    _, found, vhi, vlo = leaf_search(tree, leaf, khi, klo, eps_leaf)
    return vhi, vlo, found


@partial(jax.jit, static_argnames=("limit", "max_leaves"))
def range_batch_from_versioned(
    tree: DeviceTree,
    res_table: jnp.ndarray,
    start_leaf: jnp.ndarray,
    khi: jnp.ndarray,
    klo: jnp.ndarray,
    *,
    limit: int,
    max_leaves: int = 4,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, ScanCursor]:
    """One bounded versioned walk: follow the CURRENT ``leaf_next`` chain
    (so the walk always terminates and covers the live key space) but gather
    each visited leaf's *content* from its epoch-E resolved ancestor.

    Resolved ancestors of adjacent live leaves can overlap (several current
    leaves resolving into one wide ancestor): in-round duplicates are killed
    by the sort + first-occurrence dedup below; cross-round duplicates by
    the driver's ``advance_kmin`` (see :func:`continuation_loop`).  No
    insert-buffer overlay and no tombstones — the snapshot is a stitched
    state."""
    assert limit >= 1, "limit=0 is guarded by the callers"
    B = khi.shape[0]

    def gather_leaf(leaf, alive):
        r = res_table[leaf]
        slot = tree.leaf_slot[r]
        lk = tree.hbm_keys[slot]  # (B,128,2) — epoch-E bytes (rows survive)
        lv = tree.hbm_vals[slot]
        lcnt = tree.leaf_count[r]
        lvalid = (
            jnp.arange(lk.shape[1])[None, :] < lcnt[:, None]
        ) & alive[:, None]
        return lk[:, :, 0], lk[:, :, 1], lv[:, :, 0], lv[:, :, 1], lvalid

    parts = []
    leaf = start_leaf
    alive = start_leaf >= 0
    for _ in range(max_leaves):
        safe = jnp.maximum(leaf, 0)
        parts.append(gather_leaf(safe, alive))
        nxt = tree.leaf_next[safe]
        alive = alive & (nxt >= 0)
        leaf = nxt

    keys_h = jnp.concatenate([p[0] for p in parts], axis=1)
    keys_l = jnp.concatenate([p[1] for p in parts], axis=1)
    vals_h = jnp.concatenate([p[2] for p in parts], axis=1)
    vals_l = jnp.concatenate([p[3] for p in parts], axis=1)
    valid = jnp.concatenate([p[4] for p in parts], axis=1)

    ge_min = limb_le(khi[:, None], klo[:, None], keys_h, keys_l)
    live = valid & ge_min
    pad = jnp.uint32(0xFFFFFFFF)
    keys_h = jnp.where(live, keys_h, pad)
    keys_l = jnp.where(live, keys_l, pad)

    order = jnp.lexsort((keys_l, keys_h), axis=-1)
    keys_h = jnp.take_along_axis(keys_h, order, axis=1)
    keys_l = jnp.take_along_axis(keys_l, order, axis=1)
    vals_h = jnp.take_along_axis(vals_h, order, axis=1)
    vals_l = jnp.take_along_axis(vals_l, order, axis=1)
    live = jnp.take_along_axis(live, order, axis=1)

    first = jnp.concatenate(
        [
            jnp.ones((B, 1), dtype=bool),
            (keys_h[:, 1:] != keys_h[:, :-1]) | (keys_l[:, 1:] != keys_l[:, :-1]),
        ],
        axis=1,
    )
    keep = live & first

    target = jnp.cumsum(keep.astype(jnp.int32), axis=1) - 1
    in_out = keep & (target < limit)
    tgt = jnp.where(in_out, target, limit)
    out_kh = jnp.full((B, limit + 1), pad, dtype=jnp.uint32)
    out_kl = jnp.full((B, limit + 1), pad, dtype=jnp.uint32)
    out_vh = jnp.zeros((B, limit + 1), dtype=jnp.uint32)
    out_vl = jnp.zeros((B, limit + 1), dtype=jnp.uint32)
    rows = jnp.arange(B)[:, None]
    out_kh = out_kh.at[rows, tgt].set(jnp.where(in_out, keys_h, pad))
    out_kl = out_kl.at[rows, tgt].set(jnp.where(in_out, keys_l, pad))
    out_vh = out_vh.at[rows, tgt].set(jnp.where(in_out, vals_h, 0))
    out_vl = out_vl.at[rows, tgt].set(jnp.where(in_out, vals_l, 0))
    n_found = jnp.minimum(jnp.sum(keep, axis=1), limit)
    out_valid = jnp.arange(limit)[None, :] < n_found[:, None]
    out_keys = jnp.stack([out_kh[:, :limit], out_kl[:, :limit]], axis=-1)
    out_vals = jnp.stack([out_vh[:, :limit], out_vl[:, :limit]], axis=-1)
    truncated = alive & (n_found < limit)
    cursor = make_cursor(khi, klo, out_keys, n_found, leaf, truncated)
    return out_keys, out_vals, out_valid, truncated, cursor


@partial(jax.jit, static_argnames=("limit", "max_leaves", "max_rounds"))
def range_batch_loop_versioned(
    tree: DeviceTree,
    res_table: jnp.ndarray,
    start_leaf: jnp.ndarray,
    khi: jnp.ndarray,
    klo: jnp.ndarray,
    ub_hi: jnp.ndarray,
    ub_lo: jnp.ndarray,
    *,
    limit: int,
    max_leaves: int = 4,
    max_rounds: int = 0,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, ScanCursor, jnp.ndarray]:
    """Multi-round versioned RANGE in ONE device dispatch — the ``as_of``
    analogue of :func:`range_batch_loop`: :func:`range_batch_from_versioned`
    rounds driven by :func:`continuation_loop` with the k_min advance on
    (rounds stay disjoint even though resolved ancestors overlap)."""
    n_leaves = tree.leaf_next.shape[0]
    hard_cap = n_leaves // max(max_leaves, 1) + 2

    def round_fn(start, h, l):
        return range_batch_from_versioned(
            tree, res_table, start, h, l, limit=limit, max_leaves=max_leaves
        )

    return continuation_loop(
        round_fn,
        start_leaf,
        khi,
        klo,
        ub_hi,
        ub_lo,
        limit=limit,
        max_rounds=max_rounds,
        hard_cap=hard_cap,
        advance_kmin=True,
    )
