"""The GET walk's row-and-mask window search equals the row-then-pick one.

``core/lookup.py`` fetches each request's 128-key row once and searches the
eps window inside it with a lane mask (which also says whether the window
holds the key), gathers the anchor, child and values one entry each, and
reads only the newest buffered value.  The walk it replaced gathered the
window out of the fetched row one index per key, picked the matched key, the
anchor, the child pointer and the buffer's op and value with further
gathers, and fetched the buffer's whole value row.  A copy of that walk is
kept here; every output of the new one must equal it bitwise, on synthetic
pools built for the edge cases and on small stores with staged writes.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import DPAStore, TreeConfig, lookup
from repro.core.datasets import sparse
from repro.core.keys import KEY_MAX, limb_eq, limb_le, split_u64
from repro.core.lookup import IB_DEL, IB_EMPTY, IB_PUT, InsertBuffers, _predict
from repro.core.tree import DeviceTree, SEG_CAP

EPS = [1, 4, 8]


# ---------------------------------------------------------------------------
# the row-then-pick walk, as it was
# ---------------------------------------------------------------------------


def old_window_rank(pool_keys, slot, count, pred, eps, khi, klo):
    w = 2 * eps + 2
    lo = jnp.clip(jnp.floor(pred).astype(jnp.int32) - eps, 0, jnp.maximum(count - w, 0))
    idx = lo[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
    rows = pool_keys[slot]
    wk = jnp.take_along_axis(rows, idx[:, :, None], axis=1)
    le = limb_le(wk[:, :, 0], wk[:, :, 1], khi[:, None], klo[:, None])
    c = jnp.sum((le & (idx < count[:, None])).astype(jnp.int32), axis=1)
    return lo + c - 1, lo


def old_route_one_level(tree, node, khi, klo, eps):
    sf = tree.node_seg_first[node]
    le = limb_le(sf[:, :, 0], sf[:, :, 1], khi[:, None], klo[:, None])
    seg = jnp.maximum(jnp.sum(le[:, 1:].astype(jnp.int32), axis=1), 0)
    bidx = jnp.arange(node.shape[0])
    slope = tree.node_seg_slope[node, seg]
    count = tree.node_seg_count[node, seg]
    slot = tree.node_seg_slot[node, seg]
    pred = _predict(slope, sf[bidx, seg, 0], sf[bidx, seg, 1], khi, klo)
    rank, _ = old_window_rank(tree.pivot_keys, slot, count, pred, eps, khi, klo)
    rank = jnp.maximum(rank, 0)
    return jnp.take_along_axis(tree.pivot_child[slot], rank[:, None], axis=1)[:, 0]


def old_traverse(tree, khi, klo, depth, eps_inner):
    node = jnp.broadcast_to(tree.root, khi.shape).astype(jnp.int32)
    for _ in range(depth - 1):
        node = old_route_one_level(tree, node, khi, klo, eps_inner)
    return node


def old_leaf_search(tree, leaf, khi, klo, eps_leaf):
    slot = tree.leaf_slot[leaf]
    count = tree.leaf_count[leaf]
    anchor = tree.leaf_anchor[leaf]
    pred = _predict(tree.leaf_slope[leaf], anchor[:, 0], anchor[:, 1], khi, klo)
    rank, _ = old_window_rank(tree.hbm_keys, slot, count, pred, eps_leaf, khi, klo)
    safe = jnp.maximum(rank, 0)
    kk = jnp.take_along_axis(tree.hbm_keys[slot], safe[:, None, None].repeat(2, -1), axis=1)[:, 0]
    found = (rank >= 0) & limb_eq(kk[:, 0], kk[:, 1], khi, klo)
    vv = jnp.take_along_axis(tree.hbm_vals[slot], safe[:, None, None].repeat(2, -1), axis=1)[:, 0]
    return rank, found, vv[:, 0], vv[:, 1]


def old_ib_search(ib, leaf, khi, klo):
    bk, bv, bop, cnt = ib.keys[leaf], ib.vals[leaf], ib.op[leaf], ib.count[leaf]
    pos = jnp.arange(bk.shape[1], dtype=jnp.int32)[None, :]
    match = (
        limb_eq(bk[:, :, 0], bk[:, :, 1], khi[:, None], klo[:, None])
        & (pos < cnt[:, None])
        & (bop != IB_EMPTY)
    )
    newest = jnp.max(jnp.where(match, pos, -1), axis=1)
    has = newest >= 0
    safe = jnp.maximum(newest, 0)
    op = jnp.take_along_axis(bop, safe[:, None], axis=1)[:, 0]
    v = jnp.take_along_axis(bv, safe[:, None, None].repeat(2, -1), axis=1)[:, 0]
    return has & (op == IB_PUT), has & (op == IB_DEL), v[:, 0], v[:, 1]


def old_get_batch(tree, ib, khi, klo, depth, eps_inner, eps_leaf):
    leaf = old_traverse(tree, khi, klo, depth, eps_inner)
    present, deleted, ivh, ivl = old_ib_search(ib, leaf, khi, klo)
    _, tfound, tvh, tvl = old_leaf_search(tree, leaf, khi, klo, eps_leaf)
    found = present | (tfound & ~deleted)
    return jnp.where(present, ivh, tvh), jnp.where(present, ivl, tvl), found


def old_get_batch_versioned(tree, res_table, khi, klo, depth, eps_inner, eps_leaf):
    leaf = res_table[old_traverse(tree, khi, klo, depth, eps_inner)]
    _, found, vhi, vlo = old_leaf_search(tree, leaf, khi, klo, eps_leaf)
    return vhi, vlo, found


def assert_same(new, old):
    assert len(new) == len(old)
    for a, b in zip(new, old):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a, b)


def limbs(keys):
    lm = split_u64(np.asarray(keys, dtype=np.uint64))
    return jnp.asarray(lm[:, 0]), jnp.asarray(lm[:, 1])


# ---------------------------------------------------------------------------
# synthetic pools: every edge case on purpose
# ---------------------------------------------------------------------------


def synthetic_rows(rng, n_rows, eps):
    """Rows of sorted unique u64 keys padded with KEY_MAX past ``count``;
    counts cover empty rows, rows shorter than the window and full rows."""
    w = 2 * eps + 2
    counts = np.concatenate(
        [np.arange(0, w + 1), [SEG_CAP, SEG_CAP - 1], rng.integers(0, SEG_CAP + 1, n_rows)]
    )[:n_rows].astype(np.int32)
    keys = np.full((n_rows, SEG_CAP), KEY_MAX, dtype=np.uint64)
    for r, c in enumerate(counts):
        keys[r, :c] = np.sort(rng.choice(2**62, size=c, replace=False).astype(np.uint64) + 1000)
    return keys, counts


def synthetic_queries(rng, keys, counts, slot):
    """Per lane: a stored key, a key between two entries, one below the
    row's first key, one above its last, 0, and KEY_MAX - 1."""
    kind = rng.integers(0, 6, slot.size)
    q = np.empty(slot.size, dtype=np.uint64)
    for i, (s, k) in enumerate(zip(slot, kind)):
        c = counts[s]
        row = keys[s]
        if k == 0 and c:
            q[i] = row[rng.integers(0, c)]
        elif k == 1 and c:
            q[i] = row[rng.integers(0, c)] + np.uint64(1)
        elif k == 2 and c:
            q[i] = row[0] - np.uint64(1)
        elif k == 3 and c:
            q[i] = row[c - 1] + np.uint64(7)
        elif k == 4:
            q[i] = 0
        else:
            q[i] = KEY_MAX - np.uint64(1)
    return q


def synthetic_preds(rng, n, counts, slot):
    """Predictions at 0, inside the row, at and past ``count``, past the row,
    and negative (the clamp's lower side)."""
    c = counts[slot].astype(np.float32)
    pick = rng.integers(0, 6, n)
    return np.select(
        [pick == 0, pick == 1, pick == 2, pick == 3, pick == 4],
        [
            np.zeros(n, np.float32),
            rng.uniform(0, np.maximum(c, 1)).astype(np.float32),
            c,
            c + rng.uniform(1, 400, n).astype(np.float32),
            np.full(n, 1e6, np.float32),
        ],
        rng.uniform(-50, 0, n).astype(np.float32),
    ).astype(np.float32)


@pytest.mark.parametrize("eps", EPS)
def test_window_rank_matches_row_then_pick(eps):
    rng = np.random.default_rng(100 + eps)
    keys, counts = synthetic_rows(rng, 64, eps)
    pool = jnp.asarray(split_u64(keys.reshape(-1)).reshape(64, SEG_CAP, 2))
    n = 4096
    slot = rng.integers(0, 64, n).astype(np.int32)
    q = synthetic_queries(rng, keys, counts, slot)
    pred = synthetic_preds(rng, n, counts, slot)
    khi, klo = limbs(q)
    args = (jnp.asarray(slot), jnp.asarray(counts[slot]), jnp.asarray(pred), eps, khi, klo)
    rank, hit = lookup._window_rank(pool, *args)
    old_rank, lo = old_window_rank(pool, *args)
    kk = np.asarray(pool)[slot, np.maximum(np.asarray(old_rank), 0)]
    old_hit = (np.asarray(old_rank) >= 0) & (kk[:, 0] == np.asarray(khi)) & (kk[:, 1] == np.asarray(klo))
    assert_same([rank, hit], [old_rank, old_hit])
    assert old_hit.any() and (~old_hit).any()
    # a prediction that misses the key by one: it sits just before the window
    assert (old_hit & (np.asarray(old_rank) == np.asarray(lo) - 1)).any()
    # every case the walk meets: rank -1 (key below the window), ranks
    # inside short rows, and the window's last slot
    r = np.asarray(rank)
    assert (r == -1).any() and (r >= 0).any()
    assert ((counts[slot] < 2 * eps + 2) & (r >= 0)).any()


def synthetic_tree(rng, eps):
    """A DeviceTree whose leaves are the synthetic rows; each leaf's model
    (anchor, slope) is random, so predictions land at 0, inside, at and past
    ``count``; the inner pools are unused by ``leaf_search``."""
    n = 96
    keys, counts = synthetic_rows(rng, n, eps)
    vals = rng.integers(0, 2**63, (n, SEG_CAP), dtype=np.uint64)
    slots = rng.permutation(n).astype(np.int32)  # leaf -> slot, not identity
    anchor = np.where(counts[slots] > 0, keys[slots, 0], np.uint64(0))
    anchor[::5] = KEY_MAX - np.uint64(1)  # every key is below it: pred 0
    slope = rng.choice(
        [0.0, 1e-18, 1e-16, 1e-12, 1.0], size=n, p=[0.1, 0.3, 0.3, 0.2, 0.1]
    ).astype(np.float32)
    z = jnp.zeros((1,), jnp.int32)
    tree = DeviceTree(
        root=jnp.int32(0),
        node_seg_first=jnp.zeros((1, 7, 2), jnp.uint32),
        node_seg_slope=jnp.zeros((1, 7), jnp.float32),
        node_seg_count=jnp.zeros((1, 7), jnp.int32),
        node_seg_slot=jnp.zeros((1, 7), jnp.int32),
        pivot_keys=jnp.zeros((1, SEG_CAP, 2), jnp.uint32),
        pivot_child=jnp.zeros((1, SEG_CAP), jnp.int32),
        leaf_anchor=jnp.asarray(split_u64(anchor)),
        leaf_slope=jnp.asarray(slope),
        leaf_count=jnp.asarray(counts[slots]),
        leaf_slot=jnp.asarray(slots),
        leaf_next=z,
        hbm_keys=jnp.asarray(split_u64(keys.reshape(-1)).reshape(n, SEG_CAP, 2)),
        hbm_vals=jnp.asarray(split_u64(vals.reshape(-1)).reshape(n, SEG_CAP, 2)),
    )
    return tree, keys, counts, slots


@pytest.mark.parametrize("eps", EPS)
def test_leaf_search_matches_row_then_pick(eps):
    rng = np.random.default_rng(200 + eps)
    tree, keys, counts, slots = synthetic_tree(rng, eps)
    leaf = rng.integers(0, slots.size, 4096).astype(np.int32)
    khi, klo = limbs(synthetic_queries(rng, keys, counts, slots[leaf]))
    new = lookup.leaf_search(tree, jnp.asarray(leaf), khi, klo, eps)
    old = old_leaf_search(tree, jnp.asarray(leaf), khi, klo, eps)
    assert_same(new, old)
    rank, found = np.asarray(new[0]), np.asarray(new[1])
    assert found.any() and (~found).any() and (rank == -1).any()


@pytest.mark.parametrize("cap", [1, 16])
def test_ib_search_matches_row_then_pick(cap):
    """Buffers with tombstones, repeated keys (newest wins), empty slots
    inside the count, and entries past the count that must be ignored."""
    rng = np.random.default_rng(300 + cap)
    n_leaves = 40
    universe = rng.integers(0, 2**63, 12, dtype=np.uint64)  # few keys: repeats
    bkeys = rng.choice(universe, size=(n_leaves, cap))
    bvals = rng.integers(0, 2**63, (n_leaves, cap), dtype=np.uint64)
    op = rng.choice([IB_PUT, IB_DEL, IB_EMPTY], size=(n_leaves, cap), p=[0.5, 0.35, 0.15])
    count = rng.integers(0, cap + 1, n_leaves).astype(np.int32)
    ib = InsertBuffers(
        keys=jnp.asarray(split_u64(bkeys.reshape(-1)).reshape(n_leaves, cap, 2)),
        vals=jnp.asarray(split_u64(bvals.reshape(-1)).reshape(n_leaves, cap, 2)),
        op=jnp.asarray(op.astype(np.int32)),
        count=jnp.asarray(count),
    )
    n = 4096
    leaf = jnp.asarray(rng.integers(0, n_leaves, n).astype(np.int32))
    q = np.where(rng.random(n) < 0.9, rng.choice(universe, n), rng.integers(0, 2**63, n, dtype=np.uint64))
    khi, klo = limbs(q)
    new = lookup.ib_search(ib, leaf, khi, klo)
    assert_same(new, old_ib_search(ib, leaf, khi, klo))
    present, deleted = np.asarray(new[0]), np.asarray(new[1])
    assert present.any() and deleted.any() and (~present & ~deleted).any()


# ---------------------------------------------------------------------------
# whole walks over small stores with staged writes
# ---------------------------------------------------------------------------


def staged_store(eps, seed):
    """A bulk-loaded store, then waves of overwrites, deletes and re-puts of
    the same keys, so the insert buffers hold repeated keys and tombstones
    (flushes may run between waves; the buffers are whatever is left)."""
    rng = np.random.default_rng(seed)
    keys = sparse(6000, seed=seed)
    store = DPAStore(
        keys, keys ^ np.uint64(0x5A5A), TreeConfig(eps_inner=eps, eps_leaf=eps, ib_cap=16),
        cache_cfg=None, scan_cache_cfg=None,
    )
    hot = rng.choice(keys, 300, replace=False)
    fresh = np.setdiff1d(rng.integers(1, 2**63, 200, dtype=np.uint64), keys)
    for step in range(4):
        store.put(hot[step::4], hot[step::4] + np.uint64(step + 1))
        store.delete(hot[(step + 1) % 4 :: 8])
        store.put(hot[step::8], hot[step::8] + np.uint64(100 + step))
        store.put(fresh[step::4], fresh[step::4] + np.uint64(3))
    store.delete(fresh[::3])
    assert int(np.asarray(store.ib.count).sum()) > 0, "no staged writes left"
    q = np.concatenate([
        keys[rng.integers(0, keys.size, 1500)],
        hot, fresh,
        rng.integers(0, 2**63, 500, dtype=np.uint64),  # misses
        np.array([0, 1, keys[0] - 1, keys[0], keys[-1], keys[-1] + 1, KEY_MAX - 1], np.uint64),
    ])
    return store, q


@pytest.mark.parametrize("eps", EPS)
def test_get_batch_matches_row_then_pick(eps):
    store, q = staged_store(eps, seed=40 + eps)
    khi, klo = limbs(q)
    kw = dict(depth=store.depth, eps_inner=eps, eps_leaf=eps)
    new = lookup.get_batch(store.tree, store.ib, khi, klo, **kw)
    assert_same(new, old_get_batch(store.tree, store.ib, khi, klo, **kw))
    assert_same(
        [lookup.traverse(store.tree, khi, klo, depth=store.depth, eps_inner=eps)],
        [old_traverse(store.tree, khi, klo, store.depth, eps)],
    )
    found = np.asarray(new[2])
    assert found.any() and (~found).any()


@pytest.mark.parametrize("eps", EPS)
def test_get_batch_versioned_matches_row_then_pick(eps):
    """The identity table and a shuffled one (answers for the wrong leaf,
    which both walks must still agree on)."""
    store, q = staged_store(eps, seed=50 + eps)
    khi, klo = limbs(q)
    kw = dict(depth=store.depth, eps_inner=eps, eps_leaf=eps)
    n_leaves = store.tree.leaf_slot.shape[0]
    rng = np.random.default_rng(eps)
    for table in (np.arange(n_leaves), rng.permutation(n_leaves)):
        res = jnp.asarray(table.astype(np.int32))
        new = lookup.get_batch_versioned(store.tree, res, khi, klo, **kw)
        assert_same(new, old_get_batch_versioned(store.tree, res, khi, klo, **kw))
