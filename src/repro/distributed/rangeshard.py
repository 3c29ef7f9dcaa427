"""Range-partitioned distributed tier: boundary routing + scatter-gather RANGE.

Why a second partition.  The paper's headline RANGE result (13 MOPS at
limit=10) relies on leaves being chained in key order; the hash tier
(``kvshard``) deliberately destroys that order across shards, so a scan
there must broadcast to every shard and aggregate RANGE throughput can never
exceed one shard's.  This module keeps the *global* order: the u64 key space
is cut at quantile boundaries fitted over the loaded keys
(``core.pla.fit_boundaries`` — the empirical-CDF / learned-index view of
partitioning), each shard bulk-loads its contiguous slice into its own
``DPAStore``, and every request is routed by a boundary search that is
bit-identical between the numpy client (``np.searchsorted(b, k, 'right')``)
and the device wave (count of boundaries <= key in u32 limb arithmetic).

Scatter-gather RANGE.  A RANGE(k_min, limit) may spill past its owner
shard's slice, so the wave fans each request out to the owner and its
``fanout - 1`` successors (successors scan from their first leaf: k_min is
below their slice, and the bounded leaf-chain walk of
``lookup.range_batch`` / ``kernels.range_scan`` starts at the floor leaf).
Because shard slices are disjoint and ascending, the gather epilogue needs
no merge network: it concatenates each request's per-shard results in shard
order — already globally sorted — and compacts the first ``limit`` live
entries.  Fan-out replicas that run past the last shard are dropped at
bucketize time and count as trivially-complete empties.

RETRY semantics.  The exchange uses the same fixed per-shard-pair capacity
as the GET wave (``kvshard._bucketize``): a replica that overflows its
(src, dst) bucket is never silently lost — the request's ``ok`` flag comes
back False and the client re-sends, the batched analogue of the paper's
receive-queue overflow handling (Sec 3.1.3).  A request is ``ok`` only if
*every* in-range replica of its fan-out wave landed.

In-mesh continuation (exhausted vs bounded).  Each per-shard walk is
bounded by ``max_leaves`` — the paper's 64-pairs-per-response
packetisation — so a single walk can come back short for two very
different reasons: the slice ran out of keys (*exhausted* — the
successor's slice is the correct continuation) or the bounded walk was
cut mid-slice (*bounded* — stitching the successor would leave a gap).
``lookup.range_batch_from`` distinguishes them with a device-side
``truncated`` flag + resume cursor (last key + first unwalked leaf —
representationally a scan anchor, see ``core/scancache``).  The wave does
NOT hand that flag back to the host: ``lookup.range_batch_loop`` wraps
the walk in a ``jax.lax.while_loop`` that re-walks only truncated lanes
from their cursor, entirely between the two ``all_to_all`` exchanges —
no collectives inside the loop, so shards iterate independently and a
multi-round scan never leaves the mesh (the DPA-to-host hop it saves is
what dominates tail latency in the off-path SmartNIC measurements the
README cites).  The gather epilogue still (a) drops contributions past
the first truncated replica so the wave output is always an exact
ascending prefix of the oracle answer, and (b) surfaces per-request
``truncated`` — which with the default unbounded loop only fires on the
chain-length hard cap; the host facade's cursor resume survives solely as
that rare fallback (``max_rounds=1`` reproduces the old one-walk wave for
tests).  Each wave additionally reports per-shard ``rounds`` — the
round-trips the loop absorbed — which ``benchmarks/fig16_range.py``
records as ``rounds_in_mesh`` against the (steady-state zero) host
``reissues``.

Execution paths (mirroring ``kvshard``):

  * ``range_wave_emulated`` — vmap over the shard dim on one device; the
    exchange is a transpose.  CPU tests run this, asserting bit-equality
    with the host-orchestrated ``ShardedDPAStore.range`` and a single-store
    oracle.
  * ``range_wave_sharded`` — shard_map over the mesh 'data' axis with
    ``all_to_all`` exchanges (production / dry-run lowering).

Ownership windows + epoch tags (rebalance safety).  Every shard's RANGE
contribution is confined to its *owned* key window under the boundary
vector of the epoch each request was admitted under: requests carry an
``epoch_tag`` (0 = previous vector, 1 = current) that rides the
bucketize/all_to_all exchange next to the key limbs, successor replicas
scan from the destination's slice start under that epoch
(``_replicate``), and every round of the in-mesh loop clips entries
at/above the slice end with the ``truncated`` flag cleared (the clip
lives inside ``lookup.continuation_loop``).  All of it is a steady-state
no-op — a shard holds nothing outside its slice — but during an online
rebalance handoff (``distributed.rebalance``) a donor shard still
physically holds a migrated-away slice for one boundary epoch, and the
per-epoch window is what keeps that stale copy invisible to new-epoch
requests while old-epoch requests of the SAME wave still read it
(``route_range_epoch`` is the routing half; the production wave builders
take ``boundaries_prev`` + ``epoch_tag`` directly) — the two-phase
ownership analogue of the paper's transactional stitch-back.

Host-side orchestration (boundary fitting, per-shard ``DPAStore`` builds,
the sequential scatter-gather used by benchmarks — one
``range_with_state`` dispatch per shard with the same in-mesh loop and
per-epoch window clip, zero steady-state re-issues) lives on
``kvshard.ShardedDPAStore(partition="range")`` so both tiers share one
facade; each shard store also carries its own scan-anchor cache, so the
owner-shard descent of a repeated scan wave is skipped entirely.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import lookup
from repro.core.keys import limb_le, split_u64
from repro.distributed.kvshard import _bucketize


def boundary_limbs(boundaries: np.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(n_shards-1,) u64 boundary array -> device (hi, lo) u32 limb arrays."""
    limbs = split_u64(np.asarray(boundaries, dtype=np.uint64))
    return jnp.asarray(limbs[..., 0]), jnp.asarray(limbs[..., 1])


def replica_serving_stores(groups, primary) -> list:
    """The store that serves each shard group under a given primary map
    (``OwnershipTable.primary_for(epoch)``).  A crashed slot falls back to
    the group's first live replica — the pre-failover map legitimately
    points at the replica whose death opened the handoff, and any live
    replica is content-identical (synchronous write fan-out), so the wave
    results are bitwise the same under either live epoch — the property
    the failover tests pin down."""
    serving = []
    for g, p in zip(groups, primary):
        st = g[int(p)]
        if st is None:
            st = next((r for r in g if r is not None), None)
        assert st is not None, "shard group has no live replica"
        serving.append(st)
    return serving


def route_range(b_hi, b_lo, khi, klo):
    """Owner shard per request key: count of shard-start boundaries <= key
    (bit-identical to ``np.searchsorted(boundaries, key, side='right')``)."""
    if b_hi.shape[0] == 0:
        return jnp.zeros(khi.shape, dtype=jnp.int32)
    le = limb_le(b_hi[None, :], b_lo[None, :], khi[:, None], klo[:, None])
    return jnp.sum(le.astype(jnp.int32), axis=1)


def route_range_epoch(bp_hi, bp_lo, bc_hi, bc_lo, epoch_tag, khi, klo):
    """Two-phase ownership routing for a mixed in-flight wave.

    During a rebalance handoff two boundary vectors are live
    (``rebalance.OwnershipTable``); a wave whose requests were admitted
    under different epochs routes each request by exactly the vector of the
    epoch it carries (``epoch_tag``: 0 = previous vector, 1 = current) —
    the device analogue of ``OwnershipTable.route(keys, epoch=...)``, and
    the same admitted-epoch discipline the paper's packet-counter epochs
    give a stitch CONNECT."""
    d_prev = route_range(bp_hi, bp_lo, khi, klo)
    d_cur = route_range(bc_hi, bc_lo, khi, klo)
    return jnp.where(epoch_tag > 0, d_cur, d_prev)


def make_route_fn(boundaries: np.ndarray):
    """Device route_fn(khi, klo) for the GET wave paths in ``kvshard``."""
    b_hi, b_lo = boundary_limbs(boundaries)
    return partial(route_range, b_hi, b_lo)


def _replicate(bp_hi, bp_lo, bc_hi, bc_lo, tag, khi, klo, n_shards: int, fanout: int):
    """Fan each request out to its owner shard and ``fanout - 1`` successors,
    routing each request under the boundary vector of the epoch it carries
    (``tag``: 0 = previous, 1 = current; pass the same vector twice for a
    single-epoch wave).

    Returns (rep_hi, rep_lo, rep_tag, dest, oob) with the replica dim
    innermost: replica ``j*fanout + f`` of request ``j`` targets
    ``owner_j + f``.  Replicas past the last shard get the ``n_shards``
    drop sentinel and are flagged ``oob`` (trivially-complete empties, not
    RETRYs).
    """
    W = khi.shape[0]
    owner = route_range_epoch(bp_hi, bp_lo, bc_hi, bc_lo, tag, khi, klo)
    rep_hi = jnp.repeat(khi, fanout)
    rep_lo = jnp.repeat(klo, fanout)
    rep_tag = jnp.repeat(tag, fanout)
    off = jnp.tile(jnp.arange(fanout, dtype=jnp.int32), W)
    dest = jnp.repeat(owner, fanout) + off
    oob = dest >= n_shards
    # Ownership-window lower bound: a successor replica's scan starts at its
    # destination shard's slice start — under the replica's OWN epoch — not
    # at the original k_min.  In steady state the walk's >= k_min filter
    # made this a no-op (a shard holds no keys below its slice); during a
    # rebalance handoff it is load-bearing — a donor still physically
    # holding a migrated-away slice *below* its owned window must not
    # contribute those stale keys to the gather.
    lbp_hi = jnp.concatenate([jnp.zeros((1,), jnp.uint32), bp_hi])
    lbp_lo = jnp.concatenate([jnp.zeros((1,), jnp.uint32), bp_lo])
    lbc_hi = jnp.concatenate([jnp.zeros((1,), jnp.uint32), bc_hi])
    lbc_lo = jnp.concatenate([jnp.zeros((1,), jnp.uint32), bc_lo])
    safe_dest = jnp.clip(dest, 0, n_shards - 1)
    d_hi = jnp.where(rep_tag > 0, lbc_hi[safe_dest], lbp_hi[safe_dest])
    d_lo = jnp.where(rep_tag > 0, lbc_lo[safe_dest], lbp_lo[safe_dest])
    use_lb = ~limb_le(d_hi, d_lo, rep_hi, rep_lo)  # slice start > k_min
    rep_hi = jnp.where(use_lb, d_hi, rep_hi)
    rep_lo = jnp.where(use_lb, d_lo, rep_lo)
    return rep_hi, rep_lo, rep_tag, jnp.where(oob, n_shards, dest), oob


def _upper_bound_limbs(b_hi, b_lo):
    """(n_shards,) per-shard owned-window upper bounds: the successor's
    start boundary, KEY_MAX limbs for the last shard."""
    pad = jnp.full((1,), 0xFFFFFFFF, jnp.uint32)
    return jnp.concatenate([b_hi, pad]), jnp.concatenate([b_lo, pad])


def _gather_epilogue(
    origin, valid, oob, rs_kh, rs_kl, rs_vh, rs_vl, rs_valid, rs_trunc,
    *, W: int, fanout: int, limit: int,
):
    """Stitch one source shard's fan-out responses into per-request outputs.

    ``origin``/``valid`` are this shard's bucketize maps ((n_dest, cap),
    origin indexing the W*fanout replica stream); ``rs_*`` are the routed-
    back responses ((n_dest, cap, limit)).  Per-shard results are disjoint
    ascending slices, so concatenating a request's replicas in fan-out order
    is already globally sorted — compact the first ``limit`` live entries.

    ``rs_trunc`` is each replica's device-side continuation flag ("my
    bounded walk stopped with chain remaining *and* an under-filled row").
    A truncated replica leaves a *gap* between its last entry and its
    successor shard's slice, so the epilogue drops every contribution past
    the first truncated replica — the output is always an exact ascending
    prefix of the oracle answer — and folds the flag into a per-request
    ``truncated`` output: True = the prefix under-fills ``limit`` because a
    bounded walk was cut (re-issue — bigger ``max_leaves`` or the host
    continuation path), False + under-filled = the key space is genuinely
    exhausted.  The host orchestration (``ShardedDPAStore.range``)
    re-issues only the former, and only to the truncated shards.
    """
    WF = W * fanout
    flat_origin = origin.reshape(-1)
    safe = jnp.where(flat_origin >= 0, flat_origin, WF)
    r_kh = jnp.zeros((WF, limit), jnp.uint32).at[safe].set(
        rs_kh.reshape(-1, limit), mode="drop"
    )
    r_kl = jnp.zeros((WF, limit), jnp.uint32).at[safe].set(
        rs_kl.reshape(-1, limit), mode="drop"
    )
    r_vh = jnp.zeros((WF, limit), jnp.uint32).at[safe].set(
        rs_vh.reshape(-1, limit), mode="drop"
    )
    r_vl = jnp.zeros((WF, limit), jnp.uint32).at[safe].set(
        rs_vl.reshape(-1, limit), mode="drop"
    )
    r_valid = jnp.zeros((WF, limit), bool).at[safe].set(
        rs_valid.reshape(-1, limit).astype(bool), mode="drop"
    )
    r_trunc = jnp.zeros((WF,), bool).at[safe].set(
        rs_trunc.reshape(-1).astype(bool), mode="drop"
    )
    r_ok = jnp.zeros((WF,), bool).at[safe].set(valid.reshape(-1), mode="drop")
    r_ok = r_ok | oob  # past-the-end replicas are complete empties

    cat_kh = r_kh.reshape(W, fanout * limit)
    cat_kl = r_kl.reshape(W, fanout * limit)
    cat_vh = r_vh.reshape(W, fanout * limit)
    cat_vl = r_vl.reshape(W, fanout * limit)
    # a truncated replica breaks contiguity: keep only replicas strictly
    # before the first truncated one (plus its own — valid prefix — output)
    r_trunc_wf = r_trunc.reshape(W, fanout)
    prefix_ok = jnp.cumsum(r_trunc_wf.astype(jnp.int32), axis=1) == (
        r_trunc_wf.astype(jnp.int32)
    )  # True through the first truncated replica, False after it
    cat_valid = (r_valid.reshape(W, fanout, limit) & prefix_ok[:, :, None]).reshape(
        W, fanout * limit
    )

    target = jnp.cumsum(cat_valid.astype(jnp.int32), axis=1) - 1
    in_out = cat_valid & (target < limit)
    tgt = jnp.where(in_out, target, limit)  # overflow -> scratch column
    rows = jnp.arange(W)[:, None]
    out_kh = jnp.zeros((W, limit + 1), jnp.uint32).at[rows, tgt].set(
        jnp.where(in_out, cat_kh, 0)
    )
    out_kl = jnp.zeros((W, limit + 1), jnp.uint32).at[rows, tgt].set(
        jnp.where(in_out, cat_kl, 0)
    )
    out_vh = jnp.zeros((W, limit + 1), jnp.uint32).at[rows, tgt].set(
        jnp.where(in_out, cat_vh, 0)
    )
    out_vl = jnp.zeros((W, limit + 1), jnp.uint32).at[rows, tgt].set(
        jnp.where(in_out, cat_vl, 0)
    )
    n_found = jnp.minimum(jnp.sum(cat_valid, axis=1), limit)
    out_valid = jnp.arange(limit)[None, :] < n_found[:, None]
    ok = jnp.all(r_ok.reshape(W, fanout), axis=1)
    truncated = (n_found < limit) & jnp.any(r_trunc.reshape(W, fanout), axis=1)
    return (
        out_kh[:, :limit],
        out_kl[:, :limit],
        out_vh[:, :limit],
        out_vl[:, :limit],
        out_valid,
        ok,
        truncated,
    )


def _serve_subqueries(
    tree,
    ib,
    rq_hi,
    rq_lo,
    rq_tag,
    rq_live,
    ub_prev,
    ub_cur,
    *,
    depth: int,
    eps_inner: int,
    limit: int,
    max_leaves: int,
    max_rounds: int,
):
    """One shard's half of the wave: descend to each landed sub-query's
    floor leaf, then run the ENTIRE multi-round continuation in a single
    device loop (``lookup.range_batch_loop``), clipping every round to the
    sub-query's owned window under the epoch it carries (``rq_tag``).
    Slots where no request landed (``rq_live`` False) ride along as dead
    lanes.  Returns (keys, vals, valid, truncated, rounds)."""
    hf = rq_hi.reshape(-1)
    lf = rq_lo.reshape(-1)
    tf = rq_tag.reshape(-1)
    ub_hi = jnp.where(tf > 0, ub_cur[0], ub_prev[0])
    ub_lo = jnp.where(tf > 0, ub_cur[1], ub_prev[1])
    start = lookup.traverse(tree, hf, lf, depth=depth, eps_inner=eps_inner)
    start = jnp.where(rq_live.reshape(-1) > 0, start, -1)
    rk, rv, rvalid, rtrunc, _, rounds = lookup.range_batch_loop(
        tree,
        ib,
        start,
        hf,
        lf,
        ub_hi,
        ub_lo,
        limit=limit,
        max_leaves=max_leaves,
        max_rounds=max_rounds,
    )
    return rk, rv, rvalid, rtrunc, rounds


def _epoch_inputs(boundaries, boundaries_prev):
    """(prev, cur) boundary limb pairs; a single-epoch wave repeats cur."""
    b_hi, b_lo = boundary_limbs(boundaries)
    if boundaries_prev is None:
        return (b_hi, b_lo), (b_hi, b_lo)
    return boundary_limbs(boundaries_prev), (b_hi, b_lo)


def range_wave_emulated(
    stacked_tree,
    stacked_ib,
    khi: jnp.ndarray,  # (n_shards, W) per-client-shard k_min limbs
    klo: jnp.ndarray,
    boundaries: np.ndarray,
    *,
    cap: int,
    depth: int,
    eps_inner: int,
    limit: int,
    max_leaves: int = 4,
    fanout: Optional[int] = None,
    max_rounds: int = 0,
    boundaries_prev: Optional[np.ndarray] = None,
    epoch_tag: Optional[jnp.ndarray] = None,
):
    """Single-device emulation of the scatter-gather RANGE wave with the
    in-mesh continuation loop.

    Returns (out_kh, out_kl, out_vh, out_vl, out_valid, ok, truncated,
    rounds); the first seven carry a leading (n_shards, W) client layout
    (rows are ascending live entries with ``out_valid`` a prefix mask),
    ``rounds`` is the per-serving-shard count of continuation rounds the
    device loop ran ((n_shards,) i32 — ``max(rounds)`` is the wave's
    wall-clock depth, ``sum(rounds - 1)`` the host round-trips the loop
    absorbed).  ``ok=False`` means a capacity overflow dropped part of the
    fan-out — RETRY, never silent loss.  With the default ``max_rounds=0``
    the loop runs until every lane hit ``limit``, exhausted its chain, or
    ran into its owned window, so ``truncated`` only surfaces for a
    bounded ``max_rounds`` (the single-round ``max_rounds=1`` reproduces
    the old one-walk wave exactly).

    ``epoch_tag`` ((n_shards, W) i32; 0 = previous epoch, 1 = current,
    requires ``boundaries_prev``) routes a mixed in-flight wave per
    request: owner search, fan-out lower bounds AND the per-round upper
    clip all follow the admitted epoch — mid-rebalance the donor's stale
    copy stays visible to old-epoch requests and invisible to new-epoch
    ones.
    """
    n_shards, W = khi.shape
    fanout = n_shards if fanout is None else fanout
    (bp_hi, bp_lo), (bc_hi, bc_lo) = _epoch_inputs(boundaries, boundaries_prev)
    tag = (
        jnp.asarray(epoch_tag, dtype=jnp.int32)
        if epoch_tag is not None
        else jnp.ones((n_shards, W), dtype=jnp.int32)
    )

    rep = jax.vmap(
        lambda h, l, t: _replicate(
            bp_hi, bp_lo, bc_hi, bc_lo, t, h, l, n_shards, fanout
        )
    )(khi, klo, tag)
    rep_hi, rep_lo, rep_tag, dest, oob = rep
    bk_hi, bk_lo, origin, valid, bk_tag = jax.vmap(
        lambda d, h, l, t: _bucketize(d, h, l, n_shards, cap, extra=(t,))
    )(dest, rep_hi, rep_lo, rep_tag)
    rq_hi = jnp.swapaxes(bk_hi, 0, 1)  # (dest, src, cap)
    rq_lo = jnp.swapaxes(bk_lo, 0, 1)
    rq_tag = jnp.swapaxes(bk_tag, 0, 1)
    rq_live = jnp.swapaxes(valid, 0, 1).astype(jnp.int32)
    ubp = _upper_bound_limbs(bp_hi, bp_lo)  # each (n_shards,)
    ubc = _upper_bound_limbs(bc_hi, bc_lo)

    def per_shard(tree, ib, h, l, t, live, up_hi, up_lo, uc_hi, uc_lo):
        return _serve_subqueries(
            tree, ib, h, l, t, live,
            (up_hi, up_lo), (uc_hi, uc_lo),
            depth=depth, eps_inner=eps_inner, limit=limit,
            max_leaves=max_leaves, max_rounds=max_rounds,
        )

    rk, rv, rvalid, rtrunc, rounds = jax.vmap(per_shard)(
        stacked_tree, stacked_ib, rq_hi, rq_lo, rq_tag, rq_live,
        ubp[0], ubp[1], ubc[0], ubc[1],
    )
    # responses back: (dest, src, cap, limit) -> (src, dest, cap, limit)
    shape = (n_shards, n_shards, cap, limit)
    rs_kh = jnp.swapaxes(rk[..., 0].reshape(shape), 0, 1)
    rs_kl = jnp.swapaxes(rk[..., 1].reshape(shape), 0, 1)
    rs_vh = jnp.swapaxes(rv[..., 0].reshape(shape), 0, 1)
    rs_vl = jnp.swapaxes(rv[..., 1].reshape(shape), 0, 1)
    rs_valid = jnp.swapaxes(rvalid.reshape(shape), 0, 1)
    rs_trunc = jnp.swapaxes(rtrunc.reshape(shape[:3]), 0, 1)

    gather = partial(_gather_epilogue, W=W, fanout=fanout, limit=limit)
    outs = jax.vmap(gather)(
        origin, valid, oob, rs_kh, rs_kl, rs_vh, rs_vl, rs_valid, rs_trunc
    )
    return tuple(outs) + (rounds,)


def range_wave_sharded(
    mesh: Mesh,
    stacked_tree,
    stacked_ib,
    boundaries: np.ndarray,
    *,
    cap: int,
    depth: int,
    eps_inner: int,
    limit: int,
    max_leaves: int = 4,
    fanout: Optional[int] = None,
    max_rounds: int = 0,
    boundaries_prev: Optional[np.ndarray] = None,
):
    """shard_map scatter-gather RANGE over the mesh 'data' axis with the
    in-mesh continuation loop (the per-shard ``lax.while_loop`` contains no
    collectives — both ``all_to_all`` exchanges bracket it — so shards
    iterate independently and a multi-round scan never leaves the mesh).

    Returns a jit-able fn(stacked_tree, stacked_ib, khi, klo) — or, when
    ``boundaries_prev`` is given (a live rebalance handoff),
    fn(stacked_tree, stacked_ib, khi, klo, epoch_tag) with per-request
    epoch tags — with state and requests sharded on their leading shard
    dim; outputs match ``range_wave_emulated`` (8 outputs incl. the
    per-shard ``rounds``).
    """
    n_shards = mesh.shape["data"]
    F = n_shards if fanout is None else fanout
    (bp_hi, bp_lo), (bc_hi, bc_lo) = _epoch_inputs(boundaries, boundaries_prev)
    ubp = _upper_bound_limbs(bp_hi, bp_lo)
    ubc = _upper_bound_limbs(bc_hi, bc_lo)

    def a2a(x):
        # x (n_shards, X) per shard: row d -> shard d
        return jax.lax.all_to_all(
            x[None], "data", split_axis=1, concat_axis=0, tiled=False
        ).reshape(x.shape)

    def per_shard(tree, ib, khi, klo, tag):
        tree = jax.tree.map(lambda a: a[0], tree)
        ib = jax.tree.map(lambda a: a[0], ib)
        h, l, t = khi[0], klo[0], tag[0]
        W = h.shape[0]
        rep_hi, rep_lo, rep_tag, dest, oob = _replicate(
            bp_hi, bp_lo, bc_hi, bc_lo, t, h, l, n_shards, F
        )
        bk_hi, bk_lo, origin, valid, bk_tag = _bucketize(
            dest, rep_hi, rep_lo, n_shards, cap, extra=(rep_tag,)
        )
        rq_hi = a2a(bk_hi)
        rq_lo = a2a(bk_lo)
        rq_tag = a2a(bk_tag)
        rq_live = a2a(valid.astype(jnp.int32))
        s = jax.lax.axis_index("data")
        rk, rv, rvalid, rtrunc, rounds = _serve_subqueries(
            tree, ib, rq_hi, rq_lo, rq_tag, rq_live,
            (ubp[0][s], ubp[1][s]), (ubc[0][s], ubc[1][s]),
            depth=depth, eps_inner=eps_inner, limit=limit,
            max_leaves=max_leaves, max_rounds=max_rounds,
        )
        flat = (n_shards, cap * limit)
        rs_kh = a2a(rk[..., 0].reshape(flat)).reshape(n_shards, cap, limit)
        rs_kl = a2a(rk[..., 1].reshape(flat)).reshape(n_shards, cap, limit)
        rs_vh = a2a(rv[..., 0].reshape(flat)).reshape(n_shards, cap, limit)
        rs_vl = a2a(rv[..., 1].reshape(flat)).reshape(n_shards, cap, limit)
        rs_valid = a2a(rvalid.astype(jnp.int32).reshape(flat)).reshape(
            n_shards, cap, limit
        )
        rs_trunc = a2a(rtrunc.astype(jnp.int32).reshape(n_shards, cap))
        outs = _gather_epilogue(
            origin, valid, oob, rs_kh, rs_kl, rs_vh, rs_vl, rs_valid, rs_trunc,
            W=W, fanout=F, limit=limit,
        )
        return tuple(o[None] for o in outs) + (rounds[None],)

    state_specs = jax.tree.map(lambda _: P("data"), (stacked_tree, stacked_ib))
    fn = jax.shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(
            state_specs[0],
            state_specs[1],
            P("data"),
            P("data"),
            P("data"),
        ),
        out_specs=tuple(P("data") for _ in range(8)),
        check_vma=False,
    )
    if boundaries_prev is not None:
        return fn  # caller supplies per-request epoch tags

    def single_epoch(tree, ib, khi, klo):
        return fn(tree, ib, khi, klo, jnp.ones(khi.shape, dtype=jnp.int32))

    return single_epoch
