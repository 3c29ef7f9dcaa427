"""DPA-Store facade: the full KV store wired together.

The public surface is the paper's stateless-client protocol: batched GET /
INSERT / UPDATE / DELETE / RANGE over u64 keys and u64 values.  One call =
one *request wave* (the batched analogue of a volley of UDP packets hitting
the DPA thread grid).  Internals:

  request wave -> steering hash -> hot cache probe -> learned-index traversal
  -> insert buffer / leaf HBM access -> responses
  RANGE wave  -> scan-anchor probe (descent skip on hit) -> bounded leaf
  walk -> truncated rows resume from their cursor until limit/exhaustion
  full insert buffers -> host patcher -> stitch batch -> COPY, CONNECT
  -> epoch advance (+ scan-anchor invalidation) -> quarantined ids reclaimed

Write statuses mirror the wire protocol: OK, RETRY (buffer full — the paper's
traverser re-enqueue; ``auto_retry`` hides it behind the patch cycle like a
client library would).

Counters track everything the paper measures (stitched bytes for the
120 MB/s bound, patch kinds, cache hits, wave counts) so the benchmarks can
derive MOPS figures through the latency model without instrument-on-demand
hacks.
"""

from __future__ import annotations

import contextlib
import functools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from . import api, hotcache, insert_buffer, lookup, patch, scancache, stitch
from .api import RangeResult
from .epoch import EpochManager, EpochRetiredError
from .ledger import span
from .ttl import TTLTracker
from .hotcache import CacheConfig, CacheState
from .keys import KEY_MAX, join_u64, limb_hash_np, split_u64
from .lookup import IB_DEL, IB_PUT, InsertBuffers
from .scancache import ScanCacheConfig, ScanCacheState
from .tree import SEG_CAP, TreeConfig, TreeImage, build_image

STATUS_OK = insert_buffer.STATUS_OK
STATUS_RETRY = insert_buffer.STATUS_RETRY


def _pad_pow2(n: int, minimum: int = 8) -> int:
    p = minimum
    while p < n:
        p *= 2
    return p


def _on_device(method):
    """Run a store method with JAX's default device set to the store's
    device, so every array it creates (per-wave inputs, cache and stitch
    updates) is put there directly and its programs run there.  A store
    without a device (``device=None``) calls straight through."""

    @functools.wraps(method)
    def placed(self, *args, **kw):
        if self.device is None:
            return method(self, *args, **kw)
        with jax.default_device(self.device):
            return method(self, *args, **kw)

    return placed


def append_range_results(keys_out, vals_out, counts, idxs, rk, rv, rc, limit):
    """Vectorized stitch shared by the continuation loop and the sharded
    scatter-gather epilogue: append each row's first ``take`` results at its
    current fill level.  ``idxs`` maps the sub-batch rows of ``rk``/``rv``/
    ``rc`` to rows of the accumulators; mutates them in place and returns
    the per-row appended counts."""
    cols = np.arange(limit)
    take = np.minimum(rc, limit - counts[idxs])
    src = cols[None, :] < take[:, None]  # (k, limit)
    dst_col = counts[idxs][:, None] + cols[None, :]
    dst_row = np.repeat(idxs, take)
    keys_out[dst_row, dst_col[src]] = rk[src]
    vals_out[dst_row, dst_col[src]] = rv[src]
    counts[idxs] += take
    return take


@dataclass
class StoreStats:
    waves: int = 0
    gets: int = 0
    puts: int = 0
    deletes: int = 0
    ranges: int = 0
    cache_hits: int = 0
    cache_probes: int = 0
    patches_update: int = 0
    patches_structural: int = 0
    new_leaves: int = 0
    stitched_bytes: int = 0  # total batch bytes (host + DPA paths)
    stitched_dpa_bytes: int = 0  # host->DPA bytes (the 120 MB/s path)
    bulk_load_bytes: int = 0
    bulk_load_dpa_bytes: int = 0
    retries: int = 0
    reclaimed: int = 0
    # batched patch/stitch pipeline accounting: a flush *cycle* drains some
    # set of full buffers; each COPY+CONNECT transaction applied to the
    # device counts one stitch_apply.  Batched mode: applies == cycles.
    # Per-leaf oracle mode: applies == patched leaves >= cycles.
    flush_cycles: int = 0
    stitch_applies: int = 0
    patched_leaves: int = 0
    # scan-anchor cache (RANGE descent skip) + continuation accounting
    scan_probes: int = 0  # fresh-descent RANGE rows probed against the cache
    scan_hits: int = 0  # rows whose descent the anchor cache skipped
    scan_invalidated: int = 0  # anchors dropped by stitch-cycle invalidation
    scan_cursor_admits: int = 0  # truncated-scan cursors admitted as anchors
    range_rounds_in_mesh: int = 0  # continuation rounds run INSIDE the device
    # loop (rounds after the first of each dispatch) — zero host round-trips
    range_reissue_rounds: int = 0  # host-orchestrated re-issue waves (the
    # rare fallback: only bounded-max_rounds callers resuming from a cursor)
    range_truncated: int = 0  # rows returned truncated (bounded max_rounds)
    # chain compaction: empty routing stubs (left by extract_slice / heavy
    # deletes) removed from the leaf chain + parents
    stub_leaves_compacted: int = 0
    # slice migration (online rebalance): keys shipped out of / into this
    # store through extract_slice / ingest_slice
    migrated_out_keys: int = 0
    migrated_in_keys: int = 0
    # host time of maintenance (the ``flush``, ``plan`` and ``stitch``
    # spans): flush cycles that drain insert buffers, patch planning, and
    # COPY + CONNECT + epoch end; plan and stitch lie inside flush
    flush_ns: int = 0
    plan_ns: int = 0
    stitch_ns: int = 0


@dataclass
class _GetWave:
    """In-flight GET wave: device arrays only (split-phase donation rule —
    a wave ctx never retains store state handles, see serving.pipeline)."""

    n: int
    vhi: object
    vlo: object
    found: object
    hits: Optional[object]  # c_hit & active, or None when the cache is off
    # host-side TTL expiry mask (None when no deadline can apply): computed
    # at issue time against the live tracker — or the frozen per-epoch
    # snapshot for as_of reads — so finalize stays a pure drain
    expired: Optional[np.ndarray] = None


@dataclass
class _WriteWave:
    """In-flight fast-path write wave (all lanes proven to land)."""

    n: int
    status: object  # device status array (B,), all-OK by construction


@dataclass
class _RangeWave:
    """In-flight RANGE wave: device outputs of ``range_batch_loop`` plus the
    pre-sized host accumulators the finalize phase stitches into."""

    n: int
    limit: int
    arity: int
    resumed: bool  # start_leaves was given (host-orchestrated re-issue)
    keys_out: np.ndarray
    vals_out: np.ndarray
    counts: np.ndarray
    trunc_out: np.ndarray
    cur_leaf_out: np.ndarray
    cur_key_out: np.ndarray
    rk: object = None
    rv: object = None
    valid: object = None
    trunc: object = None
    cursor: object = None
    rounds: object = None
    empty: bool = False  # limit<=0 / n==0 short-circuit: no device wave
    # prebaked waves (TTL-filtered / versioned refill loops run at issue
    # time): results already sit in the host accumulators, finalize only
    # wraps them — ``empty`` is also True so no device gather happens
    rounds_done: int = 0
    stats_out: Optional[dict] = None
    as_of: Optional[int] = None


class DPAStore:
    """Single-shard DPA-Store (the distributed wrapper lives in
    ``repro.distributed.kvshard``).

    ``device`` places the store: its tree pools, insert buffers and both
    caches are committed to that device, every per-wave input is put on it
    and every program of the store runs there, so no array of the store
    crosses to another chip.  ``None`` keeps JAX's default device."""

    def __init__(
        self,
        keys: np.ndarray,
        vals: np.ndarray,
        tree_cfg: TreeConfig = TreeConfig(),
        cache_cfg: Optional[CacheConfig] = CacheConfig(),
        bulk_load_via_stitch: bool = False,
        epoch_grace: int = 2,
        batched_patch: bool = True,
        scan_cache_cfg: Optional[ScanCacheConfig] = ScanCacheConfig(),
        retain_epochs: int = 0,
        device: Optional[jax.Device] = None,
    ):
        # batched_patch=True (default): a flush cycle plans every full leaf
        # into ONE merged stitch batch and applies it as a single COPY+CONNECT
        # transaction (Sec 3.2 batching).  False keeps the per-leaf stream —
        # the semantic oracle the equivalence tests compare against.
        self.batched_patch = batched_patch
        keys = np.asarray(keys, dtype=np.uint64)
        vals = np.asarray(vals, dtype=np.uint64)
        if not np.all(keys < KEY_MAX):
            raise ValueError("2^64-1 is a reserved sentinel")
        self.cfg = tree_cfg
        self.image: TreeImage = build_image(keys, vals, tree_cfg)
        bulk = stitch.bulk_load_batch(self.image)
        self.stats = StoreStats()
        self.stats.bulk_load_bytes = bulk.payload_bytes()
        self.stats.bulk_load_dpa_bytes = bulk.dpa_bytes()
        self.device = device
        # no device: not jax.default_device(None), which would override a
        # scope the caller holds (chip_smoke.py builds shards under one)
        with (
            contextlib.nullcontext() if device is None else jax.default_device(device)
        ):
            if bulk_load_via_stitch:
                tree0 = stitch.empty_device_tree(self.image)
                tree0 = stitch.apply_copies(tree0, bulk)
                self.tree, _ = stitch.apply_connects(
                    tree0,
                    lookup.make_insert_buffers(
                        self.image.leaf_anchor.shape[0], tree_cfg.ib_cap
                    ),
                    bulk,
                )
            else:
                self.tree = self.image.to_device()
            self.ib: InsertBuffers = lookup.make_insert_buffers(
                self.image.leaf_anchor.shape[0], tree_cfg.ib_cap
            )
            self.cache_cfg = cache_cfg
            self.cache: Optional[CacheState] = (
                hotcache.make_cache(cache_cfg) if cache_cfg else None
            )
            # Scan-anchor cache (RANGE descent skip): key -> leaf where the
            # descent bottomed out.  Invalidation is wired through the epoch
            # manager's quarantine listener — every leaf id a stitch cycle
            # obsoletes is collected at defer time and its anchors dropped
            # before the cycle ends (see _apply_scan_invalidation).
            self.scan_cache_cfg = scan_cache_cfg
            self.scan_cache: Optional[ScanCacheState] = (
                scancache.make_cache(scan_cache_cfg) if scan_cache_cfg else None
            )
        if device is not None:  # commit: later programs stay on this chip
            self.tree, self.ib, self.cache, self.scan_cache = jax.device_put(
                (self.tree, self.ib, self.cache, self.scan_cache), device
            )
        self._stale_anchor_leaves: List[int] = []
        # retain_epochs > 0 keeps every superseded leaf version addressable
        # for that many stitch cycles: reads accept ``as_of=<epoch>`` and are
        # served through a host-built resolve table over the version chain
        # (see _resolve_table).  Costs pool headroom — quarantined rows are
        # withheld from the allocator for the whole window — and forces
        # every patch copy-on-write (no in-place value updates).
        self.retain_epochs = retain_epochs
        self.epochs = EpochManager(grace=epoch_grace, retain=retain_epochs)
        self.epochs.on_defer = self._note_deferred_free
        # TTL sidecar (logical clock) + frozen per-cycle deadline snapshots
        # for as_of reads; both empty until the first ``put(ttl=...)``
        self.ttl = TTLTracker()
        self._ttl_snaps: Dict[int, Tuple[Dict[int, int], int]] = {}
        # Host shadow of ib.count for the async write fast path: lets
        # write_issue prove "this wave cannot fill any buffer" without
        # blocking on the device (None = stale, recomputed on demand; every
        # non-fast-path ib mutation invalidates it)
        self._ib_shadow: Optional[np.ndarray] = None

    # ------------------------------------------------------------------ util
    @property
    def depth(self) -> int:
        return self.image.depth

    def _limbs(self, keys_u64: np.ndarray, pad_to: int):
        keys_u64 = np.asarray(keys_u64, dtype=np.uint64)
        n = keys_u64.size
        padded = np.full(pad_to, 0, dtype=np.uint64)
        padded[:n] = keys_u64
        limbs = split_u64(padded)
        active = np.zeros(pad_to, dtype=bool)
        active[:n] = True
        return (
            jnp.asarray(limbs[:, 0]),
            jnp.asarray(limbs[:, 1]),
            jnp.asarray(active),
        )

    def _steer(self, khi, klo):
        if self.cache_cfg is None:
            return jnp.zeros_like(khi, dtype=jnp.int32)
        return hotcache.steer(khi, klo, self.cache_cfg.n_threads)

    def _end_wave(self):
        self.stats.waves += 1
        self.epochs.advance()
        self.stats.reclaimed += self.epochs.reclaim(self.image)

    # -------------------------------------------- scan-anchor invalidation
    def _note_deferred_free(self, pool: str, idx: int) -> None:
        """EpochManager.on_defer listener: collect leaves a stitch cycle
        obsoleted.  Runs at quarantine time (right after the CONNECT), so
        the set is complete before the cycle's invalidation flush."""
        if pool == "leaves" and self.scan_cache is not None:
            self._stale_anchor_leaves.append(int(idx))

    def _apply_scan_invalidation(self) -> None:
        """Drop every cached scan anchor whose leaf this cycle replaced.
        Called inside the patch paths after the cycle's frees are deferred —
        i.e. before any later wave can probe the cache — so a stale anchor
        can never start a leaf walk on a restitched chain."""
        if self.scan_cache is None or not self._stale_anchor_leaves:
            self._stale_anchor_leaves.clear()
            return
        ids = np.asarray(self._stale_anchor_leaves, dtype=np.int32)
        self._stale_anchor_leaves.clear()
        padded = np.full(_pad_pow2(ids.size), -1, dtype=np.int32)
        padded[: ids.size] = ids
        self.scan_cache, n = scancache.invalidate_leaves(
            self.scan_cache, jnp.asarray(padded)
        )
        with span("wait.invalidate", waits=1):
            self.stats.scan_invalidated += int(n)

    # ------------------------------------------- point-in-time read window
    @_on_device
    def snapshot_epoch(self) -> int:
        """Flush staged writes and return the version epoch naming the
        current stitched state — the handle for ``as_of`` reads.  Raises
        :class:`EpochRetiredError` when the store keeps no window
        (``retain_epochs=0``)."""
        self.flush()
        if self.epochs.retain <= 0:
            raise EpochRetiredError(
                "snapshot_epoch: store was built with retain_epochs=0"
            )
        return self.epochs.cycle

    def _resolve_table(self, e: int):
        """Per-epoch leaf-id overlay: a gather table ``res[l] -> l'`` mapping
        every leaf id to the version of its window live at epoch ``e`` —
        walk ``ver_prev`` while the version was born after ``e``.  Host-side
        numpy fixpoint (vectorized passes; chains shorten by one cycle per
        step, so ``retain`` passes bound any retained epoch's chain), shipped
        to the device as one i32 array: the versioned kernels pay one extra
        gather per leaf visit and stay a single dispatch.

        Safety: every id a *validated* epoch's chain visits is still
        quarantined (reclaim's retention gate releases an id freed at cycle
        F only once the oldest retained epoch exceeds F-1), so no entry a
        versioned walk can reach has been released or restamped.  Entries
        for free-pool ids may be garbage — no current leaf gathers them."""
        vb, vp = self.image.ver_birth, self.image.ver_prev
        res = np.arange(vb.shape[0], dtype=np.int32)
        for _ in range(max(self.epochs.retain, 1) + 1):
            need = (vb[res] > e) & (vp[res] >= 0)
            if not need.any():
                break
            res[need] = vp[res[need]]
        return jnp.asarray(res)

    def _note_cycle_end(self) -> None:
        """Per-cycle retention bookkeeping (runs after ``end_cycle``): freeze
        the TTL deadline sidecar for the cycle that just completed (so
        ``as_of`` reads judge expiry by that epoch's clock, not the present)
        and age frozen snapshots out with the retention horizon."""
        # once any snapshot exists, keep freezing even when the tracker
        # empties — later epochs must supersede stale deadlines with the
        # (empty) truth, not inherit them via _ttl_snap_for's floor lookup
        if self.retain_epochs > 0 and (self.ttl or self._ttl_snaps):
            self._ttl_snaps[self.epochs.cycle] = self.ttl.freeze()
        if self._ttl_snaps:
            h = self.epochs.horizon
            for c in [c for c in self._ttl_snaps if c <= h]:
                del self._ttl_snaps[c]

    def _ttl_snap_for(self, e: int):
        """Frozen TTL snapshot governing epoch ``e``: the newest freeze at
        or before ``e`` (deadline edits only land with a cycle).  None when
        no deadline existed then — the read path's zero-cost fast lane."""
        cands = [c for c in self._ttl_snaps if c <= e]
        return self._ttl_snaps[max(cands)] if cands else None

    # ------------------------------------------------------------------ GET
    def get(
        self,
        keys=None,
        *,
        epoch: Optional[int] = None,
        as_of: Optional[int] = None,
        **legacy,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Batched point lookup: returns (values u64, found bool).

        Canonical ``KVStore`` signature: ``epoch`` exists for signature
        parity with the sharded tiers — a single store has no routing
        epochs, so only ``None`` is accepted.  ``as_of=<version epoch>``
        (from :meth:`snapshot_epoch`) serves the lookup from the retained
        point-in-time window instead of the live tree; reads outside the
        window raise :class:`EpochRetiredError`."""
        keys = api.take_legacy("get", legacy, keys, "keys", "keys_u64")
        api.reject_unknown("get", legacy)
        return self.get_finalize(self.get_issue(keys, epoch=epoch, as_of=as_of))

    @_on_device
    def get_issue(
        self,
        keys,
        *,
        epoch: Optional[int] = None,
        as_of: Optional[int] = None,
    ) -> _GetWave:
        """Issue half of GET: host build + async device dispatch (cache
        probe, traverse, cache admit) — returns without blocking on device
        results.  ``get() == get_finalize(get_issue())`` by construction,
        which is what makes pipelined execution bitwise-equal to serial
        (see ``serving.pipeline``)."""
        if epoch is not None:
            # NOT an assert: under ``python -O`` an assert vanishes and the
            # caller's routing epoch would be silently accepted and ignored
            raise ValueError(
                "single-store GET has no routing epochs (epoch must be None)"
            )
        keys_u64 = np.asarray(keys, dtype=np.uint64)
        n = keys_u64.size
        with span("build"):
            B = _pad_pow2(n)
            khi, klo, active = self._limbs(keys_u64, B)
        if as_of is not None:
            e = self.epochs.check_retained(as_of)
            with span("launch"):
                res_table = self._resolve_table(e)
                vhi, vlo, found = lookup.get_batch_versioned(
                    self.tree,
                    res_table,
                    khi,
                    klo,
                    depth=self.depth,
                    eps_inner=self.cfg.eps_inner,
                    eps_leaf=self.cfg.eps_leaf,
                )
            snap = self._ttl_snap_for(e)
            expired = (
                TTLTracker.expired_at(snap, keys_u64)
                if snap is not None
                else None
            )
            self.stats.gets += n
            self._end_wave()
            return _GetWave(
                n=n, vhi=vhi, vlo=vlo, found=found, hits=None, expired=expired
            )
        use_cache = self.cache is not None
        with span("launch"):
            if use_cache:
                tid = self._steer(khi, klo)
                c_hit, c_vhi, c_vlo = hotcache.probe(
                    self.cache, tid, khi, klo, cfg=self.cache_cfg
                )
            vhi, vlo, found = lookup.get_batch(
                self.tree,
                self.ib,
                khi,
                klo,
                depth=self.depth,
                eps_inner=self.cfg.eps_inner,
                eps_leaf=self.cfg.eps_leaf,
            )
            hits = None
            if use_cache:
                out_vhi = jnp.where(c_hit, c_vhi, vhi)
                out_vlo = jnp.where(c_hit, c_vlo, vlo)
                out_found = c_hit | found
                eligible = found & ~c_hit & active
                self.cache = hotcache.admit(
                    self.cache,
                    tid,
                    khi,
                    klo,
                    vhi,
                    vlo,
                    eligible,
                    cfg=self.cache_cfg,
                    wave=self.stats.waves & 0xFFFFFFFF,
                )
                hits = c_hit & active
                self.stats.cache_probes += n
            else:
                out_vhi, out_vlo, out_found = vhi, vlo, found
        self.stats.gets += n
        expired = self.ttl.is_expired_np(keys_u64) if self.ttl else None
        self._end_wave()
        return _GetWave(
            n=n, vhi=out_vhi, vlo=out_vlo, found=out_found, hits=hits,
            expired=expired,
        )

    @_on_device
    def get_finalize(self, w: _GetWave) -> Tuple[np.ndarray, np.ndarray]:
        """Drain half of GET: blocking gather + host epilogue."""
        if w.hits is not None:
            with span("wait.stats", waits=1):
                self.stats.cache_hits += int(jnp.sum(w.hits))
        n = w.n
        with span("wait.results", waits=3):
            vhi = np.asarray(w.vhi)[:n]
            vlo = np.asarray(w.vlo)[:n]
            found = np.asarray(w.found)[:n]
        with span("epilogue"):
            vals = join_u64(np.stack([vhi, vlo], axis=-1))
            if w.expired is not None:
                # TTL: a key past its deadline reads as absent (the sweep will
                # physically delete it later; filter-vs-reclaim equivalence)
                found = found & ~w.expired
            # protocol contract: not-found rows carry 0, never slot residue —
            # so responses are bitwise identical no matter which tier serves
            vals[~found] = 0
        return vals, found

    # ---------------------------------------------------------------- writes
    def _write(
        self, keys_u64, vals_u64, op_code: int, auto_retry: bool = True
    ) -> np.ndarray:
        keys_u64 = np.asarray(keys_u64, dtype=np.uint64)
        if not np.all(keys_u64 < KEY_MAX):
            raise ValueError("2^64-1 is a reserved sentinel")
        vals_u64 = (
            np.zeros_like(keys_u64)
            if vals_u64 is None
            else np.asarray(vals_u64, dtype=np.uint64)
        )
        n = keys_u64.size
        statuses = np.full(n, STATUS_RETRY, dtype=np.int32)
        pending = np.arange(n)
        first = True
        stalled = 0
        while pending.size and (auto_retry or first):
            # every round after the first re-sends lanes a full buffer refused
            with contextlib.nullcontext() if first else span("retry"):
                first = False
                st = self._write_wave(
                    keys_u64[pending], vals_u64[pending], op_code
                )
                statuses[pending] = st
                self._process_full_leaves()
                next_pending = pending[st == STATUS_RETRY]
                if next_pending.size == pending.size:
                    # no lane landed: drain the responsible buffers so the
                    # re-send can succeed (paper: client re-sends after
                    # timeout, by which time the patch cycle has emptied it)
                    stalled += 1
                    self._flush_leaves_of(keys_u64[next_pending])
                    if stalled >= 3:  # defensive; cannot happen after a flush
                        break
                else:
                    stalled = 0
                if next_pending.size:
                    self.stats.retries += next_pending.size
                pending = next_pending
        return statuses

    def _write_wave(self, keys_u64, vals_u64, op_code: int) -> np.ndarray:
        n = keys_u64.size
        with span("build"):
            B = _pad_pow2(n)
            khi, klo, active = self._limbs(keys_u64, B)
            vv = np.zeros(B, dtype=np.uint64)
            vv[:n] = vals_u64
            vlimbs = split_u64(vv)
            vhi = jnp.asarray(vlimbs[:, 0])
            vlo = jnp.asarray(vlimbs[:, 1])
        with span("launch"):
            leaf = lookup.traverse(
                self.tree, khi, klo, depth=self.depth, eps_inner=self.cfg.eps_inner
            )
            op = jnp.full(B, op_code, dtype=jnp.int32)
            self.ib, status = insert_buffer.append_wave(
                self.ib, leaf, khi, klo, vhi, vlo, op, active
            )
            if self.cache is not None:
                # UPDATE/DELETE invalidate cached entries (paper Sec 3.1.2)
                tid = self._steer(khi, klo)
                self.cache = hotcache.invalidate(
                    self.cache, tid, khi, klo, active, cfg=self.cache_cfg
                )
        self._ib_shadow = None  # serial append: shadow prediction is stale
        self._end_wave()
        with span("wait.results", waits=1):
            return np.asarray(status)[:n]

    # ------------------------------------------- async write fast path
    def _write_plan(self, keys_u64: np.ndarray):
        """Prove host-side that a write wave lands every lane WITHOUT
        filling any insert buffer to ``ib_cap``.  Uses ``image.find_leaf``
        — the host descent replica that is bit-identical to the device
        traverse (the invariant ``_flush_leaves_of`` already rests on) —
        plus a host shadow of ``ib.count``.  Returns the per-leaf append
        counts on success, or ``None`` when any touched buffer could reach
        the cap (or a lane could RETRY): the caller must then drain the
        pipeline and take the serial path, so stitch cycles happen at
        exactly the serial op-stream points (identical leaf layout ⇒
        identical RANGE cursors)."""
        if self._ib_shadow is None:
            # blocks only if an in-flight wave donated ib — the pipelined
            # facade never lets that happen on this path (reads don't touch
            # ib; prior fast-path writes kept the shadow live)
            with span("wait.shadow", waits=1):
                self._ib_shadow = np.asarray(self.ib.count).copy()
        leaves = np.fromiter(
            (self.image.find_leaf(k)[0] for k in keys_u64),
            dtype=np.int64,
            count=keys_u64.size,
        )
        adds = np.zeros_like(self._ib_shadow)
        np.add.at(adds, leaves, 1)
        touched = np.unique(leaves)
        # strict <: landing the wave must also leave every buffer BELOW the
        # cap, else serial's post-wave _process_full_leaves would stitch
        if np.any(self._ib_shadow[touched] + adds[touched] >= self.cfg.ib_cap):
            return None
        return adds

    @_on_device
    def write_issue(self, op: str, keys, vals=None) -> Optional[_WriteWave]:
        """Issue half of PUT/DELETE — async dispatch on the proven-safe
        fast path only.  Returns ``None`` when the wave needs the serial
        path (possible buffer fill / RETRY): the pipelined facade drains
        and falls back — the flush/stitch epoch barrier."""
        assert op in ("put", "delete"), op
        keys_u64 = np.asarray(keys, dtype=np.uint64)
        if not np.all(keys_u64 < KEY_MAX):
            raise ValueError("2^64-1 is a reserved sentinel")
        n = keys_u64.size
        if n == 0:
            return _WriteWave(n=0, status=np.zeros(0, dtype=np.int32))
        with span("build"):
            adds = self._write_plan(keys_u64)
            if adds is None:
                return None
            vals_u64 = (
                np.zeros_like(keys_u64)
                if vals is None
                else np.asarray(vals, dtype=np.uint64)
            )
            op_code = IB_PUT if op == "put" else IB_DEL
            B = _pad_pow2(n)
            khi, klo, active = self._limbs(keys_u64, B)
            vv = np.zeros(B, dtype=np.uint64)
            vv[:n] = vals_u64
            vlimbs = split_u64(vv)
            vhi = jnp.asarray(vlimbs[:, 0])
            vlo = jnp.asarray(vlimbs[:, 1])
        with span("launch"):
            leaf = lookup.traverse(
                self.tree, khi, klo, depth=self.depth, eps_inner=self.cfg.eps_inner
            )
            opv = jnp.full(B, op_code, dtype=jnp.int32)
            self.ib, status = insert_buffer.append_wave(
                self.ib, leaf, khi, klo, vhi, vlo, opv, active
            )
            self._ib_shadow += adds  # exact: every lane proven to land
            if self.cache is not None:
                tid = self._steer(khi, klo)
                self.cache = hotcache.invalidate(
                    self.cache, tid, khi, klo, active, cfg=self.cache_cfg
                )
        self._end_wave()
        if op == "put":
            self.stats.puts += n
            # fast-path PUT carries no ttl; clears stale deadlines so the
            # overwrite's no-expiry policy wins (no-op while tracker empty)
            self.ttl.note_put(keys_u64, None)
        else:
            self.stats.deletes += n
            self.ttl.note_delete(keys_u64)
        return _WriteWave(n=n, status=status)

    @_on_device
    def write_finalize(self, w: _WriteWave) -> np.ndarray:
        """Drain half of PUT/DELETE: gather the device statuses (all OK by
        the issue-time proof, but the device array is authoritative)."""
        if w.n == 0:
            return np.asarray(w.status)
        with span("wait.results", waits=1):
            return np.asarray(w.status)[: w.n]

    @_on_device
    def put(
        self,
        keys=None,
        vals=None,
        *args,
        auto_retry: bool = True,
        ttl: Optional[int] = None,
        **legacy,
    ) -> np.ndarray:
        """INSERT or UPDATE (the buffer treats both as PUT; the patcher
        classifies the patch).  Canonical signature keeps ``auto_retry``
        keyword-only; the old positional third argument still works via a
        deprecation shim.

        ``ttl=K`` stamps each written key with a logical-clock deadline
        ``now + K`` (see :class:`~repro.core.ttl.TTLTracker`): once the
        store's clock reaches it the key reads as absent, and the next
        :meth:`ttl_sweep` physically deletes it.  ``ttl=None`` (default)
        never expires — and clears any deadline a previous write left."""
        keys = api.take_legacy("put", legacy, keys, "keys", "keys_u64")
        vals = api.take_legacy("put", legacy, vals, "vals", "vals_u64")
        api.reject_unknown("put", legacy)
        if args:  # legacy positional auto_retry
            api.warn_legacy("put", "positional auto_retry", "auto_retry=...")
            (auto_retry,) = args
        st = self._write(keys, vals, IB_PUT, auto_retry)
        keys_u64 = np.asarray(keys, dtype=np.uint64)
        self.ttl.note_put(keys_u64[st == STATUS_OK], ttl)
        self.stats.puts += keys_u64.size
        return st

    insert = put
    update = put

    @_on_device
    def delete(self, keys=None, *args, auto_retry: bool = True, **legacy) -> np.ndarray:
        keys = api.take_legacy("delete", legacy, keys, "keys", "keys_u64")
        api.reject_unknown("delete", legacy)
        if args:  # legacy positional auto_retry
            api.warn_legacy("delete", "positional auto_retry", "auto_retry=...")
            (auto_retry,) = args
        st = self._write(keys, None, IB_DEL, auto_retry)
        keys_u64 = np.asarray(keys, dtype=np.uint64)
        self.ttl.note_delete(keys_u64[st == STATUS_OK])
        self.stats.deletes += keys_u64.size
        return st

    # ---------------------------------------------------------------- range
    def range(
        self,
        k_min=None,
        limit: int = 10,
        *args,
        k_max=None,
        epoch: Optional[int] = None,
        as_of: Optional[int] = None,
        max_leaves: int = 4,
        **legacy,
    ) -> RangeResult:
        """RANGE(k_min, limit) per request: a :class:`~repro.core.api.
        RangeResult` whose named fields are ``keys (B, limit)``, ``vals
        (B, limit)``, ``counts (B,)`` — ascending, live entries only (zeros
        past ``counts``) — and which still tuple-unpacks at the legacy
        3-arity.  ``k_max`` (scalar or per-row u64, exclusive) clips the
        scan window; ``epoch`` exists for signature parity with the sharded
        tiers (only ``None`` here).

        The scan walks ``max_leaves`` leaves per device wave and *resumes*
        truncated rows from their continuation cursor until every row hit
        ``limit`` or exhausted the chain — results are exact for any
        ``max_leaves`` >= 1 (callers no longer need to size it to cover
        ``limit``).  ``range_with_state`` exposes the truncation flag and
        cursor for callers that bound the re-issue rounds themselves.

        Edge cases: ``limit=0`` and empty request batches short-circuit to
        empty outputs host-side (keeping degenerate shapes out of the jit
        cache); a ``k_min`` above the largest key or inside an empty window
        comes back with ``count=0``.
        """
        k_min = api.take_legacy("range", legacy, k_min, "k_min", "start_keys_u64")
        api.reject_unknown("range", legacy)
        if args:  # legacy positional max_leaves
            api.warn_legacy("range", "positional max_leaves", "max_leaves=...")
            (max_leaves,) = args
        if epoch is not None:
            # NOT an assert: must survive ``python -O`` (see get_issue)
            raise ValueError(
                "single-store RANGE has no routing epochs (epoch must be None)"
            )
        res = self.range_with_state(
            k_min, limit=limit, max_leaves=max_leaves, k_max=k_max, as_of=as_of
        )
        return RangeResult(
            keys=res.keys,
            vals=res.vals,
            counts=res.counts,
            truncated=res.truncated,
            cursor_leaf=res.cursor_leaf,
            cursor_key=res.cursor_key,
            rounds=res.rounds,
            stats=res.stats,
            _arity=3,
        )

    def _scan_start(self, khi, klo, resume_np: np.ndarray, n_active: int):
        """Resolve the start leaf of each lane: continuation cursor if
        resuming, cached anchor on a hit, learned-index descent otherwise.
        The traversal device call is skipped entirely when no lane needs it
        — the anchor cache's descent-skip fast path."""
        B = int(khi.shape[0])
        fresh_np = np.zeros(B, dtype=bool)
        fresh_np[:n_active] = resume_np[:n_active] < 0
        hit_np = np.zeros(B, dtype=bool)
        tid = None
        probe = self.scan_cache is not None and fresh_np.any()
        with span("launch"):
            start = jnp.asarray(resume_np)  # -1 = fresh descent wanted
            if probe:
                # steer with the SCAN cache's thread geometry (the point
                # cache may be differently sized or disabled entirely)
                tid = hotcache.steer(khi, klo, self.scan_cache_cfg.n_threads)
                hit, cleaf = scancache.probe(
                    self.scan_cache, tid, khi, klo, cfg=self.scan_cache_cfg
                )
        if probe:
            with span("wait.scan_probe", waits=1):
                hit_np = np.asarray(hit) & fresh_np
            self.stats.scan_probes += int(fresh_np.sum())
            self.stats.scan_hits += int(hit_np.sum())
        with span("launch"):
            if probe:
                start = jnp.where((start < 0) & jnp.asarray(hit_np), cleaf, start)
            need_traverse = fresh_np & ~hit_np
            tstart = None
            if need_traverse.any():
                tstart = lookup.traverse(
                    self.tree, khi, klo, depth=self.depth,
                    eps_inner=self.cfg.eps_inner,
                )
                start = jnp.where(start < 0, tstart, start)
            if self.scan_cache is not None and tstart is not None:
                # admit the fresh descents the cache missed (anchor = the leaf
                # the descent bottomed out at; exact-key entries, so a later
                # RANGE with the same k_min skips the whole descent)
                self.scan_cache = scancache.admit(
                    self.scan_cache,
                    tid,
                    khi,
                    klo,
                    tstart,
                    jnp.asarray(need_traverse),
                    cfg=self.scan_cache_cfg,
                    wave=self.stats.waves & 0xFFFFFFFF,
                    epoch=self.stats.flush_cycles,
                )
        return start

    def range_with_state(
        self,
        start_keys_u64,
        limit: int = 10,
        max_leaves: int = 4,
        max_rounds: Optional[int] = None,
        start_leaves: Optional[np.ndarray] = None,
        k_max=None,
        as_of: Optional[int] = None,
    ) -> RangeResult:
        """RANGE with explicit continuation state: a :class:`RangeResult`
        carrying (keys (n, limit), vals, counts (n,), truncated (n,),
        cursor_leaf (n,), cursor_key (n,)) — tuple-unpacks at the legacy
        6-arity.

        ONE device dispatch: the scan-anchor cache resolves fresh rows'
        start leaves, then ``lookup.range_batch_loop`` runs the multi-round
        continuation entirely on device (``jax.lax.while_loop`` re-walking
        only truncated lanes from their cursor) — the host never re-issues.
        ``max_rounds=None`` loops until limit/exhaustion/window; a bounded
        ``max_rounds`` returns honestly-truncated rows with the cursor to
        resume from (``start_leaves`` accepts those cursors back, -1 = fresh
        descent).  ``k_max`` (scalar or per-row u64, exclusive) clips every
        round to an owned key window — clipped rows report ``truncated=
        False`` (the window is exhausted; whoever owns the successor window
        owns the continuation), which is what lets the sharded facade issue
        one sub-query per shard mid-rebalance.  ``truncated=False`` with
        ``count < limit`` means the key space (or window) genuinely ran out
        — the exhausted-vs-bounded distinction the scatter-gather epilogue
        keys on.  ``stats.range_rounds_in_mesh`` counts the interior rounds
        beyond the first; ``stats.range_reissue_rounds`` now only counts
        host-resumed calls (``start_leaves`` given) — the rare fallback.
        """
        return self.range_finalize(
            self.range_issue(
                start_keys_u64,
                limit=limit,
                k_max=k_max,
                max_leaves=max_leaves,
                max_rounds=max_rounds,
                start_leaves=start_leaves,
                arity=6,
                as_of=as_of,
            )
        )

    @_on_device
    def range_issue(
        self,
        k_min,
        limit: int = 10,
        *,
        k_max=None,
        epoch: Optional[int] = None,
        max_leaves: int = 4,
        max_rounds: Optional[int] = None,
        start_leaves: Optional[np.ndarray] = None,
        arity: int = 3,
        as_of: Optional[int] = None,
        pad_to: int = 0,
        _raw: bool = False,
    ) -> _RangeWave:
        """Issue half of RANGE: anchor-cache start resolution + the single
        ``range_batch_loop`` device dispatch (the in-mesh continuation loop
        runs without the host).  Returns without blocking on results;
        ``range_with_state() == range_finalize(range_issue())``.

        ``as_of=<version epoch>`` walks the retained snapshot instead of the
        live tree (one dispatch through the resolve-table kernels).  When a
        TTL filter applies (live tracker non-empty, or the epoch's frozen
        snapshot for as_of), expiry can hollow out a full row — the wave
        then runs its refill loop synchronously at issue time and comes
        back prebaked (``_raw=True`` is that loop's unfiltered inner call).

        The wave's programs run at ``n`` rows padded to a power of two, and
        at no fewer than ``pad_to``: a caller that splits one client wave
        into sub-waves gives each the client wave's shape, so the programs
        compiled follow the client's wave sizes and not how its rows split."""
        if max_rounds is not None and max_rounds < 1:
            # NOT an assert: must survive ``python -O`` (see get_issue)
            raise ValueError(
                "max_rounds: None = loop until limit/exhaustion/window; a "
                "bound must be >= 1 (0 would silently alias the unbounded "
                "loop)"
            )
        if epoch is not None:
            raise ValueError(
                "single-store RANGE has no routing epochs (epoch must be None)"
            )
        if as_of is not None:
            as_of = self.epochs.check_retained(as_of)
        start_keys_u64 = np.asarray(k_min, dtype=np.uint64)
        n = start_keys_u64.size
        lim = max(limit, 0)
        if not _raw and n and lim:
            if as_of is not None:
                snap = self._ttl_snap_for(as_of)
                expired_fn = (
                    (lambda k: TTLTracker.expired_at(snap, k))
                    if snap is not None
                    else None
                )
            else:
                expired_fn = self.ttl.is_expired_np if self.ttl else None
            if expired_fn is not None:
                return self._range_filtered(
                    start_keys_u64,
                    limit=limit,
                    k_max=k_max,
                    max_leaves=max_leaves,
                    arity=arity,
                    as_of=as_of,
                    expired_fn=expired_fn,
                )
        w = _RangeWave(
            n=n,
            limit=limit,
            arity=arity,
            resumed=start_leaves is not None,
            keys_out=np.zeros((n, lim), dtype=np.uint64),
            vals_out=np.zeros((n, lim), dtype=np.uint64),
            counts=np.zeros(n, dtype=np.int64),
            trunc_out=np.zeros(n, dtype=bool),
            cur_leaf_out=np.full(n, -1, dtype=np.int32),
            cur_key_out=start_keys_u64.copy(),
        )
        self.stats.ranges += n
        if n == 0 or limit <= 0:
            w.empty = True
            return w
        if start_leaves is not None:
            self.stats.range_reissue_rounds += 1
        with span("build"):
            B = _pad_pow2(max(n, pad_to))
            khi, klo, active = self._limbs(start_keys_u64, B)
            res_pad = np.full(B, -1, dtype=np.int32)
            if start_leaves is not None:
                res_pad[:n] = np.asarray(start_leaves, dtype=np.int32)
            ubs = np.full(B, KEY_MAX, dtype=np.uint64)  # sentinel: no clip
            if k_max is not None:
                ubs[:n] = np.asarray(k_max, dtype=np.uint64)
            ub_limbs = split_u64(ubs)
        if as_of is not None:
            # versioned walk: plain descent for fresh rows (the scan-anchor
            # cache serves LIVE pagination; versioned reads must not churn
            # its admissions), resolve table gathered per walked leaf
            w.as_of = as_of
            with span("launch"):
                start = jnp.asarray(res_pad)
                if (res_pad[:n] < 0).any():
                    tstart = lookup.traverse(
                        self.tree,
                        khi,
                        klo,
                        depth=self.depth,
                        eps_inner=self.cfg.eps_inner,
                    )
                    start = jnp.where(start < 0, tstart, start)
                start = jnp.where(active, start, -1)
                w.rk, w.rv, w.valid, w.trunc, w.cursor, w.rounds = (
                    lookup.range_batch_loop_versioned(
                        self.tree,
                        self._resolve_table(as_of),
                        start,
                        khi,
                        klo,
                        jnp.asarray(ub_limbs[:, 0]),
                        jnp.asarray(ub_limbs[:, 1]),
                        limit=limit,
                        max_leaves=max_leaves,
                        max_rounds=0 if max_rounds is None else max_rounds,
                    )
                )
            self._end_wave()
            return w
        start = self._scan_start(khi, klo, res_pad, n)
        with span("launch"):
            start = jnp.where(active, start, -1)  # pad rows ride along dead
            w.rk, w.rv, w.valid, w.trunc, w.cursor, w.rounds = (
                lookup.range_batch_loop(
                    self.tree,
                    self.ib,
                    start,
                    khi,
                    klo,
                    jnp.asarray(ub_limbs[:, 0]),
                    jnp.asarray(ub_limbs[:, 1]),
                    limit=limit,
                    max_leaves=max_leaves,
                    max_rounds=0 if max_rounds is None else max_rounds,
                )
            )
        self._end_wave()
        return w

    @_on_device
    def range_finalize(self, w: _RangeWave) -> RangeResult:
        """Drain half of RANGE: gather, host stitch, truncation epilogue,
        and pagination cursor admission."""
        n, limit = w.n, w.limit
        keys_out, vals_out = w.keys_out, w.vals_out
        counts, trunc_out = w.counts, w.trunc_out
        cur_leaf_out, cur_key_out = w.cur_leaf_out, w.cur_key_out
        if w.empty:
            # degenerate short-circuit OR a prebaked (filtered/refilled)
            # wave: the host accumulators already hold the final answer
            return RangeResult(
                keys=keys_out, vals=vals_out, counts=counts,
                truncated=trunc_out, cursor_leaf=cur_leaf_out,
                cursor_key=cur_key_out, rounds=w.rounds_done,
                stats=w.stats_out or {}, _arity=w.arity,
            )
        with span("wait.results", waits=8):
            rounds = int(w.rounds)
            va = np.asarray(w.valid)[:n]
            rk = np.asarray(w.rk)[:n]
            rv = np.asarray(w.rv)[:n]
            trunc = np.asarray(w.trunc)[:n]
            cleaf = np.asarray(w.cursor.leaf)[:n]
            ckhi = np.asarray(w.cursor.khi)[:n]
            cklo = np.asarray(w.cursor.klo)[:n]
        eligible = None
        with span("epilogue"):
            self.stats.range_rounds_in_mesh += max(rounds - 1, 0)
            rc = va.sum(axis=1)
            keys_out[:] = np.where(va, join_u64(rk), 0)
            vals_out[:] = np.where(va, join_u64(rv), 0)
            counts[:] = rc
            trunc_out[:] = trunc
            cur_leaf_out[:] = cleaf
            last_key = join_u64(np.stack([ckhi, cklo], axis=-1))
            emitted = rc > 0
            cur_key_out[emitted] = last_key[emitted]
            trunc_out &= counts < limit
            self.stats.range_truncated += int(trunc_out.sum())
            if not w.resumed and w.as_of is None:
                # only fresh client-entry scans admit their cursors: a
                # resumed call (start_leaves given) is an orchestration
                # round — the sharded facade re-issues those itself, so its
                # interior cursors would never be probed and would only
                # evict real pagination anchors (and cost a host descent)
                eligible = self._admit_cursor_anchors(trunc_out, cur_key_out)
        if eligible is not None:
            with span("wait.stats", waits=1):
                self.stats.scan_cursor_admits += int(np.asarray(eligible).sum())
        stats = {"rounds_in_mesh": max(rounds - 1, 0), "reissue": int(w.resumed)}
        if w.as_of is not None:
            stats["as_of"] = int(w.as_of)
        return RangeResult(
            keys=keys_out,
            vals=vals_out,
            counts=counts,
            truncated=trunc_out,
            cursor_leaf=cur_leaf_out,
            cursor_key=cur_key_out,
            rounds=rounds,
            stats=stats,
            _arity=w.arity,
        )

    def _admit_cursor_anchors(self, trunc: np.ndarray, last_keys: np.ndarray):
        """Scan-anchor cursor admission (pagination pre-warm).

        A truncated RANGE's continuation cursor is representationally an
        anchor (``lookup.ScanCursor`` == scancache entry), and the client's
        next page is ``RANGE(last_key + 1)`` — admit that key now, mapped to
        its host-replica descent leaf (``image.find_leaf``: the successor
        leaf of the truncated walk, or the last walked leaf when the cut key
        range still reaches into it), so the follow-up wave skips the device
        descent.  The admitted entry is bit-identical to what a later
        miss-then-traverse would admit, so the cache's existing safety
        arguments — buffered writes visible through the walk, restitch
        invalidation by leaf id — apply unchanged.

        Returns the device mask of admitted lanes (None when nothing was
        probed); the caller counts it into ``scan_cursor_admits``."""
        if self.scan_cache is None or not self.scan_cache_cfg.admit_cursors:
            return None
        m = np.where(trunc)[0]
        if m.size == 0:
            return None
        nxt = last_keys[m] + np.uint64(1)
        nxt = nxt[nxt < KEY_MAX]  # 2^64-1 is the reserved sentinel
        if nxt.size == 0:
            return None
        leaves = np.array(
            [self.image.find_leaf(k)[0] for k in nxt], dtype=np.int32
        )
        B = _pad_pow2(nxt.size)
        khi, klo, active = self._limbs(nxt, B)
        lf = np.full(B, -1, dtype=np.int32)
        lf[: nxt.size] = leaves
        tid = hotcache.steer(khi, klo, self.scan_cache_cfg.n_threads)
        hit, _ = scancache.probe(
            self.scan_cache, tid, khi, klo, cfg=self.scan_cache_cfg
        )
        eligible = active & ~hit
        self.scan_cache = scancache.admit(
            self.scan_cache,
            tid,
            khi,
            klo,
            jnp.asarray(lf),
            eligible,
            cfg=self.scan_cache_cfg,
            wave=self.stats.waves & 0xFFFFFFFF,
            epoch=self.stats.flush_cycles,
        )
        return eligible

    def _range_filtered(
        self,
        start_keys_u64: np.ndarray,
        *,
        limit: int,
        k_max,
        max_leaves: int,
        arity: int,
        as_of: Optional[int],
        expired_fn,
    ) -> _RangeWave:
        """TTL-filtered RANGE: refill loop over the unfiltered machinery.

        Expired keys are dropped post-scan, so a row whose unfiltered walk
        filled ``limit`` may come back short — those rows re-issue from the
        last *pre-filter* key + 1 until the limit fills or the window/chain
        exhausts.  Runs synchronously at issue time (each inner call is one
        device dispatch) and returns a prebaked wave, which keeps pipelined
        execution bitwise-equal to serial: the whole loop lands at this
        wave's position in the issue order.  Rows are never reported
        truncated — the loop absorbs any interior bound itself."""
        n = start_keys_u64.size
        lim = max(limit, 0)
        w = _RangeWave(
            n=n,
            limit=limit,
            arity=arity,
            resumed=False,
            keys_out=np.zeros((n, lim), dtype=np.uint64),
            vals_out=np.zeros((n, lim), dtype=np.uint64),
            counts=np.zeros(n, dtype=np.int64),
            trunc_out=np.zeros(n, dtype=bool),
            cur_leaf_out=np.full(n, -1, dtype=np.int32),
            cur_key_out=start_keys_u64.copy(),
            empty=True,  # prebaked: no pending device gather
            as_of=as_of,
        )
        kmax_arr = np.full(n, KEY_MAX, dtype=np.uint64)
        if k_max is not None:
            kmax_arr[:] = np.asarray(k_max, dtype=np.uint64)
        cur_k = start_keys_u64.copy()
        need = np.ones(n, dtype=bool)
        rounds = 0
        while need.any():
            idxs = np.where(need)[0]
            r = self.range_finalize(
                self.range_issue(
                    cur_k[idxs],
                    limit=limit,
                    k_max=kmax_arr[idxs],
                    max_leaves=max_leaves,
                    arity=6,
                    as_of=as_of,
                    _raw=True,
                )
            )
            rounds += max(int(r.rounds), 1)
            for j, i in enumerate(idxs):
                rc = int(r.counts[j])
                rk = r.keys[j, :rc]
                rv = r.vals[j, :rc]
                keep = ~expired_fn(rk)
                rk, rv = rk[keep], rv[keep]
                space = limit - int(w.counts[i])
                take = min(rk.size, space)
                if take:
                    at = int(w.counts[i])
                    w.keys_out[i, at : at + take] = rk[:take]
                    w.vals_out[i, at : at + take] = rv[:take]
                    w.counts[i] += take
                    w.cur_key_out[i] = rk[take - 1]
                if w.counts[i] >= limit or rc < limit:
                    # filled, or the unfiltered walk exhausted the window
                    need[i] = False
                    continue
                nxt = int(r.cursor_key[j]) + 1  # last pre-filter key + 1
                if nxt >= int(kmax_arr[i]) or nxt >= int(KEY_MAX):
                    need[i] = False
                else:
                    cur_k[i] = np.uint64(nxt)
        w.rounds_done = rounds
        w.stats_out = {"rounds_in_mesh": 0, "reissue": 0, "ttl_filtered": 1}
        if as_of is not None:
            w.stats_out["as_of"] = int(as_of)
        return w

    # ------------------------------------------------------------ patch path
    def _process_full_leaves(self) -> int:
        with span("wait.counts", waits=1):
            counts = np.asarray(self.ib.count)
        full = np.where(counts >= self.cfg.ib_cap)[0]
        return self._patch_cycle([int(l) for l in full])

    def _flush_leaves_of(self, keys_u64: np.ndarray) -> None:
        """Patch the (non-empty) buffers responsible for RETRYing keys."""
        with span("wait.counts", waits=1):
            counts = np.asarray(self.ib.count)
        leaves = []
        for k in np.asarray(keys_u64, dtype=np.uint64):
            leaf, _ = self.image.find_leaf(k)
            if int(counts[leaf]) > 0 and leaf not in leaves:
                leaves.append(int(leaf))
        self._patch_cycle(leaves)

    @_on_device
    def flush(self) -> int:
        """Patch every non-empty insert buffer as one flush cycle."""
        with span("wait.counts", waits=1):
            counts = np.asarray(self.ib.count)
        leaves = np.where(counts > 0)[0]
        return self._patch_cycle([int(l) for l in leaves])

    def _buffer_entries(self, leaves):
        """Snapshot the buffered ops of the given leaves (host-side read of
        the staged writes — the 'migrate to host' half of the cycle)."""
        with span("wait.buffers", waits=4):
            counts = np.asarray(self.ib.count)
            ib_keys = np.asarray(self.ib.keys)
            ib_vals = np.asarray(self.ib.vals)
            ib_ops = np.asarray(self.ib.op)
        out = []
        for leaf in leaves:
            cnt = int(counts[leaf])
            kk = join_u64(ib_keys[leaf, :cnt])
            vv = join_u64(ib_vals[leaf, :cnt])
            oo = ib_ops[leaf, :cnt]
            out.append([(int(k), int(v), int(o)) for k, v, o in zip(kk, vv, oo)])
        return out

    def _headroom_ok(self, planned_parents: int = 0) -> bool:
        """Can the pools absorb one more worst-case patch without recycling?

        A merged transaction cannot reuse the rows it obsoletes (they stay
        quarantined until after its CONNECT), so the planner probes this
        before each additional leaf.  Leaf pools: a split re-segments
        <= SEG_CAP + ib_cap merged keys at split_cap fill.  Node pools: the
        tree phase rebuilds each of the ``planned_parents`` affected nodes
        once (budget ~3 new nodes each) plus a possible root-growth chain."""
        img, cfg = self.image, self.cfg
        a_leaf = -(-(SEG_CAP + cfg.ib_cap) // cfg.split_cap) + 1
        # each affected parent rebuilds once into a handful of (retrain-
        # bound-sparse) nodes of <= NODE_SEGS pivot slots each, plus a
        # possible root-growth chain of ~one node+slot per level
        a_node = 4 * (planned_parents + 1) + 2 * self.image.depth + 4
        a_pivot = 7 * (planned_parents + 1) + 2 * self.image.depth + 4
        return (
            len(img.free_leaves) >= a_leaf
            and len(img.free_slots) >= a_leaf
            and len(img.free_nodes) >= a_node
            and len(img.free_pivots) >= a_pivot
        )

    def _patch_cycle(self, leaves) -> int:
        """Drain the given buffers as a flush cycle: plan all patches into a
        merged stitch batch, apply COPYs once, CONNECTs once, then do the
        cycle's epoch bookkeeping — one host->device transaction per cycle.
        Only when pool headroom runs out mid-plan does the cycle split into
        multiple transactions (degrading toward the per-leaf cadence, whose
        interleaved reclaim keeps the store live).  Falls back to the
        per-leaf oracle stream when ``batched_patch`` is off.  Its host
        time, buffer snapshot included, is the ``flush`` span and
        ``stats.flush_ns``."""
        with span("wait.counts", waits=1):
            counts = np.asarray(self.ib.count)
        leaves = [int(l) for l in leaves if int(counts[int(l)]) > 0]
        if not leaves:
            return 0
        with span("flush") as s:
            n = self._run_patch_cycle(
                list(zip(leaves, self._buffer_entries(leaves)))
            )
        self.stats.flush_ns += s.ns
        return n

    def _run_patch_cycle(self, pending) -> int:
        """One flush cycle over explicit ``(leaf, entries)`` work items.
        Entries normally snapshot the leaf's insert buffer (``_patch_cycle``);
        ``extract_slice`` synthesizes tombstone entries directly — either way
        the plan/apply/epoch path is identical."""
        n_leaves = len(pending)
        self.stats.flush_cycles += 1
        if not self.batched_patch:
            for leaf, entries in pending:
                self._patch_leaf_entries(leaf, entries)
            return n_leaves
        while pending:
            chunk_leaves = [l for l, _ in pending]
            chunk_entries = [e for _, e in pending]
            # version-chain stamp: leaves this transaction emits are born at
            # the cycle it completes as (end_cycle increments afterwards)
            self.image.version_cycle = self.epochs.cycle + 1
            with span("plan") as s:
                result = patch.plan_patch_batch(
                    self.image, chunk_leaves, chunk_entries,
                    headroom_ok=self._headroom_ok,
                    force_structural=self.retain_epochs > 0,
                )
            self.stats.plan_ns += s.ns
            pending = result.unplanned
            with span("stitch") as s:
                # COPY then CONNECT — the stitch atomicity contract, once
                # per transaction (one per cycle unless headroom forced a
                # split)
                self.tree = stitch.apply_copies(self.tree, result.batch)
                self.tree, self.ib = stitch.apply_connects(
                    self.tree, self.ib, result.batch
                )
                self._ib_shadow = None  # connects drained buffers: stale
                self.stats.stitch_applies += 1
                # Cycle-granularity epoch bookkeeping: quarantine everything
                # the transaction obsoleted, advance once.  (Within the
                # transaction nothing was reclaimed, so no COPY could have
                # landed on a still-reachable row.)  The on_defer listener
                # collects the cycle's obsoleted leaves; dropping their scan
                # anchors here — before the cycle returns — is what keeps a
                # restitched leaf chain from ever serving a cached-anchor
                # scan.
                self.epochs.defer_free_batch(result.batch.frees)
                self._apply_scan_invalidation()
                self.stats.reclaimed += self.epochs.end_cycle(self.image)
                self._note_cycle_end()
            self.stats.stitch_ns += s.ns
            self.stats.stitched_bytes += result.batch.payload_bytes()
            self.stats.stitched_dpa_bytes += result.batch.dpa_bytes()
            self.stats.patches_update += result.n_update
            self.stats.patches_structural += result.n_structural
            self.stats.new_leaves += len(result.new_leaves)
            self.stats.patched_leaves += len(result.results)
        return n_leaves

    def _patch_leaf(self, leaf: int) -> None:
        """Per-leaf oracle path: one stitch transaction per patched leaf
        (the pre-batching stream; kept for equivalence testing)."""
        with span("wait.counts", waits=1):
            cnt = int(np.asarray(self.ib.count)[leaf])
        if cnt == 0:
            return
        self._patch_leaf_entries(leaf, self._buffer_entries([leaf])[0])

    def _patch_leaf_entries(self, leaf: int, entries) -> None:
        self.image.version_cycle = self.epochs.cycle + 1
        with span("plan") as s:
            result = patch.plan_patch(
                self.image, leaf, entries,
                force_structural=self.retain_epochs > 0,
            )
        self.stats.plan_ns += s.ns
        with span("stitch") as s:
            # COPY then CONNECT — the stitch atomicity contract
            self.tree = stitch.apply_copies(self.tree, result.batch)
            self.tree, self.ib = stitch.apply_connects(
                self.tree, self.ib, result.batch
            )
            self._ib_shadow = None  # connects drained buffers: shadow stale
            self.stats.stitch_applies += 1
            self.stats.patched_leaves += 1
            for pool, idx in result.batch.frees:
                self.epochs.defer_free(pool, idx)
            self._apply_scan_invalidation()
            # Patches run with no wave in flight (host-serialized), so every
            # traverser has trivially "moved on": advancing the epoch here
            # is the degenerate-but-sound case of the paper's packet-counter
            # epoch.  end_cycle = advance + reclaim, plus the version-cycle
            # increment the per-leaf stream owes (one transaction per
            # patched leaf).
            self.stats.reclaimed += self.epochs.end_cycle(self.image)
            self._note_cycle_end()
        self.stats.stitch_ns += s.ns
        self.stats.stitched_bytes += result.batch.payload_bytes()
        self.stats.stitched_dpa_bytes += result.batch.dpa_bytes()
        if result.kind == "update":
            self.stats.patches_update += 1
        else:
            self.stats.patches_structural += 1
            self.stats.new_leaves += len(result.new_leaves)

    # ----------------------------------------- slice migration (rebalance)
    def live_count(self) -> int:
        """Live keys in the stitched tree (leaf-chain walk — freed pool rows
        never counted).  Buffered writes are not included; flush first for
        an exact census (the rebalance planner's occupancy probe does)."""
        total = 0
        leaf = self.image.first_leaf()
        while leaf != -1:
            total += int(self.image.leaf_count[leaf])
            leaf = int(self.image.leaf_next[leaf])
        return total

    def _slice_run(self, k_lo, k_hi) -> List[int]:
        """Leaf ids of the contiguous run intersecting ``[k_lo, k_hi)`` —
        descend once to the floor leaf of ``k_lo``, then follow
        ``leaf_next`` while anchors stay below ``k_hi`` (the same
        contiguous-run shape the stitch pipeline ships)."""
        k_lo, k_hi = np.uint64(k_lo), np.uint64(k_hi)
        if k_lo >= k_hi:
            return []
        leaf, _ = self.image.find_leaf(k_lo)
        run: List[int] = []
        while leaf != -1 and np.uint64(self.image.leaf_anchor[leaf]) < k_hi:
            run.append(int(leaf))
            leaf = int(self.image.leaf_next[leaf])
        return run

    def count_slice(self, k_lo, k_hi) -> int:
        """Stitched live keys in ``[k_lo, k_hi)`` (no flush — callers that
        need buffered writes counted flush first, as the migration path
        does)."""
        k_lo, k_hi = np.uint64(k_lo), np.uint64(k_hi)
        total = 0
        for leaf in self._slice_run(k_lo, k_hi):
            lk = self.image.leaf_keys(leaf)
            total += int(((lk >= k_lo) & (lk < k_hi)).sum())
        return total

    @_on_device
    def snapshot_slice(self, k_lo, k_hi) -> Tuple[np.ndarray, np.ndarray]:
        """Live pairs in ``[k_lo, k_hi)`` as ascending ``(keys, vals)`` —
        the copy half of a slice migration.  Flushes staged writes first so
        the stitched leaf run is the whole truth."""
        self.flush()
        k_lo, k_hi = np.uint64(k_lo), np.uint64(k_hi)
        ks, vs = [], []
        for leaf in self._slice_run(k_lo, k_hi):
            lk = self.image.leaf_keys(leaf)
            m = (lk >= k_lo) & (lk < k_hi)
            if m.any():
                ks.append(lk[m].copy())
                vs.append(self.image.leaf_vals(leaf)[m].copy())
        if not ks:
            empty = np.zeros(0, dtype=np.uint64)
            return empty, empty.copy()
        return np.concatenate(ks), np.concatenate(vs)

    @_on_device
    def extract_slice(self, k_lo, k_hi) -> Tuple[np.ndarray, np.ndarray]:
        """Detach the live pairs in ``[k_lo, k_hi)``: returns them and
        removes them from this store — the retire half of a slice
        migration.  Removal is a leaf run of synthesized tombstones planned
        through the (batched) patch/stitch pipeline, so it is one stitch
        transaction with the standard epoch bookkeeping: replaced leaves
        are quarantined, their scan anchors dropped via the
        ``EpochManager.on_defer`` listener before the cycle returns, and a
        fully-emptied leaf stays in the chain as an empty routing stub
        (``plan_patch`` keeps routing total)."""
        keys, vals = self.snapshot_slice(k_lo, k_hi)  # flushes
        if keys.size:
            k_lo, k_hi = np.uint64(k_lo), np.uint64(k_hi)
            pending = []
            for leaf in self._slice_run(k_lo, k_hi):
                lk = self.image.leaf_keys(leaf)
                m = (lk >= k_lo) & (lk < k_hi)
                if m.any():
                    pending.append(
                        (leaf, [(int(k), 0, IB_DEL) for k in lk[m]])
                    )
            self._run_patch_cycle(pending)
        self.stats.migrated_out_keys += int(keys.size)
        return keys, vals

    def stub_count(self) -> int:
        """Empty routing-stub leaves currently in the chain (the residue of
        ``extract_slice`` / all-deleting patches)."""
        n = 0
        leaf = self.image.first_leaf()
        while leaf != -1:
            n += int(self.image.leaf_count[leaf]) == 0
            leaf = int(self.image.leaf_next[leaf])
        return n

    @_on_device
    def compact_chain(self) -> int:
        """Remove empty leaf stubs from the chain (and their parent
        entries) as one stitch transaction — the reclaim pass that keeps
        ``extract_slice`` residue from accumulating across rebalance
        cycles.  The chain head is kept (routing stays total with >= 1
        leaf) and stubs with buffered writes are skipped (they are about
        to become real leaves again).  Freed rows ride the standard epoch
        quarantine, which also drops their scan anchors before the call
        returns.  Returns the number of stubs removed."""
        ib_counts = np.asarray(self.ib.count)
        stubs = []
        prev = -1
        leaf = self.image.first_leaf()
        while leaf != -1:
            nxt = int(self.image.leaf_next[leaf])
            if (
                int(self.image.leaf_count[leaf]) == 0
                and int(ib_counts[leaf]) == 0
                and prev != -1
                and self._stub_version_safe(leaf)
            ):
                stubs.append(leaf)
            else:
                prev = leaf
            leaf = nxt
        if not stubs:
            return 0
        batch, n = patch.plan_chain_compaction(self.image, stubs)
        if n == 0:
            return 0
        # COPY then CONNECT, then the cycle's epoch bookkeeping — identical
        # to a flush cycle's tail (see _run_patch_cycle)
        self.tree = stitch.apply_copies(self.tree, batch)
        self.tree, self.ib = stitch.apply_connects(self.tree, self.ib, batch)
        self._ib_shadow = None  # connects drained buffers: shadow stale
        self.stats.stitch_applies += 1
        self.epochs.defer_free_batch(batch.frees)
        self._apply_scan_invalidation()
        self.stats.reclaimed += self.epochs.end_cycle(self.image)
        self._note_cycle_end()
        self.stats.stitched_bytes += batch.payload_bytes()
        self.stats.stitched_dpa_bytes += batch.dpa_bytes()
        self.stats.stub_leaves_compacted += n
        return n

    def _stub_version_safe(self, leaf: int) -> bool:
        """Retention gate for chain compaction: removing a stub widens its
        predecessor's routed window, so any epoch-E key the stub's version
        chain still serves would become unreachable through the current
        descent.  Walk the chain back to the oldest retained epoch and
        require EVERY visited version to be empty; otherwise the stub must
        survive this sweep (it becomes removable once the window ages out).
        Version rows of retained ids are intact — reclaim's retention gate
        releases nothing the walk can visit."""
        if self.epochs.retain <= 0:
            return True
        oldest = self.epochs.horizon + 1  # oldest retained version epoch
        vb, vp = self.image.ver_birth, self.image.ver_prev
        lc = self.image.leaf_count
        node = int(leaf)
        while True:
            if int(lc[node]) != 0:
                return False
            if int(vb[node]) <= oldest:
                return True
            prev = int(vp[node])
            if prev < 0:
                return True
            node = prev

    # ------------------------------------------------------------ TTL sweep
    @_on_device
    def ttl_sweep(self) -> int:
        """Physically reclaim expired keys: tombstone every key past its
        deadline, flush the tombstones through a stitch cycle, then run the
        chain compaction pass over any leaves the deletions emptied.  After
        the sweep the reclaimed keys are gone from the live tree (reads were
        already filtering them; ``as_of`` windows still see them until the
        epochs age out).  Returns the number of keys reclaimed."""
        expired = self.ttl.expired_keys()
        if not expired:
            return 0
        keys = np.array(sorted(expired), dtype=np.uint64)
        self.delete(keys)  # note_delete drops the deadlines
        self.flush()
        self.compact_chain()
        return int(keys.size)

    def ingest_headroom(self) -> int:
        """Keys this store can absorb via :meth:`ingest_slice` without
        risking pool exhaustion: conservative — new leaves fill at
        ``split_cap`` and half the free pool stays reserved for ongoing
        churn.  The rebalance planner refuses a migration bigger than
        this."""
        free = min(len(self.image.free_leaves), len(self.image.free_slots))
        return max(0, (free // 2) * self.cfg.split_cap)

    @_on_device
    def ingest_slice(
        self, keys_u64, vals_u64, wave: int = 512, splice: bool = True
    ) -> int:
        """Bulk-ingest pairs (the receiving half of a slice migration).

        The default is a direct leaf-run splice: the incoming pairs are
        sorted, grouped by target leaf with one chain walk, and planned
        straight through the batched patch pipeline as synthesized PUT
        entries — each touched leaf is patched ONCE per call instead of
        once per ``ib_cap`` buffered keys, so the stitch traffic is the
        slice payload plus O(new leaves), ~``ib_cap``-fold less than the
        PUT path's repeated re-stitching of the same region.  Staged
        writes are flushed first, so the end state is identical to the
        PUT path (later entries win in the merge either way).

        ``splice=False`` keeps the legacy path — chunked PUT waves
        through the insert buffers — as the semantic oracle.  Both paths
        leave the slice fully stitched (visible to leaf-run walks) on
        return and raise ``MemoryError`` on pool pressure rather than
        silently dropping keys: a dropped key here would be destroyed
        for good when the migration retires the donor's copy."""
        keys = np.asarray(keys_u64, dtype=np.uint64)
        vals = np.asarray(vals_u64, dtype=np.uint64)
        if not splice:
            for i in range(0, keys.size, wave):
                st = self.put(keys[i : i + wave], vals[i : i + wave])
                if not np.all(st == STATUS_OK):
                    raise MemoryError(
                        f"ingest_slice: {int((st != STATUS_OK).sum())} keys "
                        "failed to land (pool pressure) — raise "
                        "TreeConfig.growth or shrink the migration"
                    )
            self.flush()
            self.stats.migrated_in_keys += int(keys.size)
            return int(keys.size)
        n_in = int(keys.size)
        self.flush()  # staged ops stitch first; ingest entries then win
        if keys.size:
            order = np.argsort(keys, kind="stable")
            sk, sv = keys[order], vals[order]
            last = np.ones(sk.size, dtype=bool)
            last[:-1] = sk[1:] != sk[:-1]  # duplicate key: last PUT wins
            sk, sv = sk[last], sv[last]
            pos = 0
            cfg = self.cfg
            while pos < sk.size:
                # one splice cycle: consecutive leaf groups until the pool
                # budget (same reserve as ingest_headroom: half the free
                # leaf/slot rows, new leaves filling at split_cap) is spent
                budget = min(
                    len(self.image.free_leaves), len(self.image.free_slots)
                ) // 2
                if budget < 2 or not self._headroom_ok(0):
                    raise MemoryError(
                        "ingest_slice: leaf pools exhausted mid-splice — "
                        "raise TreeConfig.growth or shrink the migration"
                    )
                pending = []
                while pos < sk.size and budget >= 2:
                    leaf, _ = self.image.find_leaf(sk[pos])
                    # group end by TREE routing, not the chain: after a
                    # chain compaction a parent legitimately routes keys
                    # below the successor's chain anchor to it, and a group
                    # crossing that routing boundary would corrupt the
                    # parent splice.  find_leaf is monotone in the key, so
                    # bisect for the last key still routed to ``leaf``.
                    lo, hi = pos + 1, sk.size
                    while lo < hi:
                        mid = (lo + hi) // 2
                        if int(self.image.find_leaf(sk[mid])[0]) == int(leaf):
                            lo = mid + 1
                        else:
                            hi = mid
                    take = lo - pos
                    have = int(self.image.leaf_count[leaf])
                    # leaves this group may consume once re-segmented
                    need = -(-(have + take) // cfg.split_cap) + 1
                    if need > budget:
                        # partial group: take only what this cycle's budget
                        # absorbs, then stitch before walking further (two
                        # pending items for one leaf cannot share a cycle)
                        take = min(take, (budget - 1) * cfg.split_cap - have)
                        if take <= 0:
                            break
                        need = budget
                    chunk = [
                        (int(k), int(v), IB_PUT)
                        for k, v in zip(sk[pos : pos + take], sv[pos : pos + take])
                    ]
                    pending.append((int(leaf), chunk))
                    pos += take
                    budget -= need
                if not pending:
                    raise MemoryError(
                        "ingest_slice: leaf pools exhausted mid-splice — "
                        "raise TreeConfig.growth or shrink the migration"
                    )
                self._run_patch_cycle(pending)
        self.stats.migrated_in_keys += n_in
        return n_in

    # ------------------------------------------------------------- analysis
    def memory_report(self) -> Dict[str, float]:
        """Table-1 style accounting: index overhead vs raw KV bytes."""
        idx = self.image.index_bytes()
        data = self.image.data_bytes()
        return {
            "index_bytes": idx,
            "data_bytes": data,
            "rel_overhead": idx / max(data, 1),
            "nic_bytes_total": idx + data,  # what would sit in DPA memory if
            # values were NIC-resident; DPA-Store keeps values host-side
            "dpa_resident_bytes": idx,
        }

    @_on_device
    def items(self) -> Tuple[np.ndarray, np.ndarray]:
        """All live pairs in key order (stitched tree + buffered writes)."""
        base = {}
        for k, v in self.image.iter_items():
            base[int(k)] = int(v)
        counts = np.asarray(self.ib.count)
        ops = np.asarray(self.ib.op)
        ibk = np.asarray(self.ib.keys)
        ibv = np.asarray(self.ib.vals)
        for leaf in np.where(counts > 0)[0]:
            for j in range(int(counts[leaf])):
                k = int(join_u64(ibk[leaf, j]))
                if ops[leaf, j] == IB_PUT:
                    base[k] = int(join_u64(ibv[leaf, j]))
                elif ops[leaf, j] == IB_DEL:
                    base.pop(k, None)
        if self.ttl:
            now = self.ttl.now
            dl = self.ttl.deadlines
            base = {
                k: v
                for k, v in base.items()
                if k not in dl or now < dl[k]
            }
        ks = np.array(sorted(base.keys()), dtype=np.uint64)
        vs = np.array([base[int(k)] for k in ks], dtype=np.uint64)
        return ks, vs
