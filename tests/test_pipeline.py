"""Pipelined-vs-serial differential suite for the async wave pipeline.

The correctness claim of ``serving/pipeline.py`` is bitwise: driving any op
batch stream through :class:`PipelinedStore` at any ``queue_depth`` — with
waves genuinely overlapping in flight — produces exactly the results, final
store contents, and counter totals of the serial facade.  These tests run
every op stream on TWIN stores (one serial, one pipelined with submit lag)
and compare every output array, across tiers:

* single ``DPAStore`` (with and without the hot cache),
* hash-partitioned and range-partitioned ``ShardedDPAStore``,
* replicated range tier (R=2) with primary kills / failover-epoch reads /
  re-replication between in-flight waves,

including truncated RANGE continuation cursors (``max_leaves=1`` with scan
lengths past one leaf), epoch-tagged reads mid rebalance handoff, and a
hypothesis-driven sweep placing flush / rebalance / failover barriers at
arbitrary points between in-flight waves.

The donation-hazard half: ``insert_buffer.append_wave`` and the two caches
donate their state argument, and on this runtime a donated handle is
DELETED — the tests pin that deliberate reuse of a stale pre-donation
handle raises, and that a deep pipelined run stays clean under JAX's
tracer-leak checker (no wave context may retain store state handles).
"""

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import DPAStore, TreeConfig
from repro.distributed import kvshard
from repro.serving.pipeline import (
    PipelinedStore,
    WaveBufferPool,
    WavePipeline,
)

pytestmark = pytest.mark.timeout(300)

KEY_BOUND = 2**63
TIERS = ("single", "hash", "range", "range_r2")


# ---------------------------------------------------------------------------
# twin-store differential harness
# ---------------------------------------------------------------------------


def _build(tier, keys, vals, cache=False):
    if tier == "single":
        from repro.core.hotcache import CacheConfig

        return DPAStore(
            keys, vals, TreeConfig(growth=16.0),
            cache_cfg=CacheConfig() if cache else None,
        )
    n_shards = 2 if tier != "range" else 3
    return kvshard.ShardedDPAStore(
        keys, vals, n_shards, TreeConfig(growth=16.0),
        partition="hash" if tier == "hash" else "range",
        cache_cfg=None,
        replication=2 if tier == "range_r2" else 1,
    )


def _gen_script(rng, n_ops, tier, wave=24):
    """A deterministic op stream (ops carry their key material, so both
    twins replay the identical stream).  Admin ops track a tiny state
    machine so begin/commit and kill/retire pair up legally."""
    sharded = tier != "single"
    rangey = tier in ("range", "range_r2")
    replicated = tier == "range_r2"
    mix = ["get", "put", "delete", "range", "flush"]
    if rangey:
        mix += ["rebalance", "begin", "commit"]
    if replicated:
        mix += ["kill", "retire", "recover"]
    in_handoff = failover = False
    script = []
    for _ in range(n_ops):
        op = mix[rng.integers(len(mix))]
        q = rng.integers(1, KEY_BOUND, wave, dtype=np.uint64)
        if op == "get":
            script.append(("get", q, bool(rng.integers(2)) and (in_handoff or failover)))
        elif op == "put":
            k = np.unique(q)
            script.append(("put", k, k ^ np.uint64(0xF)))
        elif op == "delete":
            script.append(("delete", np.unique(q[: wave // 2])))
        elif op == "range":
            limit = int(rng.choice([1, 7, 40]))
            max_leaves = int(rng.choice([1, 4]))
            old = bool(rng.integers(2)) and (in_handoff or failover)
            script.append(("range", q[: wave // 2], limit, max_leaves, old))
        elif op == "flush":
            script.append(("flush",))
        elif op == "rebalance" and not in_handoff and not failover:
            script.append(("rebalance",))
        elif op == "begin" and not in_handoff and not failover:
            script.append(("begin",))
            in_handoff = True
        elif op == "commit" and in_handoff:
            script.append(("commit",))
            in_handoff = False
        elif op == "kill" and not failover and not in_handoff:
            script.append(("kill", int(rng.integers(2))))
            failover = True
        elif op == "retire" and failover:
            script.append(("retire",))
            failover = False
        elif op == "recover" and not failover:
            script.append(("recover",))
    # leave no handoff open: final items()/counters must compare cleanly
    if in_handoff:
        script.append(("commit",))
    if failover:
        script.append(("retire",))
    if replicated:
        script.append(("recover",))
    del sharded
    return script


def _epoch(store, old):
    """Resolve an 'old epoch' tag at execution time: both twins hold the
    same epoch state, so the resolved tag is identical.  The tag only
    applies while a previous epoch is actually live (a begin_rebalance
    that proposed no moves opens no handoff)."""
    own = getattr(store, "ownership", None)
    if old and (store.in_handoff or (own is not None and own.in_handoff)):
        return store.boundary_epoch - 1
    return None


def _exec_admin(store, op):
    """Admin/barrier ops — identical calls on the serial store and the
    pipelined facade (where they drain the pipeline first)."""
    kind = op[0]
    if kind == "flush":
        return store.flush()
    if kind == "rebalance":
        if store.planner is None:
            return None
        return _norm(store.rebalance(store.planner.propose(store.boundaries)))
    if kind == "begin":
        if store.planner is None:
            return None
        moves = store.begin_rebalance(store.planner.propose(store.boundaries))
        return bool(moves)
    if kind == "commit":
        if not store.in_handoff:  # begin may have proposed no moves
            return None
        return store.commit_rebalance()
    if kind == "kill":
        g = op[1]
        if store.in_handoff or (
            store.ownership is not None and store.ownership.in_handoff
        ):
            return "busy"  # two-epoch window is single-occupancy
        if any(slot is None for slot in store.groups[g]):
            return "dead"
        return store.kill_replica(g)
    if kind == "retire":
        if store.ownership is None or not store.ownership.in_handoff:
            return None
        return store.retire_failover()
    if kind == "recover":
        if any(s is None for grp in store.groups for s in grp):
            return store.recover_replicas()
        return None
    raise AssertionError(op)


def _norm(res):
    if res is None or isinstance(res, (bool, int, float, str)):
        return res
    if isinstance(res, np.ndarray):
        return res
    try:
        return tuple(_norm(x) for x in res)
    except TypeError:
        return np.asarray(res)


def _assert_eq(ra, rb, ctx):
    if isinstance(ra, tuple):
        assert isinstance(rb, tuple) and len(ra) == len(rb), ctx
        for j, (x, y) in enumerate(zip(ra, rb)):
            _assert_eq(x, y, (*ctx, j))
    elif isinstance(ra, np.ndarray):
        assert np.array_equal(ra, np.asarray(rb)), ctx
    else:
        assert ra == rb, (ctx, ra, rb)


def _run_serial(store, script):
    single = isinstance(store, DPAStore)
    out = []
    for op in script:
        kind = op[0]
        if kind == "get":
            ep = None if single else _epoch(store, op[2])
            kw = {} if ep is None else {"epoch": ep}
            out.append(_norm(store.get(op[1], **kw)))
        elif kind == "put":
            out.append(_norm(store.put(op[1], op[2])))
        elif kind == "delete":
            out.append(_norm(store.delete(op[1])))
        elif kind == "range":
            ep = None if single else _epoch(store, op[4])
            kw = {} if ep is None else {"epoch": ep}
            out.append(
                _norm(store.range(op[1], limit=op[2], max_leaves=op[3], **kw))
            )
        else:
            out.append(_norm(_exec_admin(store, op)))
    return out


def _run_pipelined(store, qd, script):
    """Replay the stream with genuine submit lag: data-op tickets are NOT
    redeemed until the very end, so up to ``queue_depth`` waves really
    overlap and every admin op lands between in-flight waves."""
    single = isinstance(store, DPAStore)
    pipe = PipelinedStore(store, queue_depth=qd)
    out = [None] * len(script)
    tickets = []
    for idx, op in enumerate(script):
        kind = op[0]
        if kind == "get":
            ep = None if single else _epoch(pipe, op[2])
            tickets.append((idx, pipe.submit_get(op[1], epoch=ep)))
        elif kind == "put":
            tickets.append((idx, pipe.submit_put(op[1], op[2])))
        elif kind == "delete":
            tickets.append((idx, pipe.submit_delete(op[1])))
        elif kind == "range":
            ep = None if single else _epoch(pipe, op[4])
            tickets.append(
                (idx, pipe.submit_range(op[1], op[2], epoch=ep, max_leaves=op[3]))
            )
        else:
            out[idx] = _norm(_exec_admin(pipe, op))
    for idx, t in tickets:
        out[idx] = _norm(pipe.result(t))
    return out, pipe


def _differential_episode(tier, qd, seed, n_ops=10, cache=False):
    rng = np.random.default_rng(seed)
    keys = np.unique(rng.integers(1, KEY_BOUND, 260, dtype=np.uint64))
    vals = keys ^ np.uint64(0xD1FF)
    script = _gen_script(rng, n_ops, tier)
    a = _build(tier, keys, vals, cache=cache)
    b = _build(tier, keys, vals, cache=cache)
    out_a = _run_serial(a, script)
    out_b, pipe = _run_pipelined(b, qd, script)
    for i, (ra, rb) in enumerate(zip(out_a, out_b)):
        _assert_eq(ra, rb, (tier, qd, i, script[i][0]))
    ka, va = a.items()
    kb, vb = pipe.items()  # barriered: drains first
    assert np.array_equal(ka, kb) and np.array_equal(va, vb), (tier, qd)
    if isinstance(a, DPAStore):
        assert a.stats.flush_cycles == b.stats.flush_cycles, (tier, qd)
        assert a.stats.puts == b.stats.puts and a.stats.gets == b.stats.gets
    else:
        # zero lost acked writes under queue_depth > 1: every write the
        # pipelined tier acked, the serial tier acked too (and vice versa)
        assert a.acked_writes == b.acked_writes, (tier, qd)
        assert a.client_writes == b.client_writes
        assert a.replica_writes == b.replica_writes
        # host re-issues stay at their steady-state 0 under pipelining
        assert b.range_reissues == a.range_reissues == 0, (tier, qd)
    return a, b, pipe


# ---------------------------------------------------------------------------
# the differential matrix: tier x queue_depth, deterministic seeds
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tier", TIERS)
@pytest.mark.parametrize("qd", [1, 2, 4])
def test_pipelined_equals_serial(tier, qd):
    _differential_episode(tier, qd, seed=1000 * qd + hash(tier) % 997)


def test_pipelined_equals_serial_with_hot_cache():
    """Cache admits may diverge between twins only in timing, never in any
    output bit (a hit returns exactly what the tree path would)."""
    _differential_episode("single", 2, seed=77, cache=True)


def test_truncated_range_cursors_pipeline_equivalence():
    """Scans forced past one leaf per round (max_leaves=1, limit 40) drive
    the continuation machinery — in-mesh rounds plus the sharded gather's
    cursor-resume loop — under pipelined dispatch; results and the
    zero-host-reissue contract must match serial bitwise."""
    rng = np.random.default_rng(11)
    keys = np.unique(rng.integers(1, KEY_BOUND, 400, dtype=np.uint64))
    vals = keys ^ np.uint64(0xC0)
    script = [("range", rng.choice(keys, 12), 40, 1, False) for _ in range(5)]
    script.insert(2, ("put", keys[:40], vals[:40]))
    for tier in ("single", "range"):
        a = _build(tier, keys, vals)
        b = _build(tier, keys, vals)
        out_a = _run_serial(a, script)
        out_b, _ = _run_pipelined(b, 4, script)
        for i, (ra, rb) in enumerate(zip(out_a, out_b)):
            _assert_eq(ra, rb, (tier, i))
        if tier == "range":
            assert b.range_reissues == a.range_reissues == 0
            assert b.range_rounds_in_mesh == a.range_rounds_in_mesh


def test_epoch_tagged_reads_mid_handoff():
    """Old-epoch GET/RANGE waves issued while a rebalance handoff is open
    (and while a failover epoch drains) must match serial bitwise — the
    in-flight waves were admitted under the old epoch and complete under
    it on both twins."""
    rng = np.random.default_rng(23)
    keys = np.unique(rng.integers(1, KEY_BOUND, 300, dtype=np.uint64))
    vals = keys + np.uint64(1)
    fresh = np.unique(rng.integers(1, KEY_BOUND, 200, dtype=np.uint64))
    script = [
        ("put", fresh, fresh ^ np.uint64(0xA)),
        ("flush",),
        ("begin",),
        ("get", rng.choice(keys, 16), True),
        ("range", rng.choice(keys, 8), 7, 4, True),
        ("get", rng.choice(keys, 16), False),
        ("commit",),
        ("kill", 0),
        ("get", rng.choice(keys, 16), True),
        ("range", rng.choice(keys, 8), 7, 4, True),
        ("retire",),
        ("recover",),
        ("get", rng.choice(keys, 16), False),
    ]
    a = _build("range_r2", keys, vals)
    b = _build("range_r2", keys, vals)
    out_a = _run_serial(a, script)
    out_b, pipe = _run_pipelined(b, 2, script)
    for i, (ra, rb) in enumerate(zip(out_a, out_b)):
        _assert_eq(ra, rb, (i, script[i][0]))
    ka, va = a.items()
    kb, vb = pipe.items()
    assert np.array_equal(ka, kb) and np.array_equal(va, vb)
    assert a.acked_writes == b.acked_writes


@given(st.data())
@settings(max_examples=6, deadline=None)
def test_barrier_interleaving_fuzz(data):
    """Hypothesis sweep: arbitrary placements of flush / rebalance /
    failover barriers between in-flight waves, any tier, qd in {2, 4}."""
    tier = data.draw(st.sampled_from(TIERS))
    qd = data.draw(st.sampled_from([2, 4]))
    seed = data.draw(st.integers(0, 2**32 - 1))
    _differential_episode(tier, qd, seed, n_ops=8)


@pytest.mark.slow
@given(st.data())
@settings(max_examples=10, deadline=None)
def test_barrier_interleaving_fuzz_broad(data):
    """Nightly leg: longer interleavings, all tiers x depths."""
    tier = data.draw(st.sampled_from(TIERS))
    qd = data.draw(st.sampled_from([1, 2, 3, 4]))
    seed = data.draw(st.integers(0, 2**32 - 1))
    _differential_episode(tier, qd, seed, n_ops=14)


def test_write_fallback_takes_serial_path_bitwise():
    """A wave the host shadow proves COULD fill an insert buffer must
    drain the pipeline and take the serial path — landing patches at the
    same op-stream points as serial execution (same flush_cycles, same
    leaf layout, same results)."""
    rng = np.random.default_rng(3)
    keys = np.sort(rng.choice(
        np.arange(1, 10**6, dtype=np.uint64), 300, replace=False
    ))
    vals = keys ^ np.uint64(0x9)
    # dense sequential inserts aimed at one leaf neighborhood: each wave of
    # 24 overflows ib_cap=16 for sure
    base = int(keys[len(keys) // 2])
    script = []
    for i in range(4):
        nk = np.arange(base + 1 + 24 * i, base + 1 + 24 * (i + 1), dtype=np.uint64)
        script.append(("put", nk, nk ^ np.uint64(0x7)))
        script.append(("get", nk, False))
    a = _build("single", keys, vals)
    b = _build("single", keys, vals)
    out_a = _run_serial(a, script)
    out_b, pipe = _run_pipelined(b, 2, script)
    for i, (ra, rb) in enumerate(zip(out_a, out_b)):
        _assert_eq(ra, rb, (i, script[i][0]))
    assert a.stats.flush_cycles == b.stats.flush_cycles
    assert a.stats.flush_cycles > 0, "episode must actually trigger stitches"
    ka, va = a.items()
    kb, vb = pipe.items()
    assert np.array_equal(ka, kb) and np.array_equal(va, vb)


# ---------------------------------------------------------------------------
# pipeline mechanics: ordering, ledger, buffers, barriers
# ---------------------------------------------------------------------------


def _mini_store(seed=5, n=200, **kw):
    rng = np.random.default_rng(seed)
    keys = np.unique(rng.integers(1, KEY_BOUND, n, dtype=np.uint64))
    return DPAStore(keys, keys, TreeConfig(growth=16.0), cache_cfg=None, **kw), keys


def test_ordered_delivery_and_out_of_order_redeem():
    store, keys = _mini_store()
    pipe = PipelinedStore(store, queue_depth=4)
    rng = np.random.default_rng(0)
    qs = [rng.choice(keys, 16) for _ in range(3)]
    t0, t1, t2 = (pipe.submit_get(q) for q in qs)
    # redeeming the LAST ticket first must drain 0 and 1 before 2
    v2, f2 = pipe.result(t2)
    assert t0._done and t1._done, "ordered delivery: earlier waves drain first"
    assert f2.all() and np.array_equal(v2, qs[2])
    v0, _ = pipe.result(t0)  # already drained: cached result
    assert np.array_equal(v0, qs[0])
    assert [r.seq for r in pipe.ledger.records] == [0, 1, 2]


def test_queue_depth_bounds_inflight():
    store, keys = _mini_store()
    pipe = PipelinedStore(store, queue_depth=2)
    rng = np.random.default_rng(1)
    for _ in range(6):
        pipe.submit_get(rng.choice(keys, 8))
        assert pipe.pipeline.inflight <= 2
    pipe.drain()
    assert pipe.pipeline.inflight == 0
    assert pipe.ledger.n_waves == 6


def test_overlap_ledger_and_stats_sync():
    """qd=1 scores exactly 0 overlap (the serial facade); qd=2 with
    back-to-back submits measures > 0 (wave N+1's issue starts before wave
    N's drain ends, structurally).  The summary's sums are the ledger's
    records, each wave timed in both halves."""
    for qd, expect_overlap in ((1, False), (2, True)):
        store, keys = _mini_store()
        pipe = PipelinedStore(store, queue_depth=qd)
        rng = np.random.default_rng(2)
        tickets = [pipe.submit_get(rng.choice(keys, 64)) for _ in range(6)]
        for t in tickets:
            pipe.result(t)
        s = pipe.pipeline_summary()
        assert s["waves"] == 6
        assert s["wave_issue_ns"] > 0 and s["wave_drain_ns"] >= 0
        if expect_overlap:
            assert s["overlap_frac"] > 0.0, s
        else:
            assert s["overlap_frac"] == 0.0, s
        recs = pipe.ledger.records
        assert s["wave_issue_ns"] == sum(r.issue_ns for r in recs)
        assert s["wave_drain_ns"] == sum(r.drain_ns for r in recs)
        assert all(r.t_issue1 >= r.t_issue0 and r.t_drain1 >= r.t_drain0 for r in recs)


def test_barrier_methods_drain_first():
    store, keys = _mini_store()
    pipe = PipelinedStore(store, queue_depth=4)
    rng = np.random.default_rng(3)
    nk = np.unique(rng.integers(1, KEY_BOUND, 16, dtype=np.uint64))
    pipe.submit_put(nk, nk)
    pipe.submit_get(nk)
    assert pipe.pipeline.inflight == 2
    pipe.flush()  # barrier: must drain before stitching
    assert pipe.pipeline.inflight == 0
    ks, _ = pipe.items()  # also barriered
    assert np.isin(nk, ks).all()


def test_wave_buffer_pool_pins_inflight_buffers():
    made = []

    def make():
        made.append(len(made))
        return {"id": len(made) - 1}

    pool = WaveBufferPool(make, depth=2)
    a = pool.acquire()
    b = pool.acquire()
    assert a is not b and pool.pinned == 2
    pool.release(a)
    c = pool.acquire()
    assert c is a, "released buffer is reused (ping-pong)"
    d = pool.acquire()  # 3rd concurrent = depth+1: allowed, pool grows
    assert pool.pinned == 3 and len(made) == 3
    with pytest.raises(AssertionError, match="exhausted"):
        pool.acquire()  # 4th concurrent: a wave was issued without draining
    del b, d


def test_pipeline_rejects_bad_depth_and_foreign_ticket():
    from repro.serving.pipeline import WaveTicket

    with pytest.raises(AssertionError):
        WavePipeline(0)
    p1 = WavePipeline(2)
    t = p1.submit(lambda: 1, lambda c: c + 1)
    assert p1.result(t) == 2
    p1.drain()
    assert p1.result(t) == 2  # drained tickets stay redeemable
    rogue = WaveTicket(9, "x", None, lambda c: c, t.record)
    with pytest.raises(AssertionError, match="submitted"):
        p1.result(rogue)


# ---------------------------------------------------------------------------
# donation-hazard regressions
# ---------------------------------------------------------------------------


def _deleted(arr) -> bool:
    """True iff the runtime deleted the donated buffer backing ``arr``."""
    try:
        np.asarray(arr)
        return False
    except RuntimeError as e:
        return "deleted" in str(e).lower()


@pytest.fixture
def tracer_leak_check():
    jax.config.update("jax_check_tracer_leaks", True)
    try:
        yield
    finally:
        jax.config.update("jax_check_tracer_leaks", False)


def test_donated_insert_buffer_handle_is_dead(tracer_leak_check):
    """``append_wave`` donates the InsertBuffers state: any host code that
    retained the pre-donation handle (the exact hazard a pipelined wave
    context could introduce) observes a DELETED array, not stale data."""
    store, keys = _mini_store(seed=7)
    stale = store.ib  # the hazard: a retained pre-donation handle
    nk = np.unique(np.random.default_rng(7).integers(1, KEY_BOUND, 8, dtype=np.uint64))
    store.put(nk, nk)
    assert _deleted(stale.count), (
        "insert-buffer state must be donated (deleted), or in-flight waves "
        "could alias a live buffer"
    )
    # the store's own handle is the single live one
    assert np.asarray(store.ib.count).sum() >= 0


def test_donated_cache_handles_are_dead(tracer_leak_check):
    """hotcache.admit / scancache.admit donate the cache state — same
    hazard class, same pin."""
    from repro.core.hotcache import CacheConfig

    rng = np.random.default_rng(9)
    keys = np.unique(rng.integers(1, KEY_BOUND, 200, dtype=np.uint64))
    store = DPAStore(keys, keys, TreeConfig(growth=16.0), cache_cfg=CacheConfig())
    stale_hot = store.cache
    store.get(rng.choice(keys, 16))  # admits -> donates the hot cache
    assert _deleted(stale_hot.bloom)
    stale_scan = store.scan_cache
    assert stale_scan is not None
    store.range(rng.choice(keys, 8), limit=7)  # admits scan anchors
    assert _deleted(stale_scan.bloom)


def test_pipelined_run_clean_under_tracer_leak_check(tracer_leak_check):
    """A deep pipelined episode (qd=4, all op kinds, stitches included)
    under ``jax_check_tracer_leaks``: wave contexts must hold only their
    own output arrays — a retained store-state handle or leaked tracer
    fails here."""
    _differential_episode("single", 4, seed=41, n_ops=8)


def test_wave_ctx_released_after_drain():
    """Drained tickets drop their wave context — nothing may pin donated
    (or donatable) device buffers past the drain."""
    store, keys = _mini_store(seed=13)
    pipe = PipelinedStore(store, queue_depth=2)
    t = pipe.submit_get(keys[:8])
    assert t.ctx is not None
    pipe.result(t)
    assert t.ctx is None
