"""Kernel == oracle sweeps (interpret mode), per the deliverable contract:
for each Pallas kernel, sweep shapes/configs and assert exact agreement with
the pure-jnp ref."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import DPAStore, TreeConfig
from repro.core.hotcache import CacheConfig
from repro.core import hotcache
from repro.core.datasets import sparse, dense4x, osmc, face
from repro.core.keys import split_u64
from repro.kernels import ops, ref


def _mk(n, dataset=sparse, eps=(4, 8), ib_cap=16, seed=7, churn=0):
    keys = dataset(n, seed=seed)
    st = DPAStore(
        keys,
        keys ^ np.uint64(0x5A5A),
        TreeConfig(eps_inner=eps[0], eps_leaf=eps[1], ib_cap=ib_cap),
        cache_cfg=None,
    )
    rng = np.random.default_rng(seed + 1)
    if churn:
        newk = np.setdiff1d(
            rng.integers(0, 2**63, churn, dtype=np.uint64), keys
        )
        st.put(newk, newk + np.uint64(77))
        st.delete(keys[10 : 10 + churn // 4])
    return st, keys, rng


def _q(st, keys, rng, n_q):
    q = np.concatenate(
        [
            rng.choice(keys, n_q // 2),
            rng.integers(0, 2**63, n_q - n_q // 2, dtype=np.uint64),
        ]
    )
    l = split_u64(q)
    return jnp.asarray(l[:, 0]), jnp.asarray(l[:, 1])


@pytest.mark.parametrize(
    "n,dataset,eps,churn",
    [
        (1000, sparse, (4, 8), 0),
        (4000, sparse, (4, 8), 150),
        (3000, dense4x, (4, 8), 60),
        (3000, osmc, (16, 16), 60),
        (2000, face, (16, 16), 0),
        (30_000, sparse, (4, 8), 0),  # deeper tree
        (1000, sparse, (1, 2), 40),  # tiny eps windows
    ],
)
def test_get_kernel_matches_ref(n, dataset, eps, churn):
    st, keys, rng = _mk(n, dataset, eps, churn=churn)
    for n_q in (64, 128, 257):  # incl. non-multiple of the tile
        kh, kl = _q(st, keys, rng, n_q)
        vh1, vl1, f1 = ops.get(
            st.tree,
            st.ib,
            kh,
            kl,
            depth=st.depth,
            eps_inner=eps[0],
            eps_leaf=eps[1],
            impl="pallas_interpret",
        )
        vh2, vl2, f2 = ref.get(
            st.tree, st.ib, kh, kl, depth=st.depth, eps_inner=eps[0], eps_leaf=eps[1]
        )
        np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
        np.testing.assert_array_equal(
            np.asarray(jnp.where(f2, vh1, 0)), np.asarray(jnp.where(f2, vh2, 0))
        )
        np.testing.assert_array_equal(
            np.asarray(jnp.where(f2, vl1, 0)), np.asarray(jnp.where(f2, vl2, 0))
        )


@pytest.mark.parametrize("n_threads,n_buckets", [(8, 24), (176, 24), (16, 8)])
def test_cache_probe_kernel_matches_ref(n_threads, n_buckets):
    cfg = CacheConfig(n_threads=n_threads, n_buckets=n_buckets, admit_shift=0)
    cache = hotcache.make_cache(cfg)
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 2**63, 300, dtype=np.uint64)
    l = split_u64(keys)
    kh, kl = jnp.asarray(l[:, 0]), jnp.asarray(l[:, 1])
    tid = hotcache.steer(kh, kl, cfg.n_threads)
    for w in range(6):
        cache = hotcache.admit(
            cache, tid, kh, kl, kl, kh, jnp.ones(300, bool), cfg=cfg, wave=w
        )
    probes = np.concatenate([keys[:100], rng.integers(0, 2**63, 60, dtype=np.uint64)])
    pl_ = split_u64(probes)
    ph, pl2 = jnp.asarray(pl_[:, 0]), jnp.asarray(pl_[:, 1])
    ptid = hotcache.steer(ph, pl2, cfg.n_threads)
    h1, v1h, v1l = ops.cache_probe(
        cache, ptid, ph, pl2, cfg=cfg, impl="pallas_interpret"
    )
    h2, v2h, v2l = ref.cache_probe(cache, ptid, ph, pl2, cfg=cfg)
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
    np.testing.assert_array_equal(
        np.asarray(jnp.where(h2, v1h, 0)), np.asarray(jnp.where(h2, v2h, 0))
    )
    np.testing.assert_array_equal(
        np.asarray(jnp.where(h2, v1l, 0)), np.asarray(jnp.where(h2, v2l, 0))
    )


@pytest.mark.parametrize(
    "n,churn,limit,max_leaves",
    [
        (2000, 0, 10, 4),
        (2000, 120, 10, 4),
        (4000, 200, 64, 6),  # the paper's 64-per-packet bound
        (1500, 80, 3, 2),
    ],
)
def test_range_kernel_matches_ref(n, churn, limit, max_leaves):
    st, keys, rng = _mk(n, sparse, churn=churn, seed=11)
    starts = np.concatenate(
        [
            rng.choice(keys, 20),
            rng.integers(0, 2**63, 12, dtype=np.uint64),
            keys[-3:],  # near the end: chain termination
        ]
    )
    l = split_u64(starts)
    kh, kl = jnp.asarray(l[:, 0]), jnp.asarray(l[:, 1])
    k1, v1, ok1, t1, c1 = ops.range_scan(
        st.tree,
        st.ib,
        kh,
        kl,
        depth=st.depth,
        eps_inner=st.cfg.eps_inner,
        limit=limit,
        max_leaves=max_leaves,
        impl="pallas_interpret",
        block_requests=35,
    )
    k2, v2, ok2, t2, c2 = ref.range_scan(
        st.tree,
        st.ib,
        kh,
        kl,
        depth=st.depth,
        eps_inner=st.cfg.eps_inner,
        limit=limit,
        max_leaves=max_leaves,
    )
    np.testing.assert_array_equal(np.asarray(ok1), np.asarray(ok2))
    m = np.asarray(ok2)
    np.testing.assert_array_equal(np.asarray(k1)[m], np.asarray(k2)[m])
    np.testing.assert_array_equal(np.asarray(v1)[m], np.asarray(v2)[m])
    # continuation outputs: truncated flag + resume cursor, bit-identical
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    np.testing.assert_array_equal(np.asarray(c1.leaf), np.asarray(c2.leaf))
    tm = np.asarray(t2)
    np.testing.assert_array_equal(np.asarray(c1.khi)[tm], np.asarray(c2.khi)[tm])
    np.testing.assert_array_equal(np.asarray(c1.klo)[tm], np.asarray(c2.klo)[tm])


def test_range_kernel_anchor_start_matches_ref():
    """Anchor-start RANGE (descent skipped): kernel == ref when both start
    at the same cached/continuation leaf, incl. dead -1 lanes."""
    from repro.core import lookup

    st, keys, rng = _mk(2000, sparse, churn=90, seed=13)
    starts = rng.choice(keys, 24)
    l = split_u64(starts)
    kh, kl = jnp.asarray(l[:, 0]), jnp.asarray(l[:, 1])
    anchor = lookup.traverse(
        st.tree, kh, kl, depth=st.depth, eps_inner=st.cfg.eps_inner
    )
    anchor = jnp.where(jnp.arange(24) % 5 == 4, -1, anchor)  # dead lanes
    outs1 = ops.range_scan(
        st.tree, st.ib, kh, kl,
        depth=st.depth, eps_inner=st.cfg.eps_inner,
        limit=8, max_leaves=3, impl="pallas_interpret",
        block_requests=24, start_leaf=anchor,
    )
    outs2 = ref.range_scan_from(
        st.tree, st.ib, anchor, kh, kl, limit=8, max_leaves=3
    )
    for a, b in zip(outs1[:4], outs2[:4]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        np.asarray(outs1[4].leaf), np.asarray(outs2[4].leaf)
    )
    dead = np.arange(24) % 5 == 4
    assert not np.asarray(outs2[2])[dead].any(), "dead lanes return empty"
    assert not np.asarray(outs2[3])[dead].any(), "dead lanes never truncate"


@pytest.mark.parametrize("n_threads,n_buckets", [(8, 24), (176, 24), (16, 8)])
def test_anchor_probe_kernel_matches_ref(n_threads, n_buckets):
    from repro.core import scancache
    from repro.core.scancache import ScanCacheConfig

    cfg = ScanCacheConfig(n_threads=n_threads, n_buckets=n_buckets)
    cache = scancache.make_cache(cfg)
    rng = np.random.default_rng(5)
    keys = rng.integers(0, 2**63, 300, dtype=np.uint64)
    leaves = rng.integers(0, 512, 300).astype(np.int32)
    l = split_u64(keys)
    kh, kl = jnp.asarray(l[:, 0]), jnp.asarray(l[:, 1])
    tid = hotcache.steer(kh, kl, cfg.n_threads)
    for w in range(4):
        cache = scancache.admit(
            cache, tid, kh, kl, jnp.asarray(leaves), jnp.ones(300, bool),
            cfg=cfg, wave=w,
        )
    probes = np.concatenate([keys[:100], rng.integers(0, 2**63, 60, dtype=np.uint64)])
    pl_ = split_u64(probes)
    ph, pl2 = jnp.asarray(pl_[:, 0]), jnp.asarray(pl_[:, 1])
    ptid = hotcache.steer(ph, pl2, cfg.n_threads)
    h1, l1 = ops.scan_anchor_probe(
        cache, ptid, ph, pl2, cfg=cfg, impl="pallas_interpret"
    )
    h2, l2 = ref.scan_anchor_probe(cache, ptid, ph, pl2, cfg=cfg)
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
    np.testing.assert_array_equal(
        np.asarray(jnp.where(h2, l1, 0)), np.asarray(jnp.where(h2, l2, 0))
    )
    assert bool(jnp.any(h2)), "admitted keys must probe back"


def test_generic_probe_covers_both_payload_instantiations():
    """One payload-generic kernel serves both cache families: the value
    (P=2) and leaf-id (P=1) wrappers must agree with their jnp oracles on
    the SAME key stream, and a direct P=3 instantiation pins that the
    kernel is generic over the payload width, not specialised to either."""
    from repro.core import scancache
    from repro.core.scancache import ScanCacheConfig
    from repro.kernels import cache_probe

    cfg_v = CacheConfig(n_threads=16, n_buckets=8, admit_shift=0)
    cfg_a = ScanCacheConfig(n_threads=16, n_buckets=8)
    vcache = hotcache.make_cache(cfg_v)
    acache = scancache.make_cache(cfg_a)
    rng = np.random.default_rng(11)
    keys = rng.integers(0, 2**63, 256, dtype=np.uint64)
    leaves = rng.integers(0, 999, 256).astype(np.int32)
    l = split_u64(keys)
    kh, kl = jnp.asarray(l[:, 0]), jnp.asarray(l[:, 1])
    tid_v = hotcache.steer(kh, kl, cfg_v.n_threads)
    tid_a = hotcache.steer(kh, kl, cfg_a.n_threads)
    ones = jnp.ones(256, bool)
    for w in range(3):
        vcache = hotcache.admit(vcache, tid_v, kh, kl, kl, kh, ones, cfg=cfg_v, wave=w)
        acache = scancache.admit(acache, tid_a, kh, kl, jnp.asarray(leaves), ones, cfg=cfg_a, wave=w)
    probes = np.concatenate([keys[:90], rng.integers(0, 2**63, 38, dtype=np.uint64)])
    pl_ = split_u64(probes)
    ph, pl2 = jnp.asarray(pl_[:, 0]), jnp.asarray(pl_[:, 1])
    # value instantiation (P=2) == hotcache oracle
    h1, vh, vl = cache_probe.probe_pallas(
        vcache, hotcache.steer(ph, pl2, cfg_v.n_threads), ph, pl2, cfg=cfg_v,
        interpret=True,
    )
    h2, vh2, vl2 = ref.cache_probe(
        vcache, hotcache.steer(ph, pl2, cfg_v.n_threads), ph, pl2, cfg=cfg_v
    )
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
    np.testing.assert_array_equal(
        np.asarray(jnp.where(h2, vh, 0)), np.asarray(jnp.where(h2, vh2, 0))
    )
    np.testing.assert_array_equal(
        np.asarray(jnp.where(h2, vl, 0)), np.asarray(jnp.where(h2, vl2, 0))
    )
    # leaf-id instantiation (P=1) == scancache oracle
    a1, l1 = cache_probe.anchor_probe_pallas(
        acache, hotcache.steer(ph, pl2, cfg_a.n_threads), ph, pl2, cfg=cfg_a,
        interpret=True,
    )
    a2, l2 = ref.scan_anchor_probe(
        acache, hotcache.steer(ph, pl2, cfg_a.n_threads), ph, pl2, cfg=cfg_a
    )
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    np.testing.assert_array_equal(
        np.asarray(jnp.where(a2, l1, 0)), np.asarray(jnp.where(a2, l2, 0))
    )
    assert bool(jnp.any(h2)) and bool(jnp.any(a2)), "both families must hit"
    # width-generic: a synthetic P=3 payload round-trips through the kernel
    T, NB, W = cfg_a.n_threads, cfg_a.n_buckets, acache.bkey.shape[2]
    pay3 = jnp.stack(
        [acache.bleaf, acache.bleaf + 1, acache.bleaf * 2], axis=-1
    ).astype(jnp.int32)
    h3, p3 = cache_probe.generic_probe_pallas(
        acache.bloom, acache.bkey, pay3, acache.bvalid,
        hotcache.steer(ph, pl2, cfg_a.n_threads), ph, pl2,
        bloom_bits=cfg_a.bloom_bits, n_buckets=cfg_a.n_buckets,
        salts_bloom=scancache.SALT_SBLOOM, salt_bucket=scancache.SALT_SBUCKET,
        interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(h3), np.asarray(a2))
    m = np.asarray(a2)
    np.testing.assert_array_equal(np.asarray(p3)[m, 0], np.asarray(l2)[m])
    np.testing.assert_array_equal(np.asarray(p3)[m, 1], np.asarray(l2)[m] + 1)
    np.testing.assert_array_equal(np.asarray(p3)[m, 2], np.asarray(l2)[m] * 2)


def test_range_kernel_loop_carried_cursor_matches_oracle():
    """In-mesh continuation through the Pallas kernel: the kernel's
    next-leaf output is fed back as loop-carried cursor state inside ONE
    lax.while_loop dispatch (ops.range_scan_loop), and must agree bitwise
    with the jnp device loop (lookup.range_batch_loop) and with a
    single-round big-max_leaves oracle — including a bounded max_rounds
    leg and a per-row owned-window clip."""
    from repro.core import lookup

    st, keys, rng = _mk(2000, sparse, churn=100, seed=17)
    starts = np.concatenate(
        [rng.choice(keys, 28), rng.integers(0, 2**63, 4, dtype=np.uint64)]
    )
    l = split_u64(starts)
    kh, kl = jnp.asarray(l[:, 0]), jnp.asarray(l[:, 1])
    kw = dict(depth=st.depth, eps_inner=st.cfg.eps_inner, limit=40, max_leaves=1)
    k1, v1, ok1, t1, c1, r1 = ops.range_scan_loop(
        st.tree, st.ib, kh, kl, impl="pallas_interpret", block_requests=32, **kw
    )
    k2, v2, ok2, t2, c2, r2 = ops.range_scan_loop(
        st.tree, st.ib, kh, kl, impl="ref", **kw
    )
    oracle = ref.range_scan(
        st.tree, st.ib, kh, kl,
        depth=st.depth, eps_inner=st.cfg.eps_inner, limit=40, max_leaves=64,
    )
    assert int(r1) > 1 and int(r2) > 1, "max_leaves=1 over limit=40 must loop"
    np.testing.assert_array_equal(np.asarray(ok1), np.asarray(ok2))
    np.testing.assert_array_equal(np.asarray(k1), np.asarray(k2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    assert not np.asarray(t1).any(), "unbounded loop leaves nothing truncated"
    np.testing.assert_array_equal(np.asarray(ok1), np.asarray(oracle[2]))
    m = np.asarray(oracle[2])
    np.testing.assert_array_equal(np.asarray(k1)[m], np.asarray(oracle[0])[m])
    # bounded rounds: kernel loop == jnp loop incl. cursor state
    ub = jnp.full_like(kh, 0xFFFFFFFF)
    start = lookup.traverse(
        st.tree, kh, kl, depth=st.depth, eps_inner=st.cfg.eps_inner
    )
    for max_rounds in (1, 2):
        o1 = ops.range_scan_loop(
            st.tree, st.ib, kh, kl, impl="pallas_interpret",
            block_requests=32, max_rounds=max_rounds, **kw
        )
        o2 = lookup.range_batch_loop(
            st.tree, st.ib, start, kh, kl, ub, ub,
            limit=40, max_leaves=1, max_rounds=max_rounds,
        )
        np.testing.assert_array_equal(np.asarray(o1[2]), np.asarray(o2[2]))
        np.testing.assert_array_equal(np.asarray(o1[3]), np.asarray(o2[3]))
        np.testing.assert_array_equal(
            np.asarray(o1[4].leaf), np.asarray(o2[4].leaf)
        )
        mm = np.asarray(o2[2])
        np.testing.assert_array_equal(np.asarray(o1[0])[mm], np.asarray(o2[0])[mm])
    # owned-window clip: per-row ub drops the tail and clears truncation
    mid = np.sort(keys)[len(keys) // 2]
    ub_limbs = split_u64(np.full(starts.size, mid, dtype=np.uint64))
    kc, vc, okc, tc, cc, rc_ = ops.range_scan_loop(
        st.tree, st.ib, kh, kl, impl="pallas_interpret", block_requests=32,
        ub_hi=jnp.asarray(ub_limbs[:, 0]), ub_lo=jnp.asarray(ub_limbs[:, 1]),
        **kw
    )
    got_k = (np.asarray(kc)[..., 0].astype(np.uint64) << np.uint64(32)) | np.asarray(kc)[..., 1]
    okn = np.asarray(okc)
    assert not np.asarray(tc).any(), "window-clipped lanes are exhausted"
    assert (got_k[okn] < mid).all(), "no entry at/above the window bound"
