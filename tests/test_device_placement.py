"""A store placed on a device keeps its arrays and programs there.

``DPAStore(device=d)`` must answer exactly as a store on JAX's default
device and leave every pool, buffer and cache on ``d``;
``ShardedDPAStore(devices=...)`` must keep shard group ``s`` on
``devices[s]``, stores rebuilt for a slot included.  Every check runs
under ``jax.transfer_guard_device_to_device("disallow")``, so an array
that crosses from one device to another fails it.  Four CPU devices, in a
fresh process: the checks run once and each test reads its own result."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

_SCRIPT = r"""
import json, sys, traceback
import jax, numpy as np
from repro.core import DPAStore, TreeConfig
from repro.core.datasets import sparse
from repro.distributed.kvshard import ShardedDPAStore
from repro.serving.pipeline import PipelinedStore

devs = jax.devices()
assert len(devs) == 4, devs
CFG = TreeConfig(growth=20.0)
out = {}


def check(fn):
    try:
        with jax.transfer_guard_device_to_device("disallow"):
            fn()
        out[fn.__name__] = "ok"
    except Exception:
        out[fn.__name__] = traceback.format_exc()[-3000:]
    return fn


def on(tree, device):
    # every array leaf of ``tree`` lives on ``device`` alone
    leaves = [x for x in jax.tree_util.tree_leaves(tree) if isinstance(x, jax.Array)]
    assert leaves
    bad = [x.devices() for x in leaves if x.devices() != {device}]
    assert not bad, (device, bad[:3])


def state(st):
    return (st.tree, st.ib, st.cache, st.scan_cache)


keys = sparse(6000, seed=3)
vals = keys ^ np.uint64(0x5EED)
rng = np.random.default_rng(5)
fresh = np.setdiff1d(rng.integers(1, 2**63, 3000, dtype=np.uint64), keys)
# a narrow run of new keys: their leaves' buffers fill, forcing flush cycles
lo = keys[2000]
narrow = np.setdiff1d(lo + np.arange(1, 400, dtype=np.uint64) * np.uint64(7), keys)


def drive(st):
    # GET, PUT (fast and serial paths, buffer-filling writes), RANGE
    # across leaves, a forced flush, all through the pipeline; answers in order
    pipe = PipelinedStore(st, queue_depth=2)
    q = np.concatenate([keys[::7], fresh[:200]])
    starts = np.concatenate([keys[::37], keys[::53] - np.uint64(1), fresh[:50]])
    t = [pipe.submit_get(q), pipe.submit_range(starts, 40),
         pipe.submit_put(fresh[:1000], fresh[:1000] + np.uint64(1)),
         pipe.submit_put(narrow, narrow), pipe.submit_get(q),
         pipe.submit_range(starts, 40), pipe.submit_put(keys[::11], keys[::11] + np.uint64(2))]
    res = [pipe.result(x) for x in t]
    pipe.flush()
    res.append(st.get(np.concatenate([q, narrow])))
    res.append(st.range(starts, limit=100))
    flat = []
    for r in res:
        if hasattr(r, "counts"):
            flat += [np.asarray(r.keys), np.asarray(r.vals), np.asarray(r.counts)]
        elif isinstance(r, tuple):
            flat += [np.asarray(x) for x in r]
        else:
            flat.append(np.asarray(r))
    return flat


def same(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.dtype == y.dtype and np.array_equal(x, y)


@check
def store_on_device_matches_default():
    ref = DPAStore(keys, vals, CFG)
    st = DPAStore(keys, vals, CFG, device=devs[1])
    assert st.device == devs[1] and ref.device is None
    on(state(ref), devs[0])
    on(state(st), devs[1])
    assert all(x.committed for x in jax.tree_util.tree_leaves(state(st)))
    same(drive(ref), drive(st))
    assert st.stats.flush_cycles == ref.stats.flush_cycles > 1
    assert st.stats.scan_probes == ref.stats.scan_probes > 0
    on(state(st), devs[1])
    same([*ref.items()], [*st.items()])


def fleet(partition, devices, **kw):
    return ShardedDPAStore(keys, vals, 4, CFG, partition=partition,
                           rebalance_cfg=None if partition == "range" else "default",
                           devices=devices, **kw)


def drive_cross(st, cross):
    r = st.range(cross, limit=60)
    return [np.asarray(r.keys), np.asarray(r.vals), np.asarray(r.counts)]


@check
def range_fleet_one_shard_per_device():
    ref, st = fleet("range", None), fleet("range", devs)
    assert st.devices == devs
    for s, sh in enumerate(st.shards):
        on(state(sh), devs[s])
    # scans that start just below each boundary cross onto the next shard
    at = np.searchsorted(keys, st.boundaries)
    cross = keys[np.concatenate([at - 1, at - 5, at - 30])]
    spans = ref.range(cross, limit=60)
    owners = st.route_np(np.asarray(spans.keys)[:, [0, -1]].ravel()).reshape(-1, 2)
    assert (owners[:, 0] != owners[:, 1]).any()
    same(drive(ref), drive(st))
    same(drive_cross(ref, cross), drive_cross(st, cross))
    for s, sh in enumerate(st.shards):
        on(state(sh), devs[s])
    assert st.stats_totals()["flush_cycles"] == ref.stats_totals()["flush_cycles"] > 1


@check
def hash_fleet_one_shard_per_device():
    st = fleet("hash", devs)
    for s, sh in enumerate(st.shards):
        on(state(sh), devs[s])
    same(drive(fleet("hash", None)), drive(st))


@check
def rebuilt_slots_keep_their_device():
    st = fleet("range", devs, replication=2)
    for s, g in enumerate(st.groups):
        for r in g:
            on(state(r), devs[s])
    st.kill_replica(2)
    st.retire_failover()
    st.recover_replicas()
    st.evacuate_shard(1)
    st.reshard(4, new_boundaries=st.boundaries + np.uint64(12345))
    for s, g in enumerate(st.groups):
        for r in g:
            on(state(r), devs[s])
    k, v = st.items()
    assert np.array_equal(k, keys) and np.array_equal(v, vals)


@check
def wrong_device_count_raises():
    try:
        fleet("range", devs[:3])
    except ValueError as e:
        assert "3" in str(e) and "4" in str(e), e
    else:
        raise AssertionError("3 devices for 4 shards did not raise")


@check
def reshard_to_another_count_raises():
    st = ShardedDPAStore(keys, vals, 2, CFG, partition="range", rebalance_cfg=None,
                         devices=devs[:2])
    try:
        st.reshard(4)
    except ValueError as e:
        assert "2 -> 4" in str(e), e
    else:
        raise AssertionError("reshard 2 -> 4 on a placed fleet did not raise")
    assert st.n_shards == 2


json.dump(out, sys.stdout)
"""

CHECKS = [
    "store_on_device_matches_default",
    "range_fleet_one_shard_per_device",
    "hash_fleet_one_shard_per_device",
    "rebuilt_slots_keep_their_device",
    "wrong_device_count_raises",
    "reshard_to_another_count_raises",
]


@pytest.fixture(scope="module")
def results():
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=str(ROOT / "src"),
    )
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", CHECKS)
def test_placement(results, name):
    assert results.get(name) == "ok", results.get(name)
