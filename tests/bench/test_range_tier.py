"""The range tier's builder (``bench/stores/range_tier.py``) through the
harness, one shard per device: a whole tiny run of ``range-4x50m`` under
YCSB E and C traffic on four CPU devices, in a fresh process (the test
process has one).  Every check reads 0, scans span shards, each shard
stays on its own device, and the sharded tier's readers find what they
read."""

import json
import os
import subprocess
import sys

import pytest

from bench_support import BENCH, ROOT

_SCRIPT = r"""
import json, sys, time
import jax, numpy as np
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import check, harness
from bench_support import tiny_cell

cell = tiny_cell(sys.argv[3])
cell.chips = 4
assert cell.config["store"] == "range_tier"
built = []
build_store = harness.build_store
harness.build_store = lambda *a: built.append(build_store(*a)) or built[-1]
res = harness.run_cell(cell, 2**31 + 29, 0.5, False, time.perf_counter(),
                       {"hbm_bytes_per_s": 819e9})
found = check.compare(res.log, res.readback, res.keys, res.vals)
(b,) = built
store = b.store
placed = [
    sorted({str(d) for x in jax.tree_util.tree_leaves((sh.tree, sh.ib, sh.cache, sh.scan_cache))
            for d in x.devices()})
    for sh in store.shards
]
spanning = 0
for d in res.log:
    if d.wave.op == "range":
        keys, _, counts = d.out
        upto = np.minimum(counts, d.wave.lengths[d.wave.sample])
        for row, m in zip(keys, upto):
            first, last = store.route_np(row[[0, max(m - 1, 0)]])
            spanning += int(first != last)
metrics = {}
for name in ("gather_ms_per_wave.range", "subscans_per_scan", "issue_ms_per_wave.read",
             "wait_ms_per_wave.read", "stats_wait_ms_per_wave.read",
             "waits_per_wave.read", "launch_ms_per_wave.read"):
    metrics[name] = harness.load_module(harness.BENCH / "metrics" / f"{name}.py").read(res.window)
print(json.dumps({
    "numbers": {k: v["value"] for k, v in found["numbers"].items()},
    "devices": [str(d) for d in jax.devices()],
    "placed": placed,
    "n_shards": len(store.shards),
    "spanning": spanning,
    "probes": [sh.stats.scan_probes + sh.stats.cache_probes for sh in store.shards],
    "depth": b.depth,
    "depths": [sh.depth for sh in store.shards],
    "metrics": metrics,
}))
"""

MIXES = ("ycsb-e", "ycsb-c")


@pytest.fixture(scope="module", params=MIXES)
def run(request):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=str(ROOT / "src"),
    )
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT, str(BENCH), str(ROOT / "tests" / "bench"),
         f"range-4x50m.{request.param}"],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return request.param, json.loads(proc.stdout.strip().splitlines()[-1])


def test_every_check_reads_zero(run):
    mix, r = run
    numbers = r["numbers"]
    assert numbers and set(numbers.values()) == {0}, numbers
    read_check = "scan_rows_wrong" if mix == "ycsb-e" else "get_wrong"
    assert {"readback_wrong", read_check} <= set(numbers)


def test_each_shard_on_its_own_device(run):
    _, r = run
    assert r["n_shards"] == 4
    assert r["placed"] == [[d] for d in r["devices"]]
    assert sum(p > 0 for p in r["probes"]) > 1
    assert r["depth"] == max(r["depths"])


def test_scans_span_shards(run):
    mix, r = run
    # sampled scans whose pairs lie on two shards (YCSB C sends no scans)
    assert (r["spanning"] > 0) == (mix == "ycsb-e")


def test_sharded_tier_readers_read(run):
    mix, r = run
    m = r["metrics"]
    if mix == "ycsb-e":
        assert m["gather_ms_per_wave.range"] > 0
        # a scan goes to its owner and every successor: 1 to 4 shards
        assert 1 < m["subscans_per_scan"] <= 4
    else:  # no RANGE wave: nothing to read
        assert m["gather_ms_per_wave.range"] is None
        assert m["subscans_per_scan"] is None


SPAN_READERS = ("issue_ms_per_wave.read", "wait_ms_per_wave.read",
                "stats_wait_ms_per_wave.read", "waits_per_wave.read",
                "launch_ms_per_wave.read")


@pytest.mark.parametrize("name", SPAN_READERS)
def test_wave_readers_read_through_the_facade(run, name):
    # the single store's wave spans and wait counts reach the tier's
    # records through the facade, for RANGE waves as for GET waves
    _, r = run
    value = r["metrics"][name]
    if name == "stats_wait_ms_per_wave.read":
        # a RANGE drain waits on its counters only to admit the cursors of
        # truncated rows, which a tiny run may not have
        assert value is not None and value >= 0
    else:
        assert value > 0
