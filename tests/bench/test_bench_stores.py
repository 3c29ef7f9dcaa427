"""The store a configuration deploys comes from its builder in ``STORES``:
the default builds one ``DPAStore`` as the configuration states it, a
test-local builder serves a four-shard range tier through the same harness,
and the harness reads memory over every chip it is given."""

import numpy as np
import pytest

import check
from bench_support import MIXES, tiny_cell

RANGE4 = '''
from harness import Built


def build(config, keys, vals, devices):
    from repro.core import TreeConfig
    from repro.core.hotcache import CacheConfig
    from repro.core.scancache import ScanCacheConfig
    from repro.distributed.kvshard import ShardedDPAStore
    from repro.serving.pipeline import PipelinedStore

    store = ShardedDPAStore(
        keys, vals, n_shards=4,
        tree_cfg=TreeConfig(eps_inner=config["eps_inner"], eps_leaf=config["eps_leaf"],
                            ib_cap=config["ib_cap"], growth=config["growth"]),
        cache_cfg=CacheConfig() if config["hot_cache"] else None,
        scan_cache_cfg=ScanCacheConfig() if config["scan_cache"] else None,
        partition="range", rebalance_cfg=None,
    )
    return Built(store, PipelinedStore(store, queue_depth=config["queue_depth"]),
                 max(sh.depth for sh in store.shards), store.stats_totals)
'''

# each mix's reads: the number that checks them, and the counter they drive
# on every shard (the hot cache's probes for GETs, the scan-anchor cache's
# for RANGE rows)
READS = {"sparse-50m.ycsb-c": ("get_wrong", "cache_probes"),
         "sparse-50m.ycsb-e": ("scan_rows_wrong", "scan_probes")}


def _spanning_rows(log, route) -> int:
    """Sampled RANGE rows whose returned pairs lie on more than one shard."""
    n = 0
    for d in log:
        if d.wave.op != "range":
            continue
        keys, _, counts = d.out
        upto = np.minimum(counts, d.wave.lengths[d.wave.sample])
        for row, m in zip(keys, upto):
            first, last = route(row[[0, max(m - 1, 0)]])
            n += int(first != last)
    return n


@pytest.mark.parametrize("mix", MIXES)
def test_range_tier_through_a_test_local_builder(mix, run_tiny, tmp_path, monkeypatch):
    import harness

    (tmp_path / "range4.py").write_text(RANGE4)
    monkeypatch.setattr(harness, "STORES", tmp_path)
    built = []
    build_store = harness.build_store

    def recorded(*args):
        built.append(build_store(*args))
        return built[-1]

    monkeypatch.setattr(harness, "build_store", recorded)
    res, found = run_tiny(mix, config={"store": "range4"})

    (b,) = built
    assert type(b.store).__name__ == "ShardedDPAStore" and len(b.store.shards) == 4
    numbers = {k: v["value"] for k, v in found["numbers"].items()}
    assert numbers and set(numbers.values()) == {0}, found
    read_check, probes = READS[mix]
    assert {"readback_wrong", read_check} <= set(numbers)
    if read_check == "scan_rows_wrong":
        assert _spanning_rows(res.log, b.store.route_np) > 0
    per_shard = [getattr(sh.stats, probes) for sh in b.store.shards]
    assert sum(p > 0 for p in per_shard) > 1
    assert b.stats()[probes] == sum(per_shard)
    assert res.window.stats[probes] > 0


def test_config_without_store_builds_a_dpastore():
    import jax

    import harness
    from repro.core import DPAStore
    from repro.core.store import StoreStats

    config = tiny_cell(MIXES[0]).config
    assert "store" not in config
    keys = np.sort(np.random.default_rng(3).choice(2**62, size=2000, replace=False)).astype(np.uint64)
    b = harness.build_store(config, keys, keys + np.uint64(1), jax.devices()[:1])
    assert type(b.store) is DPAStore
    cfg = b.store.cfg
    assert (cfg.eps_inner, cfg.eps_leaf, cfg.ib_cap, cfg.growth) == \
        (config["eps_inner"], config["eps_leaf"], config["ib_cap"], config["growth"])
    assert (b.store.cache is not None) == config["hot_cache"]
    assert (b.store.scan_cache is not None) == config["scan_cache"]
    assert b.pipe.store is b.store and b.pipe.queue_depth == config["queue_depth"]
    assert b.depth == b.store.depth
    assert set(b.stats()) == {k for k, v in vars(StoreStats()).items() if isinstance(v, int)}
    assert b.stats()["bulk_load_bytes"] == b.store.stats.bulk_load_bytes > 0


def test_unknown_store_names_the_missing_path():
    import harness

    config = dict(tiny_cell(MIXES[0]).config, store="no-such-store")
    with pytest.raises(FileNotFoundError, match="no-such-store.py"):
        harness.build_store(config, None, None, [])


class _Device:
    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


def test_memory_peak_is_the_fullest_chips():
    import harness

    devices = [_Device({"peak_bytes_in_use": 5, "bytes_in_use": 50}),
               _Device({"peak_bytes_in_use": 9}), _Device({"peak_bytes_in_use": 7})]
    assert harness.memory_peaks(devices) == (9, [5, 9, 7])
    assert harness.memory_peaks(devices[:1]) == (5, [5])
    assert harness.memory_peaks([_Device(None)]) == (None, [None])
