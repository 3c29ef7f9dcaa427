"""A range-partitioned fleet of ``DPAStore`` shards, one per chip.

``ShardedDPAStore(partition="range")`` cuts the loaded keys at quantile
boundaries into the configuration's ``n_shards`` and places shard ``s`` on
``devices[s]`` (one device per shard, else ``ValueError``): its pools,
caches and programs live on that chip.  The boundaries stay fixed for the
run (no online rebalance).  Each shard is the configuration's single
store: the same eps, insert buffers, pool growth and caches as
``bench/stores/dpastore.py``.  The client drives the fleet through one
``PipelinedStore``."""

from harness import Built


def build(config: dict, keys, vals, devices) -> Built:
    import jax

    from repro.core import TreeConfig
    from repro.core.hotcache import CacheConfig
    from repro.core.scancache import ScanCacheConfig
    from repro.distributed.kvshard import ShardedDPAStore
    from repro.serving.pipeline import PipelinedStore

    store = ShardedDPAStore(
        keys,
        vals,
        n_shards=config["n_shards"],
        tree_cfg=TreeConfig(
            eps_inner=config["eps_inner"],
            eps_leaf=config["eps_leaf"],
            ib_cap=config["ib_cap"],
            growth=config["growth"],
        ),
        cache_cfg=CacheConfig() if config["hot_cache"] else None,
        scan_cache_cfg=ScanCacheConfig() if config["scan_cache"] else None,
        partition="range",
        rebalance_cfg=None,
        replication=config["replication"],
        devices=devices,
    )
    jax.block_until_ready([(sh.tree, sh.ib) for sh in store.shards])
    return Built(store, PipelinedStore(store, queue_depth=config["queue_depth"]),
                 max(sh.depth for sh in store.shards), store.stats_totals)
