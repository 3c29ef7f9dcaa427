"""One ``DPAStore`` on the first chip: the paper's single-NIC store.

The configuration gives the learned index's eps, the insert buffers'
capacity and pool growth, which caches are on, and the pipeline's queue
depth.  The store is bulk-loaded through its constructor; ``devices`` is
not read, since the store places its arrays on JAX's default device, the
first of them."""

import dataclasses

from harness import Built


def build(config: dict, keys, vals, devices) -> Built:
    import jax

    from repro.core import DPAStore, TreeConfig
    from repro.core.hotcache import CacheConfig
    from repro.core.scancache import ScanCacheConfig
    from repro.serving.pipeline import PipelinedStore

    store = DPAStore(
        keys,
        vals,
        TreeConfig(
            eps_inner=config["eps_inner"],
            eps_leaf=config["eps_leaf"],
            ib_cap=config["ib_cap"],
            growth=config["growth"],
        ),
        cache_cfg=CacheConfig() if config["hot_cache"] else None,
        scan_cache_cfg=ScanCacheConfig() if config["scan_cache"] else None,
    )
    jax.block_until_ready((store.tree, store.ib))

    def stats():
        return {
            k: v for k, v in dataclasses.asdict(store.stats).items() if isinstance(v, int)
        }

    return Built(store, PipelinedStore(store, queue_depth=config["queue_depth"]),
                 store.depth, stats)
