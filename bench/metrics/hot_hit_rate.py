"""hot_hit_rate (%): GET lanes the hot cache answered, over the lanes it
probed in the window (``StoreStats.cache_hits / cache_probes``)."""


def read(w):
    probes = w.stats.get("cache_probes", 0)
    return 100.0 * w.stats["cache_hits"] / probes if probes else None
