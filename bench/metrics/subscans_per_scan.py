"""subscans_per_scan (count): RANGE rows the shards were issued in the
window (``StoreStats.ranges`` summed over the shards) per client scan of
the window.  A range tier issues a scan to its owner shard and, with the
speculative fan-out, to every successor shard too; one store reads 1."""


def read(w):
    scans = w.ops(("range",))
    return w.stats.get("ranges", 0) / scans if scans else None
