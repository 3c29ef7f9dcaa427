"""chip_busy_spread (%): how unevenly the cell's chips are loaded, from the
device trace: (most busy - least busy) over most busy, of each chip's busy
time in the window (``breakdown.busy_s_per_chip``).  0 where every chip is
equally busy, 100 where one ran nothing."""


def read(w):
    if w.trace is None or not w.trace.n_devices:
        return None
    busy = w.trace.busy_ns_per_chip
    if max(busy) <= 0:
        return None
    return 100.0 * (max(busy) - min(busy)) / max(busy)
