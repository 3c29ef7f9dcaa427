"""p95_ms (ms, host clock): the 95th percentile, over every request of the
window, of the time from the client handing over its wave to the wave's
results being back (nearest rank; a request waits for its whole wave)."""

import numpy as np


def read(w):
    lat = np.array([(d.t_done - d.t_submit) / 1e6 for d in w.waves])
    n = np.array([d.wave.n for d in w.waves])
    order = np.argsort(lat, kind="stable")
    cum = np.cumsum(n[order])
    return float(lat[order][np.searchsorted(cum, 0.95 * cum[-1])])
