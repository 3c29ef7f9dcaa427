"""Host time waiting for a wave's counters per read wave (ms): the
``wait.stats`` span (``WaveRecord.phases``; in a GET wave, the hot-cache hit
count that ``cache_hits`` keeps), averaged over the window's GET and RANGE
waves.  Nothing to read where the program records no phases."""

import numpy as np


def read(w):
    recs = [r for r in w.ledger if r.kind in ("get", "range")]
    if not recs or any(getattr(r, "phases", None) is None for r in recs):
        return None
    return float(np.mean([r.phases.get("wait.stats", 0) for r in recs]) / 1e6)
