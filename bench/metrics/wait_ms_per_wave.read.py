"""Host time waiting on the device per read wave (ms): the sum of a wave's
``wait.*`` spans (``WaveRecord.phases``: the hit count, the result copies,
the scan-cache probe), averaged over the window's GET and RANGE waves.
Nothing to read where the program records no phases."""

import numpy as np


def read(w):
    recs = [r for r in w.ledger if r.kind in ("get", "range")]
    if not recs or any(getattr(r, "phases", None) is None for r in recs):
        return None
    waits = [sum(ns for k, ns in r.phases.items() if k.startswith("wait.")) for r in recs]
    return float(np.mean(waits) / 1e6)
