"""Host time to issue one wave (ms): the wave pipeline's own ``WaveLedger``
issue span (host build and device dispatch), averaged over the window's
waves.  A write wave that takes the serial path runs its retries and flush
cycles inside its issue span."""

import numpy as np


def read(w):
    if not w.ledger:
        return None
    return float(np.mean([r.issue_ns for r in w.ledger]) / 1e6)
