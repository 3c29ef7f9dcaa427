"""Host waits on device values per read wave (count): ``WaveRecord.waits``
(each ``np.asarray``, ``int()`` or ``block_until_ready`` of a device array
in the wave's issue and drain), averaged over the window's GET and RANGE
waves.  Nothing to read where the program does not count them."""

import numpy as np


def read(w):
    recs = [r for r in w.ledger if r.kind in ("get", "range")]
    if not recs or any(getattr(r, "waits", None) is None for r in recs):
        return None
    return float(np.mean([r.waits for r in recs]))
