"""get_batch_roofline (%): the GET walk's algorithm bytes (``cost.get_bytes``)
over the device time of ``lookup.get_batch`` in the trace times the chip's
HBM bandwidth.  The walk is gather-bound, so bytes bound it."""

import numpy as np

import cost


def read(w):
    if w.trace is None:
        return None
    seconds, calls = w.trace.program("get_batch")
    sizes = [d.wave.n for d in w.waves if d.wave.op == "get"]
    if not calls or not sizes:
        return None
    c = w.config
    per_call = np.mean([cost.get_bytes(n, w.depth, c["eps_inner"], c["eps_leaf"]) for n in sizes])
    return 100.0 * per_call * calls / (seconds * w.peaks["hbm_bytes_per_s"])
