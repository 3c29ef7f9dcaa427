"""range_batch_loop_roofline (%): the RANGE walk's algorithm bytes
(``range_cost.range_bytes`` over every scan of the window's RANGE waves,
each at its own length) over the device time of ``lookup.range_batch_loop``
summed over the cell's chips times one chip's HBM bandwidth.  A sharded
fleet runs one walk per shard and wave, so the time is the sum over the
chips and the bytes are those of the client's scans."""

import numpy as np

import range_cost


def read(w):
    if w.trace is None:
        return None
    seconds, calls = w.trace.program("range_batch_loop")
    lengths = [d.wave.lengths for d in w.waves if d.wave.op == "range"]
    if not calls or not lengths:
        return None
    algo = range_cost.range_bytes(np.concatenate(lengths), w.depth, w.config["eps_inner"])
    return 100.0 * algo / (seconds * w.peaks["hbm_bytes_per_s"])
