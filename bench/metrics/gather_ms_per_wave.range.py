"""Host time joining the shards' answers per RANGE wave (ms): the sharded
facade's ``gather`` span (``WaveRecord.phases``: draining each shard's
sub-wave, the accumulator stitch and any host resume) less the ``wait.*``
spans nested in it, averaged over the window's RANGE waves.  A RANGE
wave's waits other than the issue half's ``wait.scan_probe`` are all drain
waits, and the facade drains inside ``gather``, so what is left is host
work and not the device walk waited out.  Nothing to read where the
program records no ``gather`` span (a single store, or a facade without
it)."""

import numpy as np


def _host_ns(phases):
    waits = sum(ns for k, ns in phases.items()
                if k.startswith("wait.") and k != "wait.scan_probe")
    return phases["gather"] - waits


def read(w):
    recs = [r for r in w.ledger if r.kind == "range"]
    if not recs or any("gather" not in (getattr(r, "phases", None) or {}) for r in recs):
        return None
    return float(np.mean([_host_ns(r.phases) for r in recs]) / 1e6)
