"""``correct``: the answers of the timed path against the plain reference.

The delivered waves are replayed in submission order against
``reference.Reference``: each read is compared with the reference's state
after every write submitted before it, each write batch is then applied.
Compared, each with its limit (exact answers, so every limit is 0):

* ``get_wrong``: GET answers (found flag or value) that differ;
* ``scan_rows_wrong``: sampled RANGE rows whose first ``length`` pairs or
  whose count (up to ``length``) differ;
* ``writes_unacked``: write lanes whose status is not OK;
* ``readback_wrong``: keys written in the run that do not read back, after
  the window, with their last acknowledged value.

The control (``control=True``) puts the reference computed in a lower
precision in the program's place: keys, values and queries rounded through
float64, whose 53-bit mantissa cannot hold a u64.  It must come out wrong.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from reference import Reference

STATUS_OK = 0  # the store's write status for an applied lane
LIMITS = {"get_wrong": 0, "scan_rows_wrong": 0, "writes_unacked": 0, "readback_wrong": 0}


def round_f64(a) -> np.ndarray:
    """u64 through float64 and back (saturating below 2^64)."""
    f = np.asarray(a, dtype=np.uint64).astype(np.float64)
    return np.minimum(f, np.float64(2.0**64 - 4096)).astype(np.uint64)


class Lowered:
    """The reference with every key and value rounded through float64."""

    def __init__(self, keys, vals):
        self.ref = Reference(round_f64(keys), round_f64(vals))

    def get(self, q):
        return self.ref.get(round_f64(q))

    def range(self, k_min, limit):
        return self.ref.range(round_f64(k_min), limit)

    def put(self, k, v):
        self.ref.put(round_f64(k), round_f64(v))


def _range_rows_wrong(w, got, want) -> int:
    s = w.sample
    lengths = w.lengths[s]
    gk, gv, gc = got
    wk, wv, wc = want
    cols = np.arange(w.limit)[None, :] < lengths[:, None]
    bad = (np.minimum(gc, lengths) != np.minimum(wc, lengths))
    bad |= ((gk != wk) & cols).any(axis=1)
    bad |= ((gv != wv) & cols).any(axis=1)
    return int(bad.sum())


def compare(log, readback, keys, vals, control: bool = False) -> Dict[str, dict]:
    """Replay ``log`` (``harness.Delivered`` in submission order) and the
    read-back; returns ``{name: {"value": n, "limit": 0}}`` and coverage."""
    ref = Reference(keys, vals)
    low = Lowered(keys, vals) if control else None
    out = {}
    written, pending = np.zeros(0, dtype=np.uint64), []
    buffered_reads = scan_rows_written = 0
    memo = {}  # (wave, writes applied) -> expected GET answers
    n_writes = 0
    for d in log:
        w = d.wave
        if w.op in ("get", "range") and pending:  # reads see every write before them
            written = np.unique(np.concatenate([written] + pending))
            pending = []
        if w.op == "get":
            key = (id(w), n_writes)
            if key not in memo:
                memo[key] = ref.get(w.keys)
            want_v, want_f = memo[key]
            got_v, got_f = low.get(w.keys) if control else d.out
            bad = (np.asarray(got_f) != want_f) | (np.asarray(got_v) != want_v)
            out["get_wrong"] = out.get("get_wrong", 0) + int(bad.sum())
            buffered_reads += int(np.isin(w.keys, written).sum())
        elif w.op == "range":
            s = w.sample
            want = ref.range(w.keys[s], w.limit)
            in_row = np.arange(w.limit)[None, :] < np.minimum(want[2], w.lengths[s])[:, None]
            scan_rows_written += int((np.isin(want[0], written) & in_row).any(axis=1).sum())
            got = low.range(w.keys[s], w.limit) if control else d.out
            out["scan_rows_wrong"] = out.get("scan_rows_wrong", 0) + _range_rows_wrong(w, got, want)
        else:
            st = np.asarray(d.out)
            out["writes_unacked"] = out.get("writes_unacked", 0) + int((st != STATUS_OK).sum())
            ref.put(w.keys, w.vals)
            if control:
                low.put(w.keys, w.vals)
            pending.append(w.keys)
            n_writes += 1
    if readback is not None:
        w = readback.wave
        want_v, want_f = ref.get(w.keys)
        if control:
            got_v, got_f = low.get(w.keys)
        elif w.op == "get":
            got_v, got_f = readback.out
        else:  # RANGE read-back: the row's first pair must be the key itself
            gk, gv, gc = readback.out
            got_f = (gc > 0) & (gk[:, 0] == w.keys)
            got_v = np.where(got_f, gv[:, 0], np.uint64(0))
        bad = (np.asarray(got_f) != want_f) | (np.asarray(got_v) != want_v) | ~want_f
        out["readback_wrong"] = int(bad.sum())
    numbers = {k: {"value": v, "limit": LIMITS[k]} for k, v in out.items()}
    coverage = {
        "gets_checked": sum(d.wave.n for d in log if d.wave.op == "get"),
        "scan_rows_checked": sum(d.wave.sample.size for d in log if d.wave.op == "range"),
        "reads_of_written_keys": buffered_reads,
        "scan_rows_with_written_keys": scan_rows_written,
        "keys_read_back": 0 if readback is None else int(np.unique(readback.wave.keys).size),
    }
    return {"numbers": numbers, "coverage": coverage}


def verdict(numbers: Dict[str, dict]) -> bool:
    return all(v["value"] <= v["limit"] for v in numbers.values())


def lines(numbers: Dict[str, dict]) -> List[str]:
    return [f"check {k} {v['value']} limit {v['limit']}" for k, v in numbers.items()]
