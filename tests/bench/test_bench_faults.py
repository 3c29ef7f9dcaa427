"""Whole runs at a tiny size on the CPU: sound runs come out correct, and a
run whose timed path is broken underneath, or the control (the reference in
a lower precision in the program's place), comes out wrong."""

import jax.numpy as jnp
import numpy as np
import pytest

import check
from bench_support import MIXES


@pytest.mark.parametrize("cell", MIXES)
def test_sound_run_is_correct_and_control_is_not(cell, run_tiny):
    res, found = run_tiny(cell)
    assert check.verdict(found["numbers"]), found
    assert found["numbers"]["readback_wrong"]["value"] == 0
    control = check.compare(res.log, res.readback, res.keys, res.vals, control=True)
    assert not check.verdict(control["numbers"]), control


@pytest.mark.parametrize("key_seed", [1, 2, 2**31 + 5])
def test_sound_run_is_correct_on_other_key_sets(key_seed, run_tiny):
    res, found = run_tiny(MIXES[0], config={"key_seed": key_seed})
    assert check.verdict(found["numbers"]), found
    assert found["coverage"]["reads_of_written_keys"] > 0
    base = run_tiny(MIXES[0])[0]
    assert not np.array_equal(res.keys, base.keys)


def test_scan_rows_pick_up_written_keys(run_tiny):
    """YCSB E's sampled RANGE rows cover keys inserted earlier in the run."""
    _, found = run_tiny("sparse-50m.ycsb-e")
    assert check.verdict(found["numbers"]), found
    assert 0 < found["coverage"]["scan_rows_with_written_keys"] <= \
        found["coverage"]["scan_rows_checked"]


def _writes_dropped(monkeypatch):
    """A write step that returns its state unchanged, acknowledging every lane."""
    from repro.core import store

    def append_wave(ib, leaf, khi, klo, vhi, vlo, op, active):
        return ib, jnp.where(active, store.STATUS_OK, 2).astype(jnp.int32)

    monkeypatch.setattr(store.insert_buffer, "append_wave", append_wave)


def _half_left_out(monkeypatch):
    """Half of each read wave's answers left out."""
    from repro.core.store import DPAStore

    get_finalize, range_finalize = DPAStore.get_finalize, DPAStore.range_finalize

    def get_half(self, w):
        vals, found = get_finalize(self, w)
        found, vals = found.copy(), vals.copy()
        found[w.n // 2 :] = False
        vals[w.n // 2 :] = 0
        return vals, found

    def range_half(self, w):
        r = range_finalize(self, w)
        r.counts[w.n // 2 :] = 0
        r.keys[w.n // 2 :] = 0
        r.vals[w.n // 2 :] = 0
        return r

    monkeypatch.setattr(DPAStore, "get_finalize", get_half)
    monkeypatch.setattr(DPAStore, "range_finalize", range_half)


def _answer_altered(monkeypatch):
    """One answer of each read wave altered where the store produces it."""
    from repro.core.store import DPAStore

    get_finalize, range_finalize = DPAStore.get_finalize, DPAStore.range_finalize

    def get_altered(self, w):
        vals, found = get_finalize(self, w)
        vals = vals.copy()
        vals[w.n // 3] ^= np.uint64(1)
        return vals, found

    def range_altered(self, w):
        r = range_finalize(self, w)
        r.vals[:, 0] ^= np.uint64(1)  # every row, so the sampled rows see it
        return r

    monkeypatch.setattr(DPAStore, "get_finalize", get_altered)
    monkeypatch.setattr(DPAStore, "range_finalize", range_altered)


FAULTS = {"writes_dropped": _writes_dropped, "half_left_out": _half_left_out,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", MIXES)
def test_broken_timed_path_is_not_correct(cell, fault, run_tiny, monkeypatch):
    FAULTS[fault](monkeypatch)
    _, found = run_tiny(cell)
    assert not check.verdict(found["numbers"]), found
