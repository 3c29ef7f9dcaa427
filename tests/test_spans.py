"""The span recorder inside the store's wave halves (``core/ledger.py``).

Each pipelined wave's :class:`~repro.core.ledger.WaveRecord` sums host time
per step (``phases``) and counts the host's waits on device values
(``waits``); every step is also a profiler annotation named
``<pipeline>/<kind>/<step>#<seq>``.  The annotations are caught here by
swapping ``jax.profiler.TraceAnnotation`` for a recorder of labels and
host times, on a tiny store.
"""

import time

import jax
import numpy as np
import pytest

from repro.core import STATUS_OK, DPAStore, TreeConfig, ledger
from repro.core.hotcache import CacheConfig
from repro.serving.pipeline import PipelinedStore

KEY_BOUND = 2**63


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: keeps each label
    with its host enter and exit times."""

    def __init__(self):
        self.spans = []

    def __call__(self, label):
        log = self

        class _Ann:
            def __enter__(self):
                self.t0 = time.perf_counter_ns()
                return self

            def __exit__(self, *exc):
                log.spans.append((label, self.t0, time.perf_counter_ns()))
                return False

        return _Ann()

    def labels(self, prefix=""):
        return [s[0] for s in self.spans if s[0].startswith(prefix)]


@pytest.fixture
def annotations(monkeypatch):
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    return ann


def _store(cache: bool, seed: int = 5, n: int = 300):
    rng = np.random.default_rng(seed)
    keys = np.unique(rng.integers(1, KEY_BOUND, n, dtype=np.uint64))
    store = DPAStore(
        keys, keys ^ np.uint64(0xABC), TreeConfig(growth=16.0),
        cache_cfg=CacheConfig() if cache else None,
    )
    return store, keys


def _inside(spans, lo, hi):
    return all(lo <= a and b <= hi for _, a, b in spans)


def _step(label):
    """``kv/get/build#0`` -> ``build``."""
    return label.split("/")[2].split("#")[0]


def test_get_wave_records_each_step_once_inside_its_halves(annotations):
    store, keys = _store(cache=True)
    pipe = PipelinedStore(store, queue_depth=2)
    rng = np.random.default_rng(1)
    pipe.get(rng.choice(keys, 64))  # seq 0
    pipe.get(rng.choice(keys, 64))  # seq 1
    (rec, rec1) = pipe.ledger.records
    issue = ("build", "launch")
    drain = ("wait.stats", "wait.results", "epilogue")
    assert set(rec.phases) == set(issue + drain)
    assert all(ns > 0 for ns in rec.phases.values())
    for step in issue + drain:
        assert annotations.labels(f"kv/get/{step}#") == [
            f"kv/get/{step}#0", f"kv/get/{step}#1"
        ]
    mine = [s for s in annotations.spans if s[0].endswith("#0")]
    assert _inside([s for s in mine if _step(s[0]) in issue], rec.t_issue0, rec.t_issue1)
    assert _inside([s for s in mine if _step(s[0]) in drain], rec.t_drain0, rec.t_drain1)
    assert rec.phases["build"] + rec.phases["launch"] <= rec.issue_ns
    assert sum(rec.phases[k] for k in drain) <= rec.drain_ns
    assert ledger._open is None


@pytest.mark.parametrize("cache, waits", [(True, 4), (False, 3)])
def test_get_wave_waits(cache, waits):
    """The hit count and three result copies with the hot cache; the copies
    alone without it."""
    store, keys = _store(cache=cache)
    pipe = PipelinedStore(store, queue_depth=2)
    rng = np.random.default_rng(2)
    tickets = [pipe.submit_get(rng.choice(keys, 64)) for _ in range(3)]
    for t in tickets:
        pipe.result(t)
    assert [r.waits for r in pipe.ledger.records] == [waits] * 3
    assert ("wait.stats" in pipe.ledger.records[0].phases) == cache


def test_serial_write_records_flush_plan_and_stitch(annotations):
    """Twenty writes into one leaf's buffer (capacity 16) cannot take the
    fast path: the wave runs the serial path inside its issue half, fills
    the buffer, stitches it and re-sends the refused lanes."""
    store, keys = _store(cache=False)
    pipe = PipelinedStore(store, queue_depth=2)
    assert store.stats.flush_ns == store.stats.plan_ns == store.stats.stitch_ns == 0
    fresh = keys[40] + np.arange(1, 21, dtype=np.uint64)
    st = pipe.put(fresh, fresh)
    assert np.all(st == STATUS_OK)
    (rec,) = pipe.ledger.records
    assert rec.kind == "put"
    assert {"build", "launch", "wait.results", "wait.counts", "wait.buffers",
            "flush", "plan", "stitch", "retry"} <= set(rec.phases)
    s = store.stats
    assert s.flush_cycles >= 1 and s.flush_ns > 0 and s.plan_ns > 0 and s.stitch_ns > 0
    assert s.flush_ns >= s.plan_ns + s.stitch_ns
    # the stitch ran in the wave's issue half, under the wave's seq
    flush = [x for x in annotations.spans if x[0] == "kv/put/flush#0"]
    assert flush and _inside(flush, rec.t_issue0, rec.t_issue1)
    got, found = pipe.get(fresh)
    assert found.all() and np.array_equal(got, fresh)


def test_range_wave_records_scan_probe_wait_in_its_issue_half(annotations):
    store, keys = _store(cache=False)
    assert store.scan_cache is not None
    pipe = PipelinedStore(store, queue_depth=2)
    pipe.range(keys[:32], limit=5)
    (rec,) = pipe.ledger.records
    assert {"build", "launch", "wait.scan_probe", "wait.results",
            "epilogue"} <= set(rec.phases)
    probe = [x for x in annotations.spans if x[0] == "kv/range/wait.scan_probe#0"]
    assert len(probe) == 1 and _inside(probe, rec.t_issue0, rec.t_issue1)
    # the probe wait, eight result copies, and the cursor-admit count where
    # a row came back truncated
    assert rec.waits == 9 + ("wait.stats" in rec.phases)


def test_direct_get_records_on_no_wave(annotations):
    store, keys = _store(cache=True)
    pipe = PipelinedStore(store, queue_depth=2)
    pipe.get(keys[:16])
    before = [(dict(r.phases), r.waits) for r in pipe.ledger.records]
    vals, found = store.get(keys[:16])
    assert found.all()
    assert [(dict(r.phases), r.waits) for r in pipe.ledger.records] == before
    assert ledger._open is None
    direct = annotations.spans[-5:]
    assert [x[0] for x in direct] == [
        "store/build", "store/launch", "store/wait.stats", "store/wait.results",
        "store/epilogue",
    ]
