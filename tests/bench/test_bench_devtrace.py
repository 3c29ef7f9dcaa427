"""The trace reduction on a small trace recorded on a TPU v5e: a traced
run of the harness (YCSB C at a tiny size), its events kept as
``(plane, line, name, start_ns, duration_ns)``."""

import gzip
import json

import pytest

import devtrace
from bench_support import ROOT

DATA = ROOT / "tests" / "bench" / "data" / "trace_ycsb-c.json.gz"


@pytest.fixture(scope="module")
def events():
    with gzip.open(DATA, "rt") as f:
        return [tuple(e) for e in json.load(f)["events"]]


def _window(events):
    (lo, hi), = [(s, s + d) for p, l, n, s, d in events if n == devtrace.WINDOW_SPAN]
    return lo, hi


def _busy_by_sweep(events, lo, hi):
    """Busy time by a sweep over interval end points: a second way to take
    the union of the device ops."""
    points = []
    for p, l, n, s, d in events:
        if p.startswith("/device:") and l == devtrace.OP_LINE:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                points += [(a, 1), (b, -1)]
    busy, depth, last = 0.0, 0, None
    for t, step in sorted(points, key=lambda x: (x[0], -x[1])):
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_idle_share_matches_a_sweep(events):
    lo, hi = _window(events)
    r = devtrace.reduce_events(events)
    assert r.n_devices == 1
    assert r.window_s == pytest.approx((hi - lo) / 1e9)
    busy = _busy_by_sweep(events, lo, hi)
    assert 0 < busy < hi - lo
    assert r.busy_s == pytest.approx(busy / 1e9, rel=1e-9)


def test_program_time_is_the_sum_of_its_module_events(events):
    lo, hi = _window(events)
    r = devtrace.reduce_events(events)
    mods = [(s, d) for p, l, n, s, d in events
            if p.startswith("/device:") and l == devtrace.MODULE_LINE
            and n.startswith("jit_get_batch(") and lo <= s and s + d <= hi]
    seconds, calls = r.program("get_batch")
    assert calls == len(mods) >= 1
    assert seconds == pytest.approx(sum(d for _, d in mods) / 1e9, rel=1e-9)


def test_idle_gaps_are_named_by_host_spans(events):
    r = devtrace.reduce_events(events)
    b = r.breakdown()
    assert 1 <= len(b["device_ops"]) <= 10 and 1 <= len(b["idle_gaps"]) <= 10
    gaps = [s for _, s in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= r.window_s - r.busy_s + 1e-9


def test_union_and_gaps_by_hand():
    assert devtrace.union_ns([(0, 10), (5, 12), (20, 25)]) == 17
    assert devtrace.gaps([(2, 4), (3, 6), (8, 9)], 0, 10) == [(0, 2), (6, 8), (9, 10)]
