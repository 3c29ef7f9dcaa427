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
    r = devtrace.reduce_events(events, 1)
    assert r.n_devices == 1
    assert r.window_s == pytest.approx((hi - lo) / 1e9)
    busy = _busy_by_sweep(events, lo, hi)
    assert 0 < busy < hi - lo
    assert r.busy_s == pytest.approx(busy / 1e9, rel=1e-9)


def test_program_time_is_the_sum_of_its_module_events(events):
    lo, hi = _window(events)
    r = devtrace.reduce_events(events, 1)
    mods = [(s, d) for p, l, n, s, d in events
            if p.startswith("/device:") and l == devtrace.MODULE_LINE
            and n.startswith("jit_get_batch(") and lo <= s and s + d <= hi]
    seconds, calls = r.program("get_batch")
    assert calls == len(mods) >= 1
    assert seconds == pytest.approx(sum(d for _, d in mods) / 1e9, rel=1e-9)


def test_idle_gaps_are_named_by_host_spans(events):
    r = devtrace.reduce_events(events, 1)
    b = r.breakdown()
    assert 1 <= len(b["device_ops"]) <= 10 and 1 <= len(b["idle_gaps"]) <= 10
    gaps = [s for _, s in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= r.window_s - r.busy_s + 1e-9


def test_union_and_gaps_by_hand():
    assert devtrace.union_ns([(0, 10), (5, 12), (20, 25)]) == 17
    assert devtrace.gaps([(2, 4), (3, 6), (8, 9)], 0, 10) == [(0, 2), (6, 8), (9, 10)]


def test_one_chip_reads_what_it_read_before(events):
    """The numbers this trace gave before the reduction took a chip count."""
    r = devtrace.reduce_events(events, 1)
    assert r.window_ns == (52429239.0, 113829127.0)
    assert r.busy_ns == 8484165.0 and r.busy_s == 0.008484165
    assert r.program("get_batch") == (0.006869794, 5) and len(r.programs) == 13
    b = r.breakdown()
    assert b["idle_gaps"][:2] == [["PjitFunction(bitwise_and)", 0.004020478],
                                  ["kv/get/issue", 0.003896767]]
    assert len(b["device_ops"]) == len(b["idle_gaps"]) == 10
    assert b["busy_s_per_chip"] == [["chip0", 0.008484165]]


def _plane_events(chip, ops, modules=()):
    plane = f"/device:TPU:{chip}"
    return ([(plane, devtrace.OP_LINE, "fusion", s, d) for s, d in ops]
            + [(plane, devtrace.MODULE_LINE, "jit_get_batch(1)", s, d) for s, d in modules])


def test_a_chip_without_ops_counts_as_idle():
    """Four chips, one of which runs nothing in the window, read a quarter
    less busy than the three busy chips; a chip beyond the cell's is left
    out."""
    busy = ([("/host:CPU", "python", devtrace.WINDOW_SPAN, 0, 1000)]
            + _plane_events(0, [(100, 300), (250, 500)], [(100, 550)])
            + _plane_events(1, [(0, 400)], [(0, 400)])
            + _plane_events(2, [(900, 200)], [(900, 100)]))
    idle = [("/device:TPU:3", devtrace.MODULE_LINE, "jit_get_batch(1)", 2000, 50)]
    beyond = _plane_events(4, [(0, 1000)], [(0, 1000)])
    three = devtrace.reduce_events(busy, 3)
    four = devtrace.reduce_events(busy + idle + beyond, 4)
    assert three.busy_ns_per_chip == [650, 400, 100] and three.busy_s == 1150 / 3 / 1e9
    assert four.busy_ns_per_chip == [650, 400, 100, 0]
    assert four.n_devices == 4 and four.busy_s == pytest.approx(0.75 * three.busy_s, rel=1e-12)
    assert four.program("get_batch") == three.program("get_batch") == (1050 / 1e9, 3)
    assert four.idle_gaps == three.idle_gaps == [("none", 250), ("none", 100)]
    assert [n for n, _ in four.breakdown()["busy_s_per_chip"]] == ["chip0", "chip1", "chip2", "chip3"]
