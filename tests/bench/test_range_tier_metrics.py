"""The sharded tier's readers and the RANGE walk's bytes on a hand-built
window and trace: each against a hand count, and each reading nothing
where the window has nothing for it."""

from types import SimpleNamespace

import numpy as np
import pytest

from bench_support import BENCH

PEAKS = {"hbm_bytes_per_s": 819e9}


def _reader(name):
    import harness

    return harness.load_module(BENCH / "metrics" / f"{name}.py").read


def _wave(op, lengths=None):
    import harness

    n = 1 if lengths is None else len(lengths)
    w = harness.Wave(op, np.arange(n, dtype=np.uint64))
    if lengths is not None:
        w.lengths = np.asarray(lengths)
        w.limit = int(w.lengths.max())
    return harness.Delivered(w, 0, 1, None)


def _window(waves=(), ledger=(), stats=None, trace=None):
    import harness

    return harness.Window(
        config={"eps_inner": 4, "eps_leaf": 8}, depth=3, setup_s=1.0, t0=0,
        t_end=10**9, waves=list(waves), ledger=list(ledger), stats=stats or {},
        compiles=0, peaks=PEAKS, trace=trace,
    )


def _trace(busy_ns, walk_ns):
    """Four chips: chip ``c`` runs ops for ``busy_ns[c]`` of a 1000 ns window
    and one ``range_batch_loop`` of ``walk_ns[c]``."""
    import devtrace

    events = [("/host:CPU", "python", devtrace.WINDOW_SPAN, 0, 1000)]
    for c, (busy, walk) in enumerate(zip(busy_ns, walk_ns)):
        plane = f"/device:TPU:{c}"
        events.append((plane, devtrace.OP_LINE, "fusion", 100, busy))
        if walk:
            events.append((plane, devtrace.MODULE_LINE, "jit_range_batch_loop(7)", 100, walk))
    return devtrace.reduce_events(events, 4)


def test_range_bytes_hand_count():
    import range_cost

    # depth 3, eps_inner 4: two inner levels of 152 B, the leaf model
    # (slot, count, slope, anchor) 20 B, the key in 8 B; 16 B a pair, read
    # and written
    assert range_cost.descent_bytes(3, 4) == 2 * 152 + 20 == 324
    assert range_cost.range_bytes([1, 100, 50], 3, 4) == 3 * (324 + 8) + 151 * 32 == 5828
    assert range_cost.range_bytes([], 3, 4) == 0


def test_range_batch_loop_roofline_hand_count():
    waves = [_wave("range", [1, 100, 50]), _wave("insert"), _wave("range", [10])]
    w = _window(waves, trace=_trace([400, 300, 200, 100], [250, 250, 250, 250]))
    want = 100.0 * (5828 + 332 + 10 * 32) / (1000e-9 * 819e9)
    assert _reader("range_batch_loop_roofline")(w) == pytest.approx(want)


def test_chip_busy_spread_hand_count():
    w = _window(trace=_trace([400, 300, 200, 100], [0, 0, 0, 0]))
    assert _reader("chip_busy_spread")(w) == pytest.approx(100.0 * (400 - 100) / 400)
    even = _window(trace=_trace([300, 300, 300, 300], [0, 0, 0, 0]))
    assert _reader("chip_busy_spread")(even) == 0.0


def test_subscans_per_scan_hand_count():
    waves = [_wave("range", [5, 5]), _wave("insert"), _wave("range", [1, 2])]
    assert _reader("subscans_per_scan")(_window(waves, stats={"ranges": 10})) == 2.5


def _rec(kind, **phases):
    return SimpleNamespace(kind=kind, waits=0, phases=phases)


def test_gather_ms_per_wave_range_hand_count():
    # the drain waits nested in gather come off it; the issue half's
    # scan-probe wait lies outside gather and does not
    ledger = [_rec("range", gather=2_000_000, launch=9,
                   **{"wait.results": 500_000, "wait.stats": 500_000,
                      "wait.scan_probe": 700_000}),
              _rec("put", gather=9e9),
              _rec("range", gather=4_000_000)]
    assert _reader("gather_ms_per_wave.range")(_window(ledger=ledger)) == pytest.approx(2.5)


@pytest.mark.parametrize("name", ["range_batch_loop_roofline", "chip_busy_spread",
                                  "subscans_per_scan", "gather_ms_per_wave.range"])
def test_reads_nothing_in_an_empty_window(name):
    # no trace, no RANGE wave, no counters, no spans
    assert _reader(name)(_window()) is None


def test_gather_reads_nothing_without_the_facade_span():
    # a single store, or a facade that records no gather span
    ledger = [_rec("range", launch=5), _rec("range", gather=1)]
    assert _reader("gather_ms_per_wave.range")(_window(ledger=ledger)) is None


def test_roofline_reads_nothing_without_the_walk():
    w = _window([_wave("range", [3])], trace=_trace([1, 1, 1, 1], [0, 0, 0, 0]))
    assert _reader("range_batch_loop_roofline")(w) is None
