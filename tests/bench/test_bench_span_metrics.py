"""The readers of the wave spans and wait counters, on hand-built windows:
each is a mean over the window's read waves, and reads nothing where
there is no read wave or where the program records no phases."""

from types import SimpleNamespace

import pytest

from bench_support import BENCH


def _reader(name):
    import harness

    return harness.load_module(BENCH / "metrics" / f"{name}.py").read


def _window(ledger):
    import harness

    return harness.Window(
        config={}, depth=3, setup_s=1.0, t0=0, t_end=10**9, waves=[], ledger=ledger,
        stats={}, compiles=0, peaks={},
    )


def _rec(kind, waits, **phases):
    return SimpleNamespace(kind=kind, waits=waits,
                           phases={k.replace("__", "."): v for k, v in phases.items()})


LEDGER = [
    _rec("get", 4, build=1_000_000, launch=6_000_000, wait__stats=70_000_000,
         wait__results=2_000_000, epilogue=500_000),
    _rec("get", 4, build=3_000_000, launch=8_000_000, wait__stats=50_000_000,
         wait__results=4_000_000, epilogue=700_000),
    _rec("range", 9, build=1_000_000, launch=4_000_000, wait__scan_probe=3_000_000,
         wait__results=12_000_000, epilogue=900_000),
    # a write wave is not a read wave: none of the readers sees it
    _rec("put", 1, build=9e9, launch=9e9, wait__results=9e9, wait__stats=9e9),
]


@pytest.mark.parametrize("name, expect", [
    ("wait_ms_per_wave.read", (72 + 54 + 15) / 3),
    ("stats_wait_ms_per_wave.read", (70 + 50 + 0) / 3),
    ("waits_per_wave.read", (4 + 4 + 9) / 3),
    ("launch_ms_per_wave.read", (6 + 8 + 4) / 3),
])
def test_span_metric_is_the_mean_over_read_waves(name, expect):
    assert _reader(name)(_window(LEDGER)) == pytest.approx(expect)


@pytest.mark.parametrize("name", ["wait_ms_per_wave.read", "stats_wait_ms_per_wave.read",
                                  "waits_per_wave.read", "launch_ms_per_wave.read"])
def test_span_metric_reads_nothing_without_read_waves_or_spans(name):
    read = _reader(name)
    assert read(_window([])) is None
    assert read(_window(LEDGER[3:])) is None
    # records of a program that keeps no phases or waits
    bare = [SimpleNamespace(kind="get", seq=0, t_issue0=0, t_issue1=5)]
    assert read(_window(bare)) is None
