import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[2] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from bench_support import tiny_cell  # noqa: E402


@pytest.fixture
def run_tiny():
    """Drive a whole run of a cell at a tiny size on whatever device JAX
    has (the harness's look for a chip is skipped) and return its checks;
    ``config`` overrides keys of the cell's configuration."""
    import time

    import check
    import harness

    def run(name: str, seed: int = 2**31 + 11, seconds: float = 0.5, control: bool = False,
            config: dict = None):
        cell = tiny_cell(name)
        cell.config.update(config or {})
        res = harness.run_cell(cell, seed, seconds, False, time.perf_counter(),
                               {"hbm_bytes_per_s": 819e9})
        return res, check.compare(res.log, res.readback, res.keys, res.vals, control=control)

    return run
