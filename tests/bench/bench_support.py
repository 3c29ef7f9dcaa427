"""What the benchmark's tests share: paths, the cells, tiny runs."""

import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"

CELLS = ("sparse-50m.ycsb-c",)
# what the tiny runs drive: every cell, and YCSB E's traffic (RANGE and
# INSERT waves), which no cell runs on the chip yet
MIXES = ("sparse-50m.ycsb-c", "sparse-50m.ycsb-e")


def tiny_cell(name: str):
    """``<config>.<traffic>`` from their files, at a size a CPU test can hold."""
    import harness

    config_name, traffic_name = name.split(".", 1)
    config = json.loads((BENCH / "configs" / f"{config_name}.json").read_text())
    traffic = json.loads((BENCH / "traffic" / f"{traffic_name}.json").read_text())
    config.update(n_keys=4000, wave_size=256)
    traffic["pool_steps"] = 4
    if traffic["check"]["range_rows_per_wave"]:
        traffic["check"]["range_rows_per_wave"] = 32
    return harness.Cell(name, 1, config, traffic, [], [])
