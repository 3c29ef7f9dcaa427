"""Run one cell of ``BENCHMARK.json`` on the accelerator and print its result.

    python3 bench/run.py --workload sparse-50m.ycsb-c --seed 7 --seconds 30 --trace 0

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics read from a profiler trace of the
window), ``device`` and, traced, ``breakdown``; its last key, ``checks``,
holds every number compared with the reference beside its limit.  The same
numbers are the last lines of standard error.

The run fails, with no result line, where JAX finds no TPU or fewer chips
than the cell asks for, and where the program (``src/``) is not beside the
benchmark.  ``--control 1`` also computes the control: the reference in a
lower precision put in the program's place, which has to come out wrong.
``--key-seed <n>`` serves a key set other than the configuration's
``key_seed``, to check answers over other key layouts; its programs are new
shapes and compile in set-up.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
SRC = BENCH.parent / "src"
sys.path.insert(0, str(BENCH))

import check  # noqa: E402
import harness  # noqa: E402


class NoDevice(Exception):
    pass


def import_program() -> None:
    """Put the checkout's ``src/`` first on the path; the program must come
    from there."""
    sys.path.insert(0, str(SRC))
    import repro

    if not Path(repro.__file__).resolve().is_relative_to(SRC):
        raise ImportError(f"repro imported from {repro.__file__}, not from {SRC}")


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if info["platform"] != "tpu" or info["count"] < chips:
        raise NoDevice(f"need {chips} TPU chip(s), JAX found {info}")
    return info


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def read_metrics(entries, window) -> dict:
    out = {}
    for m in entries:
        value = harness.load_module(BENCH / "metrics" / f"{m['name']}.py").read(window)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--key-seed", type=int, default=None)
    args = ap.parse_args(argv)
    try:
        cell = harness.resolve(args.workload)
        if args.key_seed is not None:
            cell.config["key_seed"] = args.key_seed
        import_program()
        from repro.launch.compile_cache import enable_compile_cache

        enable_compile_cache()
        device = device_info(cell.chips)
        peaks = peaks_for(device["kind"])
        res = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START, peaks)
        window = res.window
        metrics = read_metrics(cell.per_layer if args.trace else cell.end_to_end, window)
        found = check.compare(res.log, res.readback, res.keys, res.vals)
        control = (
            check.compare(res.log, res.readback, res.keys, res.vals, control=True)
            if args.control
            else None
        )
    except Exception as e:  # every failure exits non-zero, with no result line
        if not isinstance(e, NoDevice):
            traceback.print_exc()
        print(f"bench: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    device["memory_peak_bytes"] = res.peak_bytes
    device["memory_peak_bytes_per_chip"] = res.peak_bytes_per_chip
    line = {
        "correct": check.verdict(found["numbers"]),
        "attempted": window.ops(),
        "failed": found["numbers"].get("writes_unacked", {}).get("value", 0),
        "metrics": metrics,
        "device": device,
        "key_seed": cell.config["key_seed"],
        "window_compiles": window.compiles,
        "coverage": found["coverage"],
    }
    if window.trace is not None:
        device["busy_s"] = window.trace.busy_s
        device["window_s"] = window.trace.window_s
        line["breakdown"] = window.trace.breakdown()
    if control is not None:
        line["control"] = {
            "correct": check.verdict(control["numbers"]),
            "checks": control["numbers"],
        }
        for text in check.lines(control["numbers"]):
            print("control " + text, file=sys.stderr)
    line["checks"] = found["numbers"]
    print("phases " + ", ".join(f"{k} {v:.1f} s" for k, v in res.phases.items()),
          file=sys.stderr)
    print(f"window {window.window_s:.3f} s, {window.ops()} ops, "
          f"{window.compiles} compiles in the window", file=sys.stderr)
    for text in check.lines(found["numbers"]):
        print(text, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
