"""From a ``jax.profiler`` trace of the window to device metrics.

``Tracer`` records the window with the Python function tracer off (it would
slow the host path being measured) and reads the ``.xplane.pb`` back with
``jax.profiler.ProfileData``.  ``reduce_events`` does the arithmetic on a
plain list of ``(plane, line, name, start_ns, duration_ns)`` events, so the
same code runs on a small recorded trace in the tests:

* the traced window is the host span ``bench/window``;
* the cell's chips are the device planes ``/device:<kind>:<n>`` with ``n``
  below the cell's chip count; planes of other chips are left out;
* a chip's busy time is the union of the intervals of the events on its op
  lines, clipped to the window; busy time is their mean over the cell's
  chips, a chip that ran no op counting as idle throughout; idle share is
  1 minus busy over the window;
* a program's time is the sum of the durations of its module events
  (``jit_<name>(...)``) on the chips' module lines, over all the chips;
* each idle gap of the first chip is named by the innermost host span open
  at its start.
"""

from __future__ import annotations

import glob
import re
import tempfile
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench/window"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
TOP = 10

Event = Tuple[str, str, str, float, float]  # plane, line, name, start_ns, dur_ns


def is_device(plane: str) -> bool:
    return plane.startswith("/device:")


_CHIP = re.compile(r"^/device:[A-Za-z_]+:(\d+)")


def chip_of(plane: str) -> Optional[int]:
    """The device ordinal of a device plane, ``None`` for any other plane."""
    m = _CHIP.match(plane)
    return int(m.group(1)) if m else None


def union_ns(intervals: Sequence[Tuple[float, float]]) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, cur_a, cur_b = 0.0, None, None
    for a, b in sorted(intervals):
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total


def gaps(intervals: Sequence[Tuple[float, float]], lo: float, hi: float):
    """The uncovered stretches of ``[lo, hi]``."""
    out, cur = [], lo
    for a, b in sorted(intervals):
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


_SEQ = re.compile(r"#\d+$")
_MODULE = re.compile(r"^jit_(\w+?)(\(\d+\))?$")


@dataclass
class Reduction:
    window_ns: Tuple[float, float]
    busy_ns: float
    programs: Dict[str, Tuple[float, int]]  # name -> (ns, calls)
    top_ops: List[Tuple[str, float]]  # (op, ns), most time first
    idle_gaps: List[Tuple[str, float]]  # (host span, ns), longest first
    n_devices: int  # the cell's chips
    busy_ns_per_chip: List[float]

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the cell's chips."""
        return self.busy_ns / self.n_devices / 1e9

    def program(self, name: str) -> Tuple[float, int]:
        """Device seconds and executions of the jitted program ``name``."""
        ns, calls = self.programs.get(name, (0.0, 0))
        return ns / 1e9, calls

    def breakdown(self) -> dict:
        return {
            "device_ops": [[n, ns / 1e9] for n, ns in self.top_ops],
            "idle_gaps": [[n, ns / 1e9] for n, ns in self.idle_gaps],
            "busy_s_per_chip": [[f"chip{i}", ns / 1e9]
                                for i, ns in enumerate(self.busy_ns_per_chip)],
        }


def reduce_events(events: Sequence[Event], chips: int) -> Reduction:
    """The window's device metrics over the first ``chips`` chips."""
    spans = [(s, s + d) for p, l, n, s, d in events if n == WINDOW_SPAN and not is_device(p)]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = spans[0]
    ops_by_dev = defaultdict(list)
    op_time = defaultdict(float)
    programs: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    for plane, line, name, s, d in events:
        chip = chip_of(plane)
        if chip is None or chip >= chips:
            continue
        a, b = max(s, lo), min(s + d, hi)
        if line == OP_LINE and b > a:
            ops_by_dev[chip].append((a, b))
            op_time[name] += b - a
        elif line == MODULE_LINE and b > a:
            m = _MODULE.match(name)
            if m:
                programs[m.group(1)][0] += b - a
                programs[m.group(1)][1] += 1
    busy_per_chip = [union_ns(ops_by_dev[c]) for c in range(chips)]
    # idle gaps of the first chip, named by what the host was doing
    host = sorted(
        (s, s + d, _SEQ.sub("", n))
        for p, l, n, s, d in events
        if not is_device(p) and n != WINDOW_SPAN and lo <= s <= hi
    )
    named = []
    longest = sorted(gaps(ops_by_dev[0], lo, hi), key=lambda g: g[0] - g[1])[:TOP]
    for a, b in longest:
        open_spans = [(s, e, n) for s, e, n in host if s <= a < e]
        label = min(open_spans, key=lambda x: x[1] - x[0])[2] if open_spans else "none"
        named.append((label, b - a))
    top = sorted(op_time.items(), key=lambda x: -x[1])[:TOP]
    return Reduction(
        window_ns=(lo, hi),
        busy_ns=sum(busy_per_chip),
        programs={k: (v[0], int(v[1])) for k, v in programs.items()},
        top_ops=top,
        idle_gaps=named,
        n_devices=chips,
        busy_ns_per_chip=busy_per_chip,
    )


def load_events(path: str) -> List[Event]:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                out.append((plane.name, line.name, e.name, e.start_ns, e.duration_ns))
    return out


class Tracer:
    """A profiler session over the window, in a temporary directory,
    reduced over the cell's ``chips``."""

    def __init__(self, chips: int):
        self.chips = chips
        self.dir = tempfile.TemporaryDirectory()

    def start(self) -> None:
        import jax.profiler

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir.name, profiler_options=opts)

    def stop(self) -> Reduction:
        import jax.profiler

        jax.profiler.stop_trace()
        try:
            (path,) = glob.glob(f"{self.dir.name}/**/*.xplane.pb", recursive=True)
            return reduce_events(load_events(path), self.chips)
        finally:
            self.dir.cleanup()
