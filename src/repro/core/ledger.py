"""The wave ledger: per-wave timing records and the span recorder.

A wave pipeline (``serving.pipeline.WavePipeline``) keeps one
:class:`WaveRecord` per wave in a :class:`WaveLedger` and opens the record
(:class:`open_wave`) around the wave's issue half and again around its drain
half.  Inside either half the store marks its steps with :class:`span`:

* the span's host time (``time.perf_counter_ns``, the ledger's clock) is
  added to the open record's ``phases`` under the span's name, summed within
  the wave;
* a ``wait.<what>`` span wraps host waits on device values (``np.asarray``,
  ``int()`` or ``block_until_ready`` of a device array); its ``waits``
  argument is the number of such calls inside, added to the record's
  ``waits``;
* every span writes a ``jax.profiler`` annotation
  ``<pipeline>/<kind>/<phase>#<seq>``, with the seq of the pipeline's own
  ``issue#<seq>`` and ``drain#<seq>`` annotations, so one wave's steps share
  an id in a profiler trace.

Phases may nest (``flush`` holds ``plan`` and ``stitch``; ``retry`` holds
a re-sent round's steps), so they need not add up to the half's time; no
``wait.*`` span holds another.  Outside an open wave (``DPAStore.get``
called directly, a barrier ``flush``) a span only writes its annotation,
``store/<phase>``.  A span costs a few microseconds of host time: two
clock reads, a dict update, and an annotation that records only while a
profiler trace runs.  It adds no device program, wait or transfer.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax.profiler


@dataclass
class WaveRecord:
    seq: int
    kind: str
    t_issue0: int  # ns, issue phase start (host build begins)
    t_issue1: int = 0  # ns, issue phase end (device dispatch enqueued)
    t_drain0: int = 0  # ns, drain phase start (blocking gather begins)
    t_drain1: int = 0  # ns, drain phase end (results on host)
    phases: Dict[str, int] = field(default_factory=dict)  # ns per span name
    waits: int = 0  # host waits on device values, both halves

    @property
    def issue_ns(self) -> int:
        return self.t_issue1 - self.t_issue0

    @property
    def drain_ns(self) -> int:
        return self.t_drain1 - self.t_drain0

    @property
    def inflight(self) -> Tuple[int, int]:
        """The wave's in-flight interval: issue start -> drain end."""
        return (self.t_issue0, self.t_drain1)


@dataclass
class WaveLedger:
    """Per-wave timing ledger — the observability half of the pipeline.

    ``overlap_frac`` is the measured double-buffering: the fraction of the
    pipeline's total in-flight time covered by >= 2 concurrent waves.
    Serial execution (queue_depth=1, or a pipeline that drains every wave
    before issuing the next) scores exactly 0; any genuine issue-while-
    draining overlap scores > 0."""

    records: List[WaveRecord] = field(default_factory=list)

    @property
    def n_waves(self) -> int:
        return len(self.records)

    @property
    def wave_issue_ns(self) -> int:
        return sum(r.issue_ns for r in self.records)

    @property
    def wave_drain_ns(self) -> int:
        return sum(r.drain_ns for r in self.records)

    def overlap_frac(self) -> float:
        """1 - merged_span / sum_of_intervals over the in-flight intervals
        (both restricted to time the pipeline was busy at all).  Disjoint
        intervals (pure serial) -> 0; full double-buffering -> ~0.5+."""
        iv = sorted(r.inflight for r in self.records if r.t_drain1 > 0)
        if not iv:
            return 0.0
        total = sum(b - a for a, b in iv)
        if total <= 0:
            return 0.0
        merged = 0
        cur_a, cur_b = iv[0]
        for a, b in iv[1:]:
            if a > cur_b:
                merged += cur_b - cur_a
                cur_a, cur_b = a, b
            else:
                cur_b = max(cur_b, b)
        merged += cur_b - cur_a
        return max(0.0, 1.0 - merged / total)

    def summary(self) -> dict:
        n = max(self.n_waves, 1)
        return {
            "waves": self.n_waves,
            "wave_issue_ns": self.wave_issue_ns,
            "wave_drain_ns": self.wave_drain_ns,
            "issue_us_per_wave": self.wave_issue_ns / n / 1e3,
            "drain_us_per_wave": self.wave_drain_ns / n / 1e3,
            "overlap_frac": self.overlap_frac(),
        }


# the wave spans record into: (annotation prefix "<pipeline>/<kind>", record)
_open: Optional[Tuple[str, WaveRecord]] = None


class open_wave:
    """Make ``rec`` the wave that spans record into until the block ends,
    under the annotation prefix ``label`` (``<pipeline>/<kind>``).  Blocks
    nest: a drain run inside another wave's issue (a write's barrier)
    records into the drained wave and then hands the issue back."""

    __slots__ = ("label", "rec", "_prev")

    def __init__(self, label: str, rec: WaveRecord):
        self.label = label
        self.rec = rec

    def __enter__(self):
        global _open
        self._prev = _open
        _open = (self.label, self.rec)

    def __exit__(self, *exc):
        global _open
        _open = self._prev
        return False


class span:
    """Time one step of a wave half into the open :class:`WaveRecord`.

    ``waits`` is the number of host waits on device values inside the span
    (nonzero only for ``wait.*`` spans).  After the block, ``ns`` holds the
    span's host time, for callers that keep it in a counter too."""

    __slots__ = ("name", "waits", "ns", "_wave", "_ann", "_t0")

    def __init__(self, name: str, waits: int = 0):
        self.name = name
        self.waits = waits
        self.ns = 0

    def __enter__(self):
        self._wave = w = _open
        label = f"{w[0]}/{self.name}#{w[1].seq}" if w else f"store/{self.name}"
        self._ann = jax.profiler.TraceAnnotation(label)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.ns = time.perf_counter_ns() - self._t0
        self._ann.__exit__(*exc)
        if self._wave is not None:
            rec = self._wave[1]
            rec.phases[self.name] = rec.phases.get(self.name, 0) + self.ns
            rec.waits += self.waits
        return False
