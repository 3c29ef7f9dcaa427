"""The benchmark's harness: one cell of ``BENCHMARK.json`` from its files.

Everything a cell needs is found by name: its configuration in the file
that ``BENCHMARK.json`` names, the store it deploys in
``bench/stores/<store>.py`` (``dpastore`` where the configuration names
none), its traffic in ``bench/traffic/<traffic>.json``, each distribution in
``bench/gen/<gen>.py`` and each metric's reader in
``bench/metrics/<metric>.py``.  A later cell or metric adds files; it edits
none of these.

A run, in order: generate keys and values from the seed; draw all traffic
from the seed; build the configuration's store on the cell's chips through
its builder, which bulk-loads it and wraps it in ``PipelinedStore``; apply
the mix's set-up writes and warm-up steps
(they warm every wave shape the window uses); then run the closed-loop
window.  The client keeps ``queue_depth`` waves in flight and submits the
next when the oldest is delivered.  It stops submitting when ``seconds``
have passed and waits for the waves in flight, so every wave submitted in
the window is counted, and the window ends at the last delivery.

Traffic is drawn as a pool of ``pool_steps`` steps that the window cycles
through; inserts come from keys of the configuration's own shape held out of
the load.  Each run keeps what the check needs: every GET answer, a seeded
sample of each RANGE wave's rows, every write status, and a read-back of the
written keys after the window.
"""

from __future__ import annotations

import importlib.util
import json
import time
from collections import deque
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
STORES = BENCH / "stores"  # one builder per deployed store, by name

OPS = ("get", "range", "insert", "update")
WRITES = ("insert", "update")

# seed streams: one generator per purpose, so adding draws to one purpose
# leaves the others unchanged
KEYS, VALUES, TRAFFIC = 1, 2, 3


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([stream, seed % 2**64])


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem.replace(".", "_"), path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def gen(name: str):
    return load_module(BENCH / "gen" / f"{name}.py")


# ---------------------------------------------------------------------------
# BENCHMARK.json
# ---------------------------------------------------------------------------


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def resolve(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    files = {c["name"]: c["file"] for c in spec["configs"]}
    config = json.loads((root / files[w["config"]]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"] if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    # an entry without "workloads" is read in every cell that reports the
    # end-to-end metric it moves
    per_layer = [
        m
        for m in spec["per_layer"]
        if (name in m["workloads"] if "workloads" in m else m["moves"] in reported)
    ]
    return Cell(name, w["chips"], config, traffic, e2e, per_layer)


# ---------------------------------------------------------------------------
# data and traffic
# ---------------------------------------------------------------------------


@dataclass
class Wave:
    op: str
    keys: np.ndarray  # u64
    vals: Optional[np.ndarray] = None  # u64, writes
    lengths: Optional[np.ndarray] = None  # RANGE: each row's own length
    limit: int = 0  # RANGE: the wave's largest length, the program's limit
    sample: Optional[np.ndarray] = None  # RANGE rows the check compares

    @property
    def n(self) -> int:
        return int(self.keys.size)


def wave_sizes(config: dict, traffic: dict, part: str) -> List[int]:
    return [round(w["share"] * config["wave_size"]) for w in traffic.get(part, [])]


def fresh_needed(config: dict, traffic: dict) -> int:
    """Held-out keys that the mix's inserts consume."""
    steps = {"setup": 1, "step": traffic["warm_steps"] + traffic["pool_steps"]}
    total = 0
    for part, times in steps.items():
        for w, n in zip(traffic.get(part, []), wave_sizes(config, traffic, part)):
            if w["key"]["gen"] == "fresh":
                total += times * n
    return total


def make_data(config: dict, n_fresh: int, seed: int):
    """Loaded keys (sorted), their values, and ``n_fresh`` keys of the same
    shape held out of the load for inserts.

    The key set is the deployment's data and comes from the configuration's
    ``key_seed``, not from the run's seed: the store sizes its pools from
    the leaf count, and a new leaf count is a new shape of every program,
    compiled again and run at a different speed.  Values and all traffic
    come from the run's seed."""
    rng = rng_for(config["key_seed"], KEYS)
    shape = gen(config["key_shape"])
    allk = shape.keys(config["n_keys"] + n_fresh, rng)
    fresh = np.zeros(0, dtype=np.uint64)
    if n_fresh:
        out = rng.choice(allk.size, size=n_fresh, replace=False)
        fresh = allk[out]
        keep = np.ones(allk.size, dtype=bool)
        keep[out] = False
        allk = allk[keep]
    vals = gen("uniform_u64").draw(allk.size, rng_for(seed, VALUES))
    return allk, vals, fresh


class TrafficDraw:
    """Draws the mix's waves, in one fixed order, from the traffic stream."""

    def __init__(self, config: dict, traffic: dict, keys, fresh, seed: int):
        self.config, self.traffic = config, traffic
        self.rng = rng_for(seed, TRAFFIC)
        self.keys, self.fresh = keys, self.rng.permutation(fresh)
        self.used = 0

    def _draw(self, spec: dict, n: int, **extra):
        params = {k: v for k, v in spec.items() if k != "gen"}
        return gen(spec["gen"]).draw(n, self.rng, **extra, **params)

    def wave(self, ws: dict, n: int) -> Wave:
        if ws["op"] not in OPS:
            raise ValueError(f"unknown op {ws['op']!r}")
        if ws["key"]["gen"] == "fresh":
            keys = self.fresh[self.used : self.used + n]
            self.used += n
        else:
            keys = self.keys[self._draw(ws["key"], n, n_items=self.keys.size)]
        if ws.get("distinct"):
            keys = self.rng.permutation(np.unique(keys))[: n // 2]
            if keys.size < n // 2:
                raise ValueError(f"{n} draws gave only {keys.size} distinct keys")
        w = Wave(ws["op"], keys)
        if ws["op"] in WRITES:
            w.vals = self._draw(ws["value"], keys.size)
        if ws["op"] == "range":
            w.lengths = self._draw(ws["length"], keys.size)
            w.limit = int(w.lengths.max())
            rows = self.traffic["check"]["range_rows_per_wave"]
            pick = self.rng.choice(keys.size, size=min(rows, keys.size), replace=False)
            w.sample = np.unique(np.append(pick, np.argmax(w.lengths)))
        return w

    def steps(self, part: str, count: int) -> List[List[Wave]]:
        sizes = wave_sizes(self.config, self.traffic, part)
        return [
            [self.wave(ws, n) for ws, n in zip(self.traffic[part], sizes)]
            for _ in range(count)
        ]


# ---------------------------------------------------------------------------
# the store and the closed-loop client
# ---------------------------------------------------------------------------


@dataclass
class Built:
    """What a store builder (``bench/stores/<name>.py``) returns from
    ``build(config, keys, vals, devices)``, ``devices`` being the cell's
    chips in order: the store served, its ``PipelinedStore``, the deepest
    index walk of its sub-stores (what ``cost.get_bytes`` counts), and
    ``stats()``, which returns the int counters summed over every live
    sub-store under ``StoreStats``' field names."""

    store: object
    pipe: object
    depth: int
    stats: Callable[[], Dict[str, int]]


def build_store(config: dict, keys, vals, devices) -> Built:
    """The store the configuration deploys, built on ``devices``."""
    name = config.get("store", "dpastore")
    return load_module(STORES / f"{name}.py").build(config, keys, vals, devices)


def memory_peaks(devices):
    """The fullest chip's peak bytes in use and each chip's, in order
    (``None`` where a device keeps no memory statistics)."""
    per_chip = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    known = [b for b in per_chip if b is not None]
    return (max(known) if known else None), per_chip


@dataclass
class Delivered:
    wave: Wave
    t_submit: int  # ns, host clock: the client hands the wave over
    t_done: int  # ns: its result is back
    out: object  # what the check compares (see ``Client.keep``)


def _span(name: str):
    import jax.profiler

    return jax.profiler.TraceAnnotation(name)


class Client:
    """Closed loop: at most ``depth`` waves in flight, results in order."""

    def __init__(self, pipe, depth: int):
        self.pipe = pipe
        self.depth = depth
        self.inflight: deque = deque()
        self.log: List[Delivered] = []

    def submit(self, w: Wave) -> None:
        if len(self.inflight) >= self.depth:
            self.deliver()
        t = time.perf_counter_ns()
        with _span(f"bench/submit/{w.op}"):
            if w.op == "get":
                ticket = self.pipe.submit_get(w.keys)
            elif w.op == "range":
                ticket = self.pipe.submit_range(w.keys, w.limit)
            else:
                ticket = self.pipe.submit_put(w.keys, w.vals)
        self.inflight.append((w, t, ticket))

    def deliver(self) -> None:
        w, t, ticket = self.inflight.popleft()
        with _span(f"bench/wait/{w.op}"):
            res = self.pipe.result(ticket)
        done = time.perf_counter_ns()
        self.log.append(Delivered(w, t, done, self.keep(w, res)))

    def drain(self) -> None:
        while self.inflight:
            self.deliver()

    @staticmethod
    def keep(w: Wave, res):
        if w.op == "get":
            return res  # (values, found)
        if w.op == "range":
            s = w.sample
            return res.keys[s], res.vals[s], np.asarray(res.counts)[s]
        return np.asarray(res)  # write statuses

    def run_steps(self, steps: List[List[Wave]]) -> None:
        for step in steps:
            for w in step:
                self.submit(w)
        self.drain()


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


@dataclass
class Window:
    """What the metric readers read."""

    config: dict
    depth: int  # the tree's depth after the load
    setup_s: float
    t0: int
    t_end: int
    waves: List[Delivered]
    ledger: list  # pipeline WaveRecords of the window's waves
    stats: Dict[str, int]  # StoreStats counted over the window
    compiles: int  # programs compiled or read from the cache in the window
    peaks: dict
    trace: object = None  # devtrace.Reduction of the window, in a traced run

    @property
    def window_s(self) -> float:
        return (self.t_end - self.t0) / 1e9

    def ops(self, kinds=OPS) -> int:
        return sum(d.wave.n for d in self.waves if d.wave.op in kinds)


@dataclass
class RunResult:
    window: Window
    log: List[Delivered]  # every delivered wave, set-up and warm-up included
    readback: Optional[Delivered]
    peak_bytes: Optional[int]  # the fullest chip's
    peak_bytes_per_chip: List[Optional[int]]
    keys: np.ndarray  # the loaded keys and values, for the reference
    vals: np.ndarray
    phases: Dict[str, float]  # seconds of each part of the run, in order


class CompileCounter:
    """Counts XLA compilations and persistent-cache reads."""

    EVENTS = ("/jax/compilation_cache/compile_requests_use_cache",)

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event: str, **_):
        if event in self.EVENTS:
            self.n += 1


def read_back(client: "Client", config: dict, traffic: dict, rng) -> Optional[Delivered]:
    """Read every key the run wrote back through the program, after the
    window, in waves of a shape the window already uses: GET waves of the
    mix's GET size, else RANGE waves whose first pair must be the key.  At
    most ``readback_waves`` waves: a seeded sample when there are more keys.
    Returns one ``Delivered`` over all of them."""
    written = [d.wave.keys for d in client.log if d.wave.op in WRITES]
    if not written:
        return None
    keys = np.unique(np.concatenate(written))
    sizes = dict(zip((w["op"] for w in traffic["step"]), wave_sizes(config, traffic, "step")))
    op = "get" if "get" in sizes else "range"
    size = sizes[op]
    cap = traffic["check"]["readback_waves"] * size
    if keys.size > cap:
        keys = np.sort(rng.choice(keys, size=cap, replace=False))
    keys = np.concatenate([keys, np.repeat(keys[-1:], (-keys.size) % size)])
    limit = max((d.wave.limit for d in client.log if d.wave.op == "range"), default=0)
    outs = []
    for i in range(0, keys.size, size):
        part = Wave(op, keys[i : i + size], limit=limit)
        if op == "range":
            part.lengths = np.ones(size, dtype=np.int64)
            part.sample = np.arange(size)
        client.submit(part)
        client.drain()
        out = client.log.pop().out
        if op == "range":  # only the first pair of each row is read back
            out = (out[0][:, :1], out[1][:, :1], out[2])
        outs.append(out)
    w = Wave(op, keys, limit=limit)
    return Delivered(w, 0, 0, tuple(np.concatenate(x) for x in zip(*outs)))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
             peaks: dict) -> RunResult:
    """Set-up, warm-up, window and read-back of one cell on the first
    ``cell.chips`` devices JAX gives; returns what the check and the metric
    readers need."""
    import jax

    import devtrace

    config, traffic = cell.config, cell.traffic
    devices = jax.devices()[: cell.chips]
    phases = {"start": time.perf_counter() - t_start}
    mark = time.perf_counter()

    def done(phase):
        nonlocal mark
        now = time.perf_counter()
        phases[phase] = now - mark
        mark = now

    keys, vals, fresh = make_data(config, fresh_needed(config, traffic), seed)
    done("data")
    draw = TrafficDraw(config, traffic, keys, fresh, seed)
    setup = draw.steps("setup", 1) if traffic.get("setup") else []
    warm = draw.steps("step", traffic["warm_steps"])
    pool = draw.steps("step", traffic["pool_steps"])
    done("traffic")
    built = build_store(config, keys, vals, devices)
    pipe = built.pipe
    done("load")
    client = Client(pipe, config["queue_depth"])
    client.run_steps(setup)
    done("setup_writes")
    client.run_steps(warm)
    done("warm")
    counter = CompileCounter()

    tracer = devtrace.Tracer(cell.chips) if trace else None
    if tracer:
        tracer.start()
    n_log, n_ledger, stats0 = len(client.log), len(pipe.ledger.records), built.stats()
    compiles0 = counter.n
    setup_s = time.perf_counter() - t_start
    with _span("bench/window"):
        t0 = time.perf_counter_ns()
        stop = t0 + int(seconds * 1e9)
        s = 0
        while time.perf_counter_ns() < stop:
            for w in pool[s % len(pool)]:
                client.submit(w)
            s += 1
        client.drain()
    t_end = client.log[-1].t_done
    compiles = counter.n - compiles0
    stats = {k: v - stats0.get(k, 0) for k, v in built.stats().items()}
    ledger = pipe.ledger.records[n_ledger:]
    reduction = tracer.stop() if tracer else None

    readback = read_back(client, config, traffic, rng_for(seed, TRAFFIC + 100))
    done("window_and_readback")
    peak, peak_per_chip = memory_peaks(devices)
    window = Window(
        config=config, depth=built.depth, setup_s=setup_s, t0=t0, t_end=t_end,
        waves=client.log[n_log:], ledger=ledger, stats=stats, compiles=compiles,
        peaks=peaks, trace=reduction,
    )
    del built, pipe
    return RunResult(window, client.log, readback, peak, peak_per_chip, keys, vals, phases)
