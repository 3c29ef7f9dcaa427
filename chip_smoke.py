"""Chip smoke run: drive the DPA-Store serve path once on a TPU and check
every answer against an independent numpy oracle.

    python chip_smoke.py             # one chip: the paper's single-store
                                     # deployment, 50M keys
    python chip_smoke.py --chips 4   # four chips: the range tier's mesh
                                     # waves, 4 shards x 25M keys

One chip runs ``configs/dpastore_service.py``: 50M ``sparse`` u64 keys with
u64 values made from ``--seed``, eps_inner 4 / eps_leaf 8, waves of 65536
requests and the default ``TreeConfig``, served by ``DPAStore`` behind
``PipelinedStore(queue_depth=2)`` with the hot cache and the scan-anchor
cache on — the objects ``launch/serve.py --kv`` drives.  Phases, one line
each: device, compile cache, bulk load, GET waves (Zipf 0.99 and uniform),
PUT/DELETE waves with a flush and a read-back of every acknowledged write,
RANGE waves (limit 10 and 100), peak device bytes.

``--chips 4`` runs only the mesh phase: a range tier of four 25M-key
shards, each shard's pools on its own chip, assembled with
``NamedSharding(mesh, P("data"))`` and served by
``kvshard.serve_wave_sharded`` (GET) and ``rangeshard.range_wave_sharded``
(RANGE) on a 4-device mesh.

The oracle is numpy sorted arrays (``searchsorted`` and slices), not the
store's code.  Any mismatch or exception exits non-zero.  So does a run
where JAX finds no TPU, or where the repo's ``src/`` is not next to this
file; no result line is printed then.  On success the last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

N_KEYS = 50_000_000  # configs/dpastore_service.py
MESH_KEYS_PER_CHIP = 25_000_000  # the paper's lower per-NIC key count
WAVE = 65536  # configs/dpastore_service.py: requests per wave
GET_WAVES = 4  # of each distribution
WRITE_WAVE = WAVE // 4  # the write share launch/serve.py uses per wave
LIMITS = (10, 100)
MESH_GET_WAVE = WAVE // 4  # per shard: 65536 requests over 4 chips
MESH_RANGE_WAVE = 4096  # per shard
MESH_FANOUT = 2  # owner + successor: a 100-key scan never spans 3 shards


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def _same(name: str, got, want) -> None:
    got, want = np.asarray(got), np.asarray(want)
    check(got.shape == want.shape, f"{name}: shape {got.shape} != {want.shape}")
    bad = int(np.count_nonzero(got != want))
    check(bad == 0, f"{name}: {bad} of {want.size} entries differ from the oracle")


def _gib(n) -> str:
    return "not reported" if n is None else f"{n / 2**30:.2f} GiB"


def _mem(dev, key: str):
    stats = dev.memory_stats()
    return None if not stats else stats.get(key)


# ---------------------------------------------------------------------------
# the oracle: sorted numpy arrays, searchsorted and slices
# ---------------------------------------------------------------------------


class Oracle:
    """Reference key-value map over sorted unique u64 keys."""

    def __init__(self, keys: np.ndarray, vals: np.ndarray):
        self.keys = np.array(keys, dtype=np.uint64)
        self.vals = np.array(vals, dtype=np.uint64)

    def _find(self, q):
        q = np.asarray(q, dtype=np.uint64)
        pos = np.searchsorted(self.keys, q)
        safe = np.minimum(pos, self.keys.size - 1)
        return pos, safe, (pos < self.keys.size) & (self.keys[safe] == q)

    def get(self, q):
        _, safe, found = self._find(q)
        return np.where(found, self.vals[safe], np.uint64(0)), found

    def range(self, k_min, limit: int):
        start = np.searchsorted(self.keys, np.asarray(k_min, dtype=np.uint64))
        idx = start[:, None] + np.arange(limit)
        valid = idx < self.keys.size
        safe = np.minimum(idx, self.keys.size - 1)
        zero = np.uint64(0)
        return (
            np.where(valid, self.keys[safe], zero),
            np.where(valid, self.vals[safe], zero),
            valid.sum(axis=1),
        )

    def put(self, k, v) -> None:
        """Upsert unique keys ``k``."""
        k = np.asarray(k, dtype=np.uint64)
        v = np.asarray(v, dtype=np.uint64)
        pos, _, hit = self._find(k)
        self.vals[pos[hit]] = v[hit]
        order = np.argsort(k[~hit])
        new_k, new_v = k[~hit][order], v[~hit][order]
        at = np.searchsorted(self.keys, new_k)
        self.keys = np.insert(self.keys, at, new_k)
        self.vals = np.insert(self.vals, at, new_v)

    def delete(self, k) -> None:
        pos, _, hit = self._find(k)
        self.keys = np.delete(self.keys, pos[hit])
        self.vals = np.delete(self.vals, pos[hit])


def make_data(n_keys: int, seed: int):
    """``n_keys`` sorted unique ``sparse`` keys and random u64 values."""
    from repro.core.datasets import sparse

    keys = sparse(n_keys, seed=seed)
    vals = np.random.default_rng(seed + 1).integers(
        0, 2**64, keys.size, dtype=np.uint64
    )
    return keys, vals


def _absent(oracle: Oracle, n: int, rng) -> np.ndarray:
    """``n`` distinct keys the oracle does not hold (2^64-1 is reserved)."""
    cand = np.unique(rng.integers(0, 2**64 - 1, 2 * n + 64, dtype=np.uint64))
    cand = cand[~oracle.get(cand)[1]]
    rng.shuffle(cand)
    check(cand.size >= n, "could not draw enough absent keys")
    return cand[:n]


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_device(require: str = "tpu", min_count: int = 1) -> dict:
    import jax

    devs = jax.devices()
    info = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    say("device", f"platform={info['platform']} kind={info['kind']} count={info['count']}")
    check(info["platform"] == require, f"platform is {info['platform']}, not {require}")
    check(info["count"] >= min_count, f"{info['count']} devices, need {min_count}")
    return info


def phase_cache() -> dict:
    """Turn the persistent compile cache on; count its reads and writes."""
    import jax

    from repro.launch.compile_cache import ENV_VAR, enable_compile_cache

    counts = {"read": 0, "written": 0}
    events = {
        "/jax/compilation_cache/cache_hits": "read",
        "/jax/compilation_cache/cache_misses": "written",
    }

    def listen(event: str, **_):
        if event in events:
            counts[events[event]] += 1

    jax.monitoring.register_event_listener(listen)
    path = enable_compile_cache()
    source = ENV_VAR if os.environ.get(ENV_VAR) else "checkout default"
    say("cache", f"compile cache at {path} ({source})")
    return counts


def phase_load(n_keys: int, seed: int):
    """Bulk-load the store; returns (pipelined store, store, oracle)."""
    import jax

    from repro.configs.dpastore_service import CONFIG
    from repro.core import DPAStore, TreeConfig
    from repro.serving.pipeline import PipelinedStore

    t0 = time.perf_counter()
    keys, vals = make_data(n_keys, seed)
    t_gen = time.perf_counter() - t0
    t1 = time.perf_counter()
    store = DPAStore(
        keys,
        vals,
        TreeConfig(eps_inner=CONFIG.eps_inner, eps_leaf=CONFIG.eps_leaf),
    )
    jax.block_until_ready((store.tree, store.ib))
    t_load = time.perf_counter() - t1
    live = store.image.leaf_anchor.shape[0] - len(store.image.free_leaves)
    say(
        "load",
        f"{keys.size} keys generated in {t_gen:.1f}s, bulk-loaded in "
        f"{t_load:.1f}s (depth {store.depth}, {live} leaves, pools of "
        f"{store.image.leaf_anchor.shape[0]}); device bytes in use "
        f"{_gib(_mem(jax.devices()[0], 'bytes_in_use'))}",
    )
    return PipelinedStore(store, queue_depth=2), store, Oracle(keys, vals)


def phase_get(pipe, store, oracle: Oracle, wave: int, n_waves: int, seed: int):
    """Zipf-0.99 waves (hot cache on), then uniform waves over present and
    absent keys; every answer against the oracle."""
    from repro.core.datasets import zipf_indices

    n = oracle.keys.size
    rng = np.random.default_rng(seed + 2)
    zipf = oracle.keys[zipf_indices(n, n_waves * wave, alpha=0.99, seed=seed)]
    half = n_waves * wave // 2
    uniform = np.concatenate(
        [oracle.keys[rng.integers(0, n, half)], _absent(oracle, half, rng)]
    )
    rng.shuffle(uniform)

    def run(batch):
        qs = [batch[i * wave : (i + 1) * wave] for i in range(n_waves)]
        tickets = [pipe.submit_get(q) for q in qs]
        return qs, [pipe.result(t) for t in tickets]

    hits0, probes0 = store.stats.cache_hits, store.stats.cache_probes
    zq, zres = run(zipf)  # also compiles every GET program
    hits = store.stats.cache_hits - hits0
    probes = store.stats.cache_probes - probes0
    t0 = time.perf_counter()
    uq, ures = run(uniform)
    wave_ms = (time.perf_counter() - t0) / n_waves * 1e3
    for name, qs, res in (("zipf", zq, zres), ("uniform", uq, ures)):
        for i, (q, (vals, found)) in enumerate(zip(qs, res)):
            want_v, want_f = oracle.get(q)
            _same(f"GET {name} wave {i} found", found, want_f)
            _same(f"GET {name} wave {i} values", vals, want_v)
    check(hits > 0, "Zipf GET waves never hit the hot cache")
    say(
        "get",
        f"{2 * n_waves} waves x {wave}: Zipf 0.99 (hot-cache hits {hits}/"
        f"{probes}) and uniform ({int(np.mean([r[1].mean() for r in ures]) * 100)}"
        f"% present) match the oracle; informational wall time "
        f"{wave_ms:.1f} ms per pipelined uniform wave (not a benchmark number)",
    )


def phase_write(pipe, store, oracle: Oracle, n_writes: int, seed: int):
    """PUT updates, PUT fresh inserts and DELETEs, read back before and
    after a flush that must run at least one stitch cycle."""
    from repro.core.store import STATUS_OK

    rng = np.random.default_rng(seed + 3)
    n = oracle.keys.size
    pick = np.unique(rng.integers(0, n, 4 * n_writes))
    rng.shuffle(pick)
    check(pick.size >= 2 * n_writes, "could not draw enough distinct keys")
    upd = oracle.keys[pick[:n_writes]]
    dels = oracle.keys[pick[n_writes : 2 * n_writes]]
    fresh = _absent(oracle, n_writes, rng)
    upd_v = rng.integers(0, 2**64, n_writes, dtype=np.uint64)
    fresh_v = rng.integers(0, 2**64, n_writes, dtype=np.uint64)
    tickets = [
        pipe.submit_put(upd, upd_v),
        pipe.submit_put(fresh, fresh_v),
        pipe.submit_delete(dels),
    ]
    for what, t in zip(("update", "insert", "delete"), tickets):
        st = np.asarray(pipe.result(t))
        check(bool((st == STATUS_OK).all()), f"{what} wave: not every write acknowledged")
    oracle.put(upd, upd_v)
    oracle.put(fresh, fresh_v)
    oracle.delete(dels)
    touched = np.concatenate([upd, fresh, dels])
    want_v, want_f = oracle.get(touched)
    vals, found = pipe.get(touched)
    _same("read-back before flush: found", found, want_f)
    _same("read-back before flush: values", vals, want_v)
    cycles0, applies0 = store.stats.flush_cycles, store.stats.stitch_applies
    t0 = time.perf_counter()
    pipe.flush()
    t_flush = time.perf_counter() - t0
    cycles = store.stats.flush_cycles - cycles0
    applies = store.stats.stitch_applies - applies0
    check(cycles >= 1 and applies >= 1, "flush ran no stitch cycle")
    vals, found = pipe.get(touched)
    _same("read-back after flush: found", found, want_f)
    _same("read-back after flush: values", vals, want_v)
    say(
        "write",
        f"PUT {n_writes} updates + {n_writes} inserts, DELETE {n_writes}: all "
        f"acknowledged; flush ran {cycles} cycle(s) / {applies} stitch "
        f"apply(s) in {t_flush:.1f}s; every acknowledged write and delete "
        f"reads back before and after the flush",
    )


def phase_range(pipe, store, oracle: Oracle, wave: int, limits, seed: int):
    """RANGE waves at each limit, each sent twice so the second one starts
    from the scan-anchor cache."""
    from repro.core.datasets import zipf_indices

    n = oracle.keys.size
    starts = oracle.keys[zipf_indices(n, wave, alpha=0.99, seed=seed + 4)]
    starts[::7] = _absent(oracle, starts[::7].size, np.random.default_rng(seed + 5))
    out = []
    for limit in limits:
        want_k, want_v, want_c = oracle.range(starts, limit)
        h0, p0 = store.stats.scan_hits, store.stats.scan_probes
        tickets = [pipe.submit_range(starts, limit) for _ in range(2)]
        for rep, t in enumerate(tickets):
            res = pipe.result(t)
            tag = f"RANGE limit {limit} pass {rep}"
            _same(f"{tag} counts", res.counts, want_c)
            _same(f"{tag} keys", res.keys, want_k)
            _same(f"{tag} values", res.vals, want_v)
        hits = store.stats.scan_hits - h0
        check(hits > 0, f"RANGE limit {limit}: the scan-anchor cache never hit")
        out.append(f"limit {limit}: anchor hits {hits}/{store.stats.scan_probes - p0}")
    say(
        "range",
        f"{2 * len(limits)} waves x {wave} match the oracle; " + "; ".join(out),
    )


def phase_peak(counts: dict, devices) -> None:
    peaks = ", ".join(
        f"{d.id}: {_gib(_mem(d, 'peak_bytes_in_use'))}" for d in devices
    )
    say(
        "peak",
        f"peak device bytes in use {peaks}; compile cache: "
        f"{counts['read']} programs read, {counts['written']} written",
    )


def phase_mesh(n_chips: int, keys_per_chip: int, get_wave: int,
               range_wave: int, limits, seed: int) -> None:
    """The range tier's mesh waves: one shard per chip, GET and RANGE
    through shard_map, every answer against the global oracle."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs.dpastore_service import CONFIG
    from repro.core import DPAStore, TreeConfig, pla
    from repro.core.keys import join_u64, split_u64
    from repro.distributed import kvshard, rangeshard

    devs = jax.devices()[:n_chips]
    check(len(devs) == n_chips, f"{len(devs)} devices, need {n_chips}")
    t0 = time.perf_counter()
    keys, vals = make_data(n_chips * keys_per_chip, seed)
    oracle = Oracle(keys, vals)
    bounds = pla.fit_boundaries(keys, n_chips)
    owner = np.searchsorted(bounds, keys, side="right")
    cfg = TreeConfig(eps_inner=CONFIG.eps_inner, eps_leaf=CONFIG.eps_leaf)
    stores = []
    for s, dev in enumerate(devs):
        with jax.default_device(dev):  # shard s's pools live on chip s
            stores.append(
                DPAStore(
                    keys[owner == s], vals[owner == s], cfg,
                    cache_cfg=None, scan_cache_cfg=None,
                )
            )
    check(
        all(st.tree.hbm_keys.devices() == {d} for st, d in zip(stores, devs)),
        "a shard's pools are not on its own chip",
    )
    mesh = Mesh(np.array(devs), ("data",))
    tree, ib, depth = kvshard.stack_shards(stores, mesh=mesh)
    jax.block_until_ready((tree, ib))
    t_load = time.perf_counter() - t0
    placed = {d for leaf in jax.tree.leaves((tree, ib)) for d in leaf.devices()}
    check(placed == set(devs), "stacked state is not spread over every chip")
    in_use = ", ".join(f"{d.id}: {_gib(_mem(d, 'bytes_in_use'))}" for d in devs)
    say(
        "mesh-load",
        f"{n_chips} shards x {keys_per_chip} keys (depth {depth}) built and "
        f"placed one shard per chip in {t_load:.1f}s; bytes in use per "
        f"device {in_use}",
    )

    rows = NamedSharding(mesh, P("data"))
    rng = np.random.default_rng(seed + 6)

    def limbs(q):
        l = split_u64(q)
        return (
            jax.device_put(jnp.asarray(l[..., 0]), rows),
            jax.device_put(jnp.asarray(l[..., 1]), rows),
        )

    half = n_chips * get_wave // 2
    q = np.concatenate(
        [keys[rng.integers(0, keys.size, half)], _absent(oracle, half, rng)]
    )
    rng.shuffle(q)
    q = q.reshape(n_chips, get_wave)
    get_fn = jax.jit(
        kvshard.serve_wave_sharded(
            mesh, tree, ib, cap=get_wave, depth=depth,
            eps_inner=cfg.eps_inner, eps_leaf=cfg.eps_leaf,
            route_fn=rangeshard.make_route_fn(bounds),
        )
    )
    vhi, vlo, found, ok = get_fn(tree, ib, *limbs(q))
    check(bool(np.asarray(ok).all()), "mesh GET: a request overflowed its bucket")
    want_v, want_f = oracle.get(q)
    _same("mesh GET found", np.asarray(found), want_f)
    got_v = join_u64(np.stack([np.asarray(vhi), np.asarray(vlo)], axis=-1))
    _same("mesh GET values", np.where(want_f, got_v, 0), want_v)

    starts = keys[rng.integers(0, keys.size, n_chips * range_wave)]
    starts[::5] = _absent(oracle, starts[::5].size, rng)
    starts = starts.reshape(n_chips, range_wave)
    rounds = []
    for limit in limits:
        range_fn = jax.jit(
            rangeshard.range_wave_sharded(
                mesh, tree, ib, bounds, cap=range_wave * MESH_FANOUT,
                depth=depth, eps_inner=cfg.eps_inner, limit=limit,
                fanout=MESH_FANOUT,
            )
        )
        kh, kl, vh, vl, valid, ok, trunc, rnd = range_fn(tree, ib, *limbs(starts))
        check(bool(np.asarray(ok).all()), f"mesh RANGE limit {limit}: overflow")
        check(not np.asarray(trunc).any(), f"mesh RANGE limit {limit}: truncated rows")
        want_k, want_v, want_c = oracle.range(starts.reshape(-1), limit)
        valid = np.asarray(valid).reshape(-1, limit)
        got_k = join_u64(np.stack([np.asarray(kh), np.asarray(kl)], -1)).reshape(-1, limit)
        got_v = join_u64(np.stack([np.asarray(vh), np.asarray(vl)], -1)).reshape(-1, limit)
        _same(f"mesh RANGE limit {limit} counts", valid.sum(axis=1), want_c)
        _same(f"mesh RANGE limit {limit} keys", np.where(valid, got_k, 0), want_k)
        _same(f"mesh RANGE limit {limit} values", np.where(valid, got_v, 0), want_v)
        rounds.append(f"limit {limit}: {int(np.asarray(rnd).max())} round(s)")
    in_use = ", ".join(f"{d.id}: {_gib(_mem(d, 'bytes_in_use'))}" for d in devs)
    peaks = [_mem(d, "peak_bytes_in_use") for d in devs]
    if None not in peaks:  # one chip's peak far above the rest = it held
        # (even briefly) other shards' state
        check(max(peaks) < 1.5 * min(peaks), f"uneven device peaks {peaks}")
    say(
        "mesh",
        f"GET {n_chips}x{get_wave} and RANGE {n_chips}x{range_wave} "
        f"({'; '.join(rounds)}) on a {n_chips}-device mesh match the "
        f"oracle; bytes in use per device {in_use}",
    )


# ---------------------------------------------------------------------------


def _import_repo() -> None:
    """Put this checkout's ``src/`` first on the path and make sure the
    ``repro`` package really comes from it."""
    sys.path.insert(0, str(SRC))
    import repro

    check(
        Path(repro.__file__).resolve().is_relative_to(SRC),
        f"repro imported from {repro.__file__}, not from {SRC}",
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        _import_repo()
        info = phase_device("tpu", min_count=args.chips)
        counts = phase_cache()
        import jax

        if args.chips == 4:
            phase_mesh(4, MESH_KEYS_PER_CHIP, MESH_GET_WAVE, MESH_RANGE_WAVE,
                       LIMITS, args.seed)
            phase_peak(counts, jax.devices()[:4])
        else:
            pipe, store, oracle = phase_load(N_KEYS, args.seed)
            phase_get(pipe, store, oracle, WAVE, GET_WAVES, args.seed)
            phase_write(pipe, store, oracle, WRITE_WAVE, args.seed)
            phase_range(pipe, store, oracle, WAVE, LIMITS, args.seed)
            phase_peak(counts, jax.devices()[:1])
    except Exception as e:  # every failure exits non-zero, with no result
        if not isinstance(e, SmokeFailure):
            traceback.print_exc()
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
