"""Figure 16 (repo extension): sharded RANGE throughput vs shard count.

The paper's 13 MOPS RANGE figure is single-NIC; this sweep shows what the
distributed tier does to it.  For each (partition, n_shards, scan length)
cell we RUN the scatter-gather (range tier) or broadcast (hash tier) path on
the CPU store — correctness plus the *measured* fan-out feed the model — and
``derived`` pushes the per-shard BlueField-3 RANGE model through the scaling
law of the tier:

  * range tier: each request costs ``fanout`` shard-scans, so aggregate
    throughput is ``n_shards / fanout`` times one shard's model MOPS (the
    measured fan-out is ~1 for scans that fit the owner's slice);
  * hash tier: every shard scans every request (broadcast), so aggregate
    RANGE throughput never exceeds ONE shard's — flat in n_shards.  That gap
    is the reason the range-partitioned tier exists.

A third leg, ``fig16/mesh_cpu/...``, runs the same scatter-gather RANGE
wave on a multi-device mesh of CPU devices: a subprocess with
``JAX_PLATFORMS=cpu`` forces XLA's host platform to expose 4 devices (the
kv_dryrun trick) and times the ``rangeshard.range_wave_sharded`` shard_map
program end to end.  It always runs on the CPU — on a chip machine the
parent process already holds the chip — so its times are CPU wall clock,
not device numbers; ``chip_smoke.py --chips 4`` runs the same wave on four
chips.
"""

import json
import os
import subprocess
import sys

import numpy as np

from repro.core import perfmodel
from repro.core.datasets import load
from repro.distributed.kvshard import ShardedDPAStore

from . import common
from .common import emit, time_op, wave

SHARDS = (2, 4, 8)
SHARDS_SMOKE = (2, 4)
LIMITS = (10, 100)
WAVE = 1024
MESH_SHARDS = 4

# runs in a fresh interpreter (env set before jax imports): CPU devices only
_MESH_SCRIPT = r"""
import json, sys, time
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core.datasets import sparse
from repro.core.keys import split_u64
from repro.distributed import kvshard, rangeshard

n, W, limit, max_leaves, repeats = (int(a) for a in sys.argv[1:6])
n_shards = 4
keys = sparse(n, seed=16)
sharded = kvshard.ShardedDPAStore(
    keys, keys ^ np.uint64(0xE), n_shards, cache_cfg=None, partition="range"
)
tree, ib, depth = sharded.stacked()
b = sharded.boundaries
mesh = Mesh(np.array(jax.devices()[:n_shards]), ("data",))
rng = np.random.default_rng(0)
qs = rng.choice(keys, (n_shards, W))
limbs = split_u64(qs)
khi, klo = jnp.asarray(limbs[..., 0]), jnp.asarray(limbs[..., 1])
rfn = rangeshard.range_wave_sharded(
    mesh, tree, ib, b, cap=n_shards * W, depth=depth,
    eps_inner=4, limit=limit, max_leaves=max_leaves,
)
out = rfn(tree, ib, khi, klo)  # pays the compile before the timed loop
jax.block_until_ready(out)
t0 = time.perf_counter()
for _ in range(repeats):
    out = rfn(tree, ib, khi, klo)
    jax.block_until_ready(out)
dt = time.perf_counter() - t0
print(json.dumps({
    "measured_mops": n_shards * W * repeats / dt / 1e6,
    "wave_us": dt / repeats * 1e6,
    "rounds": int(np.asarray(out[7]).max()),
    "truncated": int(np.asarray(out[6]).sum()),
    "depth": depth,
    "n_devices": jax.device_count(),
}))
"""


def _run_mesh_leg():
    """Time the scatter-gather RANGE wave on a forced 4-device CPU mesh
    and emit measured-vs-roofline cells; errors surface as a module failure
    (the harness keeps sweeping, the smoke gate records it)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    ).strip()
    n = 4000 if common.SMOKE else 20000
    w = 256 if common.SMOKE else 1024
    repeats = 2 if common.SMOKE else 4
    limits = (10,) if common.SMOKE else LIMITS
    for limit in limits:
        max_leaves = max(4, limit // 16)
        proc = subprocess.run(
            [
                sys.executable,
                "-c",
                _MESH_SCRIPT,
                str(n),
                str(w // MESH_SHARDS),
                str(limit),
                str(max_leaves),
                str(repeats),
            ],
            capture_output=True,
            text=True,
            timeout=600,
            env=env,
        )
        if proc.returncode != 0:
            raise RuntimeError(f"mesh leg failed:\n{proc.stderr[-2000:]}")
        res = json.loads(proc.stdout.strip().splitlines()[-1])
        assert res["n_devices"] >= MESH_SHARDS, res
        roof = perfmodel.range_mops(res["depth"], limit=limit) * MESH_SHARDS
        emit(
            f"fig16/mesh_cpu/shards{MESH_SHARDS}/limit{limit}",
            res["wave_us"] / w,
            f"measured_mops={res['measured_mops']:.3e};"
            f"model_mops={roof:.1f};"
            f"mops_vs_roofline={res['measured_mops'] / roof:.2e};"
            f"rounds_in_mesh={res['rounds']};reissues=0;"
            f"devices={res['n_devices']}",
        )


def run():
    rng = np.random.default_rng(16)
    n = common.n_keys()
    w = wave(WAVE)
    keys = load("sparse", n, seed=9)
    vals = keys ^ np.uint64(0x5EED)
    shard_counts = SHARDS_SMOKE if common.SMOKE else SHARDS
    for n_shards in shard_counts:
        for part in ("range", "hash"):
            store = ShardedDPAStore(
                keys, vals, n_shards, cache_cfg=None, partition=part
            )
            depth = max(sh.depth for sh in store.shards)
            for limit in LIMITS:
                q = rng.choice(keys, w)
                # max_leaves sized so the bounded per-shard scan covers the
                # scan length (SEG_CAP=128-wide leaves)
                max_leaves = max(4, limit // 16)
                r0, s0 = store.range_requests, store.range_subqueries
                m0, i0 = store.range_rounds_in_mesh, store.range_reissues
                t = time_op(
                    store.range, q, limit, max_leaves, repeats=1
                ) / w
                fan = (store.range_subqueries - s0) / max(
                    store.range_requests - r0, 1
                )
                # continuation accounting: rounds the device loop ran
                # in-mesh vs host re-issues that survived (steady state: 0 —
                # the acceptance gate of the in-mesh continuation)
                rounds = store.range_rounds_in_mesh - m0
                reissues = store.range_reissues - i0
                per_shard = perfmodel.range_mops(depth, limit=limit)
                if part == "range":
                    m = per_shard * n_shards / max(fan, 1.0)
                else:  # broadcast: all shards scan -> no scale-out
                    m = per_shard
                emit(
                    f"fig16/{part}/shards{n_shards}/limit{limit}",
                    t * 1e6,
                    f"model_mops={m:.1f};fanout={fan:.2f};depth={depth};"
                    f"rounds_in_mesh={rounds};reissues={reissues}",
                )
    # real-mesh leg: forced 4-device host platform in a subprocess (reissues
    # is 0 by construction there — the shard_map loop has no host path)
    _run_mesh_leg()


if __name__ == "__main__":
    run()
