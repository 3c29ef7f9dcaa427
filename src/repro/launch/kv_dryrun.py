import os

os.environ["JAX_PLATFORMS"] = "cpu"
_HOSTS = "--xla_force_host_platform_device_count=512"
if _HOSTS not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = f"{os.environ.get('XLA_FLAGS', '')} {_HOSTS}".strip()
# Must precede every other import (jax locks platform and device count at
# first init); CPU only, like launch/dryrun.py.

"""Multi-pod dry-run of the DPA-Store service itself.

Lowers + compiles the shard_map'd request wave (hash routing -> all_to_all
-> local learned-index GET -> all_to_all back) for the production meshes,
sized to the paper's setup (Sec 4.1: 50M keys, here spread over the mesh's
data axis).  This is the distributed form of the paper's UDP steering and
proves the KV service scales over the same fabric as the LM cells.

    PYTHONPATH=src python -m repro.launch.kv_dryrun --mesh both
"""

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.dpastore_service import CONFIG as SVC
from repro.core import lookup
from repro.core.tree import DeviceTree, NODE_SEGS, SEG_CAP
from repro.distributed import kvshard
from repro.launch.mesh import make_production_mesh
from repro.launch.dryrun import RESULTS, _compile_stats, _write


def _abstract_shard_state(n_shards: int, keys_per_shard: int):
    """ShapeDtypeStruct pools for one shard's store, stacked n_shards-wide.
    Pool sizes follow the bulk-load planner's arithmetic for the paper's
    eps=(4,8) configuration (no allocation — dry run)."""
    n_leaves = keys_per_shard // 96 + 2  # ~75% fill at eps_leaf=8
    n_slots = n_leaves
    n_segs = n_leaves // 100 + 2
    n_nodes = n_segs // NODE_SEGS + 2
    cap = lambda n: int(np.ceil(n * 1.5 / 8)) * 8

    def s(shape, dt):
        return jax.ShapeDtypeStruct((n_shards,) + shape, dt)

    tree = DeviceTree(
        root=s((), jnp.int32),
        node_seg_first=s((cap(n_nodes), NODE_SEGS, 2), jnp.uint32),
        node_seg_slope=s((cap(n_nodes), NODE_SEGS), jnp.float32),
        node_seg_count=s((cap(n_nodes), NODE_SEGS), jnp.int32),
        node_seg_slot=s((cap(n_nodes), NODE_SEGS), jnp.int32),
        pivot_keys=s((cap(n_segs), SEG_CAP, 2), jnp.uint32),
        pivot_child=s((cap(n_segs), SEG_CAP), jnp.int32),
        leaf_anchor=s((cap(n_leaves), 2), jnp.uint32),
        leaf_slope=s((cap(n_leaves),), jnp.float32),
        leaf_count=s((cap(n_leaves),), jnp.int32),
        leaf_slot=s((cap(n_leaves),), jnp.int32),
        leaf_next=s((cap(n_leaves),), jnp.int32),
        hbm_keys=s((cap(n_slots), SEG_CAP, 2), jnp.uint32),
        hbm_vals=s((cap(n_slots), SEG_CAP, 2), jnp.uint32),
    )
    ib = lookup.InsertBuffers(
        keys=s((cap(n_leaves), 16, 2), jnp.uint32),
        vals=s((cap(n_leaves), 16, 2), jnp.uint32),
        op=s((cap(n_leaves), 16), jnp.int32),
        count=s((cap(n_leaves),), jnp.int32),
    )
    return tree, ib


def run(multi_pod: bool, out_dir: Path):
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    n_shards = mesh.shape["data"]
    keys_per_shard = SVC.n_keys // n_shards
    wave_local = SVC.wave_size // n_shards
    cap = wave_local  # ample capacity: no overflow in the dry run
    tree, ib = _abstract_shard_state(n_shards, keys_per_shard)
    fn = kvshard.serve_wave_sharded(
        mesh,
        tree,
        ib,
        cap=cap,
        depth=SVC.depth,
        eps_inner=SVC.eps_inner,
        eps_leaf=SVC.eps_leaf,
    )
    req = jax.ShapeDtypeStruct((n_shards, wave_local), jnp.uint32)
    jitted = jax.jit(
        fn,
        in_shardings=(
            jax.tree.map(lambda _: NamedSharding(mesh, P("data")), tree),
            jax.tree.map(lambda _: NamedSharding(mesh, P("data")), ib),
            NamedSharding(mesh, P("data")),
            NamedSharding(mesh, P("data")),
        ),
    )
    rec = {"arch": "dpastore-service", "shape": f"wave{SVC.wave_size}", "mesh": mesh_name, "supported": True}
    t0 = time.time()
    lowered = jitted.lower(tree, ib, req, req)
    rec["lower_s"] = round(time.time() - t0, 1)
    t1 = time.time()
    rec.update(_compile_stats(lowered))
    rec["compile_s"] = round(time.time() - t1, 1)
    rec["status"] = "ok"
    rec["params_total"] = rec["params_active"] = SVC.n_keys * 16
    rec["tokens"] = SVC.wave_size
    cell = f"dpastore-service__wave__{mesh_name}"
    _write(out_dir, cell, rec)
    print(
        f"[kv-dryrun] {cell}: OK lower={rec['lower_s']}s compile={rec['compile_s']}s "
        f"coll/dev={rec['collective_bytes_per_device']/2**20:.1f}MiB "
        f"mem={rec['memory']['temp_bytes']/2**20:.1f}MiB"
    )
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--out", default=str(RESULTS))
    args = ap.parse_args()
    out = Path(args.out)
    if args.mesh in ("single", "both"):
        run(False, out)
    if args.mesh in ("multi", "both"):
        run(True, out)


if __name__ == "__main__":
    main()
