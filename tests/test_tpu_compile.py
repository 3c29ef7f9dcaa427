"""Ahead-of-time compiles of the served wave programs for a TPU v5e.

The TPU compiler ships with jaxlib, so a v5e can be described without a
chip attached and the served programs compiled for it at the paper's
deployment (``configs/dpastore_service.py``: 50M keys, waves of 65536,
eps 4/8, depth 3).  What the chip's compiler refuses — an unsupported
gather, a block shape, a program that does not fit the device — fails here
at no chip time.  Nothing runs: these tests say nothing about results or
times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports every test
file.  The Pallas kernels are off the served path and do not lower yet;
their cases are strict xfails, so the day one lowers the test says so.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs.dpastore_service import CONFIG
from repro.core import hotcache, lookup, tree as treemod
from repro.core.lookup import InsertBuffers
from repro.core.tree import NODE_SEGS, SEG_CAP, DeviceTree, TreeConfig

HBM_BYTES = 16 * 2**30  # TPU v5e: 16 GiB of HBM per chip
# a bulk load of 50M ``sparse`` keys at eps_leaf 8 fills 420,613 leaves
# (118.9 keys per 128-key leaf); the pools below follow build_image's sizing
KEYS_PER_LEAF = CONFIG.n_keys / 420_613


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def store_shapes(sharding, n_keys: int, lead=()):
    """ShapeDtypeStructs of one store's device pools + insert buffers as
    ``build_image`` sizes them for ``n_keys`` keys (``lead`` prepends a
    shard dim)."""
    cfg = TreeConfig()
    live = int(np.ceil(n_keys / KEYS_PER_LEAF))
    segs = live // SEG_CAP + 1
    leaves = treemod._round_pool(live, cfg.growth, minimum=64)
    nodes = treemod._round_pool(
        segs // NODE_SEGS + 1, cfg.growth, minimum=max(32, leaves // 32)
    )
    pivots = treemod._round_pool(segs, cfg.growth, minimum=max(64, leaves // 8))

    def s(shape, dt):
        return jax.ShapeDtypeStruct(lead + shape, dt, sharding=sharding)

    u32, i32, f32 = jnp.uint32, jnp.int32, jnp.float32
    tree = DeviceTree(
        root=s((), i32),
        node_seg_first=s((nodes, NODE_SEGS, 2), u32),
        node_seg_slope=s((nodes, NODE_SEGS), f32),
        node_seg_count=s((nodes, NODE_SEGS), i32),
        node_seg_slot=s((nodes, NODE_SEGS), i32),
        pivot_keys=s((pivots, SEG_CAP, 2), u32),
        pivot_child=s((pivots, SEG_CAP), i32),
        leaf_anchor=s((leaves, 2), u32),
        leaf_slope=s((leaves,), f32),
        leaf_count=s((leaves,), i32),
        leaf_slot=s((leaves,), i32),
        leaf_next=s((leaves,), i32),
        hbm_keys=s((leaves, SEG_CAP, 2), u32),
        hbm_vals=s((leaves, SEG_CAP, 2), u32),
    )
    ib = InsertBuffers(
        keys=s((leaves, cfg.ib_cap, 2), u32),
        vals=s((leaves, cfg.ib_cap, 2), u32),
        op=s((leaves, cfg.ib_cap), i32),
        count=s((leaves,), i32),
    )
    return tree, ib


def _fits(compiled, limit=HBM_BYTES):
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
    assert total < limit, f"{total / 2**30:.2f} GiB does not fit a v5e chip"
    return m


def test_get_batch_compiles_for_v5e_at_50m_keys(one_chip):
    tree, ib = store_shapes(one_chip, CONFIG.n_keys)
    k = jax.ShapeDtypeStruct((CONFIG.wave_size,), jnp.uint32, sharding=one_chip)
    compiled = lookup.get_batch.lower(
        tree, ib, k, k,
        depth=CONFIG.depth, eps_inner=CONFIG.eps_inner, eps_leaf=CONFIG.eps_leaf,
    ).compile()
    m = _fits(compiled)
    assert m.argument_size_in_bytes > 4 * 10**9, "pools are not 50M-key sized"


def test_range_batch_loop_compiles_for_v5e_at_50m_keys(one_chip):
    tree, ib = store_shapes(one_chip, CONFIG.n_keys)
    w = CONFIG.wave_size
    k = jax.ShapeDtypeStruct((w,), jnp.uint32, sharding=one_chip)
    start = jax.ShapeDtypeStruct((w,), jnp.int32, sharding=one_chip)
    compiled = lookup.range_batch_loop.lower(
        tree, ib, start, k, k, k, k, limit=10, max_leaves=4
    ).compile()
    _fits(compiled)


def test_hot_cache_probe_compiles_for_v5e(one_chip):
    """The paper's cache geometry (176 threads x 96 entries).  Compiled at
    an eighth of the served wave: its compile time grows with the wave,
    and the GET wave's width is already covered by get_batch above."""
    cfg = hotcache.CacheConfig()
    cache = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: hotcache.make_cache(cfg)),
    )
    w = CONFIG.wave_size // 8
    k = jax.ShapeDtypeStruct((w,), jnp.uint32, sharding=one_chip)
    tid = jax.ShapeDtypeStruct((w,), jnp.int32, sharding=one_chip)
    _fits(hotcache.probe.lower(cache, tid, k, k, cfg=cfg).compile())


def test_range_wave_sharded_compiles_for_v5e_2x2(topo):
    """The range tier's RANGE wave on a 4-chip mesh, one shard per chip at
    25M keys (``chip_smoke.py --chips 4``'s state): compiles, keeps both
    all_to_all exchanges, and fits each chip.  One leaf per round and a
    small wave keep the compile short; the sharding and the collectives
    are the same at the served widths, which ``chip_smoke.py --chips 4``
    runs on the chips."""
    from repro.distributed import rangeshard

    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    rows = NamedSharding(mesh, P("data"))
    n_shards, per_shard = 4, CONFIG.n_keys // 2
    tree, ib = store_shapes(rows, per_shard, lead=(n_shards,))
    bounds = (np.arange(1, n_shards, dtype=np.uint64) << np.uint64(62))
    fn = rangeshard.range_wave_sharded(
        mesh, tree, ib, bounds, cap=512, depth=CONFIG.depth,
        eps_inner=CONFIG.eps_inner, limit=10, max_leaves=1, fanout=2,
    )
    k = jax.ShapeDtypeStruct((n_shards, 256), jnp.uint32, sharding=rows)
    compiled = jax.jit(fn).lower(tree, ib, k, k).compile()
    _fits(compiled)
    assert "all-to-all" in compiled.as_text()


# ---------------------------------------------------------------------------
# the Pallas kernels: off the served path, and refused by Mosaic today
# ---------------------------------------------------------------------------


@pytest.mark.xfail(
    strict=True, raises=NotImplementedError,
    reason="Mosaic: only 2D gathers are supported",
)
def test_get_pallas_lowers_for_v5e(one_chip):
    from repro.kernels.traverse import get_pallas

    tree, ib = store_shapes(one_chip, 200_000)
    k = jax.ShapeDtypeStruct((1024,), jnp.uint32, sharding=one_chip)
    fn = jax.jit(
        lambda t, b, h, l: get_pallas(
            t, b, h, l, depth=3, eps_inner=4, eps_leaf=8, interpret=False
        )
    )
    fn.lower(tree, ib, k, k).compile()


@pytest.mark.xfail(
    strict=True, raises=ValueError,
    reason="Mosaic: a rank-1 block of 64 is not a multiple of 128; at 128, "
    "scalar stores to VMEM",
)
@pytest.mark.parametrize("block_requests", [64, 128])
def test_range_pallas_lowers_for_v5e(one_chip, block_requests):
    from repro.kernels.range_scan import range_pallas

    tree, _ = store_shapes(one_chip, 200_000)
    k = jax.ShapeDtypeStruct((1024,), jnp.uint32, sharding=one_chip)
    start = jax.ShapeDtypeStruct((1024,), jnp.int32, sharding=one_chip)
    fn = jax.jit(
        lambda t, s, h, l: range_pallas(
            t, s, h, l, limit=10, block_requests=block_requests, interpret=False
        )
    )
    fn.lower(tree, start, k, k).compile()


@pytest.mark.xfail(
    strict=True, raises=ValueError,
    reason="Mosaic: shape mismatch in the bucket gather",
)
def test_cache_probe_pallas_lowers_for_v5e(one_chip):
    from repro.kernels.cache_probe import probe_pallas

    cfg = hotcache.CacheConfig()
    cache = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: hotcache.make_cache(cfg)),
    )
    k = jax.ShapeDtypeStruct((1024,), jnp.uint32, sharding=one_chip)
    tid = jax.ShapeDtypeStruct((1024,), jnp.int32, sharding=one_chip)
    fn = jax.jit(lambda c, t, h, l: probe_pallas(c, t, h, l, cfg=cfg, interpret=False))
    fn.lower(cache, tid, k, k).compile()
