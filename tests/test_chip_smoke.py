"""``chip_smoke.py`` on the CPU at a small size.

The script's phases are the chip run's main path (50M keys, waves of 65536);
here they run at ~20k keys against the same numpy oracle, so a broken
phase or a dead check shows up without a chip.  ``main()`` itself must
refuse the CPU: it exits non-zero and prints no result line.
"""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _phases(out: str):
    return [line.split("]")[0].lstrip("[") for line in out.splitlines()]


def test_chip_smoke_phases_match_oracle_on_cpu(smoke, capsys):
    pipe, store, oracle = smoke.phase_load(20_000, seed=3)
    smoke.phase_get(pipe, store, oracle, wave=1024, n_waves=2, seed=3)
    smoke.phase_write(pipe, store, oracle, n_writes=256, seed=3)
    smoke.phase_range(pipe, store, oracle, wave=256, limits=(10, 100), seed=3)
    smoke.phase_peak({"read": 0, "written": 0}, jax.devices()[:1])
    assert _phases(capsys.readouterr().out) == ["load", "get", "write", "range", "peak"]
    # the oracle tracked every acknowledged write: the store agrees on all
    k, v = store.items()
    np.testing.assert_array_equal(k, oracle.keys)
    np.testing.assert_array_equal(v, oracle.vals)


def test_chip_smoke_mesh_phase_on_one_cpu_device(smoke, capsys):
    smoke.phase_mesh(1, 5000, get_wave=512, range_wave=128, limits=(10, 100), seed=5)
    assert _phases(capsys.readouterr().out) == ["mesh-load", "mesh"]


def test_chip_smoke_checks_catch_a_wrong_answer(smoke):
    pipe, store, oracle = smoke.phase_load(5000, seed=4)
    oracle.vals[oracle.keys.size // 2] ^= np.uint64(1)  # one stale value
    with pytest.raises(smoke.SmokeFailure, match="differ from the oracle"):
        smoke.phase_range(pipe, store, oracle, wave=4096, limits=(100,), seed=4)


def test_chip_smoke_oracle_tracks_writes(smoke):
    rng = np.random.default_rng(9)
    keys = np.unique(rng.integers(0, 2**40, 300, dtype=np.uint64))
    oracle = smoke.Oracle(keys, keys + np.uint64(1))
    ref = dict(zip(keys.tolist(), (keys + np.uint64(1)).tolist()))
    upd = keys[::3]
    new = np.array([2**41 + 5, 7, 2**41 + 1], dtype=np.uint64)
    oracle.put(np.concatenate([upd, new]), np.arange(upd.size + 3, dtype=np.uint64))
    ref.update(zip(np.concatenate([upd, new]).tolist(), range(upd.size + 3)))
    oracle.delete(keys[1::5])
    for k in keys[1::5].tolist():
        ref.pop(k)
    assert oracle.keys.tolist() == sorted(ref)
    assert oracle.vals.tolist() == [ref[k] for k in sorted(ref)]
    got_k, got_v, counts = oracle.range(np.array([0, 2**41 + 2], dtype=np.uint64), 4)
    assert counts.tolist() == [4, 1]
    assert got_k[1].tolist() == [2**41 + 5, 0, 0, 0]
    assert got_v[0].tolist() == [ref[k] for k in sorted(ref)[:4]]


def test_chip_smoke_main_fails_without_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "not tpu" in captured.err


_PLACEMENT_SCRIPT = r"""
import jax, numpy as np
from jax.sharding import Mesh
from repro.core import DPAStore
from repro.core.datasets import sparse
from repro.distributed import kvshard

devs = jax.devices()[:4]
keys = sparse(4000, seed=1)
stores = []
for s, d in enumerate(devs):
    with jax.default_device(d):
        stores.append(DPAStore(keys[s::4], keys[s::4], cache_cfg=None, scan_cache_cfg=None))
mesh = Mesh(np.array(devs), ("data",))
with jax.transfer_guard_device_to_device("disallow"):
    tree, ib, _ = kvshard.stack_shards(stores, mesh=mesh)
for s, d in enumerate(devs):
    blk = [x for x in tree.hbm_keys.addressable_shards if x.device == d][0]
    assert np.array_equal(np.asarray(blk.data)[0, : stores[s].tree.hbm_keys.shape[0]],
                          np.asarray(stores[s].tree.hbm_keys))
print("OK")
"""


def test_stack_shards_builds_each_block_on_its_own_device():
    """``chip_smoke.py --chips 4`` builds shard s under ``jax.default_device``
    of chip s; stacking must pad each shard on that chip.  Padding an
    uncommitted array on the default device made chip 0 hold every shard
    for a moment (a 15 GiB peak on a v5e); the transfer guard catches any
    such device-to-device copy.  Four CPU devices, in a fresh process."""
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=str(ROOT / "src"),
    )
    proc = subprocess.run(
        [sys.executable, "-c", _PLACEMENT_SCRIPT],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    """``$JAX_COMPILATION_CACHE_DIR`` when set (and no other directory set
    in code), else ``<checkout>/.jax_cache``."""
    from repro.launch import compile_cache

    saved = {
        k: getattr(jax.config, k)
        for k in ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs")
    }
    if env_dir is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        want = str(ROOT / ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv(compile_cache.ENV_VAR, want)
    try:
        assert compile_cache.enable_compile_cache() == want
        set_dir = jax.config.jax_compilation_cache_dir
        assert set_dir == (want if env_dir is None else saved["jax_compilation_cache_dir"])
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
