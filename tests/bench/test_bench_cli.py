"""The harness refuses to run without a TPU or without the program."""

import os
import shutil
import subprocess
import sys

from bench_support import ROOT


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sparse-50m.ycsb-c", "--seed",
         "2147483700", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_exits_nonzero_on_cpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "need 1 TPU chip" in p.stderr


def test_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
