"""The benchmark's command refuses to run, and prints no result, where
JAX's first device is not a TPU, and where the checkout holds only the
benchmark."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

from tiny_cells import BENCH, ROOT, spec

CELL = spec()["workloads"][0]["name"]


def _run(cwd, root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload",
         CELL, "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _run(ROOT, ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_refuses_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
