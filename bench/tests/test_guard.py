"""Without a TPU, or outside a checkout of the program, the command exits
non-zero and prints no result."""

import os
import shutil
import subprocess
import sys

from bench import run


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "forge.l2-gemm",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run(run.ROOT, env)
    assert p.returncode != 0 and p.stdout == ""
    assert "not a TPU" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(run.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = _run(tmp_path, dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout == ""
