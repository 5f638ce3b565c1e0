"""``run.py`` refuses to run without a TPU, and in a directory that holds
only BENCHMARK.json and the benchmark's files, and prints no result."""

import shutil
import subprocess
import sys

from chipbench import bench


def _run(cwd, env_extra=None):
    import os
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "mamba2-sg-m4-l2048",
         "--seed", "2147483711", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _run(bench.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(bench.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(bench.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
