"""``bench/run.py`` measures TPUs only: on the CPU, and in a directory that
holds only the benchmark's own files, it exits non-zero and prints no
result."""

import os
import shutil
import subprocess
import sys

from bench import cells


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2.8l.s2048.m1",
         "--seed", str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(p):
    assert p.returncode != 0
    assert "{" not in p.stdout and "metrics" not in p.stdout


def test_cpu_is_refused():
    p = _run(cells.ROOT)
    _no_result(p)
    assert "not a TPU" in p.stderr


def test_benchmark_files_alone_are_refused(tmp_path):
    bm = cells.load_benchmark()
    shutil.copy(cells.ROOT / "BENCHMARK.json", tmp_path)
    for d in bm["paths"]:
        shutil.copytree(cells.ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_run(tmp_path))
