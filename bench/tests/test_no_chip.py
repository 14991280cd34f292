"""The measurement path refuses a host without a TPU, and a checkout that
holds only the benchmark: it exits non-zero and prints no result."""
import json
import os
import shutil
import subprocess
import sys

from bench import registry

ROOT = registry.ROOT
CELL = registry.load_benchmark()["workloads"][0]["name"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed",
         str(2 ** 33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return True


def test_cpu_host_exits_nonzero_without_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero_without_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".*"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)
