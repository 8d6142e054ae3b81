"""The command refuses to run, and prints no result, without a TPU."""
import os
import shutil
import subprocess
import sys

from benchmarks.chip import harness


def _run(cwd):
    cell = harness.benchmark()["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", cell,
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_refuses_without_a_tpu():
    p = _run(harness.ROOT)
    assert p.returncode == 2
    assert "no TPU" in p.stderr
    assert p.stdout == ""


def test_refuses_with_only_the_benchmark(tmp_path):
    shutil.copytree(harness.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""
