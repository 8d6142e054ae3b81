"""Multi-device behaviour via subprocess drivers (each sets
xla_force_host_platform_device_count before importing jax — the main test
process must keep seeing 1 device)."""
import os
import subprocess
import sys
from pathlib import Path


DRIVERS = Path(__file__).parent / "drivers"


def _run(name, timeout=560):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    src = str(Path(__file__).resolve().parents[1] / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, str(DRIVERS / name)],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    assert "DRIVER_OK" in proc.stdout, proc.stdout[-2000:]


def test_message_queue_m_to_n():
    _run("driver_messages.py")


def test_disaggregated_distill_runtime():
    _run("driver_distill_runtime.py")


def test_disaggregated_mllm_runtime():
    _run("driver_mllm_runtime.py")


def test_pipeline_and_context_parallelism():
    _run("driver_pipeline_cp.py")


def test_elastic_restore_and_tiny_dryrun():
    _run("driver_elastic_dryrun.py")


def test_gradient_compression_8way():
    _run("driver_compression.py")


def test_chip_smoke_four_chip_path():
    _run("driver_chip_smoke.py")
