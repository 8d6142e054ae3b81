"""A run's phases at a tiny size on the CPU: set-up, the checked steps,
the window and the comparison with the plain reference, for each path;
and the control, the reference in scaled fp8 in the program's place,
which must come out not correct."""
import jax
import pytest

from benchmarks.chip import harness
from benchmarks.chip.tests.fixtures import run_cell, small  # noqa: F401

SEED = 2**31 + 54321


#: limits for the clean run at the tiny size: bf16 round-off averages over
#: far fewer elements at widths of 32-64 than at the cells' widths, so
#: the readings there are up to ten times the cells' (which set the cells'
#: limits); the control and the faults below are held to the cells' limits
TINY_LIMITS = {"first_loss_gap": 2e-3, "grad_gap": 0.1, "grad_diff": 0.06,
               "update_gap": 0.02}


@pytest.mark.parametrize("which", ["mllm", "distill"])
def test_cell_runs_and_is_correct(small, run_cell, monkeypatch, which):
    name, _, _ = small(which)
    monkeypatch.setattr(harness, "limits", lambda name: TINY_LIMITS)
    r = run_cell(name)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"tokens_per_s", "step_s_p90", "setup_s"}
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("which", ["mllm", "distill"])
def test_control_is_not_correct(small, which):
    name, c, t = small(which)
    path = harness.path_module(t["path"])
    job = path.Job(c, t, SEED, jax.devices()[:1], harness.Spans())
    prog = harness.first_steps(job, harness.Spans())
    job.free()
    ref = path.reference(c, t, SEED)
    control = path.reference(c, t, SEED, precision="fp8")
    assert not harness.passed(harness.compare(control, ref,
                                              harness.limits(name)))


