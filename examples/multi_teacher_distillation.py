"""Multi-teacher distillation, declared — the walkthrough workload for
the section-centric API (docs/workloads.md).

    PYTHONPATH=src python examples/multi_teacher_distillation.py

A generalist teacher sees every sample; a specialist teacher activates
only on samples whose ``domain`` flag routes to it.  The whole workload
is ONE declaration (``repro.distill.multi_teacher.multi_teacher_spec``,
~60 lines: three SectionSpecs + two typed ports) run by the generic
``repro.core.workload.CompoundRuntime`` — no bespoke runtime class.  The
wavefront scheduler groups specialist samples into fewer microbatches,
and all-generalist microbatches never touch the specialist's mesh."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import numpy as np

from repro.configs import get_reduced
from repro.core import workload as wl
from repro.core.types import ParallelConfig
from repro.data.synthetic import routed_lm_batches
from repro.distill.multi_teacher import multi_teacher_spec, teacher_unembed


def main():
    B, S, MBS = 16, 32, 4
    ta_cfg = get_reduced("qwen2.5-32b").replace(dtype="float32",
                                                vocab_size=1024)
    tb_cfg = get_reduced("granite-3-8b").replace(
        dtype="float32", vocab_size=1024, d_model=64, head_dim=16,
        d_ff=128)
    s_cfg = get_reduced("qwen1.5-0.5b").replace(dtype="float32",
                                                vocab_size=1024)
    spec = multi_teacher_spec(
        ta_cfg, tb_cfg, s_cfg,
        ta_parallel=ParallelConfig(dp=2),
        tb_parallel=ParallelConfig(dp=2),
        s_parallel=ParallelConfig(dp=4),
        global_batch=B, seq_len=S, mbs=MBS, impl="ref")
    rt = wl.CompoundRuntime(spec, impl="ref")
    print("== multi-teacher distillation: generalist (dp=2) + routed "
          "specialist (dp=2) -> student (dp=4) ==")
    params, opts = rt.init(jax.random.PRNGKey(0))
    smesh = rt.rt.mesh("student")
    consts = {"student": {
        "w_a": teacher_unembed(params["teacher_a"], ta_cfg, smesh),
        "w_b": teacher_unembed(params["teacher_b"], tb_cfg, smesh)}}
    data = routed_lm_batches(batch=B, seq_len=S, vocab=1024,
                             specialist_ratio=0.3, seed=0)
    ces, kbs = [], []
    for i in range(25):
        params, opts, m = rt.train_iteration(params, opts, next(data), i,
                                             consts=consts)
        ces.append(float(m["ce"]))
        kbs.append(float(m["kl_b"]))
        if i % 8 == 0:
            n_spec = len(m["plan"].activation["teacher_b"].active_mbs)
            print(f"iter {i:3d}: ce={ces[-1]:.4f} kl_a={float(m['kl_a']):.4f} "
                  f"kl_b={kbs[-1]:.4f} specialist-mbs={n_spec}/{rt.n_mb} "
                  f"student-util={m['execution'].utilization('student'):.3f}")
    print(f"ce {ces[0]:.3f} -> {ces[-1]:.3f}")
    print("cross-section traffic:", rt.rt.queue.stats())
    assert ces[-1] < ces[0], "student did not learn"
    rt.shutdown()
    print("multi_teacher_distillation example OK")


if __name__ == "__main__":
    main()
