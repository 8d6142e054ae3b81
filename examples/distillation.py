"""Knowledge distillation, the Maestro way (paper §3.1/§4.2).

    PYTHONPATH=src python examples/distillation.py

* the two-stage planner sizes the teacher section (Stage 2: minimal GPUs
  that fully overlap the student);
* teacher and student run DISAGGREGATED on disjoint (virtual) device
  meshes with fan-out (DP^t × fanout = DP^s);
* only *hidden states* cross the section boundary (the teacher's output
  layer is colocated with the student; KL computed by the chunked-vocab
  kernel without materializing teacher logits).
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_reduced
from repro.core.graph import build_distill_graph
from repro.core.planner import plan
from repro.core.types import ParallelConfig
from repro.data.synthetic import lm_batches
from repro.distill.workload import DistillRuntime


def main():
    # ---- plan the real thing (paper-scale, analytic) --------------------
    g = build_distill_graph(get_config("mixtral-8x22b"),
                            get_config("moonshot-v1-16b-a3b"))
    p = plan(g, critical_gpus=512, seq_len=8192, global_batch=512)
    print("== two-stage plan (mixtral-8x22b -> moonshot, 512 chips) ==")
    print(p.summary())
    print()

    # ---- run a reduced version for real on 8 virtual devices ------------
    t_cfg = get_reduced("qwen2.5-32b").replace(dtype="float32",
                                               vocab_size=2048)
    s_cfg = get_reduced("qwen1.5-0.5b").replace(dtype="float32",
                                                vocab_size=2048)
    rt = DistillRuntime(t_cfg, s_cfg,
                        teacher_parallel=ParallelConfig(dp=2, tp=2),
                        student_parallel=ParallelConfig(dp=4, tp=1),
                        impl="ref", alpha=0.5, temperature=2.0, lr=2e-3)
    print(f"== disaggregated runtime: teacher mesh (2x2), student mesh "
          f"(4x1), fanout={rt.fanout} ==")
    params_t, params_s, opt = rt.init(jax.random.PRNGKey(0))
    w_t = rt.teacher_unembed(params_t)
    data = lm_batches(batch=8, seq_len=32, vocab=2048, seed=0)
    kls, ces = [], []
    for i in range(30):
        params_s, opt, m = rt.train_iteration(params_t, params_s, opt,
                                              next(data), i, w_t=w_t)
        kls.append(float(m["kl"]))
        ces.append(float(m["ce"]))
        if i % 10 == 0:
            print(f"iter {i:3d}: ce={ces[-1]:.4f} kl={kls[-1]:.4f}")
    print(f"ce {ces[0]:.3f} -> {ces[-1]:.3f}; kl {kls[0]:.4f} -> "
          f"{kls[-1]:.4f}")
    print("cross-section traffic:", rt.rt.queue.stats())
    assert ces[-1] < ces[0], "student did not learn"
    rt.shutdown()
    print("distillation example OK")


if __name__ == "__main__":
    main()
