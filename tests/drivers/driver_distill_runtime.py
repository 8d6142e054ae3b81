"""Multi-device driver: disaggregated distillation runtime with fanout —
teacher and student sections on disjoint meshes; and numerical equivalence
against a monolithic (single-jit) formulation of the same loss."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_reduced
from repro.core.types import ParallelConfig
from repro.distill.workload import (DistillRuntime, distill_loss,
                                    teacher_hidden)

t_cfg = get_reduced("qwen2.5-32b").replace(dtype="float32", vocab_size=512)
s_cfg = get_reduced("qwen1.5-0.5b").replace(dtype="float32",
                                            vocab_size=512)
rt = DistillRuntime(t_cfg, s_cfg,
                    teacher_parallel=ParallelConfig(dp=2, tp=2),
                    student_parallel=ParallelConfig(dp=4, tp=1),
                    impl="ref", alpha=0.5, temperature=2.0, lr=1e-3)
assert rt.fanout == 2, rt.fanout

params_t, params_s, opt = rt.init(jax.random.PRNGKey(0))
params_s0 = jax.tree_util.tree_map(lambda x: np.asarray(x), params_s)
w_t = rt.teacher_unembed(params_t)
rng = np.random.default_rng(0)
B, S = 8, 32
losses = []
batches = []
for i in range(4):
    batch = {"tokens": jnp.asarray(rng.integers(0, 512, (B, S)), jnp.int32),
             "labels": jnp.asarray(rng.integers(0, 512, (B, S)), jnp.int32),
             "loss_mask": jnp.ones((B, S), jnp.float32)}
    batches.append(batch)
    params_s, opt, m = rt.train_iteration(params_t, params_s, opt, batch,
                                          i, w_t=w_t)
    losses.append(float(m["loss"]))
assert all(np.isfinite(losses)), losses
assert rt.rt.queue.stats()["pushes"] == 4

# equivalence of the FIRST iteration's loss vs monolithic computation on
# host (same params, same batch)
params_s_host = jax.tree_util.tree_map(jnp.asarray, params_s0)
params_t_host = jax.tree_util.tree_map(lambda x: jnp.asarray(np.asarray(x)),
                                       params_t)
h_t = teacher_hidden(params_t_host, t_cfg, batches[0]["tokens"], impl="ref")
mono, _ = distill_loss(params_s_host, s_cfg, batches[0], h_t,
                       params_t_host["unembed"], alpha=0.5,
                       temperature=2.0, impl="ref", kl_impl="ref")
assert abs(float(mono) - losses[0]) < 1e-4, (float(mono), losses[0])

rt.shutdown()
print("DRIVER_OK distill_runtime")
