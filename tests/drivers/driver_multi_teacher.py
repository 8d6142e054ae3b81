"""Multi-device driver: multi-teacher distillation declared on the
generic WorkloadSpec/CompoundRuntime API (no bespoke runtime class) —
generalist teacher (devices 0-1), domain-routed specialist teacher
(devices 2-3) and student (devices 4-7) on disjoint meshes, verified
against the colocated single-jit reference on the same microbatch
composition."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_reduced
from repro.core import workload as wl
from repro.core.types import ParallelConfig
from repro.data.synthetic import routed_lm_batches
from repro.dist.sharding import section_mesh
from repro.distill.multi_teacher import (build_colocated_step,
                                         colocated_batch,
                                         multi_teacher_spec,
                                         teacher_unembed)
from repro.optim import adamw

B, S, MBS = 16, 32, 4
ta_cfg = get_reduced("qwen2.5-32b").replace(dtype="float32",
                                            vocab_size=256)
tb_cfg = get_reduced("granite-3-8b").replace(dtype="float32",
                                             vocab_size=256, d_model=64,
                                             head_dim=16, d_ff=128)
s_cfg = get_reduced("qwen1.5-0.5b").replace(dtype="float32",
                                            vocab_size=256)
assert ta_cfg.d_model != tb_cfg.d_model, \
    "teachers should exercise genuinely different port widths"
opt_cfg = adamw.AdamWConfig(clip_norm=0.0)   # tight compare: no clip

spec = multi_teacher_spec(
    ta_cfg, tb_cfg, s_cfg,
    ta_parallel=ParallelConfig(dp=2), tb_parallel=ParallelConfig(dp=2),
    s_parallel=ParallelConfig(dp=4),
    global_batch=B, seq_len=S, mbs=MBS, impl="ref")
rt = wl.CompoundRuntime(spec, impl="ref", opt_cfg=opt_cfg)
meshes = [rt.rt.mesh(n) for n in ("teacher_a", "teacher_b", "student")]
assert sum(m.devices.size for m in meshes) == 8
flat = [d for m in meshes for d in m.devices.flat]
assert len(set(flat)) == 8, "section meshes must be disjoint"

params, opts = rt.init(jax.random.PRNGKey(0))
params_host = jax.tree_util.tree_map(np.asarray, params)
smesh = rt.rt.mesh("student")
w_a = teacher_unembed(params["teacher_a"], ta_cfg, smesh)
w_b = teacher_unembed(params["teacher_b"], tb_cfg, smesh)
consts = {"student": {"w_a": w_a, "w_b": w_b}}

data = routed_lm_batches(batch=B, seq_len=S, vocab=256,
                         specialist_ratio=0.4, seed=0)
batch = next(data)
dom = np.asarray(batch["domain"]).astype(bool)
assert 0 < dom.sum() < B, dom.sum()

# wavefront groups specialist samples into fewer microbatches than FIFO
host = {k: np.asarray(v) for k, v in batch.items()}
plan = rt.plan_iteration(host, reorder=True)
fifo = rt.plan_iteration(host, reorder=False)
act, fact = plan.activation["teacher_b"], fifo.activation["teacher_b"]
assert tuple(fifo.order) == tuple(range(B))
assert len(act.active_mbs) <= len(fact.active_mbs)

params2, opts2, m = rt.train_iteration(params, opts, batch, 0, plan=plan,
                                       consts=consts, return_grads=True)

# executed-schedule invariants: the specialist ran only on its
# microbatches, the generalist on all of them
ex = m["execution"]
assert ex.task_counts["teacher_a"] == plan.n_mb
assert ex.task_counts.get("teacher_b", 0) == len(act.active_mbs)
assert ex.task_counts["student"] == plan.n_mb + 1   # mbs + worker-side upd
assert m["n_tasks"] == ex.task_counts
ends = {(e.section, e.tag): e.end for e in ex.timeline}
for i in act.active_mbs:
    assert ends[("teacher_b", f"fwd{i}")] <= ends[("student", f"mb{i}")]
# frozen teachers: hidden pushes only, no cotangent traffic
assert rt.rt.queue.stats()["pushes"] == plan.n_mb + len(act.active_mbs)

# ---- colocated single-jit reference on the same composition ----------- #
omesh = section_mesh(jax.devices()[:4], ParallelConfig(dp=4), "oracle")
ostep, oshard = build_colocated_step(ta_cfg, tb_cfg, s_cfg, omesh,
                                     mbs=MBS, seq_len=S, impl="ref",
                                     opt_cfg=opt_cfg, return_grads=True)
ps = jax.device_put(params_host["student"], oshard["student"])
pa = jax.device_put(params_host["teacher_a"], oshard["teacher_a"])
pb = jax.device_put(params_host["teacher_b"], oshard["teacher_b"])
oopt = jax.device_put(adamw.init(ps), oshard["opt"])
ow_a = jax.device_put(np.asarray(w_a))
ow_b = jax.device_put(np.asarray(w_b))
onew, oopt2, om = ostep(ps, oopt, pa, pb, ow_a, ow_b,
                        colocated_batch(batch, plan), jnp.int32(0))

np.testing.assert_allclose(np.asarray(m["loss"]), np.asarray(om["loss"]),
                           rtol=1e-6, err_msg="loss")
for a, b in zip(jax.tree_util.tree_leaves(m["grads"]["student"]),
                jax.tree_util.tree_leaves(om["grads"])):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=1e-7, err_msg="student grads")
# Adam's mu/sqrt(nu) normalizer amplifies sub-tolerance grad noise on
# near-zero entries to sign scale, so updated params compare at a
# fraction of one optimizer step (lr=1e-3), not at grad tolerance.
for a, b in zip(jax.tree_util.tree_leaves(params2["student"]),
                jax.tree_util.tree_leaves(onew)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                               atol=2e-4, err_msg="updated student params")
print("mixed-domain batch: disaggregated == colocated reference")

# ---- all-generalist batch: the specialist section never fires --------- #
gbatch = dict(next(data))
gbatch["domain"] = jnp.zeros((B,), jnp.int32)
ghost = {k: np.asarray(v) for k, v in gbatch.items()}
gplan = rt.plan_iteration(ghost, reorder=True)
assert gplan.activation["teacher_b"].active_mbs == ()
pushes = rt.rt.queue.stats()["pushes"]
params3, opts3, gm = rt.train_iteration(params2, opts2, gbatch, 1,
                                        plan=gplan, consts=consts,
                                        return_grads=True)
assert rt.rt.queue.stats()["pushes"] == pushes + gplan.n_mb, \
    "all-generalist batch must produce zero specialist traffic"
assert not any(e.section == "teacher_b"
               for e in gm["execution"].timeline)
onew2, _, ogm = ostep(onew, oopt2, pa, pb, ow_a, ow_b,
                      colocated_batch(gbatch, gplan), jnp.int32(1))
np.testing.assert_allclose(np.asarray(gm["loss"]),
                           np.asarray(ogm["loss"]), rtol=1e-6,
                           err_msg="all-generalist loss")
for a, b in zip(jax.tree_util.tree_leaves(params3["student"]),
                jax.tree_util.tree_leaves(onew2)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                               atol=2e-4,
                               err_msg="all-generalist updated params")
print("all-generalist batch: specialist idle, KL_b exactly zero, "
      "still matches the reference")

# losses must fall over a few iterations (the student actually learns)
losses = [float(m["loss"]), float(gm["loss"])]
p, o = params3, opts3
for i in range(2, 6):
    p, o, mi = rt.train_iteration(p, o, next(data), i, consts=consts)
    losses.append(float(mi["loss"]))
assert all(np.isfinite(losses)), losses
rt.shutdown()
print("DRIVER_OK multi_teacher")
