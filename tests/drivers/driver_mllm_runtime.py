"""Multi-device driver: disaggregated MLLM runtime on the compound
executor — ViT section (devices 0-3) and LLM section (devices 4-7) on
disjoint dp=4 meshes, wavefront-scheduled microbatch dispatch, data-
dependent activation — proved bit-for-bit equal to the colocated
single-jit oracle on mixed image/text batches AND on an all-text batch
where the vision section never fires."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_reduced
from repro.core.types import ParallelConfig
from repro.data.synthetic import vlm_batches
from repro.dist.sharding import section_mesh
from repro.mllm.workload import (MLLMRuntime, build_colocated_step,
                                 colocated_batch, init_compound_params)
from repro.models.vlm import vit_config
from repro.optim import adamw

B, S, K, MBS = 16, 32, 4, 4
lm_cfg = get_reduced("pixtral-12b").replace(
    dtype="float32", vocab_size=256, vision_dim=32, max_image_tokens=K)
vit_cfg = vit_config(num_layers=2, d_model=32, num_heads=2, d_ff=64,
                     patch_dim=16, downsample=4, out_dim=32,
                     name="vit-tiny").replace(dtype="float32")
opt_cfg = adamw.AdamWConfig(clip_norm=0.0)   # bitwise: no clip threshold

rt = MLLMRuntime(vit_cfg, lm_cfg,
                 vit_parallel=ParallelConfig(dp=4),
                 lm_parallel=ParallelConfig(dp=4),
                 global_batch=B, seq_len=S, mbs=MBS,
                 impl="ref", opt_cfg=opt_cfg)
assert rt.rt.mesh("vit").devices.size == 4
assert rt.rt.mesh("llm").devices.size == 4
assert not (set(rt.rt.mesh("vit").devices.flat)
            & set(rt.rt.mesh("llm").devices.flat)), "meshes must be disjoint"

params_host = init_compound_params(vit_cfg, lm_cfg, jax.random.PRNGKey(0))


def fresh_params():
    """A copy of params_host for the oracle, whose step donates its
    inputs (a replicated device_put can alias the source buffers)."""
    return jax.tree_util.tree_map(jnp.copy, params_host)

params, opts = rt.place(params_host)

# colocated single-jit oracle on a 4-device dp=4 mesh (same section layout)
omesh = section_mesh(jax.devices()[:4], ParallelConfig(dp=4), "oracle")
ostep, oshard = build_colocated_step(vit_cfg, lm_cfg, omesh, mbs=MBS,
                                     seq_len=S, impl="ref",
                                     opt_cfg=opt_cfg, return_grads=True)
oparams = jax.device_put(fresh_params(), oshard["params"])
oopt = jax.device_put(adamw.init(oparams), oshard["opt"])

data = vlm_batches(batch=B, seq_len=S, vocab=256, vision_ratio=0.5,
                   image_tokens=K, patch_dim=16, seed=0)


def tree_equal(a, b, what):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb), (what, len(la), len(lb))
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=what)


# ---- mixed image/text batch: wavefront reordering actually happens ----- #
batch = next(data)
has = np.asarray(batch["has_image"]).astype(bool)
assert 0 < has.sum() < B, has.sum()
plan = rt.plan_iteration(has, reorder=True)
assert tuple(plan.order) != tuple(range(B)), \
    "wavefront must reorder a heterogeneous batch"
fifo_plan = rt.plan_iteration(has, reorder=False)
assert tuple(fifo_plan.order) == tuple(range(B))
# reordering groups text samples: more all-text microbatches than FIFO
assert len(plan.image_mbs) <= len(fifo_plan.image_mbs)

params2, opts2, m = rt.train_iteration(params, opts, batch, 0, plan=plan,
                                       return_grads=True)
onew_p, onew_opt, om = ostep(oparams, oopt, colocated_batch(batch, plan),
                             jnp.int32(0))

np.testing.assert_array_equal(np.asarray(m["loss"]),
                              np.asarray(om["loss"]), err_msg="loss")
tree_equal(m["grads"]["lm"], om["grads"]["lm"], "lm grads")
tree_equal(m["grads"]["vit"], om["grads"]["vit"], "vit grads")
tree_equal(params2["lm"], onew_p["lm"], "updated lm params")
tree_equal(params2["vit"], onew_p["vit"], "updated vit params")
print("mixed batch: disaggregated == colocated oracle (bit-for-bit)")

# realized executed-schedule invariants: every vit fwd completes before
# its consumer LM microbatch completes; bwd only after the LM returned
# the cotangent; the vision section ran only for image-bearing mbs
ex = m["execution"]
ends = {(e.section, e.tag): e.end for e in ex.timeline}
assert set(ex.dispatch_order["vit"]) == \
    {f"fwd{i}" for i in plan.image_mbs} | \
    {f"bwd{i}" for i in plan.image_mbs} | {"upd"}
for i in plan.image_mbs:
    assert ends[("vit", f"fwd{i}")] <= ends[("llm", f"mb{i}")]
    assert ends[("vit", f"fwd{i}")] <= ends[("vit", f"bwd{i}")]
assert m["n_vit_tasks"] == 2 * len(plan.image_mbs)
# embeddings + cotangents, plus the 2-push joint grad-norm rendezvous
assert rt.rt.queue.stats()["pushes"] == 2 * len(plan.image_mbs) + 2

# ---- all-text batch: the vision section never fires ------------------- #
data_text = vlm_batches(batch=B, seq_len=S, vocab=256, vision_ratio=0.0,
                        image_tokens=K, patch_dim=16, seed=1)
tbatch = next(data_text)
assert not np.asarray(tbatch["has_image"]).any()
tplan = rt.plan_iteration(np.asarray(tbatch["has_image"]), reorder=True)
assert tplan.image_mbs == ()
pushes_before = rt.rt.queue.stats()["pushes"]
params3, opts3, tm = rt.train_iteration(params2, opts2, tbatch, 1,
                                        plan=tplan, return_grads=True)
assert rt.rt.queue.stats()["pushes"] == pushes_before + 2, \
    "all-text batch: gnorm rendezvous only, zero activation traffic"
assert tm["n_vit_tasks"] == 0
assert [e.tag for e in tm["execution"].timeline
        if e.section == "vit"] == ["upd"], \
    "idle vision section runs only its (exact-zero-grad) update"

onew_p2, onew_opt2, otm = ostep(onew_p, onew_opt,
                                colocated_batch(tbatch, tplan),
                                jnp.int32(1))
np.testing.assert_array_equal(np.asarray(tm["loss"]),
                              np.asarray(otm["loss"]),
                              err_msg="all-text loss")
tree_equal(tm["grads"]["lm"], otm["grads"]["lm"], "all-text lm grads")
tree_equal(tm["grads"]["vit"], otm["grads"]["vit"], "all-text vit grads")
tree_equal(params3["lm"], onew_p2["lm"], "all-text updated lm params")
tree_equal(params3["vit"], onew_p2["vit"], "all-text updated vit params")
print("all-text batch: vision section idle, still bit-for-bit")

rt.shutdown()

# ---- clip-ACTIVE path: the joint cross-section grad norm must drive the
# same clip scale the colocated oracle computes (this is what
# adamw.update(gnorm=) + MLLMRuntime._joint_gnorm exist for) ------------- #
clip_cfg = adamw.AdamWConfig(clip_norm=0.05)
rt2 = MLLMRuntime(vit_cfg, lm_cfg,
                  vit_parallel=ParallelConfig(dp=4),
                  lm_parallel=ParallelConfig(dp=4),
                  global_batch=B, seq_len=S, mbs=MBS,
                  impl="ref", opt_cfg=clip_cfg)
params_c, opts_c = rt2.place(params_host)
ostep2, oshard2 = build_colocated_step(vit_cfg, lm_cfg, omesh, mbs=MBS,
                                       seq_len=S, impl="ref",
                                       opt_cfg=clip_cfg, return_grads=True)
oparams_c = jax.device_put(fresh_params(), oshard2["params"])
oopt_c = jax.device_put(adamw.init(oparams_c), oshard2["opt"])
cbatch = next(data)
cplan = rt2.plan_iteration(np.asarray(cbatch["has_image"]), reorder=True)
params_c2, _, cm_ = rt2.train_iteration(params_c, opts_c, cbatch, 0,
                                        plan=cplan, return_grads=True)
onew_pc, _, ocm = ostep2(oparams_c, oopt_c, colocated_batch(cbatch, cplan),
                         jnp.int32(0))
assert float(cm_["grad_norm"]) > clip_cfg.clip_norm, \
    "clipping must actually fire for this check to mean anything"
np.testing.assert_array_equal(np.asarray(cm_["loss"]),
                              np.asarray(ocm["loss"]),
                              err_msg="clip-path loss")
tree_equal(cm_["grads"]["lm"], ocm["grads"]["lm"], "clip-path lm grads")
tree_equal(cm_["grads"]["vit"], ocm["grads"]["vit"], "clip-path vit grads")
# The joint gnorm matches the oracle's to a few ulps but not always
# bitwise: the per-leaf sums of squares ARE bitwise equal (probed), but
# inside the oracle jit XLA fuses the stack-of-scalars sum into a scalar
# expression tree whose association differs from the runtime's
# materialized-vector reduce — an inherent cross-jit-boundary fusion
# limit, data-dependent, a couple of ulps of the norm.
gr, go = float(cm_["grad_norm"]), float(ocm["grad_norm"])
assert abs(gr - go) <= 4 * np.spacing(np.float32(go)), (gr, go)
# the 1-ulp clip scale propagates multiplicatively into the update
for sec in ("lm", "vit"):
    for a, b in zip(jax.tree_util.tree_leaves(params_c2[sec]),
                    jax.tree_util.tree_leaves(onew_pc[sec])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7,
                                   err_msg=f"clipped {sec} params")
print("clip-active path: grads bitwise, joint gnorm within ulps, "
      "clipped updates within scale-ulp of oracle")
rt2.shutdown()

# ---- ViT-CP section: context parallelism on the vision section's long
# patch sequences (the paper's own use of CP) now runs through the
# executor via the consolidated parallel_regime dispatch instead of the
# old blanket _reject_pp_cp.  vit mesh (data=2, seq=2) + llm dp=4. ------- #
rt3 = MLLMRuntime(vit_cfg, lm_cfg,
                  vit_parallel=ParallelConfig(dp=2, cp=2),
                  lm_parallel=ParallelConfig(dp=4),
                  global_batch=B, seq_len=S, mbs=MBS,
                  impl="ref", opt_cfg=opt_cfg)
vm3 = rt3.rt.mesh("vit")
assert dict(vm3.shape)["seq"] == 2 and dict(vm3.shape)["data"] == 2
params_cp, opts_cp = rt3.place(params_host)
oparams3 = jax.device_put(fresh_params(), oshard["params"])
oopt3 = jax.device_put(adamw.init(oparams3), oshard["opt"])
cpbatch = next(data)
cp_plan = rt3.plan_iteration(np.asarray(cpbatch["has_image"]),
                             reorder=True)
assert len(cp_plan.image_mbs) > 0
params_cp2, _, mcp = rt3.train_iteration(params_cp, opts_cp, cpbatch, 0,
                                         plan=cp_plan, return_grads=True)
onew_p3, _, ocp = ostep(oparams3, oopt3, colocated_batch(cpbatch, cp_plan),
                        jnp.int32(0))
np.testing.assert_allclose(np.asarray(mcp["loss"]),
                           np.asarray(ocp["loss"]), rtol=1e-6,
                           err_msg="vit-cp loss")
for sec in ("lm", "vit"):
    for a, b in zip(jax.tree_util.tree_leaves(mcp["grads"][sec]),
                    jax.tree_util.tree_leaves(ocp["grads"][sec])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-7,
                                   err_msg=f"vit-cp {sec} grads")
    for a, b in zip(jax.tree_util.tree_leaves(params_cp2[sec]),
                    jax.tree_util.tree_leaves(onew_p3[sec])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-7,
                                   err_msg=f"vit-cp {sec} params")
ex3 = mcp["execution"]
assert set(ex3.dispatch_order["vit"]) == \
    {f"fwd{i}" for i in cp_plan.image_mbs} | \
    {f"bwd{i}" for i in cp_plan.image_mbs} | {"upd"}
print("ViT-CP section (dp=2, cp=2): runs through the executor, "
      "loss/grads/params match the oracle")
rt3.shutdown()

# ---- streaming with cross-iteration lookahead ENABLED: three pipelined
# iterations through submit/retire must stay bit-for-bit with the oracle
# stepped three times — removing the global barrier must not change a
# single bit of the training trajectory ------------------------------------ #
rt4 = MLLMRuntime(vit_cfg, lm_cfg,
                  vit_parallel=ParallelConfig(dp=4),
                  lm_parallel=ParallelConfig(dp=4),
                  global_batch=B, seq_len=S, mbs=MBS,
                  impl="ref", opt_cfg=opt_cfg, lookahead=1)
params_s, opts_s = rt4.place(params_host)
rt4.install(params_s, opts_s)
op4 = jax.device_put(fresh_params(), oshard["params"])
oo4 = jax.device_put(adamw.init(op4), oshard["opt"])
sbatches = [next(data) for _ in range(3)]
splans = []
max_inflight = 0
for i, b in enumerate(sbatches):
    pl = rt4.plan_iteration(np.asarray(b["has_image"]), reorder=True)
    splans.append(pl)
    rt4.submit_iteration(b, i, plan=pl)
    max_inflight = max(max_inflight, rt4.in_flight)
assert max_inflight == 2, \
    f"lookahead=1 must pipeline two iterations in flight: {max_inflight}"
ms = rt4.drain()
assert rt4.in_flight == 0 and len(ms) == 3
params_s2, opts_s2 = rt4.state()
oms = []
for i, b in enumerate(sbatches):
    op4, oo4, om_i = ostep(op4, oo4, colocated_batch(b, splans[i]),
                           jnp.int32(i))
    oms.append(om_i)
for i in range(3):
    np.testing.assert_array_equal(
        np.asarray(ms[i]["loss"]), np.asarray(oms[i]["loss"]),
        err_msg=f"streaming loss, iteration {i}")
tree_equal(params_s2["lm"], op4["lm"], "streamed lm params after 3 iters")
tree_equal(params_s2["vit"], op4["vit"],
           "streamed vit params after 3 iters")
print("streaming lookahead=1: three pipelined iterations bit-for-bit "
      "with the oracle trajectory")
rt4.shutdown()
print("DRIVER_OK mllm_runtime")
