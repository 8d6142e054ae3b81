"""Multi-device driver: build_train_step pp/cp dispatch end-to-end.

Verifies on an 8-device host-platform mesh that

* a ``pp=2``, a ``cp=2`` and a ``pp=2 × tp=2`` ``build_train_step`` yield
  the same loss and post-update parameters as the monolithic
  ``pp=cp=1`` reference step (fp32 tolerance), and
* ``build_pp_loss`` with microbatching is *exact* against the monolithic
  MoE loss — the aux term is rebuilt from accumulated router stats, not
  per-microbatch-averaged.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_reduced
from repro.launch.mesh import make_mesh
from repro.core.types import ParallelConfig, ShapeConfig
from repro.dist import sharding as shd
from repro.dist.pipeline import build_pp_loss
from repro.models import transformer as tf
from repro.models.common import init_params
from repro.models.model import build_model
from repro.optim import adamw, schedules
from repro.train import step as step_mod

GB, S = 8, 16
# larger eps keeps the Adam direction Lipschitz in the grads, so the
# fp32-reduction-order differences between regimes stay first-order in
# the post-update params instead of flipping sign-like updates
OPT = adamw.AdamWConfig(eps=1e-3)
LR = functools.partial(schedules.constant, peak_lr=1e-3)


def make_batch(cfg, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (GB, S)),
                              jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (GB, S)),
                              jnp.int32),
        "loss_mask": jnp.ones((GB, S), jnp.float32)}


def run_step(cfg, parallel, batch, params, opt):
    model = build_model(cfg, impl="ref")
    shape = ShapeConfig("t", "train", S, GB)
    mesh = shd.section_mesh(jax.devices()[:parallel.devices], parallel)
    step, shardings = step_mod.build_train_step(
        model, mesh, parallel, shape, lr_schedule=LR, opt_cfg=OPT)
    with mesh:
        p = jax.device_put(params, shardings["params"])
        o = jax.device_put(opt, shardings["opt"])
        new_p, _, metrics = step(p, o, batch, jnp.int32(0))
        new_p = jax.device_get(new_p)
    return new_p, float(metrics["loss"]), float(metrics["grad_norm"])


def tree_max_diff(a, b):
    return max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda x, y: float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                           - y.astype(jnp.float32)))),
        a, b)))


# ---- pp=2 / cp=2 / pp×tp train steps vs monolithic reference -------------
cfg = get_reduced("granite-3-8b").replace(dtype="float32", num_layers=4)
model = build_model(cfg, impl="ref")
# keep host copies: the jitted steps donate their inputs, and device_put
# aliases (doesn't copy) arrays whose sharding already matches
params = jax.device_get(model.init(jax.random.PRNGKey(0)))
opt = jax.device_get(adamw.init(params))
batch = make_batch(cfg)

ref_p, ref_loss, ref_gn = run_step(
    cfg, ParallelConfig(mbs=GB), batch, params, opt)

for tag, par in [
        ("pp2",   ParallelConfig(dp=2, pp=2, mbs=2)),
        ("cp2",   ParallelConfig(dp=2, cp=2, mbs=2)),
        ("pp2tp2", ParallelConfig(dp=2, pp=2, tp=2, mbs=2))]:
    got_p, got_loss, got_gn = run_step(cfg, par, batch, params, opt)
    dl = abs(got_loss - ref_loss)
    dg = abs(got_gn - ref_gn)
    dp_ = tree_max_diff(got_p, ref_p)
    print(f"{tag}: dloss={dl:.2e} dgnorm={dg:.2e} dparams={dp_:.2e}")
    assert dl < 1e-5, (tag, got_loss, ref_loss)
    assert dg < 1e-3, (tag, got_gn, ref_gn)
    assert dp_ < 1e-4, (tag, dp_)

# ---- colocated distill step under CP vs plain ----------------------------
from repro.distill.workload import build_colocated_step


def run_distill(parallel, mesh_dims, axes):
    mesh = make_mesh(mesh_dims, axes)
    shape = ShapeConfig("d", "train", S, GB)
    step, sh = build_colocated_step(
        cfg, cfg, mesh, shape, parallel, impl="ref", lr_schedule=LR,
        opt_cfg=OPT)
    with mesh:
        ps = jax.device_put(params, sh["student"])
        o = jax.device_put(opt, sh["opt"])
        pt = jax.device_put(params, sh["teacher"])
        new_p, _, metrics = step(ps, o, pt, batch, jnp.int32(0))
        new_p = jax.device_get(new_p)
    return new_p, float(metrics["loss"])


d_ref_p, d_ref_loss = run_distill(
    ParallelConfig(mbs=GB), (1, 1), ("data", "model"))
d_cp_p, d_cp_loss = run_distill(
    ParallelConfig(dp=2, cp=2, mbs=4), (2, 1, 2, 1),
    ("data", "pipe", "seq", "model"))
dl = abs(d_cp_loss - d_ref_loss)
dp_ = tree_max_diff(d_cp_p, d_ref_p)
print(f"distill cp2: dloss={dl:.2e} dparams={dp_:.2e}")
assert dl < 1e-5, (d_cp_loss, d_ref_loss)
assert dp_ < 1e-4, dp_

# ---- build_pp_loss MoE aux exactness vs monolithic reference -------------
mcfg = get_reduced("mixtral-8x22b").replace(dtype="float32", num_layers=2)
mparams = init_params(tf.lm_specs(mcfg), jax.random.PRNGKey(1))
mbatch = make_batch(mcfg, seed=1)
l_ref, _ = tf.lm_loss(mparams, mcfg, mbatch, impl="ref")
mesh = make_mesh((2, 2), ("data", "pipe"))
loss_fn, info = build_pp_loss(mcfg, mesh, n_micro=2, impl="ref")
assert info["moe_layers_per_stage"] == 1, info
with mesh:
    l_pp = jax.jit(loss_fn)(mparams, mbatch)
    g_pp = jax.jit(jax.grad(lambda p: loss_fn(p, mbatch)))(mparams)
err = abs(float(l_pp) - float(l_ref))
print(f"moe pp loss: ref={float(l_ref):.6f} pp={float(l_pp):.6f} "
      f"err={err:.2e}")
assert err < 1e-5, (float(l_pp), float(l_ref))
g_ref = jax.grad(
    lambda p: tf.lm_loss(p, mcfg, mbatch, impl="ref")[0])(mparams)
gerr = tree_max_diff(g_pp, g_ref)
print(f"moe pp grad err={gerr:.2e}")
assert gerr < 5e-4, gerr

# ---- HLO proof: vocab-parallel CE FLOPs, TP-in-stage sharding ------------
# post-SPMD shapes are per-device, so matching the local vocab-shard /
# FFN-shard width isolates exactly the dots the optimizations target.
# The expectations themselves are data: the declarative gate files under
# repro/analysis/gates/, evaluated here against this reduced config
# (vocab 512 overrides the gate's bench-config default).
from repro.analysis import hlo_gates

# dims chosen so V (512), V/pp (128) and d_ff (no collision) identify dots
hcfg = get_reduced("qwen1.5-0.5b").replace(
    dtype="float32", num_layers=4, vocab_size=512, d_model=64,
    num_heads=4, num_kv_heads=4, head_dim=16, d_ff=160,
    tie_embeddings=False)


def pp_grad_hlo(mesh, vocab_parallel):
    loss_fn, _ = build_pp_loss(hcfg, mesh, n_micro=2, impl="ref",
                               vocab_parallel=vocab_parallel)
    hp = init_params(tf.lm_specs(hcfg), jax.random.PRNGKey(0))
    hb = make_batch(hcfg, seed=2)
    with mesh:
        return jax.jit(jax.value_and_grad(
            lambda p: loss_fn(p, hb))).lower(hp).compile().as_text()


hmesh = make_mesh((2, 4), ("data", "pipe"))
rep, m = hlo_gates.evaluate_file(
    hlo_gates.GATES_DIR / "vp_ce.json",
    {"masked": pp_grad_hlo(hmesh, vocab_parallel=False),
     "vp": pp_grad_hlo(hmesh, vocab_parallel=True)},
    symbols={"vocab": float(hcfg.padded_vocab)})
print(f"vp-CE unembed dot FLOPs: masked {m['baseline_full_vocab']:.3g} "
      f"vp {m['shard_present']:.3g} ratio {m['reduction']:.2f} (pp=4)")
rep.raise_on_error(AssertionError)

tmesh1 = make_mesh((2, 2, 1), ("data", "pipe", "model"))
tmesh2 = make_mesh((1, 2, 2), ("data", "pipe", "model"))
rep, m = hlo_gates.evaluate_file(
    hlo_gates.GATES_DIR / "tp_in_stage.json",
    {"tp1": pp_grad_hlo(tmesh1, vocab_parallel=True),
     "tp2": pp_grad_hlo(tmesh2, vocab_parallel=True)})
print(f"TP-in-stage FFN dot FLOPs: tp1 {m['tp1_ffn_present']:.3g} "
      f"tp2 {m['tp2_shard_present']:.3g} per-sample ratio "
      f"{m['reduction']:.2f} (tp=2)")
rep.raise_on_error(AssertionError)

# ---- multi-pod PP: the pod axis must carry data parallelism --------------
mesh3 = make_mesh((2, 2, 2), ("pod", "data", "pipe"))
loss3, info3 = build_pp_loss(cfg, mesh3, n_micro=2, impl="ref")
assert info3["data_axis"] == ("pod", "data"), info3
l_base, _ = tf.lm_loss(params, cfg, batch, impl="ref")
with mesh3:
    l3 = jax.jit(loss3)(params, batch)
err3 = abs(float(l3) - float(l_base))
print(f"multipod pp loss err={err3:.2e}")
assert err3 < 1e-5, (float(l3), float(l_base))

print("DRIVER_OK train_step_dist")
