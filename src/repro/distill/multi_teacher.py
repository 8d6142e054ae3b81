"""Multi-teacher knowledge distillation with data-dependent routing.

The third compound workload on the declarative API — and the proof of its
generality: TWO frozen teacher sections feed one student.  The generalist
teacher sees every sample; the *specialist* teacher activates only on
samples whose ``domain`` flag routes to it (data-dependent activation,
exactly the mechanism MLLM training uses for text-only samples), so the
wavefront scheduler groups specialist samples into fewer microbatches and
the specialist section never runs on pure-generalist microbatches.

Per §3.1 both teachers' output layers are colocated with the student
(consts ``w_a`` / ``w_b``): only hidden states cross the section
boundaries, and the student computes

    loss = (1-α)·CE + α·T²·(KL_a + KL_b·[domain])

with the chunked-vocab ``distill_kl`` kernel.  Specialist rows travel in
the capacity layout (gathered + zero-padded, like ViT embeddings) and are
scattered back to sample slots inside the student loss; the KL_b token
mask comes from scattering ``act_valid`` — an all-generalist microbatch
contributes an exact-zero KL_b (the kernel's mask normalization is
zero-safe).

The whole workload is ~60 lines of declaration (:func:`multi_teacher_spec`)
run by the generic :class:`repro.core.workload.CompoundRuntime`;
``build_colocated_step`` is the single-jit oracle the driver
(``tests/drivers/driver_multi_teacher.py``) verifies the disaggregated
execution against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import workload as wl
from repro.core.types import ArchConfig, ParallelConfig
from repro.dist import sharding as shd
from repro.distill.workload import teacher_hidden
from repro.kernels import ops as kops
from repro.models import common as cm
from repro.models import transformer as tf
from repro.optim import adamw, schedules
from repro.train.step import _act_hook_for

LM_KEYS = ("tokens", "labels", "loss_mask")


# --------------------------------------------------------------------------- #
# Shared arithmetic (oracle ≡ disaggregated)
# --------------------------------------------------------------------------- #
def specialist_hidden(pt, tb_cfg: ArchConfig, tokens, valid, *,
                      impl: str = "ref"):
    """Specialist-teacher hidden states for the gathered (capacity-layout)
    domain samples of one microbatch, padding rows masked to exact zero.
    tokens [cap, S], valid [cap] → h [cap, S, D_b]."""
    h = teacher_hidden(pt, tb_cfg, tokens, impl=impl)
    return h * valid[:, None, None].astype(h.dtype)


def student_mt_loss(ps, s_cfg: ArchConfig, batch, h_a, w_a, h_b, b_idx,
                    b_valid, w_b, *, alpha: float, temperature: float,
                    impl: str = "ref", kl_impl: str = "ref"):
    """CE + α·T²·(KL vs generalist + domain-masked KL vs specialist).
    h_b arrives in capacity layout and is scattered back to sample slots
    by ``b_idx``; the KL_b mask is the scattered ``b_valid``."""
    h_s, _ = tf.lm_forward(ps, s_cfg, batch, impl=impl, remat=True,
                           logits_out=False)
    logits = tf.unembed(ps, s_cfg, h_s)
    ce = cm.cross_entropy(logits, batch["labels"], batch.get("loss_mask"))
    B, S, Ds = h_s.shape
    w_s = ps["embed"].T if s_cfg.tie_embeddings else ps["unembed"]
    sg = jax.lax.stop_gradient
    hsf = h_s.reshape(B * S, Ds)
    lm = batch["loss_mask"].reshape(B * S)
    T = temperature
    kl_a = kops.distill_kl(hsf, w_s, sg(h_a).reshape(B * S, -1), sg(w_a),
                           mask=lm, temperature=T, impl=kl_impl)
    hb = jnp.zeros((B,) + h_b.shape[1:], h_b.dtype).at[b_idx].add(h_b)
    mb = jnp.zeros((B,), jnp.float32).at[b_idx].add(b_valid)
    mask_b = (batch["loss_mask"] * mb[:, None]).reshape(B * S)
    kl_b = kops.distill_kl(hsf, w_s, sg(hb).reshape(B * S, -1), sg(w_b),
                           mask=mask_b, temperature=T, impl=kl_impl)
    loss = (1 - alpha) * ce + alpha * T * T * (kl_a + kl_b)
    return loss, {"ce": ce, "kl_a": kl_a, "kl_b": kl_b}


# --------------------------------------------------------------------------- #
# The declaration (run it with CompoundRuntime — no bespoke runtime class)
# --------------------------------------------------------------------------- #
def multi_teacher_spec(ta_cfg: ArchConfig, tb_cfg: ArchConfig,
                       s_cfg: ArchConfig, *,
                       ta_parallel: ParallelConfig,
                       tb_parallel: ParallelConfig,
                       s_parallel: ParallelConfig,
                       global_batch: int, seq_len: int, mbs: int,
                       alpha: float = 0.5, temperature: float = 2.0,
                       impl: str = "ref") -> wl.WorkloadSpec:
    """Two frozen teachers → one student, specialist routed by the
    per-sample ``domain`` flag."""
    h_a = wl.Port("hidden", (wl.SEQ, ta_cfg.d_model), ta_cfg.dtype)
    h_b = wl.Port("hidden", (wl.SEQ, tb_cfg.d_model), tb_cfg.dtype)
    kl_impl = "ref" if impl == "ref" else "auto"

    def ta_fn(pt, x):
        return {"hidden": teacher_hidden(pt, ta_cfg, x["tokens"],
                                         impl=impl)}

    def tb_fn(pt, x):
        return {"hidden": specialist_hidden(pt, tb_cfg, x["tokens"],
                                            x["act_valid"], impl=impl)}

    def s_fn(ps, x):
        batch = {k: x[k] for k in LM_KEYS}
        return student_mt_loss(
            ps, s_cfg, batch, x["teacher_a.hidden"], x["w_a"],
            x["teacher_b.hidden"], x["teacher_b.act_idx"],
            x["teacher_b.act_valid"], x["w_b"], alpha=alpha,
            temperature=temperature, impl=impl, kl_impl=kl_impl)

    tok = {"tokens": wl.Field((wl.SEQ,), "int32")}
    teacher_a = wl.SectionSpec(
        "teacher_a", ta_cfg, ta_parallel, ta_fn, tf.lm_specs(ta_cfg),
        inputs=tok, emits=(h_a,), mode="fwd_only")
    teacher_b = wl.SectionSpec(
        "teacher_b", tb_cfg, tb_parallel, tb_fn, tf.lm_specs(tb_cfg),
        inputs=tok, emits=(h_b,), mode="fwd_only",
        activation=lambda b: np.asarray(b["domain"]).astype(bool))
    student = wl.SectionSpec(
        "student", s_cfg, s_parallel, s_fn, tf.lm_specs(s_cfg),
        inputs={"tokens": wl.Field((wl.SEQ,), "int32"),
                "labels": wl.Field((wl.SEQ,), "int32"),
                "loss_mask": wl.Field((wl.SEQ,), "float32", fill=1.0)},
        consumes=(wl.Consume("teacher_a", h_a),
                  wl.Consume("teacher_b", h_b)),
        loss=True, loss_aux=True, critical=True,
        consts={"w_a": wl.Field((ta_cfg.d_model, ta_cfg.padded_vocab),
                                ta_cfg.dtype),
                "w_b": wl.Field((tb_cfg.d_model, tb_cfg.padded_vocab),
                                tb_cfg.dtype)})
    return wl.WorkloadSpec("multi_teacher",
                           (teacher_a, teacher_b, student),
                           seq_len=seq_len, global_batch=global_batch,
                           mbs=mbs)


def teacher_unembed(params_t, t_cfg: ArchConfig, mesh: Mesh):
    """A teacher's (student-colocated) output layer, replicated on the
    student mesh."""
    w = (params_t["embed"].T if t_cfg.tie_embeddings
         else params_t["unembed"])
    return jax.device_put(jax.device_get(w), shd.replicated(mesh))


# --------------------------------------------------------------------------- #
# Colocated single-jit oracle (dry-run cell + driver reference)
# --------------------------------------------------------------------------- #
def colocated_batch(batch: dict, plan: wl.IterationPlan) -> dict:
    """Permute into the plan's dispatch order, pre-split into
    [n_mb, mbs, ...], and attach the specialist capacity layout — the
    oracle's scan sees exactly the executor's microbatch composition."""
    idx = list(plan.order)
    out = {}
    for k in LM_KEYS:
        v = np.asarray(batch[k])[idx]
        out[k] = jnp.asarray(v.reshape((plan.n_mb, plan.mbs)
                                       + v.shape[1:]))
    act = plan.activation["teacher_b"]
    out["b_idx"] = jnp.asarray(act.idx)
    out["b_valid"] = jnp.asarray(act.valid)
    return out


def build_colocated_step(ta_cfg: ArchConfig, tb_cfg: ArchConfig,
                         s_cfg: ArchConfig, mesh: Mesh, *, mbs: int,
                         seq_len: int, alpha: float = 0.5,
                         temperature: float = 2.0, impl: str = "ref",
                         lr_schedule=None,
                         opt_cfg: adamw.AdamWConfig = adamw.AdamWConfig(),
                         return_grads: bool = False):
    """One jit over the pre-microbatched batch from
    :func:`colocated_batch`: per microbatch, both teacher forwards (the
    specialist on its gathered domain samples) + the student loss,
    student grads accumulated in dispatch order, one AdamW update.
    Returns (step, shardings)."""
    s_specs = tf.lm_specs(s_cfg)
    a_specs = tf.lm_specs(ta_cfg)
    b_specs = tf.lm_specs(tb_cfg)
    sp = shd.param_shardings(s_specs, mesh, shd.rules_for(s_cfg, mesh))
    ap = shd.param_shardings(a_specs, mesh,
                             shd.rules_for(ta_cfg, mesh, teacher=True))
    bp = shd.param_shardings(b_specs, mesh,
                             shd.rules_for(tb_cfg, mesh, teacher=True))
    o_shard = shd.opt_state_shardings(s_specs, mesh,
                                      shd.rules_for(s_cfg, mesh))
    dp = shd.dp_axes(mesh) or None
    rep = shd.replicated(mesh)

    def mb_sharding(ndim):
        return NamedSharding(mesh, P(None, dp, *([None] * (ndim - 2))))

    b_shard = {"tokens": mb_sharding(3), "labels": mb_sharding(3),
               "loss_mask": mb_sharding(3), "b_idx": rep, "b_valid": rep}
    hook = _act_hook_for(mesh, mbs, seq_len)
    lr_fn = lr_schedule or functools.partial(schedules.constant,
                                             peak_lr=1e-3)
    kl_impl = "ref" if impl == "ref" else "auto"

    def mb_loss(ps, pa, pb, w_a, w_b, mb, bidx, bval):
        with cm.act_hook(*hook):
            h_a = teacher_hidden(pa, ta_cfg, mb["tokens"], impl=impl)
            h_b = specialist_hidden(pb, tb_cfg, mb["tokens"][bidx], bval,
                                    impl=impl)
            loss, _ = student_mt_loss(
                ps, s_cfg, mb, h_a, w_a, h_b, bidx, bval, w_b,
                alpha=alpha, temperature=temperature, impl=impl,
                kl_impl=kl_impl)
            return loss

    grad_fn = jax.value_and_grad(mb_loss)

    def step(params_s, opt_state, params_a, params_b, w_a, w_b, batch,
             step_idx):
        n_mb = batch["tokens"].shape[0]
        mbs_tree = {k: batch[k] for k in LM_KEYS}

        def body(carry, xs):
            g_acc, l_acc = carry
            mb, bidx, bval = xs
            loss, g = grad_fn(params_s, params_a, params_b, w_a, w_b, mb,
                              bidx, bval)
            g_acc = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(jnp.float32), g_acc, g)
            return (g_acc, l_acc + loss), None

        g0 = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), params_s)
        (g_sum, l_sum), _ = jax.lax.scan(
            body, (g0, jnp.float32(0)),
            (mbs_tree, batch["b_idx"], batch["b_valid"]))
        grads = jax.tree_util.tree_map(
            lambda g, p: (g / n_mb).astype(p.dtype), g_sum, params_s)
        loss = l_sum / n_mb
        lr = lr_fn(step_idx)
        new_p, new_opt, gnorm = adamw.update(grads, opt_state, lr, opt_cfg)
        metrics = {"loss": loss.astype(jnp.float32), "grad_norm": gnorm,
                   "lr": lr}
        if return_grads:
            metrics["grads"] = grads
        return new_p, new_opt, metrics

    out_metrics = {"loss": rep, "grad_norm": rep, "lr": rep}
    if return_grads:
        out_metrics["grads"] = sp
    jitted = jax.jit(step,
                     in_shardings=(sp, o_shard, ap, bp, rep, rep, b_shard,
                                   rep),
                     out_shardings=(sp, o_shard, out_metrics))
    return jitted, {"student": sp, "teacher_a": ap, "teacher_b": bp,
                    "opt": o_shard, "batch": b_shard}
