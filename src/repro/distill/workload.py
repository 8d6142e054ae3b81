"""Knowledge-distillation workload (paper §2.2, §3.1, §4.2).

Section construction follows the paper exactly:

* the **teacher body** (all layers, final norm) is a forward-only section
  producing final *hidden states* [B, S, D_t];
* the **teacher's output layer (unembedding) is colocated with the student
  section** — only hidden states cross the section boundary (d_model
  floats/token instead of vocab floats/token, a ~62× traffic cut at
  Qwen-scale vocabularies);
* the student computes CE + KL(p_teacher ‖ p_student) where both logit
  streams are produced *inside the student section*, via the chunked-vocab
  ``distill_kl`` kernel that never materializes [N, V] logits in HBM.

Two execution modes:

* ``build_colocated_step`` — single SPMD jit (dry-run / equivalence oracle);
* ``DistillRuntime``       — disaggregated: teacher and student sections on
  disjoint meshes, hidden states flowing through the MessageQueue with
  fan-out (DP^t × fanout = DP^s).
"""
from __future__ import annotations

import contextlib
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core import workload as wl
from repro.core.types import ArchConfig, ParallelConfig, ShapeConfig
from repro.dist import context as cpx
from repro.dist import sharding as shd
from repro.kernels import ops as kops
from repro.models import attention as att
from repro.models import common as cm
from repro.models import transformer as tf
from repro.optim import adamw, schedules


def _cp_ctx(mesh, *cfgs):
    """Attention-impl context for one section mesh: installs cp_attention
    when the mesh has a non-trivial ``seq`` axis, else a no-op.  Every
    arch running on the mesh must pass the CP support check — an
    attention-free section would otherwise never call the installed impl
    and silently replicate the seq axis.  PP for distillation sections is
    rejected by the callers (the staged loss builder only covers the
    plain LM CE tail)."""
    if dict(mesh.shape).get(shd.AXIS_SEQ, 1) > 1:
        from repro.train.step import _check_pp_cp_support
        for cfg in cfgs:
            _check_pp_cp_support(cfg, "cp")
        impl = cpx.cp_attention_impl(
            mesh, batch_axes=shd.dp_axes(mesh) or None)
        return lambda: att.attention_impl(impl)
    return contextlib.nullcontext


def teacher_hidden(params_t, t_cfg: ArchConfig, tokens, *, impl="auto",
                   remat=True):
    """Teacher body forward: final hidden states (no unembedding), under
    the scope ``teacher``."""
    with jax.named_scope("teacher"):
        h, _ = tf.lm_forward(params_t, t_cfg, {"tokens": tokens},
                             impl=impl, remat=remat, logits_out=False)
        return h


def distill_loss(params_s, s_cfg: ArchConfig, batch, h_teacher,
                 teacher_unembed, *, alpha: float = 0.5,
                 temperature: float = 2.0, impl="auto", remat=True,
                 kl_impl="auto"):
    """CE + α·T²·KL from hidden states (teacher output layer colocated),
    under the scope ``student``."""
    with jax.named_scope("student"):
        h_s, aux = tf.lm_forward(params_s, s_cfg, batch, impl=impl,
                                 remat=remat, logits_out=False)
        logits = tf.unembed(params_s, s_cfg, h_s)
        ce = cm.cross_entropy(logits, batch["labels"], batch.get("loss_mask"))
        B, S, Ds = h_s.shape
        w_s = (params_s["embed"].T if s_cfg.tie_embeddings
               else params_s["unembed"])
        mask = batch.get("loss_mask")
        kl = kops.distill_kl(
            h_s.reshape(B * S, Ds), w_s,
            jax.lax.stop_gradient(h_teacher).reshape(B * S, -1),
            jax.lax.stop_gradient(teacher_unembed),
            mask=None if mask is None else mask.reshape(B * S),
            temperature=temperature, impl=kl_impl)
        loss = (1 - alpha) * ce + alpha * (temperature ** 2) * kl
        return loss, {"ce": ce, "kl": kl, "aux": aux}


# --------------------------------------------------------------------------- #
# Colocated SPMD step (dry-run cell + numerical oracle)
# --------------------------------------------------------------------------- #
def build_colocated_step(t_cfg: ArchConfig, s_cfg: ArchConfig, mesh: Mesh,
                         shape: ShapeConfig, parallel: ParallelConfig, *,
                         alpha=0.5, temperature=2.0, impl="auto",
                         lr_schedule=None,
                         opt_cfg: adamw.AdamWConfig = adamw.AdamWConfig()):
    """One jit: teacher fwd (frozen) + student train step. Teacher unembed
    is passed separately (it lives with the student per §3.1).

    Dispatches like ``build_train_step``: ``ParallelConfig.cp > 1`` (mesh
    ``seq`` axis) runs both teacher and student attention through
    ``cp_attention``; ``pp > 1`` raises (no staged distill loss)."""
    from repro.train.step import (_act_hook_for, _split_microbatches,
                                  num_microbatches)
    # consolidated section-parallelism validation (one path for every
    # workload section, colocated or disaggregated): PP raises with the
    # section + mesh axis named (the distill loss tail is not staged)
    regime = wl.validate_section_parallel(
        "distill.colocated(teacher)", t_cfg, parallel, mesh)
    wl.validate_section_parallel(
        "distill.colocated(student)", s_cfg, parallel, mesh)
    cp_ctx = (_cp_ctx(mesh, t_cfg, s_cfg) if regime == "cp"
              else contextlib.nullcontext)
    t_rules = shd.rules_for(t_cfg, mesh, teacher=True)
    s_rules = shd.rules_for(s_cfg, mesh)
    t_specs = tf.lm_specs(t_cfg)
    s_specs = tf.lm_specs(s_cfg)
    tp_shard = shd.param_shardings(t_specs, mesh, t_rules)
    sp_shard = shd.param_shardings(s_specs, mesh, s_rules)
    o_shard = shd.opt_state_shardings(s_specs, mesh, s_rules, zero=True)
    batch_specs = {
        "tokens": jax.ShapeDtypeStruct((shape.global_batch, shape.seq_len),
                                       jnp.int32),
        "labels": jax.ShapeDtypeStruct((shape.global_batch, shape.seq_len),
                                       jnp.int32),
        "loss_mask": jax.ShapeDtypeStruct(
            (shape.global_batch, shape.seq_len), jnp.float32)}
    b_shard = shd.data_shardings(mesh, batch_specs)
    dp_total = shd.axis_size(mesh, shd.dp_axes(mesh))
    n_micro = num_microbatches(shape, mesh, parallel)
    hook = _act_hook_for(mesh, shape.global_batch // n_micro, shape.seq_len)
    lr_fn = lr_schedule or functools.partial(
        schedules.warmup_cosine, peak_lr=3e-4, warmup_steps=100,
        total_steps=10_000)
    rep = shd.replicated(mesh)

    def loss_fn(p_s, mb, params_t):
        with cm.act_hook(*hook), cp_ctx():
            h_t = teacher_hidden(jax.lax.stop_gradient(params_t), t_cfg,
                                 mb["tokens"], impl=impl)
            w_t = (params_t["embed"].T if t_cfg.tie_embeddings
                   else params_t["unembed"])
            # colocated SPMD: vocab-sharded naive KL — per-device logits
            # are [N, V/tp]; the chunked kernel is a *per-shard-local*
            # algorithm (it forces full-vocab gathers under SPMD) and
            # belongs to the disaggregated / Pallas-TPU paths
            return distill_loss(p_s, s_cfg, mb, h_t, w_t, alpha=alpha,
                                temperature=temperature, impl=impl,
                                kl_impl="ref_naive"
                                if kops.resolve(impl) == "ref" else "auto")

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def step(params_s, opt_state, params_t, batch, step_idx):
        if n_micro == 1:
            (loss, met), grads = grad_fn(params_s, batch, params_t)
        else:
            mbs_tree = _split_microbatches(batch, n_micro, dp_total)

            def micro(carry, mb):
                g_acc, l_acc = carry
                (loss, _), grads = grad_fn(params_s, mb, params_t)
                with jax.named_scope("grad_accum"):
                    g_acc = jax.tree_util.tree_map(
                        lambda a, g: a + g.astype(jnp.float32), g_acc,
                        grads)
                return (g_acc, l_acc + loss), None

            with jax.named_scope("grad_accum"):
                g0 = jax.tree_util.tree_map(
                    lambda x: jnp.zeros(x.shape, jnp.float32), params_s)
            (g_sum, l_sum), _ = jax.lax.scan(micro, (g0, jnp.float32(0)),
                                             mbs_tree)
            with jax.named_scope("grad_accum"):
                grads = jax.tree_util.tree_map(
                    lambda g, p: (g / n_micro).astype(p.dtype), g_sum,
                    params_s)
            loss = l_sum / n_micro
        lr = lr_fn(step_idx)
        new_p, new_opt, gnorm = adamw.update(grads, opt_state, lr, opt_cfg)
        return new_p, new_opt, {"loss": loss.astype(jnp.float32),
                                "grad_norm": gnorm, "lr": lr}

    jitted = jax.jit(step,
                     in_shardings=(sp_shard, o_shard, tp_shard, b_shard,
                                   rep),
                     out_shardings=(sp_shard, o_shard,
                                    {"loss": rep, "grad_norm": rep,
                                     "lr": rep}),
                     donate_argnums=(0, 1))
    return jitted, {"teacher": tp_shard, "student": sp_shard,
                    "opt": o_shard, "batch": b_shard}


# --------------------------------------------------------------------------- #
# Declarative workload spec + thin runtime wrapper (paper-faithful)
# --------------------------------------------------------------------------- #
def distill_spec(t_cfg: ArchConfig, s_cfg: ArchConfig, *,
                 teacher_parallel: ParallelConfig,
                 student_parallel: ParallelConfig,
                 alpha: float = 0.5, temperature: float = 2.0,
                 impl: str = "auto") -> wl.WorkloadSpec:
    """KD as a declaration: a forward-only teacher section emitting final
    hidden states, and the critical student section computing CE + KL
    with the teacher's (colocated) output layer as a const.  Left
    shape-polymorphic (no global_batch/seq_len): the generic runtime
    binds shapes from the first batch, one microbatch per iteration."""
    hidden = wl.Port("hidden", (wl.SEQ, t_cfg.d_model), t_cfg.dtype)

    def teacher_fn(pt, x):
        return {"hidden": teacher_hidden(pt, t_cfg, x["tokens"],
                                         impl=impl)}

    def student_fn(ps, x):
        batch = {"tokens": x["tokens"], "labels": x["labels"],
                 "loss_mask": x["loss_mask"]}
        loss, met = distill_loss(
            ps, s_cfg, batch, x["teacher.hidden"], x["w_t"], alpha=alpha,
            temperature=temperature, impl=impl,
            kl_impl="ref" if impl == "ref" else "auto")
        return loss, {"ce": met["ce"], "kl": met["kl"]}

    teacher = wl.SectionSpec(
        "teacher", t_cfg, teacher_parallel, teacher_fn,
        tf.lm_specs(t_cfg),
        inputs={"tokens": wl.Field((wl.SEQ,), "int32")},
        emits=(hidden,), mode="fwd_only")
    student = wl.SectionSpec(
        "student", s_cfg, student_parallel, student_fn,
        tf.lm_specs(s_cfg),
        inputs={"tokens": wl.Field((wl.SEQ,), "int32"),
                "labels": wl.Field((wl.SEQ,), "int32"),
                "loss_mask": wl.Field((wl.SEQ,), "float32", fill=1.0)},
        consumes=(wl.Consume("teacher", hidden),),
        loss=True, loss_aux=True, critical=True,
        consts={"w_t": wl.Field((t_cfg.d_model, t_cfg.padded_vocab),
                                t_cfg.dtype)})
    return wl.WorkloadSpec("distill", (teacher, student))


class DistillRuntime:
    """Teacher and student sections on disjoint meshes, hidden states
    flowing through the M-to-N message queue with fan-out.

    Now a thin declaration over the generic
    :class:`~repro.core.workload.CompoundRuntime` (``distill_spec``
    above): the teacher's forward and the student's loss are plain
    section fns; executor wiring, jitted AdamW, grad-norm and the
    realized timeline are the shared machinery distillation and MLLM
    training get from one place."""

    def __init__(self, t_cfg: ArchConfig, s_cfg: ArchConfig, *,
                 teacher_parallel: ParallelConfig,
                 student_parallel: ParallelConfig,
                 devices=None, alpha=0.5, temperature=2.0, impl="auto",
                 lr=1e-3):
        fanout = student_parallel.dp // teacher_parallel.dp
        assert teacher_parallel.dp * fanout == student_parallel.dp, \
            "fanout constraint (paper eq. 1) violated"
        self.fanout = fanout
        self.t_cfg, self.s_cfg = t_cfg, s_cfg
        self.alpha, self.temperature = alpha, temperature
        spec = distill_spec(t_cfg, s_cfg,
                            teacher_parallel=teacher_parallel,
                            student_parallel=student_parallel,
                            alpha=alpha, temperature=temperature,
                            impl=impl)
        self._crt = wl.CompoundRuntime(
            spec, devices=devices, impl=impl,
            lr_schedule=functools.partial(schedules.constant,
                                          peak_lr=lr))
        self.rt = self._crt.rt
        self.graph = self._crt.graph
        self.executor = self._crt.executor
        self.last_execution = None

    # ------------------------------------------------------------------ #
    def init(self, rng) -> Tuple:
        params, opts = self._crt.init(rng)
        return params["teacher"], params["student"], opts["student"]

    def teacher_unembed(self, params_t):
        w = (params_t["embed"].T if self.t_cfg.tie_embeddings
             else params_t["unembed"])
        return jax.device_put(jax.device_get(w),
                              shd.replicated(self.rt.mesh("student")))

    def train_iteration(self, params_t, params_s, opt, batch, step_idx, *,
                        w_t=None, timeout: float = 300.0):
        """One global-batch iteration on the compound executor: teacher
        fwd (its own mesh/worker) → hidden-state push → student pull +
        loss/grads, wavefront-submitted Dispatches with the realized
        timeline on ``last_execution``.  Returns (params_s, opt,
        metrics)."""
        if w_t is None:
            w_t = self.teacher_unembed(params_t)
        params, opts, metrics = self._crt.train_iteration(
            {"teacher": params_t, "student": params_s},
            {"student": opt}, batch, step_idx,
            consts={"student": {"w_t": w_t}}, timeout=timeout)
        self.last_execution = metrics["execution"]
        return params["student"], opts["student"], metrics

    def shutdown(self):
        self._crt.shutdown()
