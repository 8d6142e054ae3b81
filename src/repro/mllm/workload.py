"""MLLM compound workload: ViT encoder section → LLM backbone section.

The headline Maestro workload (§2.1/§4.1): modality sections activate
*per sample* — text-only samples bypass the vision section entirely — and
the wavefront scheduler reorders samples so the critical (LLM) section
never stalls on vision work.  Two execution modes share one arithmetic:

* :func:`build_colocated_step` — ONE jit: scan over microbatches, each
  doing ViT-encode of its image samples (gathered to the static per-
  microbatch capacity, and only where the microbatch holds an image) +
  LM loss with image-slot injection, grads accumulated in microbatch
  order.  This is the numerical oracle.
* :class:`MLLMRuntime` — disaggregated on the compound executor: the ViT
  section runs fwd/bwd tasks for *image-bearing microbatches only* on its
  own carved mesh, embeddings / embedding-cotangents cross the
  MessageQueue, and the per-iteration microbatch composition comes from
  the wavefront dispatch order.

Because both modes perform the same per-microbatch computations in the
same order (both only *skip* vision work whose contribution is an exact
zero), the disaggregated per-step loss and grads match the
colocated oracle bit-for-bit on equal section layouts — driver-verified
on mixed and all-text batches (``tests/drivers/driver_mllm_runtime.py``).

Static vs dynamic shapes: each microbatch has a *static* vision capacity
(= its sample count); image samples are gathered into that capacity and
zero-padded — padding only ever exists inside a microbatch that holds
an image.  Whether a microbatch runs vision work at all is dynamic
(data-dependent activation): a task the executor does not dispatch in
:class:`MLLMRuntime`, a ``lax.cond`` branch in the colocated step.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import workload as wl
from repro.core.scheduler import ScheduleResult
from repro.core.types import ArchConfig, ParallelConfig
from repro.dist import sharding as shd
from repro.models import common as cm
from repro.models import vlm
from repro.models.model import Model, build_model
from repro.optim import adamw, schedules
from repro.train.step import _act_hook_for

#: batch keys the LM step consumes (vision arrives as ``image_embeds``)
LM_KEYS = ("tokens", "labels", "loss_mask", "image_pos", "image_valid")


# --------------------------------------------------------------------------- #
# Shared per-microbatch arithmetic (oracle ≡ disaggregated, bit-for-bit)
# --------------------------------------------------------------------------- #
def vit_forward(pv, vit_cfg: ArchConfig, patches, valid, *,
                impl: str = "auto", remat: bool = True):
    """ViT-encode the gathered image samples of one microbatch and mask
    padding rows, under the scope ``vit``.  patches [cap, P, pd], valid
    [cap] → emb [cap, K, Vd]."""
    with jax.named_scope("vit"):
        emb = vlm.vit_encode(pv, vit_cfg, patches, impl=impl, remat=remat)
        return emb * valid[:, None, None].astype(emb.dtype)


def lm_microbatch_loss(pl, model: Model, mb: dict, emb, vidx):
    """LM loss of one microbatch: scatter the (masked) vision embeddings
    back into per-sample image slots, then the backbone loss with
    image-slot injection, under the scope ``lm``.  emb [cap, K, Vd], vidx
    [cap] local indices."""
    with jax.named_scope("lm"):
        mbs_n = mb["tokens"].shape[0]
        img = jnp.zeros((mbs_n,) + emb.shape[1:], emb.dtype).at[vidx].add(
            emb)
        lmb = {k: mb[k] for k in LM_KEYS if k in mb}
        lmb["image_embeds"] = img
        loss, _ = model.loss(pl, lmb)
        return loss


# --------------------------------------------------------------------------- #
# Per-iteration plan: wavefront order → microbatch composition
# --------------------------------------------------------------------------- #
class IterationPlan(wl.IterationPlan):
    """The generic :class:`repro.core.workload.IterationPlan` with the
    MLLM-historical accessors (the ViT is the one activated section)."""

    @property
    def image_mbs(self):
        return self.activation["vit"].active_mbs

    @property
    def vis_idx(self):
        return self.activation["vit"].idx

    @property
    def vis_valid(self):
        return self.activation["vit"].valid


def build_plan(order: Sequence[int], has_image: np.ndarray, mbs: int,
               schedule: Optional[ScheduleResult] = None) -> IterationPlan:
    act = wl.build_activation(order, has_image, mbs)
    return IterationPlan(tuple(order), mbs, len(order) // mbs,
                         {"vit": act}, schedule)


def colocated_batch(batch: dict, plan: IterationPlan) -> dict:
    """Lay one global batch out for the colocated oracle: permute into the
    plan's dispatch order and pre-split into [n_mb, mbs, ...] so the
    oracle's scan sees exactly the executor's microbatch composition.
    Under a profiler it is the host span ``layout``."""
    with jax.profiler.TraceAnnotation("layout"):
        idx = list(plan.order)
        out = {}
        for k in LM_KEYS + ("patches",):
            v = np.asarray(batch[k])[idx]
            out[k] = jnp.asarray(
                v.reshape((plan.n_mb, plan.mbs) + v.shape[1:]))
        out["vis_idx"] = jnp.asarray(plan.vis_idx)
        out["vis_valid"] = jnp.asarray(plan.vis_valid)
        return out


# --------------------------------------------------------------------------- #
# Colocated single-jit oracle
# --------------------------------------------------------------------------- #
def build_colocated_step(vit_cfg: ArchConfig, lm_cfg: ArchConfig,
                         mesh: Mesh, *, mbs: int, seq_len: int,
                         impl: str = "auto", lr_schedule=None,
                         opt_cfg: adamw.AdamWConfig = adamw.AdamWConfig(),
                         return_grads: bool = False):
    """One jit over the pre-microbatched batch from
    :func:`colocated_batch`: per microbatch, ViT fwd on the gathered image
    samples + LM loss, per-microbatch joint grads accumulated in dispatch
    order, one AdamW update.  A microbatch with no image skips the ViT
    (forward and backward) under a ``lax.cond`` and hands the LM zeros,
    which is what the masked ViT output would be; the metric ``vit_mbs``
    counts the microbatches whose ViT ran.  ``params`` and ``opt_state``
    are donated.  Returns (step, shardings)."""
    model = build_model(lm_cfg, impl=impl)
    v_specs = vlm.vit_specs(vit_cfg)
    l_specs = model.specs()
    l_rules = shd.rules_for(lm_cfg, mesh)
    v_rules = shd.rules_for(vit_cfg, mesh)
    p_shard = {"lm": shd.param_shardings(l_specs, mesh, l_rules),
               "vit": shd.param_shardings(v_specs, mesh, v_rules)}
    ol = shd.opt_state_shardings(l_specs, mesh, l_rules)
    ov = shd.opt_state_shardings(v_specs, mesh, v_rules)
    o_shard = adamw.AdamWState(step=ol.step, mu={"lm": ol.mu, "vit": ov.mu},
                               nu={"lm": ol.nu, "vit": ov.nu},
                               master={"lm": ol.master, "vit": ov.master})
    dp = shd.dp_axes(mesh) or None
    rep = shd.replicated(mesh)

    def mb_sharding(ndim):
        return NamedSharding(mesh, P(None, dp, *([None] * (ndim - 2))))

    b_shard = {"tokens": mb_sharding(3), "labels": mb_sharding(3),
               "loss_mask": mb_sharding(3), "image_pos": mb_sharding(3),
               "image_valid": mb_sharding(3), "patches": mb_sharding(4),
               "vis_idx": rep, "vis_valid": rep}
    hook = _act_hook_for(mesh, mbs, seq_len)
    lr_fn = lr_schedule or functools.partial(schedules.constant,
                                             peak_lr=1e-3)

    emb_shape = (mbs, lm_cfg.max_image_tokens, lm_cfg.vision_dim)

    def joint_loss(ps, mb, vidx, vval, vit_on):
        with cm.act_hook(*hook):
            sub = mb["patches"][vidx]
            # an imageless microbatch's masked ViT output is exact zeros
            # and its ViT gradient an exact zero: skip computing them
            emb = jax.lax.cond(
                vit_on,
                lambda pv, x, v: vit_forward(pv, vit_cfg, x, v, impl=impl),
                lambda pv, x, v: jnp.zeros(emb_shape, pv["merge_proj"].dtype),
                ps["vit"], sub, vval)
            return lm_microbatch_loss(ps["lm"], model, mb, emb, vidx)

    grad_fn = jax.value_and_grad(joint_loss)

    def step(params, opt_state, batch, step_idx):
        n_mb = batch["tokens"].shape[0]
        mbs_tree = {k: batch[k] for k in LM_KEYS + ("patches",)}
        vit_on = jnp.any(batch["vis_valid"] > 0, axis=1)

        def body(carry, xs):
            g_acc, l_acc = carry
            loss, g = grad_fn(params, *xs)
            with jax.named_scope("grad_accum"):
                g_acc = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g)
            return (g_acc, l_acc + loss), None

        with jax.named_scope("grad_accum"):
            g0 = jax.tree_util.tree_map(
                lambda x: jnp.zeros(x.shape, jnp.float32), params)
        (g_sum, l_sum), _ = jax.lax.scan(
            body, (g0, jnp.float32(0)),
            (mbs_tree, batch["vis_idx"], batch["vis_valid"], vit_on))
        with jax.named_scope("grad_accum"):
            grads = jax.tree_util.tree_map(
                lambda g, p: (g / n_mb).astype(p.dtype), g_sum, params)
        loss = l_sum / n_mb
        lr = lr_fn(step_idx)
        new_p, new_opt, gnorm = adamw.update(grads, opt_state, lr, opt_cfg)
        metrics = {"loss": loss.astype(jnp.float32), "grad_norm": gnorm,
                   "lr": lr, "vit_mbs": jnp.sum(vit_on, dtype=jnp.int32)}
        if return_grads:
            metrics["grads"] = grads
        return new_p, new_opt, metrics

    out_metrics = {"loss": rep, "grad_norm": rep, "lr": rep, "vit_mbs": rep}
    if return_grads:
        out_metrics["grads"] = p_shard
    # params and optimizer state are donated: at published widths two
    # copies of them do not fit one chip's HBM
    jitted = jax.jit(step,
                     in_shardings=(p_shard, o_shard, b_shard, rep),
                     out_shardings=(p_shard, o_shard, out_metrics),
                     donate_argnums=(0, 1))
    return jitted, {"params": p_shard, "opt": o_shard, "batch": b_shard}


def init_compound_params(vit_cfg: ArchConfig, lm_cfg: ArchConfig, rng,
                         shardings=None):
    """Joint {vit, lm} params, drawn into ``shardings`` (``{"vit", "lm"}``
    sharding trees: the oracle's or the section meshes') or, without it,
    onto the default device.  The values do not depend on the
    placement."""
    model = build_model(lm_cfg)
    r_v, r_l = jax.random.split(rng)
    sh = shardings or {"vit": None, "lm": None}
    return {"vit": cm.init_params(vlm.vit_specs(vit_cfg), r_v, sh["vit"]),
            "lm": cm.init_params(model.specs(), r_l, sh["lm"])}


# --------------------------------------------------------------------------- #
# Declarative workload spec + thin runtime wrapper
# --------------------------------------------------------------------------- #
def mllm_spec(vit_cfg: ArchConfig, lm_cfg: ArchConfig, *,
              vit_parallel: ParallelConfig, lm_parallel: ParallelConfig,
              global_batch: int, seq_len: int, mbs: int,
              impl: str = "auto") -> wl.WorkloadSpec:
    """The MLLM workload as a declaration: a data-dependent ViT section
    emitting per-microbatch vision embeddings, and the critical LM
    section scattering them into image slots.  Everything else — carved
    meshes, jits, AdamW, joint grad-norm, wavefront dispatch — is the
    generic :class:`repro.core.workload.CompoundRuntime`."""
    model = build_model(lm_cfg, impl=impl)
    K, Vd = lm_cfg.max_image_tokens, lm_cfg.vision_dim
    P = K * vlm.downsample_factor(vit_cfg)
    pd = vit_cfg.frontend_dim
    emb = wl.Port("emb", (K, Vd), vit_cfg.dtype)

    def vit_fn(pv, x):
        return {"emb": vit_forward(pv, vit_cfg, x["patches"],
                                   x["act_valid"], impl=impl)}

    def llm_fn(pl, x):
        mb = {k: x[k] for k in LM_KEYS}
        return lm_microbatch_loss(pl, model, mb, x["vit.emb"],
                                  x["vit.act_idx"])

    vit = wl.SectionSpec(
        "vit", vit_cfg, vit_parallel, vit_fn, vlm.vit_specs(vit_cfg),
        inputs={"patches": wl.Field((P, pd), vit_cfg.dtype)},
        emits=(emb,),
        activation=lambda b: np.asarray(b["has_image"]).astype(bool),
        seq_len=P)
    llm = wl.SectionSpec(
        "llm", lm_cfg, lm_parallel, llm_fn, model.specs(),
        inputs={"tokens": wl.Field((wl.SEQ,), "int32"),
                "labels": wl.Field((wl.SEQ,), "int32"),
                "loss_mask": wl.Field((wl.SEQ,), "float32", fill=1.0),
                "image_pos": wl.Field((K,), "int32"),
                "image_valid": wl.Field((K,), "int32")},
        consumes=(wl.Consume("vit", emb),),
        loss=True, critical=True)
    return wl.WorkloadSpec("mllm", (vit, llm), seq_len=seq_len,
                           global_batch=global_batch, mbs=mbs)


class MLLMRuntime:
    """ViT and LLM sections on disjoint carved meshes, driven by the
    generic :class:`~repro.core.workload.CompoundRuntime` — this class is
    now only the historical parameter/metric surface (params keyed
    ``{"vit", "lm"}``, ``n_vit_tasks``, the MLLM ``IterationPlan``) over
    the declarative spec above.  Section parallelism goes through the
    consolidated ``validate_section_parallel`` path, so dp/tp *and* CP
    configs (the paper gives the ViT's long patch sequences to CP) run
    through the executor; only PP still raises."""

    def __init__(self, vit_cfg: ArchConfig, lm_cfg: ArchConfig, *,
                 vit_parallel: ParallelConfig, lm_parallel: ParallelConfig,
                 global_batch: int, seq_len: int, mbs: int,
                 devices=None, impl: str = "auto", lr_schedule=None,
                 opt_cfg: adamw.AdamWConfig = adamw.AdamWConfig(),
                 lookahead: int = 0):
        assert global_batch % mbs == 0, (global_batch, mbs)
        self.vit_cfg, self.lm_cfg = vit_cfg, lm_cfg
        self.impl = impl
        self.opt_cfg = opt_cfg
        self.B, self.S, self.mbs = global_batch, seq_len, mbs
        self.n_mb = global_batch // mbs
        self.K = lm_cfg.max_image_tokens
        self.Vd = lm_cfg.vision_dim
        self.P = self.K * vlm.downsample_factor(vit_cfg)
        self.pd = vit_cfg.frontend_dim
        spec = mllm_spec(vit_cfg, lm_cfg, vit_parallel=vit_parallel,
                         lm_parallel=lm_parallel,
                         global_batch=global_batch, seq_len=seq_len,
                         mbs=mbs, impl=impl)
        self._crt = wl.CompoundRuntime(
            spec, devices=devices, impl=impl,
            lr_schedule=lr_schedule or functools.partial(
                schedules.constant, peak_lr=1e-3),
            opt_cfg=opt_cfg, lookahead=lookahead)
        self.rt = self._crt.rt
        self.executor = self._crt.executor
        self.graph = self._crt.graph

    # ------------------------------------------------------------------ #
    def init(self, rng):
        sh = self._crt._p_shard
        params = init_compound_params(self.vit_cfg, self.lm_cfg, rng,
                                      {"vit": sh["vit"], "lm": sh["llm"]})
        return self.place(params)

    def place(self, params):
        """Place a joint {vit, lm} param tree onto the section meshes and
        build matching optimizer states."""
        p, o = self._crt.place({"vit": params["vit"],
                                "llm": params["lm"]})
        return ({"vit": p["vit"], "lm": p["llm"]},
                {"vit": o["vit"], "lm": o["llm"]})

    def plan_iteration(self, has_image, *, reorder: bool = True
                       ) -> IterationPlan:
        p = self._crt.plan_iteration(
            {"has_image": np.asarray(has_image)}, reorder=reorder)
        return IterationPlan(p.order, p.mbs, p.n_mb, p.activation,
                             p.schedule)

    # ------------------------------------------------------------------ #
    @staticmethod
    def _remap_metrics(metrics, return_grads):
        # n_vit_tasks keeps its historical meaning — data-dependent
        # compute tasks only — so the worker-side ``upd`` (which every
        # trainable section always runs) is excluded
        metrics["n_vit_tasks"] = metrics["n_tasks"].get("vit", 0) - 1
        if return_grads:
            g = metrics["grads"]
            metrics["grads"] = {"lm": g["llm"], "vit": g["vit"]}
        return metrics

    def train_iteration(self, params, opts, batch, step_idx, *,
                        reorder: bool = True,
                        plan: Optional[IterationPlan] = None,
                        return_grads: bool = False,
                        timeout: float = 300.0):
        """One serialized global-batch iteration through the executor.
        Returns (params, opts, metrics) with metrics carrying the
        realized ExecutionResult (timeline, makespan, utilization) and
        the plan."""
        if plan is None:
            plan = self.plan_iteration(np.asarray(batch["has_image"]),
                                       reorder=reorder)
        p, o, metrics = self._crt.train_iteration(
            {"vit": params["vit"], "llm": params["lm"]},
            {"vit": opts["vit"], "llm": opts["lm"]},
            batch, step_idx, plan=plan, return_grads=return_grads,
            timeout=timeout)
        self._remap_metrics(metrics, return_grads)
        return ({"vit": p["vit"], "lm": p["llm"]},
                {"vit": o["vit"], "lm": o["llm"]}, metrics)

    # ------------------------------------------------------------------ #
    # streaming surface (cross-iteration lookahead)
    # ------------------------------------------------------------------ #
    def install(self, params, opts):
        """Adopt (params, opts) — keyed ``{"vit", "lm"}`` — as the
        streaming state advanced by worker-side updates."""
        self._crt.install({"vit": params["vit"], "llm": params["lm"]},
                          {"vit": opts["vit"], "llm": opts["lm"]})

    def state(self):
        p, o = self._crt.state()
        return ({"vit": p["vit"], "lm": p["llm"]},
                {"vit": o["vit"], "lm": o["llm"]})

    @property
    def in_flight(self) -> int:
        return self._crt.in_flight

    @property
    def lookahead(self) -> int:
        return self._crt.lookahead

    @lookahead.setter
    def lookahead(self, depth: int) -> None:
        self._crt.lookahead = int(depth)

    def submit_iteration(self, batch, step_idx, *,
                         reorder: bool = True,
                         plan: Optional[IterationPlan] = None,
                         return_grads: bool = False,
                         timeout: float = 300.0) -> int:
        if plan is None:
            plan = self.plan_iteration(np.asarray(batch["has_image"]),
                                       reorder=reorder)
        return self._crt.submit_iteration(
            batch, step_idx, plan=plan, return_grads=return_grads,
            timeout=timeout)

    def retire(self, *, timeout: float = 300.0):
        metrics = self._crt.retire(timeout=timeout)
        return self._remap_metrics(metrics, "grads" in metrics)

    def drain(self, *, timeout: float = 300.0):
        return [self._remap_metrics(m, "grads" in m)
                for m in self._crt.drain(timeout=timeout)]

    def shutdown(self):
        self._crt.shutdown()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()
        return False
