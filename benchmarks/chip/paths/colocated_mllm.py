"""Colocated MLLM training on one chip: the jitted step of
``repro.mllm.workload.build_colocated_step`` (ViT over every microbatch's
image slots, LM with the image embeddings scattered in, per-microbatch
gradients accumulated, one AdamW update), fed per step as the system feeds
it: cost-model 6-tuples, the wavefront order, the microbatch plan, the
laid-out batch and its transfer to the device."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.chip import flops, gen, readings
from benchmarks.chip.reference import common as rc
from benchmarks.chip.reference import mllm as ref


def arch_configs(cfg: dict):
    """(vit, lm) ``ArchConfig``s of the program from the configuration
    file; refuses a file whose ViT the program does not build."""
    from repro.core.types import ArchConfig
    from repro.models import vlm
    v = cfg["vision_encoder"]
    lm = ArchConfig(**{k: cfg[k] for k in (
        "name", "family", "num_layers", "d_model", "num_heads",
        "num_kv_heads", "d_ff", "vocab_size", "head_dim", "vision_dim",
        "max_image_tokens", "rope_theta", "norm_eps", "dtype")})
    vit = vlm.vit_config(num_layers=v["num_layers"], d_model=v["d_model"],
                         num_heads=v["num_heads"], d_ff=v["d_ff"],
                         patch_dim=v["patch_dim"],
                         downsample=v["downsample"], out_dim=lm.vision_dim)
    built = (vit.rope_theta, vit.norm_eps, vit.norm_type, vit.mlp_act)
    if built != (v["rope_theta"], v["norm_eps"], "rms", "swiglu"):
        raise ValueError(f"the program's ViT is {built}, the configuration "
                         f"states {v}")
    return vit, lm


def pool(cfg: dict, tr: dict, seed: int, n: int) -> list:
    v = cfg["vision_encoder"]
    return gen.batches("vlm", seed, n, batch=tr["batch"],
                       seq_len=tr["seq_len"], vocab=cfg["vocab_size"],
                       vision_ratio=tr["vision_ratio"],
                       image_tokens=cfg["max_image_tokens"],
                       patch_dim=v["patch_dim"], downsample=v["downsample"])


def program_specs(vit, lm):
    from repro.models import vlm
    from repro.models.model import build_model
    return {"vit": vlm.vit_specs(vit), "lm": build_model(lm).specs()}


class Job:
    def __init__(self, cfg: dict, tr: dict, seed: int, devices, spans):
        from repro.core.types import ParallelConfig
        from repro.launch.mesh import make_mesh
        from repro.mllm import workload as mllm
        from repro.optim import adamw, schedules

        self.cfg, self.tr, self.seed, self.spans = cfg, tr, seed, spans
        self.mllm = mllm
        self.B, self.S, self.mbs = tr["batch"], tr["seq_len"], tr["mbs"]
        self.vit, self.lm = arch_configs(cfg)
        mesh = make_mesh((1, 1), ("data", "model"), devices=devices[:1])
        self.graph = mllm.mllm_spec(
            self.vit, self.lm, vit_parallel=ParallelConfig(),
            lm_parallel=ParallelConfig(), global_batch=self.B,
            seq_len=self.S, mbs=self.mbs).to_graph()
        o = cfg["optimizer"]
        self.step, self.shard = mllm.build_colocated_step(
            self.vit, self.lm, mesh, mbs=self.mbs, seq_len=self.S,
            lr_schedule=functools.partial(schedules.constant,
                                          peak_lr=o["lr"]),
            opt_cfg=adamw.AdamWConfig(b1=o["b1"], b2=o["b2"], eps=o["eps"],
                                      weight_decay=o["weight_decay"],
                                      clip_norm=o["clip_norm"]))
        self.layout = ref.layout(cfg)
        readings.check_layout(self.layout, program_specs(self.vit, self.lm))
        self.params = gen.draw_weights(self.layout, seed, jnp.bfloat16,
                                       self.shard["params"])
        self.opt = jax.jit(adamw.init, out_shardings=self.shard["opt"])(
            self.params)
        self.pool = pool(cfg, tr, seed, tr["pool"])
        self.tokens_per_step = self.B * self.S
        self.model_flops_per_step = flops.mllm_step(cfg, tr)
        v = cfg["vision_encoder"]
        n_mb = self.B // self.mbs
        P = cfg["max_image_tokens"] * v["downsample"]
        # flash-attention forward calls of one step: each microbatch's LM
        # layers (causal) and ViT layers over its mbs image slots, twice
        # each, since remat runs the forward again in the backward pass
        self.flash_calls = [
            (flops.flash_fwd(self.mbs, cfg["num_heads"], cfg["num_kv_heads"],
                             self.S, cfg["head_dim"], causal=True),
             2 * n_mb * cfg["num_layers"]),
            (flops.flash_fwd(self.mbs, v["num_heads"], v["num_heads"], P,
                             v["d_model"] // v["num_heads"], causal=False),
             2 * n_mb * v["num_layers"])]
        self.kl_calls: list = []

    # ------------------------------------------------------------------ #
    def prepare(self, i: int):
        from repro.core import cost_model
        from repro.core.executor import order_samples
        batch = self.pool[i % len(self.pool)]
        with self.spans("schedule"):
            has = batch["has_image"].astype(bool)
            samples = cost_model.sample_tuples(self.graph, {"vit": has},
                                               self.S, n=len(has))
            order, sched = order_samples(samples, reorder=True)
        with self.spans("prep"):
            plan = self.mllm.build_plan(order, has, self.mbs, sched)
            return jax.device_put(self.mllm.colocated_batch(batch, plan),
                                  self.shard["batch"])

    def dispatch(self, laid, i: int):
        self.params, self.opt, m = self.step(self.params, self.opt, laid,
                                             jnp.int32(i))
        return m

    def wait(self, m) -> float:
        jax.block_until_ready((self.params, self.opt, m))
        return float(m["loss"])

    def first_grads(self, m) -> dict:
        return readings.first_grads(self.opt, m["grad_norm"],
                                    self.cfg["optimizer"])

    def update_norms(self) -> dict:
        return readings.update_norms(self.opt.master, self.layout,
                                     self.seed)

    def free(self):
        self.params = self.opt = self.pool = None


#: faults that can be planted in the reference put in the program's place
FAULTS = ("half_batch", "pairing")


def reference(cfg: dict, tr: dict, seed: int, precision: str = "f32",
              fault: str | None = None) -> dict:
    """The plain reference over the checked steps' batches, grouped into
    microbatches by its own rule (``reference.mllm.wavefront_order``)."""
    from benchmarks.chip.harness import CHECKED_STEPS
    return ref.train(rc.Math(precision), cfg, seed,
                     pool(cfg, tr, seed, CHECKED_STEPS), tr["mbs"],
                     fault=fault)
