"""Colocated teacher-to-student distillation on one chip: the jitted step
of ``repro.distill.workload.build_colocated_step`` (the frozen teacher's
forward pass, the student's forward and backward passes, CE plus the
Pallas ``distill_kl`` term, per-microbatch gradients accumulated, one
AdamW update), fed per step with the batch's transfer to the device."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.chip import flops, gen, readings
from benchmarks.chip.reference import common as rc
from benchmarks.chip.reference import distill as ref

ARCH_KEYS = ("name", "family", "num_layers", "d_model", "num_heads",
             "num_kv_heads", "d_ff", "vocab_size", "head_dim",
             "tie_embeddings", "rope_theta", "norm_eps", "dtype")


def arch_config(c: dict):
    from repro.core.types import ArchConfig
    return ArchConfig(**{k: c[k] for k in ARCH_KEYS})


def pool(cfg: dict, tr: dict, seed: int, n: int) -> list:
    return gen.batches("text", seed, n, batch=tr["batch"],
                       seq_len=tr["seq_len"],
                       vocab=cfg["student"]["vocab_size"])


class Job:
    def __init__(self, cfg: dict, tr: dict, seed: int, devices, spans):
        from repro.core.types import ParallelConfig, ShapeConfig
        from repro.distill import workload as dw
        from repro.launch.mesh import make_mesh
        from repro.models import transformer as tf
        from repro.optim import adamw, schedules

        self.cfg, self.tr, self.seed, self.spans = cfg, tr, seed, spans
        t_cfg, s_cfg = arch_config(cfg["teacher"]), arch_config(
            cfg["student"])
        B, S, mbs = tr["batch"], tr["seq_len"], tr["mbs"]
        mesh = make_mesh((1, 1), ("data", "model"), devices=devices[:1])
        o, d = cfg["optimizer"], cfg["distill"]
        self.step, self.shard = dw.build_colocated_step(
            t_cfg, s_cfg, mesh, ShapeConfig("bench", "train", S, B),
            ParallelConfig(mbs=mbs), alpha=d["alpha"],
            temperature=d["temperature"],
            lr_schedule=functools.partial(schedules.constant,
                                          peak_lr=o["lr"]),
            opt_cfg=adamw.AdamWConfig(b1=o["b1"], b2=o["b2"], eps=o["eps"],
                                      weight_decay=o["weight_decay"],
                                      clip_norm=o["clip_norm"]))
        self.layout = ref.layout(cfg)
        readings.check_layout(self.layout, {"student": tf.lm_specs(s_cfg),
                                   "teacher": tf.lm_specs(t_cfg)})
        w = gen.draw_weights(self.layout, seed, jnp.bfloat16,
                             {"student": self.shard["student"],
                              "teacher": self.shard["teacher"]})
        self.ps, self.pt = w["student"], w["teacher"]
        self.opt = jax.jit(adamw.init, out_shardings=self.shard["opt"])(
            self.ps)
        self.pool = pool(cfg, tr, seed, tr["pool"])
        self.tokens_per_step = B * S
        self.model_flops_per_step = flops.distill_step(cfg, tr)
        t, s = cfg["teacher"], cfg["student"]
        n_mb = B // mbs
        # the frozen teacher's layers run the forward kernel once, the
        # student's twice (remat runs it again in the backward pass)
        self.flash_calls = [
            (flops.flash_fwd(mbs, c["num_heads"], c["num_kv_heads"], S,
                             c["head_dim"], causal=True),
             runs * n_mb * c["num_layers"]) for c, runs in ((t, 1), (s, 2))]
        self.kl_calls = [(flops.distill_kl_stats(
            mbs * S, s["vocab_size"], s["d_model"], t["d_model"]), n_mb)]

    def prepare(self, i: int):
        batch = self.pool[i % len(self.pool)]
        with self.spans("prep"):
            return jax.device_put(
                {k: batch[k] for k in ("tokens", "labels", "loss_mask")},
                self.shard["batch"])

    def dispatch(self, dev_batch, i: int):
        self.ps, self.opt, m = self.step(self.ps, self.opt, self.pt,
                                         dev_batch, jnp.int32(i))
        return m

    def wait(self, m) -> float:
        jax.block_until_ready((self.ps, self.opt, m))
        return float(m["loss"])

    def first_grads(self, m) -> dict:
        return readings.first_grads(self.opt, m["grad_norm"],
                                    self.cfg["optimizer"])

    def update_norms(self) -> dict:
        return readings.update_norms(self.opt.master, self.layout,
                                     self.seed, "student")

    def free(self):
        self.ps = self.pt = self.opt = self.pool = None


#: faults that can be planted in the reference put in the program's place
FAULTS = ("half_batch",)


def reference(cfg: dict, tr: dict, seed: int, precision: str = "f32",
              fault: str | None = None) -> dict:
    from benchmarks.chip.harness import CHECKED_STEPS
    return ref.train(rc.Math(precision), cfg, seed,
                     pool(cfg, tr, seed, CHECKED_STEPS), tr["mbs"],
                     drop_half=fault == "half_batch")
