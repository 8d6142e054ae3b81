"""Step-roofline scoreboard: prove the distributed step stopped paying
the known FLOP/comm waste, from compiled post-SPMD HLO.

Three claims, each asserted (a regression fails the bench, and CI):

* **vocab-parallel PP cross-entropy** — on the same ``pp`` mesh, the
  per-device unembed-projection dot FLOPs drop by ``pp×`` vs the masked
  full-vocab baseline, and NO full-vocab dot remains.
* **TP inside PP stages** — with ``tp=2`` carved into the stage bodies,
  the per-device FFN dot FLOPs halve (Megatron column/row sharding).
* **compressed DP grad all-reduce** — ring-model collective wire bytes
  of the bf16 / int8 steps are ≤ 0.55× / ≤ 0.35× the fp32 baseline, and
  the compressed payloads ship as 2-byte ``u16`` (bitcast bf16) /
  1-byte ``s8`` on the wire.

The expectations are no longer inline asserts: each claim is a
declarative gate file under ``repro/analysis/gates/`` (``vp_ce`` /
``tp_in_stage`` / ``compress``), evaluated by
``repro.analysis.hlo_gates`` — the scoreboard numbers below come from
the same evaluation that asserts them.

Run via ``python benchmarks/run.py --step-roofline`` (subprocess with 8
virtual devices); the JSON lands in ``BENCH_step_roofline.json`` at the
repo root.  Numbers are per-device (post-SPMD HLO shapes are local).
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as ShdP

from repro.analysis import hlo_gates
from repro.configs import get_reduced
from repro.core.types import ParallelConfig, ShapeConfig
from repro.dist import sharding as shd
from repro.dist.pipeline import build_pp_loss
from repro.models import transformer as tf
from repro.models.common import init_params
from repro.models.model import build_model
from repro.optim import adamw
from repro.train import step as step_mod
from repro.launch.mesh import make_mesh

GB, S, PP, TP = 8, 32, 4, 2
# dims chosen so the vocab shard (256) / full vocab (1024) / d_ff (160)
# collide with no other dot-output width in the program
CFG = get_reduced("qwen1.5-0.5b").replace(
    dtype="float32", num_layers=4, vocab_size=1024, d_model=64,
    num_heads=4, num_kv_heads=4, head_dim=16, d_ff=160,
    tie_embeddings=False)


def make_batch(cfg, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (GB, S)),
                              jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (GB, S)),
                              jnp.int32),
        "loss_mask": jnp.ones((GB, S), jnp.float32)}


def pp_grad_hlo(cfg, mesh, *, vocab_parallel):
    loss_fn, _ = build_pp_loss(cfg, mesh, n_micro=2, impl="ref",
                               vocab_parallel=vocab_parallel)
    params = init_params(tf.lm_specs(cfg), jax.random.PRNGKey(0))
    batch = make_batch(cfg)
    with mesh:
        return jax.jit(
            jax.value_and_grad(lambda p: loss_fn(p, batch))
        ).lower(params).compile().as_text()


def _gate(name: str, programs: dict, symbols=None) -> dict:
    """Evaluate one gate file; die on any ERROR finding; return the
    measurements keyed by check id (the scoreboard reads numbers from
    the same evaluation that asserted them)."""
    rep, measured = hlo_gates.evaluate_file(
        hlo_gates.GATES_DIR / f"{name}.json", programs, symbols=symbols)
    rep.raise_on_error(AssertionError)
    return measured


def vp_ce_claim() -> dict:
    """Unembed dot FLOPs no longer scale with pp (gate: vp_ce)."""
    mesh = make_mesh((2, PP), ("data", "pipe"))
    m = _gate("vp_ce",
              {"masked": pp_grad_hlo(CFG, mesh, vocab_parallel=False),
               "vp": pp_grad_hlo(CFG, mesh, vocab_parallel=True)})
    return {"pp": PP, "full_vocab_dot_flops": m["baseline_full_vocab"],
            "vocab_shard_dot_flops": m["shard_present"],
            "reduction": m["reduction"]}


def tp_in_stage_claim() -> dict:
    """TP inside the stage bodies shards the FFN compute (gate:
    tp_in_stage; per-sample normalization for the dp-2 vs dp-1 meshes
    lives in the gate's num_scale/den_scale)."""
    m1 = make_mesh((2, 2, 1), ("data", "pipe", "model"))
    m2 = make_mesh((1, 2, TP), ("data", "pipe", "model"))
    m = _gate("tp_in_stage",
              {"tp1": pp_grad_hlo(CFG, m1, vocab_parallel=True),
               "tp2": pp_grad_hlo(CFG, m2, vocab_parallel=True)})
    return {"tp": TP,
            "ffn_dot_flops_tp1_per_sample": m["tp1_ffn_present"] / (GB // 2),
            "ffn_dot_flops_tp2_per_sample": m["tp2_shard_present"] / GB,
            "reduction": m["reduction"]}


def compressed_step_hlo(method: str) -> str:
    cfg = CFG.replace(num_layers=2)
    model = build_model(cfg, impl="ref")
    par = ParallelConfig(dp=8, mbs=1, zero_opt=False,
                         grad_compress=method)
    shape = ShapeConfig("t", "train", S, GB)
    mesh = shd.section_mesh(jax.devices(), par)
    step, sh = step_mod.build_train_step(model, mesh, par, shape,
                                         opt_cfg=adamw.AdamWConfig())
    params = jax.device_put(model.init(jax.random.PRNGKey(0)),
                            sh["params"])
    opt = jax.device_put(adamw.init(params), sh["opt"])
    batch = make_batch(cfg)
    args = [params, opt, batch, jnp.int32(0)]
    if method != "none":
        args.append(sh["ef_init"](params))
    with mesh:
        return step.lower(*args).compile().as_text()


def grad_reduce_hlo(method: str) -> str:
    """HLO of the DP gradient reduction alone (exact psum vs compressed),
    over the real 2-layer gradient tree, so the wire ratio is not diluted
    by unrelated collectives XLA adds to the full step (it reshards the
    elementwise optimizer math over dp and all-gathers the result)."""
    from repro.optim import compression as gcomp
    cfg = CFG.replace(num_layers=2)
    g = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
        build_model(cfg, impl="ref").param_shapes())
    mesh = make_mesh((8,), ("data",))

    if method == "none":
        def reduce_fn(grads, _ef):
            return jax.tree_util.tree_map(
                lambda x: jax.lax.psum(x, "data") / 8.0, grads)
    else:
        def reduce_fn(grads, ef):
            red, _ = gcomp.ef_compress_tree(
                grads, gcomp.ErrorFeedback(ef), "data", method)
            return red
    run = shd.shard_map(reduce_fn, mesh, (ShdP(), ShdP()), ShdP())
    with mesh:
        return jax.jit(run).lower(g, g).compile().as_text()


def compress_claim() -> dict:
    """Compressed DP grad all-reduce halves / quarters wire bytes
    (gate: compress — the isolated reduction's wire ratios, the
    compressed payload dtypes in the full step, and the f32 all-reduce
    residue are all declared there)."""
    programs = {}
    for meth in ("none", "bf16", "int8"):
        programs[f"red_{meth}"] = grad_reduce_hlo(meth)
        programs[f"step_{meth}"] = compressed_step_hlo(meth)
    m = _gate("compress", programs)
    return {"dp": 8,
            "bf16_over_fp32": m["bf16_over_fp32"],
            "int8_over_fp32": m["int8_over_fp32"],
            "step_u16_wire_bytes": m["bf16_ships_u16"],
            "step_s8_wire_bytes": m["int8_ships_s8"],
            "step_f32_allreduce_ratio": {"bf16": m["bf16_f32_ar_ratio"],
                                         "int8": m["int8_f32_ar_ratio"]}}


def main() -> None:
    out = {"vp_ce": vp_ce_claim(),
           "tp_in_stage": tp_in_stage_claim(),
           "compress": compress_claim()}
    print(json.dumps(out))


if __name__ == "__main__":
    sys.exit(main())
