import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count="
    + os.environ.get("REPRO_DRYRUN_DEVICES", "512")
    + (" " + os.environ["XLA_FLAGS"] if "XLA_FLAGS" in os.environ else ""))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

"""Compound-workload dry-run: lower + compile a compound training cell on
the production mesh — the cells most representative of the paper's
technique.

Registered workloads (``--workload``, one runner per registry entry):

* ``distill`` (default): the colocated distillation step (teacher fwd +
  student train with hidden-state handoff, §3.1).
* ``mllm``: the colocated MLLM oracle step from ``repro.mllm.workload``
  — the single-jit formulation the disaggregated executor runtime is
  bit-for-bit equivalent to (scan over microbatches, ViT encode + LM
  loss with image-slot injection).
* ``multi_teacher``: the colocated multi-teacher distillation step from
  ``repro.distill.multi_teacher`` — two frozen teachers (specialist
  domain-routed) + chunked-vocab KL student, the reference for the
  declarative ``WorkloadSpec``/``CompoundRuntime`` third workload.

    PYTHONPATH=src python -m repro.launch.dryrun_compound \
        [--workload distill --teacher granite-3-8b --student granite-3-8b]
    PYTHONPATH=src python -m repro.launch.dryrun_compound \
        --workload mllm [--arch pixtral-12b]
    PYTHONPATH=src python -m repro.launch.dryrun_compound \
        --workload multi_teacher [--teacher2 granite-3-8b]

``REPRO_DRYRUN_TINY=1`` reduces every workload to an 8-device-friendly
cell (pair with ``REPRO_DRYRUN_DEVICES`` / ``REPRO_DRYRUN_MESH``) — the
CI driver-smoke job lowers every registered workload that way.
"""
import argparse
import json
import time
from pathlib import Path


def _emit(rec: dict, out_dir: str, name: str) -> None:
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    (out / name).write_text(json.dumps(rec, indent=2))
    print(json.dumps(rec["roofline"]))
    print("useful:", rec["useful_flops_ratio"])
    print("wrote", out / name)


def _analyze(compiled, rec: dict, model_flops: float, n_devices: int):
    from repro.roofline.analysis import analyze_hlo, roofline_terms
    mem = compiled.memory_analysis()
    rec["memory"] = {k: int(getattr(mem, k)) for k in
                     ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "alias_size_in_bytes")}
    stats = analyze_hlo(compiled.as_text())
    rec["roofline"] = roofline_terms(stats)
    rec["hlo"] = {"flops_per_device": stats.flops,
                  "hbm_bytes_per_device": stats.hbm_bytes,
                  "deep_loop_bytes_per_device": stats.deep_loop_bytes,
                  "collective_bytes_per_device": stats.collective_bytes}
    rec["model_flops"] = model_flops
    rec["useful_flops_ratio"] = model_flops / max(
        stats.flops * n_devices, 1)
    return rec


def _run_distill(args) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.types import ParallelConfig, ShapeConfig
    from repro.distill.workload import build_colocated_step
    from repro.launch.mesh import make_production_mesh
    from repro.models import transformer as tf
    from repro.models.common import param_shapes
    from repro.optim import adamw

    from repro.configs import reduce_config
    from repro.launch.mesh import mesh_from_env

    t_cfg = get_config(args.teacher)
    s_cfg = get_config(args.student)
    seq, batch = args.seq, args.batch
    if os.environ.get("REPRO_DRYRUN_TINY"):
        t_cfg, s_cfg = reduce_config(t_cfg), reduce_config(s_cfg)
        seq, batch = min(seq, 128), min(batch, 8)
    mesh = mesh_from_env() or make_production_mesh(cp=args.cp)
    shape = ShapeConfig("distill", "train", seq, batch)
    step, _ = build_colocated_step(t_cfg, s_cfg, mesh, shape,
                                   ParallelConfig(mbs=args.mbs or 1,
                                                  cp=args.cp),
                                   impl="ref")
    t_shapes = param_shapes(tf.lm_specs(t_cfg))
    s_shapes = param_shapes(tf.lm_specs(s_cfg))
    o_shapes = adamw.state_specs(s_shapes)
    b_shapes = {
        "tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
        "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
        "loss_mask": jax.ShapeDtypeStruct((batch, seq), jnp.float32)}
    t0 = time.time()
    with mesh:
        lowered = step.lower(s_shapes, o_shapes, t_shapes, b_shapes,
                             jax.ShapeDtypeStruct((), jnp.int32))
        compiled = lowered.compile()
    rec = {"workload": f"distill:{args.teacher}->{args.student}",
           "mesh": "single", "compile_s": time.time() - t0}
    toks = batch * seq
    model_flops = (6 * s_cfg.active_params()
                   + 2 * t_cfg.active_params()) * toks
    _analyze(compiled, rec, model_flops, mesh.devices.size)
    _emit(rec, args.out,
          f"compound_distill__{args.teacher}__{args.student}__single.json")


def _run_mllm(args) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, reduce_config
    from repro.launch.mesh import make_production_mesh
    from repro.mllm.workload import build_colocated_step
    from repro.models import transformer as tf
    from repro.models.common import param_shapes
    from repro.models.vlm import vit_config, vit_specs
    from repro.optim import adamw

    lm_cfg = get_config(args.arch)
    assert lm_cfg.vision_dim, f"{args.arch} is not a VLM arch"
    seq, batch = args.seq, args.batch
    tiny = bool(os.environ.get("REPRO_DRYRUN_TINY"))
    if tiny:
        lm_cfg = reduce_config(lm_cfg).replace(
            vision_dim=64, max_image_tokens=8)
        seq, batch = min(seq, 128), min(batch, 8)
        vit_cfg = vit_config(num_layers=2, d_model=64, num_heads=4,
                             d_ff=128, patch_dim=32, downsample=4,
                             out_dim=lm_cfg.vision_dim)
    else:
        # the paper's 0.4B-class ViT encoder feeding the backbone
        vit_cfg = vit_config(out_dim=lm_cfg.vision_dim)
    mbs = args.mbs if args.mbs is not None else min(8, batch)
    if batch % mbs:
        raise ValueError(
            f"--batch {batch} is not a multiple of mbs={mbs}: the "
            "microbatched step would lower for a different sample count "
            "than the reported model_flops")
    n_mb = batch // mbs
    K = lm_cfg.max_image_tokens or min(seq // 4, 2048)
    lm_cfg = lm_cfg.replace(max_image_tokens=K)
    from repro.launch.mesh import mesh_from_env
    from repro.models.vlm import downsample_factor
    P = K * downsample_factor(vit_cfg)
    mesh = mesh_from_env() or make_production_mesh()
    step, _ = build_colocated_step(vit_cfg, lm_cfg, mesh, mbs=mbs,
                                   seq_len=seq, impl="ref")
    p_shapes = {"lm": param_shapes(tf.lm_specs(lm_cfg)),
                "vit": param_shapes(vit_specs(vit_cfg))}
    o_shapes = adamw.state_specs(p_shapes)
    i32, f32 = jnp.int32, jnp.float32
    dt = jnp.bfloat16 if lm_cfg.dtype == "bfloat16" else jnp.float32
    b_shapes = {
        "tokens": jax.ShapeDtypeStruct((n_mb, mbs, seq), i32),
        "labels": jax.ShapeDtypeStruct((n_mb, mbs, seq), i32),
        "loss_mask": jax.ShapeDtypeStruct((n_mb, mbs, seq), f32),
        "image_pos": jax.ShapeDtypeStruct((n_mb, mbs, K), i32),
        "image_valid": jax.ShapeDtypeStruct((n_mb, mbs, K), i32),
        "patches": jax.ShapeDtypeStruct((n_mb, mbs, P,
                                         vit_cfg.frontend_dim), dt),
        "vis_idx": jax.ShapeDtypeStruct((n_mb, mbs), i32),
        "vis_valid": jax.ShapeDtypeStruct((n_mb, mbs), f32)}
    t0 = time.time()
    with mesh:
        lowered = step.lower(p_shapes, o_shapes, b_shapes,
                             jax.ShapeDtypeStruct((), i32))
        compiled = lowered.compile()
    rec = {"workload": f"mllm:{vit_cfg.name}->{args.arch}",
           "mesh": "single", "compile_s": time.time() - t0,
           "n_microbatches": n_mb, "mbs": mbs,
           "image_tokens": K, "vit_patches": P}
    toks = batch * seq
    vit_toks = batch * P
    model_flops = (6 * lm_cfg.active_params() * toks
                   + 6 * vit_cfg.total_params() * vit_toks)
    _analyze(compiled, rec, model_flops, mesh.devices.size)
    _emit(rec, args.out,
          f"compound_mllm__{vit_cfg.name}__{args.arch}__single.json")


def _run_multi_teacher(args) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, reduce_config
    from repro.distill.multi_teacher import build_colocated_step
    from repro.launch.mesh import make_production_mesh, mesh_from_env
    from repro.models import transformer as tf
    from repro.models.common import param_shapes
    from repro.optim import adamw

    ta_cfg = get_config(args.teacher)
    tb_cfg = get_config(args.teacher2)
    s_cfg = get_config(args.student)
    seq, batch = args.seq, args.batch
    if os.environ.get("REPRO_DRYRUN_TINY"):
        ta_cfg, tb_cfg = reduce_config(ta_cfg), reduce_config(tb_cfg)
        s_cfg = reduce_config(s_cfg)
        seq, batch = min(seq, 128), min(batch, 8)
    mbs = args.mbs if args.mbs is not None else min(8, batch)
    if batch % mbs:
        raise ValueError(f"--batch {batch} is not a multiple of "
                         f"mbs={mbs}")
    n_mb = batch // mbs
    mesh = mesh_from_env() or make_production_mesh()
    step, _ = build_colocated_step(ta_cfg, tb_cfg, s_cfg, mesh, mbs=mbs,
                                   seq_len=seq, impl="ref")
    s_shapes = param_shapes(tf.lm_specs(s_cfg))
    a_shapes = param_shapes(tf.lm_specs(ta_cfg))
    b_shapes_t = param_shapes(tf.lm_specs(tb_cfg))
    o_shapes = adamw.state_specs(s_shapes)
    i32, f32 = jnp.int32, jnp.float32
    batch_shapes = {
        "tokens": jax.ShapeDtypeStruct((n_mb, mbs, seq), i32),
        "labels": jax.ShapeDtypeStruct((n_mb, mbs, seq), i32),
        "loss_mask": jax.ShapeDtypeStruct((n_mb, mbs, seq), f32),
        "b_idx": jax.ShapeDtypeStruct((n_mb, mbs), i32),
        "b_valid": jax.ShapeDtypeStruct((n_mb, mbs), f32)}
    dt_a = jnp.bfloat16 if ta_cfg.dtype == "bfloat16" else f32
    dt_b = jnp.bfloat16 if tb_cfg.dtype == "bfloat16" else f32
    wa = jax.ShapeDtypeStruct((ta_cfg.d_model, ta_cfg.padded_vocab), dt_a)
    wb = jax.ShapeDtypeStruct((tb_cfg.d_model, tb_cfg.padded_vocab), dt_b)
    t0 = time.time()
    with mesh:
        lowered = step.lower(s_shapes, o_shapes, a_shapes, b_shapes_t,
                             wa, wb, batch_shapes,
                             jax.ShapeDtypeStruct((), i32))
        compiled = lowered.compile()
    rec = {"workload": (f"multi_teacher:{args.teacher}+{args.teacher2}"
                        f"->{args.student}"),
           "mesh": "single", "compile_s": time.time() - t0,
           "n_microbatches": n_mb, "mbs": mbs}
    toks = batch * seq
    model_flops = (6 * s_cfg.active_params() + 2 * ta_cfg.active_params()
                   + 2 * tb_cfg.active_params()) * toks
    _analyze(compiled, rec, model_flops, mesh.devices.size)
    _emit(rec, args.out,
          f"compound_multi_teacher__{args.teacher}__{args.teacher2}"
          f"__{args.student}__single.json")


#: every registered compound workload (CI lowers each of these)
WORKLOADS = {
    "distill": _run_distill,
    "mllm": _run_mllm,
    "multi_teacher": _run_multi_teacher,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="distill",
                    choices=tuple(WORKLOADS))
    ap.add_argument("--teacher", default="granite-3-8b")
    ap.add_argument("--student", default="granite-3-8b")
    ap.add_argument("--arch", default="pixtral-12b",
                    help="mllm backbone arch (must have a vision stub)")
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--mbs", type=int, default=None,
                    help="micro-batch size (default: 1 for distill, "
                         "min(8, batch) for mllm)")
    ap.add_argument("--cp", type=int, default=1,
                    help="context parallelism: carve a seq axis out of "
                         "the data axis (teacher+student attention run "
                         "through cp_attention; distill only)")
    ap.add_argument("--teacher2", default="granite-3-8b",
                    help="specialist teacher for --workload multi_teacher")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()
    WORKLOADS[args.workload](args)


if __name__ == "__main__":
    main()
