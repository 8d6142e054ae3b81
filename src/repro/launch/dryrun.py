import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count="
    + os.environ.get("REPRO_DRYRUN_DEVICES", "512")
    + (" " + os.environ["XLA_FLAGS"] if "XLA_FLAGS" in os.environ else ""))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this proves, without hardware, that

* the sharding config is coherent (SPMD partitioner accepts it),
* it fits per-chip HBM (``compiled.memory_analysis()``),
* and it yields the roofline terms (``cost_analysis`` + HLO parsing with
  while-trip-count correction — see ``repro.roofline.analysis``).

Usage:
  python -m repro.launch.dryrun --arch granite-20b --shape train_4k \
      --mesh single --out experiments/dryrun        # one cell
  python -m repro.launch.dryrun --all [--mesh both]  # full sweep, one
      subprocess per cell (isolation against OOM/compiler failures)
"""
import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path


def _cell_name(arch: str, shape: str, mesh: str) -> str:
    return f"{arch}__{shape}__{mesh}"


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: Path,
             mbs: int = 1, sp: bool = False, pp: int = 1,
             cp: int = 1) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.types import SHAPES, ParallelConfig
    from repro.launch.mesh import make_production_mesh
    from repro.models.model import build_model
    from repro.optim import adamw
    from repro.roofline.analysis import analyze_hlo, roofline_terms
    from repro.train import step as step_mod

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    tiny = bool(os.environ.get("REPRO_DRYRUN_TINY"))
    if tiny:
        from repro.configs import reduce_config
        from repro.core.types import ShapeConfig
        cfg = reduce_config(cfg)
        shape = ShapeConfig(shape.name, shape.kind,
                            min(shape.seq_len, 128),
                            min(shape.global_batch, 8))
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "params": cfg.total_params(), "active_params": cfg.active_params()}

    if shape_name == "long_500k" and not cfg.sub_quadratic:
        rec["skipped"] = ("pure full-attention arch: 500K-token decode "
                          "needs sub-quadratic attention (DESIGN.md "
                          "§Arch-applicability)")
        return rec

    from repro.launch.mesh import mesh_from_env
    mesh = mesh_from_env()
    if mesh is None:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"),
                                    pp=pp, cp=cp)
    n_dev = mesh.devices.size
    from repro.dist.sharding import head_pad_for
    pad = head_pad_for(cfg, mesh.shape["model"])
    if pad:
        cfg = cfg.replace(head_pad=pad)
        rec["head_pad"] = pad
    vpad = (-cfg.vocab_size) % mesh.shape["model"]
    if vpad:
        cfg = cfg.replace(vocab_pad=vpad)
        rec["vocab_pad"] = vpad
    model = build_model(cfg, impl="ref")
    t0 = time.time()

    if shape.kind == "train":
        # pp/cp are read back from the mesh so REPRO_DRYRUN_MESH-built
        # meshes validate too — build_train_step rejects any mismatch
        mesh_sizes = dict(mesh.shape)
        parallel = ParallelConfig(mbs=mbs, sequence_parallel=sp,
                                  pp=mesh_sizes.get("pipe", 1),
                                  cp=mesh_sizes.get("seq", 1))
        step, _ = step_mod.build_train_step(model, mesh, parallel, shape)
        pshapes = model.param_shapes()
        oshapes = adamw.state_specs(pshapes)
        bshapes = model.input_specs(shape)
        with mesh:
            lowered = step.lower(pshapes, oshapes, bshapes,
                                 jax.ShapeDtypeStruct((), jnp.int32))
    elif shape.kind == "prefill":
        step, _ = step_mod.build_prefill_step(model, mesh, shape)
        with mesh:
            lowered = step.lower(model.param_shapes(),
                                 model.input_specs(shape))
    else:  # decode
        step, _ = step_mod.build_decode_step(model, mesh, shape)
        with mesh:
            lowered = step.lower(model.param_shapes(),
                                 model.cache_specs(shape),
                                 model.input_specs(shape)["token"],
                                 jax.ShapeDtypeStruct((), jnp.int32))
    rec["lower_s"] = time.time() - t0

    t0 = time.time()
    with mesh:
        compiled = lowered.compile()
    rec["compile_s"] = time.time() - t0

    try:
        mem = compiled.memory_analysis()
        rec["memory"] = {
            k: int(getattr(mem, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "alias_size_in_bytes",
             "generated_code_size_in_bytes") if hasattr(mem, k)}
        print("memory_analysis:", rec["memory"])
    except Exception as e:          # pragma: no cover
        rec["memory_error"] = repr(e)

    try:
        ca = compiled.cost_analysis()
        rec["cost_analysis"] = {k: float(v) for k, v in ca.items()
                                if isinstance(v, (int, float))}
        print("cost_analysis flops:", rec["cost_analysis"].get("flops"))
    except Exception as e:          # pragma: no cover
        rec["cost_analysis_error"] = repr(e)

    text = compiled.as_text()
    stats = analyze_hlo(text)
    terms = roofline_terms(stats)
    # tokens processed per executed step
    if shape.kind == "decode":
        tokens = shape.global_batch
        flops_per_tok = 2 * cfg.active_params()
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        flops_per_tok = 2 * cfg.active_params()
    else:
        tokens = shape.global_batch * shape.seq_len
        flops_per_tok = 6 * cfg.active_params()
    model_flops = flops_per_tok * tokens
    hlo_total = stats.flops * n_dev
    # kernel-adjusted memory: deep-loop (flash/SSD interior) traffic lives
    # in VMEM under the Pallas kernels; replace it with analytic kernel IO
    # (read q,k,v + write o, fwd + recompute-bwd ≈ 3×)
    from repro.core.types import V5E
    kio = _analytic_kernel_io(cfg, shape, n_dev)
    adj_bytes = max(stats.hbm_bytes - stats.deep_loop_bytes, 0.0) + kio
    rec.update({
        "devices": n_dev,
        "hlo": {"flops_per_device": stats.flops,
                "hbm_bytes_per_device": stats.hbm_bytes,
                "deep_loop_bytes_per_device": stats.deep_loop_bytes,
                "collective_bytes_per_device": stats.collective_bytes,
                "transcendental_per_device": stats.transcendental},
        "roofline": terms,
        "kernel_adjusted_memory_s": adj_bytes / V5E.hbm_bandwidth,
        "model_flops": model_flops,
        "useful_flops_ratio": model_flops / hlo_total if hlo_total else 0.0,
        "tokens": tokens,
    })
    print("roofline:", json.dumps(terms))
    print("kernel_adjusted_memory_s:", rec["kernel_adjusted_memory_s"])
    print("useful_flops_ratio:", rec["useful_flops_ratio"])
    return rec


def _analytic_kernel_io(cfg, shape, n_dev: int) -> float:
    """Per-device HBM bytes the Pallas flash/SSD kernels actually move:
    q/k/v reads + o write, forward + recompute backward (≈3× forward IO),
    per attention layer per token on this device."""
    if shape.kind == "decode":
        return 0.0
    tokens_per_dev = shape.global_batch * shape.seq_len / max(n_dev, 1)
    attn_layers = sum(1 for i in range(cfg.num_layers)
                      if cfg.is_attn_layer(i)) + cfg.encoder_layers
    H = max(cfg.num_heads + cfg.head_pad, 1)
    KV = max(cfg.num_kv_heads, 1)
    hd = cfg.hd if cfg.num_heads else 0
    per_tok = (H + 2 * KV + H) * hd * 4.0        # q,k,v read + o write, fp32
    mult = 3.0 if shape.kind == "train" else 1.0
    io = tokens_per_dev * attn_layers * per_tok * mult
    if cfg.family in ("ssm", "hybrid"):
        ssm_layers = sum(1 for i in range(cfg.num_layers)
                         if not cfg.is_attn_layer(i))
        d_in = cfg.ssm_expand * cfg.d_model
        io += tokens_per_dev * ssm_layers * (2 * d_in + 2 * cfg.ssm_state) \
            * 4.0 * mult
    return io


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mbs", type=int, default=1)
    ap.add_argument("--sp", action="store_true",
                    help="sequence-parallel residual stream (train cells)")
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline stages: carve a pipe axis out of the "
                         "data axis (train cells run the GPipe loss)")
    ap.add_argument("--cp", type=int, default=1,
                    help="context parallelism: carve a seq axis out of "
                         "the data axis (train cells run cp_attention)")
    ap.add_argument("--timeout", type=int, default=2400)
    ap.add_argument("--force", action="store_true",
                    help="recompute cells that already have results")
    args = ap.parse_args()
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    if not args.all:
        name = _cell_name(args.arch, args.shape, args.mesh)
        try:
            rec = run_cell(args.arch, args.shape, args.mesh, out_dir,
                           mbs=args.mbs, sp=args.sp, pp=args.pp,
                           cp=args.cp)
        except Exception:
            rec = {"arch": args.arch, "shape": args.shape,
                   "mesh": args.mesh, "error": traceback.format_exc()}
            (out_dir / f"{name}.json").write_text(json.dumps(rec, indent=2))
            print(rec["error"], file=sys.stderr)
            sys.exit(1)
        (out_dir / f"{name}.json").write_text(json.dumps(rec, indent=2))
        print(f"wrote {out_dir / (name + '.json')}")
        return

    # sweep: one subprocess per cell
    from repro.configs import ARCH_NAMES
    from repro.core.types import SHAPES
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = [(a, s, m) for a in ARCH_NAMES for s in SHAPES for m in meshes]
    failures = []
    for a, s, m in cells:
        name = _cell_name(a, s, m)
        path = out_dir / f"{name}.json"
        if path.exists() and not args.force:
            try:
                if "error" not in json.loads(path.read_text()):
                    print(f"skip (done): {name}")
                    continue
            except Exception:
                pass
        print(f"=== {name} ===", flush=True)
        t0 = time.time()
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun", "--arch", a,
             "--shape", s, "--mesh", m, "--out", str(out_dir),
             "--mbs", str(args.mbs)],
            timeout=args.timeout, capture_output=True, text=True)
        dur = time.time() - t0
        if proc.returncode != 0:
            failures.append(name)
            path.write_text(json.dumps(
                {"arch": a, "shape": s, "mesh": m,
                 "error": proc.stderr[-4000:]}, indent=2))
            print(f"FAIL ({dur:.0f}s): {name}\n{proc.stderr[-2000:]}",
                  flush=True)
        else:
            print(f"ok ({dur:.0f}s): {name}", flush=True)
    print(f"sweep done; {len(failures)} failures: {failures}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
