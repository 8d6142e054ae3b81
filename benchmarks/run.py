"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Figure mapping:

* bench_scheduler — Fig. 7 + Algorithm 1 (§3.4)
* bench_vlm       — Fig. 8 (VLM training, §4.1)
* bench_distill   — Fig. 9 + Fig. 10 (distillation, §4.2)
* bench_kernels   — kernel layer (substrate)

``--smoke`` runs the cheap CI subset (scheduler only, capped sweep).
``--vlm-realized`` runs the executed (multi-device subprocess) MLLM
bench and writes its JSON — wavefront-vs-FIFO plus overlap-on-vs-off —
to ``BENCH_vlm_realized.json`` at the repo root, where it is committed
so the realized-performance trajectory is tracked in-tree.
``--step-roofline`` runs the HLO-derived distributed-step scoreboard
(vocab-parallel CE FLOPs, TP-in-stage FLOPs, compressed DP all-reduce
wire bytes — each asserted via the declarative gate files, see
bench_step_roofline.py) and writes ``BENCH_step_roofline.json`` at the
repo root.
``--cp-attention`` runs the CP-attention comm scoreboard (overlap-
pipelined ulysses a2a chunking + head-replicated MQA wire reduction,
asserted via the ``cp_overlap`` / ``ulysses_mqa`` gate files against
compiled post-SPMD HLO — see bench_cp_attention.py) and writes
``BENCH_cp_attention.json`` at the repo root.
``--kernels`` runs the kernel micro-benchmarks alone and writes their
rows (wall time + derived GFLOP/s) to ``BENCH_kernels.json`` at the
repo root; with ``--smoke`` the shapes shrink and no JSON is written.
``--lint`` runs the static-analysis suite (``python -m repro.analysis``):
deadlock/donation passes over every registered workload spec plus a
schema check of the committed HLO gate files.
"""
from __future__ import annotations

import argparse
import importlib
import inspect
import json
import os
import subprocess
import sys
import traceback
from pathlib import Path

# allow `python benchmarks/run.py` from anywhere: repo root (for the
# `benchmarks` namespace package) and src/ (for `repro`)
_ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(_ROOT / "src"))
sys.path.insert(0, str(_ROOT))


def vlm_realized(smoke: bool) -> None:
    """Run bench_vlm_realized in its own interpreter (it needs 8 virtual
    devices) and record the JSON at the repo root."""
    env = dict(os.environ, PYTHONPATH=str(_ROOT / "src"))
    env.setdefault("JAX_PLATFORMS", "cpu")
    cmd = [sys.executable, str(_ROOT / "benchmarks" /
                               "bench_vlm_realized.py")]
    if smoke:
        cmd.append("--smoke")
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          timeout=1800)
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr)
        sys.exit(proc.returncode)
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    out = _ROOT / "BENCH_vlm_realized.json"
    out.write_text(json.dumps(data, indent=2) + "\n")
    print(f"wrote {out}", flush=True)
    ov = data["overlap"]
    print(f"wavefront_vs_fifo_speedup,{data['realized_speedup']:.4f}",
          flush=True)
    print(f"overlap_wall_speedup,{ov['wall_speedup']:.4f}", flush=True)
    print(f"overlap_vit_util_gain,{ov['vit_util_gain']:.4f}", flush=True)


def step_roofline() -> None:
    """Run bench_step_roofline in its own interpreter (8 virtual devices)
    and record the scoreboard at the repo root.  The bench asserts the
    perf claims itself; a regression fails this command."""
    env = dict(os.environ, PYTHONPATH=str(_ROOT / "src"))
    env.setdefault("JAX_PLATFORMS", "cpu")
    cmd = [sys.executable, str(_ROOT / "benchmarks" /
                               "bench_step_roofline.py")]
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          timeout=1800)
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr)
        sys.exit(proc.returncode)
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    out = _ROOT / "BENCH_step_roofline.json"
    out.write_text(json.dumps(data, indent=2) + "\n")
    print(f"wrote {out}", flush=True)
    print(f"vp_ce_unembed_flop_reduction,{data['vp_ce']['reduction']:.4f}",
          flush=True)
    print("tp_in_stage_ffn_flop_reduction,"
          f"{data['tp_in_stage']['reduction']:.4f}", flush=True)
    c = data["compress"]
    print(f"grad_wire_bf16_over_fp32,{c['bf16_over_fp32']:.4f}",
          flush=True)
    print(f"grad_wire_int8_over_fp32,{c['int8_over_fp32']:.4f}",
          flush=True)


def cp_attention() -> None:
    """Run bench_cp_attention in its own interpreter (8 virtual devices)
    and record the scoreboard at the repo root.  The bench asserts the
    comm claims via the cp_overlap / ulysses_mqa gate files; a
    regression fails this command."""
    env = dict(os.environ, PYTHONPATH=str(_ROOT / "src"))
    env.setdefault("JAX_PLATFORMS", "cpu")
    cmd = [sys.executable, str(_ROOT / "benchmarks" /
                               "bench_cp_attention.py")]
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          timeout=1800)
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr)
        sys.exit(proc.returncode)
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    out = _ROOT / "BENCH_cp_attention.json"
    out.write_text(json.dumps(data, indent=2) + "\n")
    print(f"wrote {out}", flush=True)
    ov, mqa = data["overlap"], data["mqa"]
    print(f"overlap_a2a_count,{ov['a2a_count_overlap']:g}", flush=True)
    print(f"overlap_min_payload_ratio,{ov['min_payload_ratio']:.4f}",
          flush=True)
    print(f"overlap_wire_ratio,{ov['wire_ratio']:.4f}", flush=True)
    print(f"mqa_wire_vs_allgather,{mqa['wire_ratio_vs_allgather']:.4f}",
          flush=True)


def kernels(smoke: bool) -> None:
    """Run the kernel micro-benchmarks alone; record the rows at the
    repo root (full run only — smoke shapes aren't comparable)."""
    from benchmarks import bench_kernels
    rows = bench_kernels.run(smoke=smoke)
    print("name,us_per_call,gflops")
    for row in rows:
        print(",".join(str(x) for x in row), flush=True)
    if smoke:
        return
    out = _ROOT / "BENCH_kernels.json"
    out.write_text(json.dumps(
        {"rows": [{"name": n, "us_per_call": t, "gflops": g}
                  for n, t, g in rows]}, indent=2) + "\n")
    print(f"wrote {out}", flush=True)


def lint() -> None:
    """Run the static-analysis suite in its own interpreter (same entry
    point as ``python -m repro.analysis``)."""
    env = dict(os.environ, PYTHONPATH=str(_ROOT / "src"))
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run([sys.executable, "-m", "repro.analysis"],
                          env=env, timeout=900)
    sys.exit(proc.returncode)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI subset: scheduler benches only")
    ap.add_argument("--vlm-realized", action="store_true",
                    help="run the executed MLLM bench (subprocess, 8 "
                         "virtual devices) and write "
                         "BENCH_vlm_realized.json at the repo root")
    ap.add_argument("--step-roofline", action="store_true",
                    help="run the HLO-derived distributed-step scoreboard "
                         "(subprocess, 8 virtual devices) and write "
                         "BENCH_step_roofline.json at the repo root")
    ap.add_argument("--cp-attention", action="store_true",
                    help="run the CP-attention comm scoreboard "
                         "(subprocess, 8 virtual devices; gate-asserted) "
                         "and write BENCH_cp_attention.json at the repo "
                         "root")
    ap.add_argument("--kernels", action="store_true",
                    help="run the kernel micro-benchmarks alone and "
                         "write BENCH_kernels.json at the repo root "
                         "(with --smoke: small shapes, no JSON)")
    ap.add_argument("--lint", action="store_true",
                    help="run the static-analysis suite (deadlock/"
                         "donation passes over registered workload specs "
                         "+ HLO gate schema checks)")
    args = ap.parse_args()

    if args.lint:
        lint()
        return
    if args.vlm_realized:
        vlm_realized(args.smoke)
        return
    if args.step_roofline:
        step_roofline()
        return
    if args.cp_attention:
        cp_attention()
        return
    if args.kernels:
        kernels(args.smoke)
        return

    names = ["scheduler"]
    if not args.smoke:
        names += ["vlm", "distill", "kernels"]
    print("name,us_per_call,derived")
    failures = 0
    for name in names:
        # import inside the guard: a collection-time failure in one bench
        # module must not take down the others (or the smoke subset)
        try:
            mod = importlib.import_module(f"benchmarks.bench_{name}")
            kw = {}
            if "smoke" in inspect.signature(mod.run).parameters:
                kw["smoke"] = args.smoke
            for row in mod.run(**kw):
                print(",".join(str(x) for x in row), flush=True)
        except Exception:
            failures += 1
            print(f"{name}_FAILED,0,0", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
