#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip, at the
cell's own size, in one process.

    python3 benchmarks/chip/calibrate.py --workload <name> \\
        --seeds 11,12,... --control-seeds 21,22,23

For every seed of ``--seeds`` it builds the cell's program, drives its
checked steps as a run does, frees it and compares with the plain float32
reference: the lower readings.  For every seed of ``--control-seeds`` it
also compares, with that reference, the reference computed in scaled fp8
(the control) and the reference with each fault that the path names in
``FAULTS`` planted in it: the upper readings.  A state left unchanged reads 1 on
``update_gap`` and needs no run.  One JSON line per reading.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    # the TPU runtime's logs go to this run's own temporary directory
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(),
                                                      "tpu_logs"))
    import jax
    from benchmarks.chip import harness
    from repro.launch.compile_cache import enable_compile_cache
    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: no TPU found", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    cell = harness.workload(args.workload, harness.benchmark(ROOT))
    cfg, tr = harness.config(cell["config"]), harness.traffic(cell["traffic"])
    path = harness.path_module(tr["path"])
    # every number, whatever the cell's limits compare
    lim = dict.fromkeys(("first_loss_gap", "loss_gap", "grad_gap",
                         "grad_diff", "update_gap"), None)
    devices = jax.devices()[:cell["chips"]]

    def emit(kind, seed, checks, **kw):
        print(json.dumps({"kind": kind, "seed": seed, **kw,
                          **{k: c["value"] for k, c in checks.items()}}),
              flush=True)

    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    for seed in dict.fromkeys(seeds + controls):
        t0 = time.perf_counter()
        job = path.Job(cfg, tr, seed, devices, harness.Spans())
        prog = harness.first_steps(job, harness.Spans())
        del prog["next"]
        job.free()
        del job
        gc.collect()
        t1 = time.perf_counter()
        ref = path.reference(cfg, tr, seed)
        t2 = time.perf_counter()
        if seed in seeds:
            emit("program", seed, harness.compare(prog, ref, lim),
                 loss=prog["loss"], ref_loss=ref["loss"],
                 program_s=t1 - t0, reference_s=t2 - t1)
        if seed in controls:
            variants = [("control_fp8", {"precision": "fp8"})] + [
                (f"fault_{f}", {"fault": f}) for f in path.FAULTS]
            for kind, kw in variants:
                other = path.reference(cfg, tr, seed, **kw)
                emit(kind, seed, harness.compare(other, ref, lim),
                     loss=other["loss"], ref_loss=ref["loss"])
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
