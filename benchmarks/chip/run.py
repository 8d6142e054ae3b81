#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  The last line
of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared with the plain
reference, beside its limit); the checks are also the last lines of
standard error.  Without a TPU, or with fewer chips than the cell asks for,
the run exits 2 and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    # one fixed directory inside the checkout: the path is part of the
    # cache's key, so only the first run of a cell there compiles
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    # the TPU runtime's logs go to this run's own temporary directory
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(),
                                                      "tpu_logs"))
    import jax
    from benchmarks.chip import harness

    chips = harness.workload(args.workload, harness.benchmark(ROOT))["chips"]
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"run.py: no TPU with {chips} chip(s) found (JAX sees "
              f"{devices}); the benchmark runs on the chip only",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = harness.run(args.workload, args.seed, args.seconds,
                         bool(args.trace), root=ROOT,
                         log=lambda s: print(s, file=sys.stderr))
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
