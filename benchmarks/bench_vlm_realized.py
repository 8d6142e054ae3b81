"""Realized (executed, not simulated) benchmarks for the MLLM compound
workload — standalone subprocess: it needs 8 virtual devices, which the
in-process bench harness (1 device) cannot provide.

Two comparisons over the same batches, both FROM THE EXECUTOR'S
TIMELINE:

* FIFO vs wavefront dispatch: per-iteration makespan, realized
  LLM-section utilization, the number of ViT microbatches actually
  dispatched (the dynamic-activation savings: wavefront clusters image
  samples so fewer microbatches carry vision work), and the realized
  dispatch permutation.
* overlap OFF (lookahead=0, the old per-iteration barrier) vs overlap ON
  (lookahead=1, cross-iteration streaming with worker-side updates):
  multi-iteration wall clock, realized overlap seconds (sum of
  per-iteration spans minus wall — positive only if iterations actually
  interleaved), and wall-normalized section utilization.

    PYTHONPATH=src python benchmarks/bench_vlm_realized.py [--smoke]
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import json
import sys
import time

import numpy as np


def main(iters: int = 4, repeats: int = 2) -> dict:
    import jax

    from repro.configs import get_reduced
    from repro.core.types import ParallelConfig
    from repro.data.synthetic import vlm_batches
    from repro.mllm.workload import MLLMRuntime
    from repro.models.vlm import vit_config

    B, S, K, MBS = 16, 64, 8, 4
    lm_cfg = get_reduced("pixtral-12b").replace(
        dtype="float32", vocab_size=256, vision_dim=64, max_image_tokens=K)
    vit_cfg = vit_config(num_layers=2, d_model=64, num_heads=4, d_ff=128,
                         patch_dim=32, downsample=4, out_dim=64,
                         name="vit-bench").replace(dtype="float32")
    from repro.mllm.workload import init_compound_params

    rt = MLLMRuntime(vit_cfg, lm_cfg,
                     vit_parallel=ParallelConfig(dp=4),
                     lm_parallel=ParallelConfig(dp=4),
                     global_batch=B, seq_len=S, mbs=MBS, impl="ref")
    params_host = init_compound_params(vit_cfg, lm_cfg,
                                       jax.random.PRNGKey(0))
    data = vlm_batches(batch=B, seq_len=S, vocab=256, vision_ratio=0.5,
                       image_tokens=K, patch_dim=32, seed=0)
    batches = [next(data) for _ in range(iters)]

    out = {}
    example_order = None
    for policy in ("fifo", "wavefront"):
        # fresh placement per run: AdamW donates its optimizer-state
        # buffers, so a state may only ever enter one trajectory
        p, o = rt.place(params_host)
        mks, utils, vit_mbs, reordered = [], [], 0, 0
        for i, b in enumerate(batches):
            p, o, m = rt.train_iteration(p, o, b, i,
                                         reorder=policy == "wavefront")
            ex = m["execution"]
            mks.append(ex.makespan)
            utils.append(ex.utilization("llm"))
            vit_mbs += len(m["plan"].image_mbs)
            if tuple(m["plan"].order) != tuple(range(B)):
                reordered += 1
                if policy == "wavefront" and example_order is None:
                    example_order = list(m["plan"].order)
        out[policy] = {
            "makespan_mean_s": float(np.mean(mks[1:] or mks)),
            "llm_util_mean": float(np.mean(utils)),
            "vit_microbatches": int(vit_mbs),
            "reordered_iters": int(reordered),
        }
    out["realized_speedup"] = (out["fifo"]["makespan_mean_s"]
                               / max(out["wavefront"]["makespan_mean_s"],
                                     1e-12))
    out["example_wavefront_order"] = example_order

    # ---- overlap on vs off: the same streamed iterations with and
    # without the cross-iteration barrier (jits warm from the loops
    # above; best-of-repeats absorbs 1-core scheduling noise) ----------- #
    def run_overlap(depth: int) -> dict:
        rt.lookahead = depth
        rt.install(*rt.place(params_host))
        t0 = time.perf_counter()
        for i, b in enumerate(batches):
            rt.submit_iteration(b, i, reorder=True)
        ms = rt.drain()
        wall = time.perf_counter() - t0
        exs = [m["execution"] for m in ms]
        span_sum = sum(ex.makespan for ex in exs)
        return {
            "lookahead": depth,
            "wall_s": wall,
            "span_sum_s": span_sum,
            # > 0 only when iteration spans actually interleaved
            "overlap_s": span_sum - wall,
            # busy seconds normalized by the whole run's wall clock —
            # the multi-iteration utilization a barrier depresses
            "vit_util_wall": sum(ex.busy("vit") for ex in exs) / wall,
            "llm_util_wall": sum(ex.busy("llm") for ex in exs) / wall,
        }

    overlap = {}
    for depth in (0, 1):
        runs = [run_overlap(depth) for _ in range(repeats)]
        overlap[f"lookahead{depth}"] = min(runs, key=lambda r: r["wall_s"])
    off, on = overlap["lookahead0"], overlap["lookahead1"]
    overlap["wall_speedup"] = off["wall_s"] / max(on["wall_s"], 1e-12)
    overlap["vit_util_gain"] = (on["vit_util_wall"]
                                - off["vit_util_wall"])
    out["overlap"] = overlap
    rt.shutdown()
    return out


if __name__ == "__main__":
    smoke = "--smoke" in sys.argv[1:]
    print(json.dumps(main(iters=2 if smoke else 4,
                          repeats=1 if smoke else 2)))
