"""Compound VLM training (paper §2.1/§4.1), the Maestro way: ViT section
and LLM section DISAGGREGATED on disjoint (virtual) device meshes, driven
by the compound executor with wavefront-scheduled microbatch dispatch.

    PYTHONPATH=src python examples/vlm_training.py

* builds the section graph (ViT → LLM) and shows the planner's per-section
  configs for the paper-scale workload;
* trains a reduced compound model for real through
  ``repro.mllm.workload.MLLMRuntime``: per iteration the cost model builds
  scheduler 6-tuples, Algorithm 1 reorders the samples, and the executor
  dispatches microbatches to the section workers — text-only microbatches
  never touch the ViT section (data-dependent activation);
* streams iterations with ``lookahead=1`` through the
  ``install / submit_iteration / retire`` API: optimizer updates run on
  the section workers, so iteration i+1 queues up behind each section's
  own update instead of a global barrier;
* reports the REALIZED (executed, from the executor timeline — not
  simulated) critical-section utilization and the wavefront-vs-FIFO
  makespan of the final iteration.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import functools

import jax
import numpy as np

from repro.configs import get_config, get_reduced
from repro.core.graph import build_vlm_graph
from repro.core.planner import plan
from repro.core.types import ParallelConfig
from repro.data.synthetic import vlm_batches
from repro.mllm.workload import MLLMRuntime
from repro.models.vlm import vit_config
from repro.optim import schedules


def main():
    # ---- paper-scale plan (analytic) ------------------------------------
    g = build_vlm_graph(vit_config(out_dim=5120), get_config("qwen2.5-32b"))
    p = plan(g, critical_gpus=256, seq_len=8192, global_batch=256,
             activation_rates={"vit": 0.3})
    print("== two-stage plan (ViT + qwen2.5-32b, 256 chips) ==")
    print(p.summary())
    print()

    # ---- reduced compound model, trained disaggregated for real ---------
    B, S, K, MBS = 16, 32, 8, 4
    lm_cfg = get_reduced("pixtral-12b").replace(
        dtype="float32", vocab_size=1024, vision_dim=64,
        max_image_tokens=K)
    vit_cfg = vit_config(num_layers=2, d_model=64, num_heads=4, d_ff=128,
                         patch_dim=16, downsample=4, out_dim=64,
                         name="vit-tiny").replace(dtype="float32")
    rt = MLLMRuntime(vit_cfg, lm_cfg,
                     vit_parallel=ParallelConfig(dp=4),
                     lm_parallel=ParallelConfig(dp=4),
                     global_batch=B, seq_len=S, mbs=MBS, impl="ref",
                     lr_schedule=functools.partial(schedules.constant,
                                                   peak_lr=2e-3),
                     lookahead=1)
    print(f"== disaggregated MLLM runtime: vit mesh (dp=4), llm mesh "
          f"(dp=4), mbs={MBS}, lookahead=1 ==")
    params, opts = rt.init(jax.random.PRNGKey(0))
    data = vlm_batches(batch=B, seq_len=S, vocab=1024, vision_ratio=0.5,
                       image_tokens=K, patch_dim=16, seed=0)

    # stream the training loop: submit_iteration enqueues i+1 while the
    # slowest section still drains i; retire() yields metrics in order
    rt.install(params, opts)
    losses, utils = [], []
    metrics = None
    done = 0

    def account(m):
        nonlocal metrics, done
        metrics = m
        ex = m["execution"]
        losses.append(float(m["loss"]))
        utils.append(ex.utilization("llm"))
        if done % 8 == 0:
            n_img = len(m["plan"].image_mbs)
            print(f"iter {done:3d}: loss={losses[-1]:.4f} "
                  f"realized-llm-util={utils[-1]:.3f} "
                  f"vit-mbs={n_img}/{rt.n_mb} "
                  f"makespan={ex.makespan*1e3:.0f}ms")
        done += 1

    batch = None
    for i in range(25):
        batch = next(data)
        rt.submit_iteration(batch, i)
        while rt.in_flight > 1:
            account(rt.retire())
    for m in rt.drain():
        account(m)
    params, opts = rt.state()

    # wavefront vs FIFO on the last batch, from the executor's timeline
    _, _, m_fifo = rt.train_iteration(params, opts, batch, 99,
                                      reorder=False)
    wf, ff = metrics["execution"].makespan, m_fifo["execution"].makespan
    print(f"loss {losses[0]:.3f} -> {losses[-1]:.3f}; "
          f"mean realized LLM utilization {np.mean(utils):.3f}")
    print(f"realized makespan: wavefront {wf*1e3:.0f}ms vs FIFO "
          f"{ff*1e3:.0f}ms (vit-mbs {len(metrics['plan'].image_mbs)} vs "
          f"{len(m_fifo['plan'].image_mbs)})")
    print("cross-section traffic:", rt.rt.queue.stats())
    assert losses[-1] < losses[0], "compound model did not learn"
    rt.shutdown()
    print("vlm_training example OK")


if __name__ == "__main__":
    main()
