#!/usr/bin/env python3
"""Bring-up check: MLLM training at Pixtral-12B widths on a TPU.

    python chip_smoke.py               # one chip (the default)
    python chip_smoke.py --four-chips  # disaggregated runtime on four chips

Configuration: Pixtral-12B (``src/repro/configs/pixtral_12b.py``) at its
published widths -- LM d_model 5120, 32 heads over 8 KV heads, head_dim
128, d_ff 14336, vision_dim 1024, 1024 image tokens -- fed by the
Pixtral-ViT defaults of ``models.vlm.vit_config`` (d_model 1024, 16 heads,
d_ff 4096, patch_dim 768, 4:1 downsample, so 4096 patches per image).
Weights are random from ``--seed``.  Cuts, depth and vocabulary only:

* LM layers 40 -> 1 (the whole period of a dense stack);
* vocabulary 131072 -> 16384 (the first eighth; token ids drawn from it);
* ViT layers 24 -> 4.

That is about 0.52e9 parameters, 18 bytes each with AdamW (bf16 param,
f32 mu/nu/master, f32 gradient accumulator), about 9 GiB of one chip.

One-chip phase (the default): ``mllm.build_colocated_step`` on a one-device
mesh, fed per step by ``vlm_batches`` (about half the samples carry an
image), the wavefront order of the repo's scheduler, ``build_plan`` and
``colocated_batch``.  Runs a few steps with ``impl="pallas"``, checks that
the compiled step holds Pallas kernels (``tpu_custom_call``) and that its
losses are finite, then compares its first step with a step of
``impl="ref"`` from the same seed.

Four-chip phase (``--four-chips``, and nothing else): ``MLLMRuntime`` with
the ViT and the LM sections at ``ParallelConfig(dp=2)`` each, Pallas,
``lookahead=1``, compared per iteration with ``build_colocated_step`` on a
dp=2 mesh fed the same plans.

Earlier lines of output report the measurements; the last line is one JSON
object, ``{"ok": true, "device": {"platform", "kind", "count"}}``.  The
script exits non-zero, without that line, when JAX finds no TPU or any
phase fails.  Everything runs in this one process, which holds the chip.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import cost_model  # noqa: E402
from repro.core.executor import order_samples  # noqa: E402
from repro.core.types import ArchConfig, ParallelConfig  # noqa: E402
from repro.data.synthetic import vlm_batches  # noqa: E402
from repro.dist.sharding import section_mesh  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.mllm import workload as mllm  # noqa: E402
from repro.models import vlm  # noqa: E402
from repro.optim import adamw, schedules  # noqa: E402

LM_LAYERS, VOCAB, VIT_LAYERS = 1, 16384, 4
SEQ, BATCH, MBS = 4096, 8, 2
STEPS, ITERS = 6, 3

# A small constant learning rate: the default 1e-3 makes a randomly
# initialised 5120-wide model diverge within a few steps (loss 10.1 -> 51.6
# in five steps on one v5e), which would leave later losses meaningless.
LR = functools.partial(schedules.constant, peak_lr=1e-5)

# Pallas vs ref, one step from the same seed.  Both run bf16 MXU passes
# with f32 accumulation (the ref path's f32 einsums take TPU's default
# one-pass bf16 precision) and the same jnp backward; they differ in where
# the softmax is rounded and in summation order.  Those roundings (about
# 2^-9 relative each) have no preferred sign, so in a mean over
# B*S = 32768 tokens they shrink to ~1e-5; a masking, scaling or
# block-indexing fault is systematic and moves the loss by far more.
KERNEL_LOSS_RTOL = 2e-4
KERNEL_GNORM_RTOL = 2e-3
# Disaggregated vs colocated, same plans and kernels.  On the CPU in f32
# they agree to a few ulps; in bf16 on the chip the section jits fuse
# differently, so a bf16 rounding may land on different intermediates --
# again without a preferred sign, so the loss stays within ~1e-5, while an
# update the runtime dropped or applied twice moves it by ~1e-3 at this
# learning rate.  Gradients are bf16: a few ulps (2^-8 each) of the
# largest-magnitude elements bound the difference relative to max |grad|.
RUNTIME_LOSS_RTOL = 5e-4
RUNTIME_GRAD_RTOL = 3e-2


def pixtral_configs(*, lm_layers: int = LM_LAYERS, vocab: int = VOCAB,
                    vit_layers: int = VIT_LAYERS):
    """(vit_cfg, lm_cfg): Pixtral-12B at published widths, cut in depth
    and vocabulary only."""
    lm = get_config("pixtral-12b").replace(num_layers=lm_layers,
                                           vocab_size=vocab)
    vit = vlm.vit_config(num_layers=vit_layers, out_dim=lm.vision_dim)
    return vit, lm


def batches(vit_cfg: ArchConfig, lm_cfg: ArchConfig, *, batch: int,
            seq_len: int, seed: int):
    return vlm_batches(batch=batch, seq_len=seq_len, vocab=lm_cfg.vocab_size,
                       vision_ratio=0.5, image_tokens=lm_cfg.max_image_tokens,
                       patch_dim=vit_cfg.frontend_dim,
                       downsample=vlm.downsample_factor(vit_cfg), seed=seed)


def wavefront_plan(graph, has_image, seq_len: int, mbs: int):
    """The scheduler's wavefront order for one batch, as the runtime's
    ``plan_iteration`` computes it, laid out into microbatches."""
    has = np.asarray(has_image).astype(bool)
    samples = cost_model.sample_tuples(graph, {"vit": has}, seq_len,
                                       n=len(has))
    order, sched = order_samples(samples, reorder=True)
    return mllm.build_plan(order, has, mbs, sched)


def _finite(x) -> bool:
    return bool(np.isfinite(np.asarray(x)).all())


def _peak_bytes(dev):
    stats = dev.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _check_close(what: str, got: float, want: float, rtol: float) -> None:
    if not abs(got - want) <= rtol * abs(want):
        raise AssertionError(f"{what}: {got!r} vs {want!r} differ by more "
                             f"than rtol={rtol}")


# --------------------------------------------------------------------------- #
# one chip
# --------------------------------------------------------------------------- #
def one_chip_phase(vit_cfg: ArchConfig, lm_cfg: ArchConfig, *,
                   batch: int = BATCH, mbs: int = MBS, seq_len: int = SEQ,
                   steps: int = STEPS, impl: str = "pallas", seed: int = 0,
                   log=print) -> dict:
    """Colocated MLLM training steps on one device with ``impl``, then one
    ``impl="ref"`` step from the same seed on the first batch."""
    dev = jax.devices()[0]
    mesh = make_mesh((1, 1), ("data", "model"), devices=[dev])
    graph = mllm.mllm_spec(vit_cfg, lm_cfg, vit_parallel=ParallelConfig(),
                           lm_parallel=ParallelConfig(), global_batch=batch,
                           seq_len=seq_len, mbs=mbs, impl=impl).to_graph()
    data = batches(vit_cfg, lm_cfg, batch=batch, seq_len=seq_len, seed=seed)
    host = [next(data) for _ in range(steps)]
    n_images = [int(np.asarray(b["has_image"]).sum()) for b in host]
    plans = [wavefront_plan(graph, b["has_image"], seq_len, mbs)
             for b in host]
    laid = [mllm.colocated_batch(b, p) for b, p in zip(host, plans)]
    log(f"one-chip: batch {batch} x seq {seq_len}, mbs {mbs}, images per "
        f"step {n_images}, wavefront orders "
        f"{[list(p.order) for p in plans]}")

    def init(step_shard):
        params = mllm.init_compound_params(vit_cfg, lm_cfg,
                                           jax.random.PRNGKey(seed),
                                           step_shard["params"])
        return params, jax.device_put(adamw.init(params), step_shard["opt"])

    step, shard = mllm.build_colocated_step(vit_cfg, lm_cfg, mesh, mbs=mbs,
                                            seq_len=seq_len, impl=impl,
                                            lr_schedule=LR)
    params, opt = init(shard)
    dev_batches = [jax.device_put(b, shard["batch"]) for b in laid]
    t0 = time.perf_counter()
    compiled = step.lower(params, opt, dev_batches[0], jnp.int32(0)).compile()
    compile_s = time.perf_counter() - t0
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    has_kernel = "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    log(f"one-chip: {n_params} parameters, compile {compile_s:.3f} s, "
        f"Pallas custom calls in the step: {has_kernel}, compiled "
        f"argument/temp/output bytes {mem.argument_size_in_bytes}/"
        f"{mem.temp_size_in_bytes}/{mem.output_size_in_bytes}")
    if impl == "pallas" and not has_kernel:
        raise AssertionError("impl='pallas' step holds no tpu_custom_call")

    losses, gnorms, times = [], [], []
    for i, b in enumerate(dev_batches):
        t = time.perf_counter()
        params, opt, m = compiled(params, opt, b, jnp.int32(i))
        jax.block_until_ready((params, opt, m))
        times.append(time.perf_counter() - t)
        losses.append(float(m["loss"]))
        gnorms.append(float(m["grad_norm"]))
        log(f"one-chip step {i}: loss {losses[-1]!r} grad_norm "
            f"{gnorms[-1]!r} time {times[-1]!r} s")
    if not all(np.isfinite(losses + gnorms)):
        raise AssertionError(f"non-finite losses {losses} / {gnorms}")
    step_s = statistics.median(times[1:]) if len(times) > 1 else times[0]
    peak = _peak_bytes(dev)
    tokens = batch * seq_len
    log(f"one-chip: median step {step_s!r} s after warm-up, "
        f"{tokens / step_s!r} tokens/s, peak_bytes_in_use {peak}")
    del params, opt, compiled, m
    gc.collect()

    ref_step, ref_shard = mllm.build_colocated_step(
        vit_cfg, lm_cfg, mesh, mbs=mbs, seq_len=seq_len, impl="ref",
        lr_schedule=LR)
    params, opt = init(ref_shard)
    _, _, rm = ref_step(params, opt, dev_batches[0], jnp.int32(0))
    ref_loss, ref_gnorm = float(rm["loss"]), float(rm["grad_norm"])
    log(f"one-chip: step 0 {impl} loss {losses[0]!r} vs ref {ref_loss!r} "
        f"(rtol {KERNEL_LOSS_RTOL}), grad_norm {gnorms[0]!r} vs ref "
        f"{ref_gnorm!r} (rtol {KERNEL_GNORM_RTOL})")
    _check_close("step-0 loss, kernel vs ref", losses[0], ref_loss,
                 KERNEL_LOSS_RTOL)
    _check_close("step-0 grad_norm, kernel vs ref", gnorms[0], ref_gnorm,
                 KERNEL_GNORM_RTOL)
    return {"compile_s": compile_s, "step_s": step_s,
            "tokens_per_s": tokens / step_s, "peak_bytes_in_use": peak,
            "losses": losses, "ref_loss": ref_loss, "has_kernel": has_kernel}


# --------------------------------------------------------------------------- #
# four chips
# --------------------------------------------------------------------------- #
def four_chip_phase(vit_cfg: ArchConfig, lm_cfg: ArchConfig, *,
                    batch: int = BATCH, mbs: int = MBS, seq_len: int = SEQ,
                    iters: int = ITERS, impl: str = "pallas", seed: int = 0,
                    log=print) -> dict:
    """Disaggregated ``MLLMRuntime`` (ViT dp=2, LM dp=2, lookahead=1) vs
    the colocated step on a dp=2 mesh, fed the same plans: per-iteration
    loss, and the largest gradient difference of the first iteration."""
    devs = jax.devices()[:4]
    if len(devs) < 4:
        raise RuntimeError(f"four-chip phase needs 4 devices, found "
                           f"{len(devs)}")
    data = batches(vit_cfg, lm_cfg, batch=batch, seq_len=seq_len, seed=seed)
    host = [next(data) for _ in range(iters)]
    t0 = time.perf_counter()
    rt = mllm.MLLMRuntime(vit_cfg, lm_cfg, vit_parallel=ParallelConfig(dp=2),
                          lm_parallel=ParallelConfig(dp=2),
                          global_batch=batch, seq_len=seq_len, mbs=mbs,
                          devices=devs, impl=impl, lr_schedule=LR,
                          lookahead=1)
    log(f"four-chip: runtime built and warmed in "
        f"{time.perf_counter() - t0:.3f} s; vit on "
        f"{[d.id for d in rt.rt.mesh('vit').devices.flat]}, llm on "
        f"{[d.id for d in rt.rt.mesh('llm').devices.flat]}")
    plans = [rt.plan_iteration(np.asarray(b["has_image"])) for b in host]
    try:
        rt.install(*rt.init(jax.random.PRNGKey(seed)))
        for i, (b, p) in enumerate(zip(host, plans)):
            rt.submit_iteration(b, i, plan=p, return_grads=(i == 0))
        ms = rt.drain()
    finally:
        rt.shutdown()
    rt_loss = [float(m["loss"]) for m in ms]
    rt_grads = [np.asarray(g, np.float32)
                for g in jax.tree_util.tree_leaves(ms[0]["grads"])]
    peaks = [_peak_bytes(d) for d in devs]
    log(f"four-chip: runtime losses {rt_loss}, wall per iteration "
        f"{[m['execution'].makespan for m in ms]!r} s")
    log(f"four-chip: peak_bytes_in_use per device "
        f"{dict(zip([d.id for d in devs], peaks))}")
    del rt, ms
    gc.collect()

    omesh = section_mesh(devs[:2], ParallelConfig(dp=2), "oracle")
    ostep, oshard = mllm.build_colocated_step(
        vit_cfg, lm_cfg, omesh, mbs=mbs, seq_len=seq_len, impl=impl,
        lr_schedule=LR, return_grads=True)
    params = mllm.init_compound_params(vit_cfg, lm_cfg,
                                       jax.random.PRNGKey(seed),
                                       oshard["params"])
    opt = jax.device_put(adamw.init(params), oshard["opt"])
    o_loss, o_grads = [], None
    for i, (b, p) in enumerate(zip(host, plans)):
        params, opt, om = ostep(params, opt, mllm.colocated_batch(b, p),
                                jnp.int32(i))
        o_loss.append(float(om["loss"]))
        if i == 0:
            # the runtime's grads come back keyed {"lm", "vit"}
            o_grads = [np.asarray(g, np.float32) for g in
                       jax.tree_util.tree_leaves(
                           {"lm": om["grads"]["lm"],
                            "vit": om["grads"]["vit"]})]
    assert o_grads is not None and len(o_grads) == len(rt_grads)
    gmax = max(float(np.abs(g).max()) for g in o_grads)
    gdiff = max(float(np.abs(a - b).max()) for a, b in zip(rt_grads, o_grads))
    log(f"four-chip: colocated losses {o_loss}; per-iteration |dloss| "
        f"{[abs(a - b) for a, b in zip(rt_loss, o_loss)]!r} "
        f"(rtol {RUNTIME_LOSS_RTOL}); iteration-0 max |dgrad| {gdiff!r} "
        f"of max |grad| {gmax!r} (rtol {RUNTIME_GRAD_RTOL})")
    if not (all(np.isfinite(rt_loss)) and all(_finite(g) for g in rt_grads)):
        raise AssertionError("non-finite runtime losses or gradients")
    for i, (a, b) in enumerate(zip(rt_loss, o_loss)):
        _check_close(f"iteration {i} loss, runtime vs colocated", a, b,
                     RUNTIME_LOSS_RTOL)
    if not gdiff <= RUNTIME_GRAD_RTOL * gmax:
        raise AssertionError(f"iteration-0 gradients differ by {gdiff} > "
                             f"{RUNTIME_GRAD_RTOL} x {gmax}")
    return {"runtime_losses": rt_loss, "colocated_losses": o_loss,
            "grad_max_diff": gdiff, "grad_max": gmax,
            "peak_bytes_in_use": peaks}


# --------------------------------------------------------------------------- #
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the disaggregated four-chip phase and "
                         "the colocated step it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {devices}); this check "
              "runs on the chip only", file=sys.stderr)
        return 2
    print(f"devices: {devices}")
    vit_cfg, lm_cfg = pixtral_configs()
    print(f"compile cache: {enable_compile_cache() or 'off'}")
    if args.four_chips:
        four_chip_phase(vit_cfg, lm_cfg, seed=args.seed)
    else:
        one_chip_phase(vit_cfg, lm_cfg, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
