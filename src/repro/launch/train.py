"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch lm-100m --steps 200

Any assigned arch id works (``--arch granite-3-8b --reduced`` for the CPU
smoke variant; full configs need real accelerators).  Presets:

* ``lm-100m`` — ~100M-param llama-style LM (the deliverable-(b) scale)
* ``lm-20m``  — CPU-friendly variant used in the checked-in example run
"""
from __future__ import annotations

import argparse
import functools
import time

import jax

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import ARCH_NAMES, get_config, get_reduced
from repro.core.types import ArchConfig, ParallelConfig, ShapeConfig
from repro.data.synthetic import lm_batches
from repro.models.model import build_model
from repro.optim import adamw, schedules
from repro.train import step as step_mod
from repro.train.loop import train
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh

PRESETS = {
    "lm-100m": ArchConfig(name="lm-100m", family="dense", num_layers=12,
                          d_model=768, num_heads=12, num_kv_heads=4,
                          d_ff=2048, vocab_size=32768, head_dim=64),
    "lm-20m": ArchConfig(name="lm-20m", family="dense", num_layers=6,
                         d_model=384, num_heads=6, num_kv_heads=2,
                         d_ff=1024, vocab_size=8192, head_dim=64),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="lm-20m",
                    help=f"preset {list(PRESETS)} or one of {ARCH_NAMES}")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced smoke config of an assigned arch")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--mbs", type=int, default=0,
                    help="microbatch size per DP shard (0 = whole batch)")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--data", type=int, default=1, help="data-axis size")
    ap.add_argument("--model", type=int, default=1, help="model-axis size")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--dtype", default="float32")
    args = ap.parse_args()

    if args.arch in PRESETS:
        cfg = PRESETS[args.arch]
    elif args.reduced:
        cfg = get_reduced(args.arch)
    else:
        cfg = get_config(args.arch)
    cfg = cfg.replace(dtype=args.dtype)

    print(f"compile cache: {enable_compile_cache() or 'off'}")
    mesh = make_mesh((args.data, args.model), ("data", "model"))
    shape = ShapeConfig("cli", "train", args.seq, args.batch)
    mbs = args.mbs or max(args.batch // args.data, 1)
    parallel = ParallelConfig(dp=args.data, tp=args.model, mbs=mbs)

    model = build_model(cfg)
    step, shardings = step_mod.build_train_step(
        model, mesh, parallel, shape,
        lr_schedule=functools.partial(
            schedules.warmup_cosine, peak_lr=args.lr,
            warmup_steps=max(args.steps // 10, 1),
            total_steps=args.steps))
    params = model.init(jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"mesh=({args.data},{args.model}) mbs={mbs}")
    opt = adamw.init(params)
    ck = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None

    with mesh:
        params = jax.device_put(params, shardings["params"])
        opt = jax.device_put(opt, shardings["opt"])
        t0 = time.time()
        res = train(step, params=params, opt_state=opt,
                    batches=lm_batches(batch=args.batch, seq_len=args.seq,
                                       vocab=cfg.vocab_size, seed=0),
                    num_steps=args.steps, checkpointer=ck,
                    checkpoint_every=args.ckpt_every, log_every=10)
        dt = time.time() - t0
    toks = args.steps * args.batch * args.seq
    print(f"done: {res.steps_run} steps, loss "
          f"{res.losses[0]:.3f} -> {res.losses[-1]:.3f}, "
          f"{toks/dt:.0f} tok/s, stragglers={res.stragglers}")


if __name__ == "__main__":
    main()
