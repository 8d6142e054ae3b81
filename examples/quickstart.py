"""Quickstart: train a small LM end-to-end with the framework's public API.

    PYTHONPATH=src python examples/quickstart.py

Covers: config → model → sharded train step → train loop with async
checkpointing + straggler monitoring → resume.
"""
import functools
import tempfile

import jax
import jax.numpy as jnp

from repro.checkpoint.checkpointer import Checkpointer
from repro.core.types import ArchConfig, ParallelConfig, ShapeConfig
from repro.data.synthetic import lm_batches
from repro.models.model import build_model
from repro.optim import adamw, schedules
from repro.train import step as step_mod
from repro.train.loop import train
from repro.launch.mesh import make_mesh


def main():
    cfg = ArchConfig(name="quickstart-lm", family="dense", num_layers=4,
                     d_model=256, num_heads=4, num_kv_heads=2, d_ff=704,
                     vocab_size=2048, head_dim=64, dtype="float32")
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeConfig("quickstart", "train", 128, 8)
    model = build_model(cfg)
    step, shardings = step_mod.build_train_step(
        model, mesh, ParallelConfig(mbs=4), shape,
        lr_schedule=functools.partial(schedules.constant, peak_lr=3e-3))
    params = model.init(jax.random.PRNGKey(0))
    print(f"params: {sum(x.size for x in jax.tree_util.tree_leaves(params))/1e6:.2f}M")
    opt = adamw.init(params)

    with tempfile.TemporaryDirectory() as ckdir:
        ck = Checkpointer(ckdir, keep_last_n=2)
        with mesh:
            params = jax.device_put(params, shardings["params"])
            opt = jax.device_put(opt, shardings["opt"])
            res = train(step, params=params, opt_state=opt,
                        batches=lm_batches(batch=8, seq_len=128,
                                           vocab=2048, seed=0),
                        num_steps=40, checkpointer=ck,
                        checkpoint_every=20, log_every=10)
        print(f"loss: {res.losses[0]:.3f} -> {res.losses[-1]:.3f} "
              f"({res.steps_run} steps, {res.stragglers} stragglers, "
              f"checkpoints at {ck.all_steps()})")
        assert res.losses[-1] < res.losses[0], "did not learn!"
    print("quickstart OK")


if __name__ == "__main__":
    main()
