"""Multi-device driver: the declarative HLO gate suite across regimes.

Compiles the real ``build_train_step`` post-SPMD HLO for every
distributed regime the repo claims (pp2 / cp2 / pp2tp2 / compressed-dp8)
and evaluates each regime's gate file (``repro/analysis/gates/``)
against it — the regime's declared collective profile (an undeclared
family = silent replication), the compressed payload dtypes, and the
f32 all-reduce residue budget are all machine-checked from data, not
inline asserts.  The per-claim gates (``vp_ce`` / ``tp_in_stage`` /
``compress``) run in ``driver_train_step_dist.py`` and the bench; this
driver owns the per-regime profiles.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import hlo_gates
from repro.configs import get_reduced
from repro.core.types import ParallelConfig, ShapeConfig
from repro.dist import sharding as shd
from repro.models.model import build_model
from repro.optim import adamw, schedules
from repro.train import step as step_mod

GB, S = 8, 16
OPT = adamw.AdamWConfig(eps=1e-3)
LR = functools.partial(schedules.constant, peak_lr=1e-3)
cfg = get_reduced("granite-3-8b").replace(dtype="float32", num_layers=4)
model = build_model(cfg, impl="ref")


def make_batch(c, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "tokens": jnp.asarray(rng.integers(0, c.vocab_size, (GB, S)),
                              jnp.int32),
        "labels": jnp.asarray(rng.integers(0, c.vocab_size, (GB, S)),
                              jnp.int32),
        "loss_mask": jnp.ones((GB, S), jnp.float32)}


def step_hlo(par):
    shape = ShapeConfig("t", "train", S, GB)
    mesh = shd.section_mesh(jax.devices()[:par.devices], par)
    step, sh = step_mod.build_train_step(model, mesh, par, shape,
                                         lr_schedule=LR, opt_cfg=OPT)
    params = jax.device_put(model.init(jax.random.PRNGKey(0)),
                            sh["params"])
    opt = jax.device_put(adamw.init(params), sh["opt"])
    args = [params, opt, make_batch(cfg), jnp.int32(0)]
    if par.grad_compress != "none":
        args.append(sh["ef_init"](params))
    with mesh:
        return step.lower(*args).compile().as_text()


REGIMES = {
    "pp2": ParallelConfig(dp=2, pp=2, mbs=2),
    "cp2": ParallelConfig(dp=2, cp=2, mbs=2),
    "pp2tp2": ParallelConfig(dp=2, pp=2, tp=2, mbs=2),
    "compressed": ParallelConfig(dp=8, mbs=1, zero_opt=False,
                                 grad_compress="int8"),
}

failed = False
for tag, par in REGIMES.items():
    rep, _ = hlo_gates.evaluate_file(
        hlo_gates.GATES_DIR / f"regime_{tag}.json",
        {"step": step_hlo(par)})
    print(rep.render())
    failed = failed or not rep.ok

assert not failed, "one or more regime gates reported errors"
print("DRIVER_OK hlo_gates")
