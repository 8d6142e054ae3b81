"""End-to-end system behaviour on CPU: the full train loop (step builder +
optimizer + checkpointing + data pipeline) actually *learns* on the
synthetic markov stream, and the serving path generates consistently."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as cfgs
from repro.core.types import ParallelConfig, ShapeConfig
from repro.data.synthetic import lm_batches
from repro.models.model import build_model
from repro.optim import adamw, schedules
from repro.train import step as step_mod
from repro.train.loop import train
from repro.launch.mesh import make_mesh


def test_training_learns_on_single_device_mesh(tmp_path):
    cfg = cfgs.get_reduced("qwen1.5-0.5b").replace(
        dtype="float32", num_layers=2, vocab_size=64, d_ff=128)
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeConfig("tiny", "train", 32, 8)
    model = build_model(cfg)
    step, shardings = step_mod.build_train_step(
        model, mesh, ParallelConfig(mbs=4), shape,
        lr_schedule=functools.partial(schedules.constant, peak_lr=3e-3))
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw.init(params)
    with mesh:
        params = jax.device_put(params, shardings["params"])
        opt = jax.device_put(opt, shardings["opt"])
        res = train(step, params=params, opt_state=opt,
                    batches=lm_batches(batch=8, seq_len=32, vocab=64,
                                       seed=0),
                    num_steps=30, log_every=1000, log_fn=lambda s: None)
    first = np.mean(res.losses[:5])
    last = np.mean(res.losses[-5:])
    assert last < first - 0.3, (first, last)   # actually learning


def test_serve_prefill_decode_loop():
    cfg = cfgs.get_reduced("granite-3-8b").replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 12)),
                         jnp.int32)
    logits, cache = model.prefill(params, {"tokens": prompt},
                                  extra_cache=4)
    toks = [jnp.argmax(logits, -1)[:, None].astype(jnp.int32)]
    for i in range(4):
        logits, cache = model.decode(params, cache, toks[-1],
                                     jnp.int32(12 + i))
        toks.append(jnp.argmax(logits, -1)[:, None].astype(jnp.int32))
    gen = jnp.concatenate(toks, axis=1)
    assert gen.shape == (2, 5)
    # greedy decode must match teacher-forced full forward on own output
    full = model.forward(params, {"tokens": jnp.concatenate(
        [prompt, gen[:, :-1]], axis=1)})
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(full[:, -1], -1)),
        np.asarray(gen[:, -1]))


def test_train_step_sharded_single_device_matches_plain():
    """The jitted/sharded step computes the same loss as a plain grad."""
    from conftest import toy_batch
    cfg = cfgs.get_reduced("granite-3-8b").replace(dtype="float32")
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeConfig("tiny", "train", 16, 4)
    model = build_model(cfg)
    step, shardings = step_mod.build_train_step(
        model, mesh, ParallelConfig(mbs=4), shape)
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw.init(params)
    batch = toy_batch(cfg, B=4, S=16)
    plain_loss, _ = model.loss(params, batch)
    with mesh:
        p = jax.device_put(params, shardings["params"])
        o = jax.device_put(opt, shardings["opt"])
        _, _, metrics = step(p, o, batch, jnp.int32(0))
    np.testing.assert_allclose(float(metrics["loss"]), float(plain_loss),
                               rtol=1e-5, atol=1e-5)
