"""Multi-device driver: cross-pod GPipe PP (loss+grads == reference) and
context-parallel attention — every CP mode (ulysses, overlap-pipelined
ulysses, head-replicated ulysses_mqa, allgather) exact forward AND
backward against the naive reference, plus the kernel-substrate dispatch
(``--cp-only`` + ``REPRO_KERNEL_IMPL=pallas_interpret`` in CI runs the
whole CP matrix through the interpreted Pallas kernel)."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_reduced
from repro.dist.context import cp_attention
from repro.dist.pipeline import build_pp_loss
from repro.kernels import ref
from repro.models import transformer as tf
from repro.models.common import init_params
from repro.launch.mesh import make_mesh

CP_ONLY = "--cp-only" in sys.argv[1:]

# ---- PP over pod × manual DP ---------------------------------------------
if not CP_ONLY:
    cfg = get_reduced("granite-3-8b").replace(dtype="float32",
                                              num_layers=4)
    mesh = make_mesh((2, 4), ("pod", "data"))
    loss_fn, _ = build_pp_loss(cfg, mesh, n_micro=2)
    params = init_params(tf.lm_specs(cfg), jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    B, S = 16, 16
    batch = {"tokens": jnp.asarray(
                 rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
             "labels": jnp.asarray(
                 rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
             "loss_mask": jnp.ones((B, S), jnp.float32)}
    l_ref, _ = tf.lm_loss(params, cfg, batch, impl="ref")
    with mesh:
        l_pp = jax.jit(loss_fn)(params, batch)
        g_pp = jax.jit(jax.grad(lambda p: loss_fn(p, batch)))(params)
    assert abs(float(l_pp) - float(l_ref)) < 1e-5, (float(l_pp),
                                                    float(l_ref))
    g_ref = jax.grad(
        lambda p: tf.lm_loss(p, cfg, batch, impl="ref")[0])(params)
    err = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), g_pp, g_ref)))
    assert err < 5e-4, err

# ---- CP attention ---------------------------------------------------------
# impl tier for the in-shard flash calls: CI also runs this driver with
# REPRO_KERNEL_IMPL=pallas_interpret, which overrides the kops dispatch
# and sends every case below through the interpreted Pallas kernel.
Bq, Sq, H, KV, D = 2, 64, 8, 4, 16
ks = jax.random.split(jax.random.PRNGKey(1), 3)
q = jax.random.normal(ks[0], (Bq, Sq, H, D))
k = jax.random.normal(ks[1], (Bq, Sq, KV, D))
v = jax.random.normal(ks[2], (Bq, Sq, KV, D))


def check(mesh, mode, chunks=1, window=0, tol=2e-5):
    """Forward and full (dq, dk, dv) backward vs the naive reference."""
    def f(q, k, v):
        return cp_attention(q, k, v, mesh, mode=mode, causal=True,
                            window=window, overlap_chunks=chunks,
                            block_q=16, block_kv=16)

    def r(q, k, v):
        return ref.mha_reference(q, k, v, causal=True, window=window)

    with mesh:
        o = f(q, k, v)
        e = float(jnp.max(jnp.abs(o - r(q, k, v))))
        assert e < tol, (mode, chunks, window, "fwd", e)
        loss = lambda fn: lambda *a: jnp.sum(fn(*a) ** 2)
        g = jax.grad(loss(f), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(r), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("dq dk dv".split(), g, gr):
        eb = float(jnp.max(jnp.abs(a - b)))
        assert eb < tol, (mode, chunks, window, name, eb)


# cp=4 divides both H=8 and KV=4: ulysses territory, monolithic and
# overlap-chunked (the a2a interleaving makes chunk positions strided —
# kv_positions keeps causal/window masking exact).
mesh4 = make_mesh((4,), ("model",))
check(mesh4, "ulysses")
check(mesh4, "allgather")
for chunks in (2, 4):
    check(mesh4, "ulysses", chunks=chunks)
check(mesh4, "ulysses", chunks=4, window=24)

# cp=8 does not divide KV=4: head-replicated ulysses vs the allgather
# fallback (the comm claim lives in the ulysses_mqa gate; exactness here).
mesh8 = make_mesh((8,), ("model",))
check(mesh8, "ulysses_mqa")
check(mesh8, "allgather")
check(mesh8, "auto")        # resolves to ulysses_mqa at this shape

# explicit kernel-tier dispatch (independent of the env override):
# the interpreted Pallas kernel must agree inside the shard too.
with mesh4:
    o_pi = cp_attention(q, k, v, mesh4, mode="ulysses",
                        impl="pallas_interpret", overlap_chunks=2,
                        block_q=16, block_kv=16)
e = float(jnp.max(jnp.abs(o_pi - ref.mha_reference(q, k, v, causal=True))))
assert e < 2e-5, ("pallas_interpret", e)

print("DRIVER_OK pipeline_cp")
