"""The token-embedding gradient as a sorted, vocabulary-tiled one-hot
contraction (``kernels/embed_grad.py``), interpreted, against a float32
``segment_sum``; its ``custom_vjp`` lookup against ``jax.grad`` of
``jnp.take``; and where ``kernels.ops`` engages it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro.kernels import embed_grad as eg
from repro.kernels import ops
from repro.models import common as cm

V, D, BV, BN = 1024, 256, 256, 128
N = 700                                       # no multiple of BN: padded


def _ids(case, rng):
    if case == "all_equal":
        return np.full(N, 517)
    if case == "all_distinct":
        return rng.permutation(V)[:N]
    if case == "tile_edges":
        edges = np.arange(BV, V, BV)
        return rng.choice(np.concatenate([[0, V - 1], edges - 1, edges]), N)
    return (rng.zipf(1.3, N) - 1) % V         # zipf, zero_rows, zipf_bf16


def _kernel_lookup(table, ids):
    return eg.embed_lookup(table, ids, block_v=BV, interpret=True)


def _take(table, ids):
    return jnp.take(table, ids, axis=0)


CASES = ["all_equal", "all_distinct", "tile_edges", "zipf", "zero_rows",
         "zipf_bf16"]


@pytest.mark.parametrize("case", CASES)
def test_embed_grad_matches_segment_sum(case, monkeypatch):
    # a budget that holds one 128-wide column tile: two tiles across D
    monkeypatch.setattr(eg, "_VMEM_BUDGET", 10**6)
    rng = np.random.default_rng(CASES.index(case))
    dtype = jnp.bfloat16 if case.endswith("bf16") else jnp.float32
    ids = jnp.asarray(_ids(case, rng), jnp.int32)
    g = rng.standard_normal((N, D)).astype(np.float32)
    if case == "zero_rows":                   # image positions send zeros
        g[rng.random(N) < 0.5] = 0.0
    g = jnp.asarray(g, dtype)
    truth = jax.ops.segment_sum(g.astype(jnp.float32), ids, V)

    got = eg.embed_grad(ids, g, V, dtype, block_v=BV, block_n=BN,
                        interpret=True)
    assert got.dtype == dtype
    # f32: sums of up to N terms in another order; bf16: one rounding
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(truth.astype(dtype), np.float32),
                               rtol=1e-5 if dtype == jnp.float32 else 8e-3,
                               atol=1e-4)

    # the lookup's gradient; the cotangent of its rows is g itself
    table = jnp.asarray(rng.standard_normal((V, D)), dtype)
    ids2 = ids.reshape(7, 100)

    def grad(lookup):
        return jax.grad(lambda t: jnp.sum(
            lookup(t, ids2).astype(jnp.float32)
            * g.reshape(7, 100, D).astype(jnp.float32)))(table)

    got, want = grad(_kernel_lookup), grad(_take)
    if dtype == jnp.float32:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)
    else:
        # f32 accumulation: never further from the f32 sum than XLA's
        # bf16 scatter-add
        def err(x):
            return float(jnp.max(jnp.abs(x.astype(jnp.float32) - truth)))
        assert err(got) <= err(want)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(truth.astype(dtype),
                                              np.float32),
                                   rtol=8e-3, atol=1e-4)


def test_backward_runs_under_its_scope():
    table = jnp.zeros((V, D), jnp.float32)
    ids = jnp.arange(N, dtype=jnp.int32)
    hlo = jax.jit(jax.grad(lambda t: _kernel_lookup(t, ids).sum())).lower(
        table).as_text(debug_info=True)
    assert "embed_grad" in hlo


def test_pallas_raises_on_a_table_it_cannot_take():
    with pytest.raises(ValueError, match="impl='ref'"):
        ops.embed_lookup(jnp.zeros((V, 96)), jnp.arange(4),
                         impl="pallas_interpret")


def test_ops_keeps_the_scatter_on_a_multi_device_mesh(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas_interpret")
    assert ops.embed_impl("auto", V, D) == "pallas_interpret"
    one = cm.KernelLayout(AbstractMesh((1,), ("data",)), "data", None)
    with cm.act_hook(None, one):
        assert ops.embed_impl("auto", V, D) == "pallas_interpret"
    two = cm.KernelLayout(AbstractMesh((2,), ("data",)), "data", None)
    with cm.act_hook(None, two):
        assert ops.embed_impl("auto", V, D) == "ref"
        assert ops.embed_impl("pallas", V, D) == "ref"
        jaxpr = jax.make_jaxpr(jax.grad(lambda t: ops.embed_lookup(
            t, jnp.arange(8)).sum()))(jnp.zeros((V, D)))
    assert "pallas_call" not in str(jaxpr)
    assert "scatter-add" in str(jaxpr)
