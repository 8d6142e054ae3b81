"""Chunked-vocab distillation KL: chunked-jnp and Pallas (interpret) vs
the full-materialization oracle; analytic backward vs autodiff."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.distill_kl import distill_kl_chunked_jnp
from repro.kernels.distill_kl_pallas import distill_kl_pallas


def _inputs(N, Ds, Dt, V, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(k[0], (N, Ds)),
            jax.random.normal(k[1], (Ds, V)) * 0.2,
            jax.random.normal(k[2], (N, Dt)),
            jax.random.normal(k[3], (Dt, V)) * 0.2)


@pytest.mark.parametrize("N,Ds,Dt,V", [(32, 16, 24, 128), (64, 8, 8, 256),
                                       (16, 32, 16, 96)])
@pytest.mark.parametrize("T", [1.0, 2.0])
@pytest.mark.parametrize("masked", [False, True])
def test_chunked_matches_oracle(N, Ds, Dt, V, T, masked):
    hs, ws, ht, wt = _inputs(N, Ds, Dt, V)
    mask = (jnp.arange(N) % 3 != 0) if masked else None
    r_ref = ref.distill_kl_reference(hs, ws, ht, wt, mask=mask,
                                     temperature=T)
    r = distill_kl_chunked_jnp(hs, ws, ht, wt, mask=mask, temperature=T,
                               block_v=32)
    np.testing.assert_allclose(float(r), float(r_ref), atol=1e-5,
                               rtol=1e-5)


@pytest.mark.parametrize("block_t,block_v", [(16, 64), (32, 32)])
def test_pallas_matches_oracle(block_t, block_v):
    hs, ws, ht, wt = _inputs(64, 16, 24, 256)
    mask = jnp.arange(64) % 4 != 0
    r_ref = ref.distill_kl_reference(hs, ws, ht, wt, mask=mask,
                                     temperature=2.0)
    r = distill_kl_pallas(hs, ws, ht, wt, mask=mask, temperature=2.0,
                          interpret=True, block_t=block_t, block_v=block_v)
    np.testing.assert_allclose(float(r), float(r_ref), atol=1e-5,
                               rtol=1e-5)


@pytest.mark.parametrize("N,V,block_v", [
    (64, 96, 64),          # vocabulary not a multiple of the block
    (16, 49155, 2048),     # Granite-3-8B's vocabulary at ops' default block
])
def test_pallas_raises_on_misaligned_vocab(N, V, block_v):
    """A Pallas tier that was asked for never hands the call to jnp."""
    from repro.kernels import ops
    hs, ws, ht, wt = _inputs(N, 8, 8, V)
    with pytest.raises(ValueError, match="impl='ref'"):
        ops.distill_kl(hs, ws, ht, wt, impl="pallas_interpret",
                       block_v=block_v)


def test_analytic_backward_matches_autodiff():
    hs, ws, ht, wt = _inputs(32, 16, 24, 128)
    mask = jnp.arange(32) % 3 != 0
    g1 = jax.grad(lambda *a: distill_kl_chunked_jnp(
        *a, mask=mask, temperature=2.0, block_v=32),
        argnums=(0, 1, 2, 3))(hs, ws, ht, wt)
    g2 = jax.grad(lambda *a: ref.distill_kl_reference(
        *a, mask=mask, temperature=2.0), argnums=(0, 1, 2, 3))(hs, ws, ht,
                                                               wt)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6,
                                   rtol=1e-5)


def test_pallas_backward():
    hs, ws, ht, wt = _inputs(32, 16, 16, 128)
    g1 = jax.grad(lambda *a: distill_kl_pallas(
        *a, temperature=1.5, interpret=True, block_t=16, block_v=32),
        argnums=(0, 1, 2, 3))(hs, ws, ht, wt)
    g2 = jax.grad(lambda *a: ref.distill_kl_reference(
        *a, temperature=1.5), argnums=(0, 1, 2, 3))(hs, ws, ht, wt)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6,
                                   rtol=1e-5)


def test_kl_properties():
    """KL(p||p) == 0; KL ≥ 0 (identical teacher/student nets give 0)."""
    hs, ws, _, _ = _inputs(16, 8, 8, 64)
    z = distill_kl_chunked_jnp(hs, ws, hs, ws, temperature=1.0, block_v=16)
    np.testing.assert_allclose(float(z), 0.0, atol=1e-6)
    _, _, ht, wt = _inputs(16, 8, 8, 64, seed=7)
    pos = distill_kl_chunked_jnp(hs, ws, ht, wt, temperature=1.0,
                                 block_v=16)
    assert float(pos) >= 0.0
