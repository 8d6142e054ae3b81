"""Optimizer + gradient compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:     # tier-1 must collect without hypothesis installed
    HAVE_HYPOTHESIS = False

from repro.optim import adamw
from repro.optim.compression import _dequant_int8, _quant_int8, ef_init, wire_bytes
from repro.launch.mesh import make_mesh


def test_adamw_converges_on_quadratic():
    target = jnp.asarray([1.5, -2.0, 0.5])
    params = {"w": jnp.zeros(3)}
    state = adamw.init(params)
    cfg = adamw.AdamWConfig(weight_decay=0.0, clip_norm=0.0)
    for _ in range(300):
        grads = {"w": params["w"] - target}
        params, state, _ = adamw.update(grads, state, jnp.float32(0.05),
                                        cfg)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target),
                               atol=1e-2)


def test_clip_bounds_update():
    params = {"w": jnp.zeros(4)}
    state = adamw.init(params)
    huge = {"w": jnp.full(4, 1e6)}
    _, state2, gnorm = adamw.update(huge, state, jnp.float32(1e-3),
                                    adamw.AdamWConfig(clip_norm=1.0))
    assert float(gnorm) > 1.0
    # first moment reflects the clipped gradient
    assert float(jnp.max(jnp.abs(state2.mu["w"]))) < 1.0


def test_master_does_not_alias_params():
    params = {"w": jnp.ones(3, jnp.float32)}
    state = adamw.init(params)
    assert state.master["w"] is not params["w"]


def test_bf16_params_updated_from_fp32_master():
    params = {"w": jnp.ones(8, jnp.bfloat16)}
    state = adamw.init(params)
    grads = {"w": jnp.full(8, 1e-4, jnp.bfloat16)}
    p2, state2, _ = adamw.update(grads, state, jnp.float32(1e-3))
    assert p2["w"].dtype == jnp.bfloat16
    assert state2.master["w"].dtype == jnp.float32
    # master moved even though bf16 cast may round
    assert float(jnp.max(jnp.abs(state2.master["w"] - 1.0))) > 0


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(st.lists(st.floats(-100, 100, allow_nan=False, width=32),
                    min_size=1, max_size=64))
    def test_int8_quantization_error_bound(vals):
        x = jnp.asarray(vals, jnp.float32)
        q, scale = _quant_int8(x)
        err = jnp.max(jnp.abs(_dequant_int8(q, scale) - x))
        assert float(err) <= float(scale) * 0.5 + 1e-6
else:
    def test_int8_quantization_error_bound():
        pytest.importorskip("hypothesis")


def test_error_feedback_accumulates_residual():
    from repro.optim.compression import ef_compress_tree
    # single device: psum over a trivial axis via shard_map on 1 device
    mesh = make_mesh((1,), ("d",))
    g = {"w": jnp.asarray([0.001, -0.002, 0.003], jnp.float32)}
    ef = ef_init(g)

    def run(g, ef):
        return ef_compress_tree(g, ef, "d", method="int8")

    from repro.dist.sharding import shard_map  # version-portable wrapper
    sm = shard_map(run, mesh, (jax.sharding.PartitionSpec(),) * 2,
                   (jax.sharding.PartitionSpec(),) * 2)
    total = jnp.zeros(3)
    for _ in range(20):
        red, ef = sm(g, ef)
        total = total + red["w"]
    # mean of compressed reductions converges to the true gradient
    np.testing.assert_allclose(np.asarray(total / 20),
                               np.asarray(g["w"]), rtol=0.05, atol=1e-5)


def test_wire_bytes():
    g = {"a": jnp.zeros((10, 10)), "b": jnp.zeros(50)}
    assert wire_bytes(g, "none") == 150 * 4
    assert wire_bytes(g, "bf16") == 150 * 2
    assert wire_bytes(g, "int8") == 150


def test_state_specs_structure():
    params = {"w": jax.ShapeDtypeStruct((4, 4), jnp.bfloat16)}
    sp = adamw.state_specs(params)
    assert sp.mu["w"].dtype == jnp.float32
    assert sp.master["w"].shape == (4, 4)


def test_gnorm_with_clipping_disabled_raises():
    """adamw.update(gnorm=) with clip_norm=0 used to silently ignore the
    precomputed joint norm; it must raise instead (the disaggregated
    runtimes only pass gnorm= when a clip threshold is active)."""
    params = {"w": jnp.ones((4,), jnp.float32)}
    state = adamw.init(params)
    grads = {"w": jnp.full((4,), 0.5, jnp.float32)}
    cfg = adamw.AdamWConfig(clip_norm=0.0)
    with pytest.raises(ValueError, match="clipping is disabled"):
        adamw.update(grads, state, jnp.float32(1e-3), cfg,
                     gnorm=jnp.float32(1.0))
    # without gnorm= the unclipped path still works
    new_p, _, gn = adamw.update(grads, state, jnp.float32(1e-3), cfg)
    assert np.isfinite(float(gn))
    # and with clipping enabled the override is honored
    cfg2 = adamw.AdamWConfig(clip_norm=0.1)
    _, _, gn2 = adamw.update(grads, state, jnp.float32(1e-3), cfg2,
                             gnorm=jnp.float32(42.0))
    assert float(gn2) == 42.0
