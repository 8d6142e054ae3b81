"""Gradient-compression primitives (single-device semantics) and the
donated-state guard.  Multi-device behavior — compressed psum vs exact
psum under shard_map, EF across steps, trajectory tolerance — lives in
tests/drivers/driver_compression.py (subprocess, 8 virtual devices)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist import sharding as shd
from repro.optim import adamw, compression as gcomp
from repro.launch.mesh import make_mesh


def _one_device_mesh():
    return make_mesh((1,), ("data",))


def _run_axis(fn, *args):
    """Run fn(*args) inside a shard_map over a size-1 'data' axis."""
    from jax.sharding import PartitionSpec as P
    mesh = _one_device_mesh()
    specs = tuple(P() for _ in args)
    return shd.shard_map(fn, mesh, specs, P())(*args)


def test_compressed_psum_n1_is_local_roundtrip():
    x = jnp.linspace(-3.0, 3.0, 101, dtype=jnp.float32)
    bf = _run_axis(lambda v: gcomp.compressed_psum_bf16(v, "data"), x)
    assert np.allclose(np.asarray(bf),
                       np.asarray(x.astype(jnp.bfloat16), np.float32))
    q = _run_axis(lambda v: gcomp.compressed_psum_int8(v, "data"), x)
    scale = float(jnp.max(jnp.abs(x))) / 127.0
    assert np.max(np.abs(np.asarray(q) - np.asarray(x))) <= 0.5 * scale + 1e-7


def test_int8_quant_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(257,)), jnp.float32)
    q, scale = gcomp._quant_int8(x)
    err = np.abs(np.asarray(gcomp._dequant_int8(q, scale)) - np.asarray(x))
    assert err.max() <= 0.5 * float(scale) + 1e-7


def test_ef_residual_is_exact_quant_error_and_reenters():
    rng = np.random.default_rng(1)
    g = {"w": jnp.asarray(rng.normal(size=(33,)), jnp.float32)}

    def step(grads, ef):
        return gcomp.ef_compress_tree(grads, ef, "data", "int8")

    red, ef1 = _run_axis(step, g, gcomp.ef_init(g))
    # n=1: reduced + residual reconstructs the input exactly (f32 math)
    recon = np.asarray(red["w"]) + np.asarray(ef1.residual["w"])
    assert np.allclose(recon, np.asarray(g["w"]), atol=1e-6)
    assert float(jnp.max(jnp.abs(ef1.residual["w"]))) > 0

    # the residual enters the NEXT step's gradient before compression:
    # feeding zero grads + ef1 must emit (approximately) the residual
    z = {"w": jnp.zeros_like(g["w"])}
    red2, _ = _run_axis(step, z, ef1)
    scale2 = float(jnp.max(jnp.abs(ef1.residual["w"]))) / 127.0
    assert np.max(np.abs(np.asarray(red2["w"])
                         - np.asarray(ef1.residual["w"]))) <= 0.5 * scale2 + 1e-7


def test_ef_none_method_is_exact_with_zero_residual():
    g = {"w": jnp.arange(8, dtype=jnp.float32)}
    red, ef = _run_axis(
        lambda gr, e: gcomp.ef_compress_tree(gr, e, "data", "none"),
        g, gcomp.ef_init(g))
    assert np.allclose(np.asarray(red["w"]), np.asarray(g["w"]))
    assert float(jnp.max(jnp.abs(ef.residual["w"]))) == 0.0


def test_unknown_method_rejected():
    g = {"w": jnp.zeros((4,), jnp.float32)}
    with pytest.raises(ValueError, match="fp4"):
        _run_axis(
            lambda gr, e: gcomp.ef_compress_tree(gr, e, "data", "fp4"),
            g, gcomp.ef_init(g))


def test_wire_bytes_payload_ratios():
    tree = {"a": jnp.zeros((16, 8), jnp.float32),
            "b": jnp.zeros((100,), jnp.float32)}
    n = 16 * 8 + 100
    assert gcomp.wire_bytes(tree, "none") == 4 * n
    assert gcomp.wire_bytes(tree, "bf16") == 2 * n
    assert gcomp.wire_bytes(tree, "int8") == n


def _tiny_state():
    params = {"w": jnp.ones((4,), jnp.float32)}
    return params, adamw.init(params)


def test_adamw_update_rejects_donated_state():
    params, state = _tiny_state()
    for leaf in jax.tree_util.tree_leaves(state):
        leaf.delete()
    with pytest.raises(adamw.DonatedStateError, match="donated"):
        adamw.update({"w": jnp.zeros((4,), jnp.float32)}, state,
                     jnp.float32(1e-3))


def test_check_live_passes_on_live_and_abstract_trees():
    params, state = _tiny_state()
    adamw.check_live(params)
    adamw.check_live(state)
    # ShapeDtypeStructs / tracers have no is_deleted — must be ignored
    adamw.check_live({"w": jax.ShapeDtypeStruct((4,), jnp.float32)})
