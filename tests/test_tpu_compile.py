"""Compile the main path's Pallas kernels at real widths for a described
TPU v5e, with no chip attached: what the TPU compiler refuses (block
layouts, VMEM limits) fails here, which interpret mode cannot show.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and the tests run under several workers.  Everything built from the
topology is built in a fixture or a test."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import distill_kl_pallas as dkp
from repro.kernels import embed_grad as eg
from repro.kernels import flash_attention as fa
from repro.kernels import ssd_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile_has_kernel(fn, *args) -> bool:
    return "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


# (B, S, H, KV, D, causal): the Pixtral-12B LM at seq 4096, and the
# Pixtral-ViT over 4096 patches
ATTN = {"lm": (1, 4096, 32, 8, 128, True),
        "vit": (1, 4096, 16, 16, 64, False)}


def _qkv(one_chip, B, S, H, KV, D):
    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    return s(B, S, H, D), s(B, S, KV, D), s(B, S, KV, D)


@pytest.mark.parametrize("widths", sorted(ATTN))
def test_flash_attention_forward_compiles(one_chip, widths):
    B, S, H, KV, D, causal = ATTN[widths]
    assert _compile_has_kernel(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=causal),
        *_qkv(one_chip, B, S, H, KV, D))


@pytest.mark.parametrize("widths", sorted(ATTN))
def test_flash_attention_backward_compiles(one_chip, widths):
    B, S, H, KV, D, causal = ATTN[widths]

    def loss(q, k, v):
        o = fa.flash_attention(q, k, v, causal=causal)
        return o.astype(jnp.float32).sum()
    assert _compile_has_kernel(jax.grad(loss, argnums=(0, 1, 2)),
                               *_qkv(one_chip, B, S, H, KV, D))


def test_flash_attention_lse_compiles(one_chip):
    B, S, H, KV, D, _ = ATTN["lm"]
    assert _compile_has_kernel(
        lambda q, k, v: fa.flash_attention_lse(q, k, v, causal=True),
        *_qkv(one_chip, B, S, H, KV, D))


def test_distill_kl_compiles(one_chip):
    # Granite-3-8B's hidden size, a 16384-row vocabulary slice, 4096 tokens
    N, D, V = 4096, 4096, 16384
    h = jax.ShapeDtypeStruct((N, D), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((D, V), jnp.bfloat16, sharding=one_chip)
    assert _compile_has_kernel(
        lambda hs, ws, ht, wt: dkp.distill_kl_pallas(hs, ws, ht, wt,
                                                     block_v=2048),
        h, w, h, w)


def test_ssd_scan_compiles(one_chip):
    cfg = get_config("mamba2-130m")
    d_inner = cfg.ssm_expand * cfg.d_model
    b, s, p, n = 1, 4096, cfg.ssm_headdim, cfg.ssm_state
    h = d_inner // p

    def s_(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    assert _compile_has_kernel(
        lambda x, dt, A, B, C, D: ssd_pallas.ssd_scan_pallas(
            x, dt, A, B, C, D, chunk=128),
        s_((b, s, h, p)), s_((b, s, h), jnp.float32), s_((h,), jnp.float32),
        s_((b, s, n)), s_((b, s, n)), s_((h,), jnp.float32))


# (V, d, mbs, S): Pixtral-12B's cut vocabulary and Granite-3.0-2B's tied
# one, each with a microbatch of 2 × 4096 ids
EMBED = {"pixtral-12b": (16384, 5120, 2, 4096),
         "granite-3.0-2b": (49152, 2048, 2, 4096)}


@pytest.mark.parametrize("widths", sorted(EMBED))
def test_embed_grad_compiles(one_chip, widths):
    V, d, mbs, S = EMBED[widths]
    table = jax.ShapeDtypeStruct((V, d), jnp.bfloat16, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((mbs, S), jnp.int32, sharding=one_chip)
    g = jax.ShapeDtypeStruct((mbs, S, d), jnp.bfloat16, sharding=one_chip)

    def table_grad(t, i, g):
        return jax.vjp(lambda t: eg.embed_lookup(t, i), t)[1](g)[0]
    assert _compile_has_kernel(table_grad, table, ids, g)
