"""Which kernel tier a call runs.  ``auto`` (and ``REPRO_KERNEL_IMPL``)
pick the Pallas kernel where it takes the call's shapes and ``ref`` where
it does not, with a warning; a Pallas tier the caller names raises
instead (``test_kernels_flash``, ``test_kernels_distill_kl``).

``REPRO_KERNEL_IMPL=pallas_interpret`` stands in for ``auto`` on a TPU:
both make the Pallas kernel the tier of choice."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.distill_kl import distill_kl_chunked_jnp
from repro.models import common as cm
from repro.models import encdec


@pytest.fixture
def kernel_tier(monkeypatch):
    monkeypatch.setattr(ops, "_REF_WARNED", set())
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas_interpret")


def test_resolve_keeps_a_named_pallas_tier(kernel_tier):
    assert ops.resolve("auto") == "pallas_interpret"
    assert ops.resolve("auto", fits=False, what="x") == "ref"
    assert ops.resolve("ref", fits=False, what="x") == "ref"
    assert ops.resolve("pallas_interpret", fits=False) == "pallas_interpret"
    assert ops.flash_impl("auto", 4096, 4096) == "pallas_interpret"
    assert ops.flash_impl("auto", 1500, 1500) == "ref"
    assert ops.flash_impl("auto", 4096, 4096, segmented=True) == "ref"


def test_whisper_small_encoder_at_1500_frames(monkeypatch):
    """whisper-small's 1500 frames are no multiple of the 512 block."""
    cfg = get_config("whisper-small").replace(encoder_layers=1,
                                              num_layers=1)
    params = cm.init_params(encdec.encdec_specs(cfg), jax.random.PRNGKey(0))
    frames = jax.random.normal(jax.random.PRNGKey(1),
                               (1, cfg.frontend_frames, cfg.frontend_dim))
    want = encdec.encode(params, cfg, frames, impl="ref")
    monkeypatch.setattr(ops, "_REF_WARNED", set())
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas_interpret")
    with pytest.warns(UserWarning, match="S=1500 T=1500"):
        got = encdec.encode(params, cfg, frames)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_distill_kl_at_granite_vocab(kernel_tier):
    """Granite-3-8B's 49,155-token vocabulary is no multiple of any
    block; the hidden width is cut to keep the test small."""
    V = get_config("granite-3-8b").vocab_size
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    hs = jax.random.normal(ks[0], (64, 16))
    ws = jax.random.normal(ks[1], (16, V), jnp.float32) * 0.2
    ht = jax.random.normal(ks[2], (64, 16))
    wt = jax.random.normal(ks[3], (16, V), jnp.float32) * 0.2
    with pytest.warns(UserWarning, match=f"V={V}"):
        got = ops.distill_kl(hs, ws, ht, wt, temperature=2.0)
    want = distill_kl_chunked_jnp(hs, ws, ht, wt, temperature=2.0,
                                  block_v=2048)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
