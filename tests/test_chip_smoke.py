"""``chip_smoke.py``'s one-chip phase at a tiny size on the CPU, with the
Pallas kernels interpreted; and its published-width configuration.  The
four-chip phase runs on virtual devices in
``tests/drivers/driver_chip_smoke.py`` (``test_multidevice.py``)."""
import importlib.util
import math
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_configs():
    from repro.configs import get_reduced
    from repro.models.vlm import vit_config
    lm = get_reduced("pixtral-12b").replace(
        dtype="float32", vocab_size=256, vision_dim=32, max_image_tokens=4)
    vit = vit_config(num_layers=2, d_model=32, num_heads=2, d_ff=64,
                     patch_dim=16, downsample=4, out_dim=32,
                     name="vit-tiny").replace(dtype="float32")
    return vit, lm


def test_pixtral_configs_keep_published_widths(smoke):
    from repro.mllm.workload import init_compound_params
    vit, lm = smoke.pixtral_configs()
    assert (lm.d_model, lm.num_heads, lm.num_kv_heads, lm.head_dim,
            lm.d_ff, lm.vision_dim, lm.max_image_tokens) == \
        (5120, 32, 8, 128, 14336, 1024, 1024)
    assert (vit.d_model, vit.num_heads, vit.d_ff, vit.frontend_dim,
            vit.vision_dim) == (1024, 16, 4096, 768, 1024)
    assert (lm.num_layers, lm.vocab_size, vit.num_layers) == (1, 16384, 4)
    shapes = jax.eval_shape(lambda k: init_compound_params(vit, lm, k),
                            jax.random.PRNGKey(0))
    n = sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(shapes))
    assert 0.45e9 < n < 0.6e9, n


def test_one_chip_phase_runs_interpreted(smoke):
    vit, lm = tiny_configs()
    lines = []
    out = smoke.one_chip_phase(vit, lm, batch=4, mbs=2, seq_len=32,
                               steps=3, impl="pallas_interpret",
                               log=lines.append)
    assert len(out["losses"]) == 3 and np.isfinite(out["losses"]).all()
    # interpreted kernels lower to plain HLO, not to Mosaic custom calls
    assert not out["has_kernel"]
    assert abs(out["losses"][0] - out["ref_loss"]) <= \
        smoke.KERNEL_LOSS_RTOL * abs(out["ref_loss"])
    assert out["tokens_per_s"] > 0 and out["compile_s"] > 0
    assert any("median step" in line for line in lines)


def test_main_refuses_without_a_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    captured = capsys.readouterr()
    assert "no TPU found" in captured.err
    assert '"ok"' not in captured.out
