"""Multi-device driver: ``chip_smoke.py --four-chips``'s phase (the
disaggregated MLLM runtime, ViT dp=2 + LM dp=2, lookahead=1, against the
colocated step on a dp=2 mesh) on four virtual CPU devices at a tiny size,
with the Pallas kernels interpreted."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import importlib.util
from pathlib import Path

import numpy as np

from repro.configs import get_reduced
from repro.models.vlm import vit_config

ROOT = Path(__file__).resolve().parents[2]
spec = importlib.util.spec_from_file_location("chip_smoke",
                                              ROOT / "chip_smoke.py")
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)

lm = get_reduced("pixtral-12b").replace(
    dtype="float32", vocab_size=256, vision_dim=32, max_image_tokens=4)
vit = vit_config(num_layers=2, d_model=32, num_heads=2, d_ff=64,
                 patch_dim=16, downsample=4, out_dim=32,
                 name="vit-tiny").replace(dtype="float32")
lines = []
out = smoke.four_chip_phase(vit, lm, batch=8, mbs=2, seq_len=32, iters=3,
                            impl="pallas_interpret", log=lines.append)
print("\n".join(lines))
assert len(out["runtime_losses"]) == 3
assert np.isfinite(out["runtime_losses"]).all()
# f32 on the CPU: the two paths agree far inside the chip's tolerances
np.testing.assert_allclose(out["runtime_losses"], out["colocated_losses"],
                           rtol=1e-5)
assert out["grad_max_diff"] <= 1e-4 * out["grad_max"], out
print("DRIVER_OK chip_smoke")
