"""Multi-device driver: (a) elastic checkpoint restore onto a different
mesh shape; (b) tiny dry-run cells (reduced configs, 8-device meshes) for a
train, a decode, and a MoE cell — exercising the exact dryrun code path."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["REPRO_DRYRUN_TINY"] = "1"
os.environ["REPRO_DRYRUN_DEVICES"] = "8"

import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint.checkpointer import Checkpointer
from repro.launch.mesh import make_mesh

# ---- elastic restore ------------------------------------------------------
mesh_a = make_mesh((4, 2), ("data", "model"))
mesh_b = make_mesh((2, 4), ("data", "model"))
x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
xa = jax.device_put(x, NamedSharding(mesh_a, P("data", "model")))
with tempfile.TemporaryDirectory() as d:
    ck = Checkpointer(d, async_save=False)
    ck.save(3, {"w": xa})
    got = ck.restore(3, {"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)},
                     {"w": NamedSharding(mesh_b, P("model", "data"))})
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(x))
    assert got["w"].sharding.mesh.shape["data"] == 2

# ---- tiny dry-run cells ---------------------------------------------------
os.environ["REPRO_DRYRUN_MESH"] = "4,2"
from repro.launch import dryrun

with tempfile.TemporaryDirectory() as d:
    for arch, shape in [("granite-3-8b", "train_4k"),
                        ("mixtral-8x22b", "train_4k"),
                        ("mamba2-130m", "decode_32k"),
                        ("whisper-small", "prefill_32k")]:
        rec = dryrun.run_cell(arch, shape, "single", Path(d))
        assert "roofline" in rec, (arch, shape, rec.get("error"))
        assert rec["hlo"]["flops_per_device"] > 0
        assert rec["memory"]["temp_size_in_bytes"] >= 0

os.environ["REPRO_DRYRUN_MESH"] = "2,2,2"
with tempfile.TemporaryDirectory() as d:
    rec = dryrun.run_cell("qwen1.5-0.5b", "train_4k", "multi", Path(d))
    assert "roofline" in rec
    rec = dryrun.run_cell("granite-20b", "long_500k", "single", Path(d))
    assert "skipped" in rec          # full-attention arch skips long_500k

print("DRIVER_OK elastic_dryrun")
