"""PP/CP dispatch in the step builders.

The numerical 8-way equivalence (pp=2 / cp=2 / pp×tp vs the monolithic
reference, exact MoE PP aux) runs in the subprocess driver
``tests/drivers/driver_train_step_dist.py`` — the main test process must
keep seeing exactly 1 device.  This file covers the guard rails: a
``ParallelConfig`` with cp/pp > 1 can no longer fall through to the
replicated step unannounced, and the microbatch split no longer silently
duplicates data.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

import repro.configs as cfgs
from repro.core.types import ParallelConfig, ShapeConfig
from repro.dist import sharding as shd
from repro.models import attention as att
from repro.models.model import build_model
from repro.train import step as step_mod
from repro.launch.mesh import make_mesh


def _tiny_model():
    cfg = cfgs.get_reduced("qwen1.5-0.5b").replace(
        dtype="float32", num_layers=2, vocab_size=64, d_ff=128)
    return build_model(cfg, impl="ref")


def test_pp_config_on_flat_mesh_raises():
    """The headline bug: pp>1 on a mesh without a pipe axis used to train
    silently replicated."""
    model = _tiny_model()
    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="pp"):
        step_mod.build_train_step(model, mesh, ParallelConfig(pp=2),
                                  ShapeConfig("t", "train", 16, 4))


def test_cp_config_on_flat_mesh_raises():
    model = _tiny_model()
    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="cp"):
        step_mod.build_train_step(model, mesh, ParallelConfig(cp=2),
                                  ShapeConfig("t", "train", 16, 4))


def test_mesh_pp_axis_without_config_raises():
    """The reverse mismatch: a carved pipe mesh with a pp=1 config."""
    mesh = shd.abstract_mesh((1, 2, 1, 1),
                             ("data", "pipe", "seq", "model"))
    with pytest.raises(ValueError, match="pipe"):
        step_mod.parallel_regime(mesh, ParallelConfig())


def test_pp_cp_composition_rejected():
    mesh = shd.abstract_mesh((1, 2, 2, 1),
                             ("data", "pipe", "seq", "model"))
    with pytest.raises(NotImplementedError, match="pp×cp"):
        step_mod.parallel_regime(mesh, ParallelConfig(pp=2, cp=2))


def test_parallel_regime_dispatch():
    axes = ("data", "pipe", "seq", "model")
    assert step_mod.parallel_regime(
        shd.abstract_mesh((2, 1, 1, 2), axes), ParallelConfig(dp=2, tp=2)
    ) == "plain"
    assert step_mod.parallel_regime(
        shd.abstract_mesh((1, 2, 1, 2), axes), ParallelConfig(pp=2, tp=2)
    ) == "pp"
    assert step_mod.parallel_regime(
        shd.abstract_mesh((2, 1, 2, 1), axes), ParallelConfig(dp=2, cp=2)
    ) == "cp"


def test_cp_on_attention_free_arch_raises():
    cfg = cfgs.get_reduced("mamba2-130m").replace(dtype="float32")
    model = build_model(cfg, impl="ref")
    mesh = shd.abstract_mesh((1, 1, 2, 1),
                             ("data", "pipe", "seq", "model"))
    with pytest.raises(NotImplementedError, match="attention-free"):
        step_mod.build_train_step(model, mesh, ParallelConfig(cp=2),
                                  ShapeConfig("t", "train", 16, 4))


def test_pp_rejects_sequence_parallel():
    model = _tiny_model()
    mesh = shd.abstract_mesh((1, 2, 1, 1),
                             ("data", "pipe", "seq", "model"))
    with pytest.raises(NotImplementedError, match="sequence_parallel"):
        step_mod.build_train_step(
            model, mesh, ParallelConfig(pp=2, sequence_parallel=True),
            ShapeConfig("t", "train", 16, 4))


def test_distill_cp_on_attention_free_arch_raises():
    from repro.distill.workload import build_colocated_step
    t_cfg = _tiny_model().cfg
    s_cfg = cfgs.get_reduced("mamba2-130m").replace(dtype="float32")
    mesh = shd.abstract_mesh((1, 1, 2, 1),
                             ("data", "pipe", "seq", "model"))
    with pytest.raises(NotImplementedError, match="attention-free"):
        build_colocated_step(t_cfg, s_cfg, mesh,
                             ShapeConfig("t", "train", 16, 4),
                             ParallelConfig(cp=2))


def test_serving_builders_reject_pp_mesh():
    model = _tiny_model()
    mesh = shd.abstract_mesh((1, 2, 1, 1),
                             ("data", "pipe", "seq", "model"))
    shape = ShapeConfig("t", "decode", 32, 4)
    with pytest.raises(NotImplementedError, match="serving|decode"):
        step_mod.build_decode_step(model, mesh, shape)
    with pytest.raises(NotImplementedError, match="serving|prefill"):
        step_mod.build_prefill_step(model, mesh,
                                    ShapeConfig("t", "prefill", 32, 4))


# ---- microbatch split: no silent duplication ------------------------------ #
def test_split_microbatches_rejects_remainder():
    batch = {"tokens": jnp.zeros((10, 4), jnp.int32)}
    with pytest.raises(ValueError, match="microbatch"):
        step_mod._split_microbatches(batch, 4, 1)


def test_split_microbatches_rejects_undersized_shards():
    batch = {"tokens": jnp.zeros((4, 4), jnp.int32)}
    with pytest.raises(ValueError, match="microbatch"):
        step_mod._split_microbatches(batch, 4, 2)


def test_num_microbatches_validates_at_build_time():
    mesh = shd.abstract_mesh((2, 1), ("data", "model"))
    with pytest.raises(ValueError, match="global_batch"):
        step_mod.num_microbatches(ShapeConfig("t", "train", 16, 10), mesh,
                                  ParallelConfig(mbs=2))
    # oversized-but-indivisible also raises, even at n_micro == 1
    with pytest.raises(ValueError, match="global_batch"):
        step_mod.num_microbatches(ShapeConfig("t", "train", 16, 6), mesh,
                                  ParallelConfig(mbs=2))
    # undersized global batches (n_micro == 1) stay legal: the batch is
    # replicated / seq-sharded, not microbatched
    assert step_mod.num_microbatches(
        ShapeConfig("t", "train", 16, 1), mesh, ParallelConfig(mbs=1)) == 1
    assert step_mod.num_microbatches(
        ShapeConfig("t", "train", 16, 8), mesh, ParallelConfig(mbs=2)) == 2


# ---- CP attention knobs / kernel dispatch --------------------------------- #
def test_parallel_regime_validates_cp_knobs():
    axes = ("data", "pipe", "seq", "model")
    mesh = shd.abstract_mesh((2, 1, 2, 1), axes)
    with pytest.raises(ValueError, match="cp_mode"):
        step_mod.parallel_regime(mesh, ParallelConfig(
            dp=2, cp=2, cp_mode="ring"))
    with pytest.raises(ValueError, match="cp_impl"):
        step_mod.parallel_regime(mesh, ParallelConfig(
            dp=2, cp=2, cp_impl="triton"))
    with pytest.raises(ValueError, match="cp_overlap_chunks"):
        step_mod.parallel_regime(mesh, ParallelConfig(
            dp=2, cp=2, cp_overlap_chunks=0))
    # chunking only exists on the ulysses a2a chain
    with pytest.raises(ValueError, match="cp_overlap_chunks"):
        step_mod.parallel_regime(mesh, ParallelConfig(
            dp=2, cp=2, cp_mode="allgather", cp_overlap_chunks=2))
    assert step_mod.parallel_regime(mesh, ParallelConfig(
        dp=2, cp=2, cp_mode="ulysses", cp_impl="pallas_interpret",
        cp_overlap_chunks=2)) == "cp"


def test_cp_attention_impl_errors_name_section():
    """CompoundRuntime installs cp_attention_impl with section=<name>;
    unsupported-feature errors must carry it (the impl raises before
    touching the mesh, so no devices are needed here)."""
    from repro.dist.context import cp_attention_impl, resolve_cp_mode
    impl = cp_attention_impl(None, section="vit_tower")
    q = jnp.zeros((1, 8, 4, 8))
    seg = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="vit_tower"):
        impl(q, q[:, :, :2], q[:, :, :2], segment_q=seg, segment_kv=seg)
    with pytest.raises(NotImplementedError, match="vit_tower"):
        impl(q, q[:, :4, :2], q[:, :4, :2])   # S_q != S_kv
    with pytest.raises(ValueError, match="vit_tower"):
        resolve_cp_mode("ulysses", H=8, KV=3, cp=4, section="vit_tower")


def test_resolve_cp_mode_auto():
    from repro.dist.context import resolve_cp_mode
    assert resolve_cp_mode("auto", H=8, KV=4, cp=4) == "ulysses"
    # KV % cp != 0 but replication is cheap: head-replicated ulysses
    assert resolve_cp_mode("auto", H=8, KV=4, cp=8) == "ulysses_mqa"
    # pure MQA: replication never beats gathering one KV head
    assert resolve_cp_mode("auto", H=8, KV=1, cp=8) == "allgather"


def test_kernel_impl_env_override(monkeypatch):
    from repro.kernels import ops as kops
    monkeypatch.delenv("REPRO_KERNEL_IMPL", raising=False)
    assert kops.resolve("ref") == "ref"
    assert kops.resolve("auto") in ("ref", "pallas")
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas_interpret")
    assert kops.resolve("ref") == "pallas_interpret"
    assert kops.resolve("auto") == "pallas_interpret"
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "cuda")
    with pytest.raises(ValueError, match="REPRO_KERNEL_IMPL"):
        kops.resolve("auto")


# ---- attention impl plumbing --------------------------------------------- #
def test_attention_impl_override_is_consulted():
    """models.attention routes full-sequence attention through the
    installed impl — the hook CP rides on."""
    import numpy as np
    cfg = _tiny_model().cfg
    from repro.models.attention import attn_specs
    from repro.models.common import init_params
    p = init_params(attn_specs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model),
                          jnp.float32)
    calls = []

    def fake_impl(q, k, v, **kw):
        calls.append((q.shape, k.shape, kw["causal"]))
        return jnp.zeros_like(q)

    with att.attention_impl(fake_impl):
        out = att.attention(p, x, cfg, impl="ref")
    assert calls and calls[0][2] is True
    np.testing.assert_array_equal(np.asarray(out), 0.0)
    # and it uninstalls on exit
    out2 = att.attention(p, x, cfg, impl="ref")
    assert float(jnp.max(jnp.abs(out2))) > 0


# ---- 8-way numerical equivalence (subprocess driver) ---------------------- #
def test_pp_cp_train_step_equivalence_8way():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    src = str(Path(__file__).resolve().parents[1] / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    driver = Path(__file__).parent / "drivers" / "driver_train_step_dist.py"
    proc = subprocess.run([sys.executable, str(driver)],
                          capture_output=True, text=True, timeout=560,
                          env=env)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    assert "DRIVER_OK train_step_dist" in proc.stdout, proc.stdout[-2000:]
