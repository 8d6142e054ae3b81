"""``flops.py`` against hand counts and against XLA's own count of a
one-layer forward pass."""
import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import flops, harness


def test_pixtral_lm_matmul_params_by_hand():
    cfg = harness.config("pixtral-12b-mllm")
    attn = 5120 * 32 * 128 * 2 + 2 * 5120 * 8 * 128      # q, o; k, v
    mlp = 3 * 5120 * 14336
    assert flops.lm_matmul_params(cfg) == attn + mlp + 5120 * 16384


def test_kernel_counts_by_hand():
    f, b = flops.flash_fwd(2, 4, 2, 8, 16, causal=True)
    assert f == 4 * 2 * 4 * 8 * 8 * 16 / 2
    assert b == 2 * 2 * 8 * 16 * (2 * 4 + 2 * 2) + 4 * 2 * 4 * 8
    f, b = flops.distill_kl_stats(N=4, V=8, Ds=2, Dt=3)
    assert f == 2 * 4 * 8 * 5
    assert b == 2 * (4 * 2 + 2 * 8 + 4 * 3 + 3 * 8) + 16 * 4
    peak = {"bf16_flops_per_s": 10.0, "hbm_bytes_per_s": 1.0}
    assert flops.least_time(100.0, 5.0, peak) == (10.0, "compute")
    assert flops.least_time(100.0, 50.0, peak) == (50.0, "memory")


def test_step_counts_cover_the_parts():
    cfg, tr = harness.config("pixtral-12b-mllm"), harness.traffic("mixed50")
    lm_only = flops.mllm_step(cfg, dict(tr, vision_ratio=0.0))
    assert lm_only == pytest.approx(6 * 356_515_840 * 8 * 4096
                                    + 3 * 8 * 2 * 4096 ** 2 * 32 * 128)
    # each image adds its ViT pass and its vision projection, no padding
    one = flops.mllm_step(cfg, dict(tr, vision_ratio=1 / 8)) - lm_only
    assert flops.mllm_step(cfg, tr) == pytest.approx(lm_only + 4 * one)
    d = harness.config("granite-3.0-8b-2b-distill")
    teacher_fwd = 2 * flops.lm_matmul_params(d["teacher"]) * 8 * 4096
    assert flops.distill_step(d, harness.traffic("text")) > teacher_fwd


def test_forward_count_matches_xla_cost_analysis():
    """A one-layer forward pass (non-causal, so XLA's dense score product
    is the work counted), wide enough that matrix products dominate."""
    c = dict(d_model=256, num_heads=4, num_kv_heads=2, head_dim=64,
             d_ff=1024, vocab_size=512, num_layers=1)
    B, S = 2, 128
    d, h, kv, hd, f, V = 256, 4, 2, 64, 1024, 512
    shapes = dict(wq=(d, h, hd), wk=(d, kv, hd), wv=(d, kv, hd),
                  wo=(h, hd, d), wg=(d, f), wu=(d, f), wd=(f, d),
                  un=(d, V))
    p = {k: jnp.ones(s, jnp.float32) for k, s in shapes.items()}

    def fwd(p, x):
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"]).reshape(B, S, kv, 2, hd)
        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
        s = jnp.einsum("bskgd,btkd->bkgst", q, k)
        o = jnp.einsum("bkgst,btkd->bskgd", s, v).reshape(B, S, h, hd)
        x = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
        m = (jnp.einsum("bsd,df->bsf", x, p["wg"])
             * jnp.einsum("bsd,df->bsf", x, p["wu"]))
        x = jnp.einsum("bsf,fd->bsd", m, p["wd"])
        return jnp.einsum("bsd,dv->bsv", x, p["un"])

    x = jnp.ones((B, S, d), jnp.float32)
    cost = jax.jit(fwd).lower(p, x).compile().cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    want = (2 * flops.lm_matmul_params(c) * B * S
            + B * flops.attention_fwd(S, h, hd, 1, causal=False))
    assert cost["flops"] == pytest.approx(want, rel=0.02)
