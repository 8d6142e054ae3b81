"""Operations and bytes of the work a step requires, from shapes alone.

Model FLOPs count what the forward and backward passes need: 2 per
multiply-add of every matrix product a token goes through (the embedding
lookup is no product), times 3 for a trained part (forward, and a backward
of twice its cost) and 1 for a frozen one, plus attention's score and
value products (halved where causal).  Recomputation under remat, padding
slots and the KL kernel's recomputed logits are not counted: they are work
the program chose, not work the model needs.

Kernel counts are those of one call at its shapes, for a roofline:
the least time of a call is max(FLOPs / peak FLOP/s, bytes / HBM bytes/s).
"""
from __future__ import annotations


def lm_matmul_params(c: dict) -> int:
    """Weights of a decoder's matrix products per token: every layer's
    attention projections and SwiGLU MLP, and the output layer."""
    d, h, kv, hd, f = (c["d_model"], c["num_heads"], c["num_kv_heads"],
                       c["head_dim"], c["d_ff"])
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    return c["num_layers"] * (attn + 3 * d * f) + d * c["vocab_size"]


def attention_fwd(S: int, heads: int, head_dim: int, layers: int,
                  causal: bool) -> float:
    """Score and value products of one sample's forward pass."""
    full = 4.0 * S * S * heads * head_dim * layers
    return full / 2 if causal else full


def vit_image(v: dict, out_dim: int) -> float:
    """Training FLOPs of one image through the ViT encoder and its merge."""
    d, f, L, ds = v["d_model"], v["d_ff"], v["num_layers"], v["downsample"]
    P = v["patches"]
    per_patch = v["patch_dim"] * d + L * (4 * d * d + 3 * d * f)
    fwd = (2.0 * per_patch * P + 2.0 * d * ds * out_dim * (P // ds)
           + attention_fwd(P, v["num_heads"], d // v["num_heads"], L,
                           causal=False))
    return 3 * fwd


def mllm_step(cfg: dict, traffic: dict) -> float:
    """Model FLOPs of one MLLM training step: the LM over every position
    of every sample, and the ViT and vision projection over the images
    present (not over padded slots)."""
    B, S = traffic["batch"], traffic["seq_len"]
    n_img = round(traffic["vision_ratio"] * B)
    K = cfg["max_image_tokens"]
    v = dict(cfg["vision_encoder"], patches=K * cfg["vision_encoder"][
        "downsample"])
    lm = (6.0 * lm_matmul_params(cfg) * B * S
          + 3 * B * attention_fwd(S, cfg["num_heads"], cfg["head_dim"],
                                  cfg["num_layers"], causal=True))
    vision = n_img * (vit_image(v, cfg["vision_dim"])
                      + 6.0 * cfg["vision_dim"] * cfg["d_model"] * K)
    return lm + vision


def distill_step(cfg: dict, traffic: dict) -> float:
    """Model FLOPs of one distillation step: the frozen teacher's forward
    pass with its output layer, and the student's training."""
    B, S = traffic["batch"], traffic["seq_len"]
    t, s = cfg["teacher"], cfg["student"]

    def attn(c):
        return B * attention_fwd(S, c["num_heads"], c["head_dim"],
                                 c["num_layers"], causal=True)
    return (2.0 * lm_matmul_params(t) * B * S + attn(t)
            + 6.0 * lm_matmul_params(s) * B * S + 3 * attn(s))


# --------------------------------------------------------------------------- #
# Kernels, one call
# --------------------------------------------------------------------------- #
def flash_fwd(B: int, H: int, KV: int, S: int, D: int, causal: bool,
              itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one flash-attention forward call: q, k, v read
    and o written once in their dtype, and the float32 log-sum-exp."""
    flops = 4.0 * B * H * S * S * D * (0.5 if causal else 1.0)
    nbytes = itemsize * B * S * D * (2 * H + 2 * KV) + 4 * B * H * S
    return flops, float(nbytes)


def distill_kl_stats(N: int, V: int, Ds: int, Dt: int,
                     itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one call of the KL statistics kernel: both
    models' logits over the whole vocabulary, each hidden state and output
    layer read once, four float32 statistics per row written."""
    flops = 2.0 * N * V * (Ds + Dt)
    nbytes = itemsize * (N * Ds + Ds * V + N * Dt + Dt * V) + 4 * 4 * N
    return flops, float(nbytes)


def least_time(flops: float, nbytes: float, peak: dict) -> tuple:
    """(seconds, bound): the roofline's least time and which peak sets it,
    ``"compute"`` or ``"memory"``."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


def kernel_roofline(run, kernel: str, calls: list):
    """Percent of its roofline that kernel ``kernel`` reached in a traced
    run: the least time of the calls the trace holds (``calls``: [((FLOPs,
    bytes), calls per step)], averaged by that mix) over the time they
    took.  None where the trace holds no such call."""
    n, seconds = run.trace.kernel(kernel)
    per_step = sum(k for _, k in calls)
    if not n or not per_step:
        return None
    least = [(least_time(f, b, run.peak), k) for (f, b), k in calls]
    mean = sum(t * k for (t, _), k in least) / per_step
    bounds = sorted({bound for (_, bound), _ in least})
    run.log(f"{kernel}: {n} calls in the trace, {per_step} a step, "
            f"{seconds!r} s on the device, least time {mean!r} s a call, "
            f"bound by {'/'.join(bounds)}")
    return 100.0 * n * mean / seconds
