"""The Pallas flash-attention forward kernel's share of its roofline
(percent), from its device events (``flash_attention_fwd``) and the
calls of a step (causal LM and non-causal ViT counted apart)."""
from benchmarks.chip import flops


def read(run):
    return flops.kernel_roofline(run, "flash_attention_fwd",
                                 run.job.flash_calls)
