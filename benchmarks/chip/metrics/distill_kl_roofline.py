"""The Pallas distillation-KL statistics kernel's share of its roofline
(percent), from its device events (``distill_kl_stats``); FLOPs
2 N V (D_s + D_t), bytes of its operands and statistics."""
from benchmarks.chip import flops


def read(run):
    return flops.kernel_roofline(run, "distill_kl_stats",
                                 run.job.kl_calls)
