"""The whole step's share of the chips' bf16 peak (percent): model FLOPs
per step (no recomputation, no padding) times the steps completed in the
traced window, over the window and the chips' peak."""


def read(run):
    return (100.0 * run.job.model_flops_per_step * len(run.steps)
            / run.window_s / (run.chips * run.peak["bf16_flops_per_s"]))
