"""The ViT section's device time (scope ``vit``,
``mllm.workload.vit_forward``: the encoder over a microbatch's image slots,
its forward, remat forward and backward), ms per measured step, from the
trace (``scopes.scope_ms``)."""
from benchmarks.chip import scopes


def read(run):
    return scopes.scope_ms(run, "vit")
