"""The LM section's device time (scope ``lm``,
``mllm.workload.lm_microbatch_loss``: image scatter, backbone and loss,
forward and backward), ms per measured step, from the trace
(``scopes.scope_ms``)."""
from benchmarks.chip import scopes


def read(run):
    return scopes.scope_ms(run, "lm")
