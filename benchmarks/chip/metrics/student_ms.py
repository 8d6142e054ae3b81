"""The student's device time (scope ``student``,
``distill.workload.distill_loss``: forward and backward, unembedding, CE and
``distill_kl``), ms per measured step, from the trace (``scopes.scope_ms``)."""
from benchmarks.chip import scopes


def read(run):
    return scopes.scope_ms(run, "student")
