"""The frozen teacher's device time (scope ``teacher``,
``distill.workload.teacher_hidden``: its body's forward pass), ms per
measured step, from the trace (``scopes.scope_ms``)."""
from benchmarks.chip import scopes


def read(run):
    return scopes.scope_ms(run, "teacher")
