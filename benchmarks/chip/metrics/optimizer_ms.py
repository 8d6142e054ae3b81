"""Device time of the optimizer step (scope ``optimizer``,
``optim.adamw.update``: global norm, clipping, AdamW, master weights cast to
the parameters' dtype), ms per measured step, from the trace
(``scopes.scope_ms``)."""
from benchmarks.chip import scopes


def read(run):
    return scopes.scope_ms(run, "optimizer")
