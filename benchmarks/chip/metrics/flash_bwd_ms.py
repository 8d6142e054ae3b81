"""Device time of the flash attention backward (scope ``flash_bwd``,
``kernels.ref._flash_bwd_core``, which the Pallas kernels' VJP rules call),
inside whichever section runs it, ms per measured step, from the trace
(``scopes.scope_ms``)."""
from benchmarks.chip import scopes


def read(run):
    return scopes.scope_ms(run, "flash_bwd")
