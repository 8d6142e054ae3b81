"""Device time of the f32 gradient accumulation (scope ``grad_accum`` in
both ``build_colocated_step``s: the accumulator's zeros, each microbatch's
sum into it, and the mean's cast), ms per measured step, from the trace
(``scopes.scope_ms``)."""
from benchmarks.chip import scopes


def read(run):
    return scopes.scope_ms(run, "grad_accum")
