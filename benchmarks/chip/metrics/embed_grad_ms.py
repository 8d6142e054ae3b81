"""Device time of the token-embedding gradient (scope ``embed_grad``,
``kernels.embed_grad``: the sort of the ids, the gather of the cotangent
rows into sorted order and the Pallas one-hot contraction), inside the
section that trains the table (``lm`` or ``student``), ms per measured
step, from the trace (``scopes.scope_ms``).  ``None`` where the program
keeps XLA's scatter-add."""
from benchmarks.chip import scopes


def read(run):
    return scopes.scope_ms(run, "embed_grad")
