"""Host time a step spends in the wavefront scheduler (cost-model
6-tuples and the sample order), mean milliseconds per measured step, from
the benchmark's own host spans; nothing where the path has no scheduler."""


def read(run):
    t0, t1 = run.window
    s = run.spans.total("schedule", t0, t1)
    return 1e3 * s / len(run.steps) if s else None
