"""Host time a step spends preparing its batch: the data and scheduler
layer (cost-model 6-tuples, wavefront order, microbatch plan, layout and
transfer to the device), mean milliseconds per measured step, from the
benchmark's own host spans."""


def read(run):
    t0, t1 = run.window
    s = run.spans.total("schedule", t0, t1) + run.spans.total("prep", t0, t1)
    return 1e3 * s / len(run.steps)
