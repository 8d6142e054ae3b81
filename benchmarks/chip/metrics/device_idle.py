"""Share of the traced window in which no operation ran on the device,
1 - busy / window, averaged over the cell's chips (percent)."""


def read(run):
    t = run.trace
    return 100.0 * (1.0 - t.busy_s() * 1e9 / t.window_ns())
