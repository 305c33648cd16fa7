"""Share of the window in which no operation ran on the device, the
mean over the cell's devices (%, from the trace)."""


def read(run):
    t = run.trace
    if t is None or not t.devices or t.window_ns <= 0:
        return None
    busy = sum(d.busy_ns for d in t.devices) / len(t.devices)
    return 100.0 * (1.0 - busy / t.window_ns)
