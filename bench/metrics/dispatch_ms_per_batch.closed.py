"""Per batch, the host time of the segment server's ``server.dispatch``
span: the ``device_anns`` call, which enqueues the search and, on a
jit-cache miss, traces and compiles it; the mean over the window's
batches (ms, the program's ``obs.trace.Tracer`` on the wall clock)."""


def read(run):
    spans = [e for e in run.spans if e.name == "server.dispatch"]
    if not spans:
        return None
    return sum(e.dur_us for e in spans) / len(spans) / 1e3
