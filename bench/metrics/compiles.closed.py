"""Jit-cache misses inside the window: the ``traces`` the segment
server's ``server.dispatch`` spans counted (``repro.obs.compiles``),
summed over the window's batches (a count; 0 when every program the
window runs was warmed up in set-up)."""


def read(run):
    spans = [e for e in run.spans if e.name == "server.dispatch"]
    if not spans:
        return None
    return sum(e.args["traces"] for e in spans)
