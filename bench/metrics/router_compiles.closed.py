"""Jit-cache misses inside the window on the router's path: the
``traces`` the mesh router's ``router.route`` spans counted around its
step call (``repro.obs.compiles``), summed over the window's batches (a
count; 0 when the step was warmed up in set-up). None where the spans
carry no such count."""


def read(run):
    counts = [e.args["traces"] for e in run.spans
              if e.name == "router.route" and "traces" in e.args]
    return sum(counts) if counts else None
