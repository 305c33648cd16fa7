"""Host-clock seconds in the program's ``build_graph`` (the disk graph,
with its exact-kNN seed)."""


def read(run):
    return run.build_graph_s
