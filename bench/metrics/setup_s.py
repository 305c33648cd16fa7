"""Seconds from the process's start to the window's: data, the in-run
build and packing, and the warm-up of the cell's own batch shapes."""


def read(run):
    return run.setup_s
