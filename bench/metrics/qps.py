"""Queries completed in the window over the window's time: whole
batches back to back, from the first batch's start to the last batch's
results on the host (host clock)."""


def read(run):
    return run.served / run.window_s if run.window_s > 0 else None
