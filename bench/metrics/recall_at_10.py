"""Mean recall@10 of every query served in the window, against the
exact top-10 of the plain reference (``bench/reference.py``)."""


def read(run):
    return run.checks["recall_at_10"]["value"]
