"""Block-search rounds a batch costs its device
(``SegmentServer.last_rounds``); the mean over the window's batches (a
count)."""


def read(run):
    if not run.batches:
        return None
    return sum(max(b.device_rounds) for b in run.batches) / len(run.batches)
