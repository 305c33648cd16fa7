"""Device busy time in the window over the block-search rounds that
device ran, per device, the largest over devices (ms, from the
trace)."""


def read(run):
    if run.trace is None or not run.trace.devices or not run.batches:
        return None
    out = []
    for d, dev in enumerate(run.trace.devices):
        rounds = sum(b.device_rounds[d] for b in run.batches
                     if d < len(b.device_rounds))
        if rounds:
            out.append(dev.busy_ns / 1e6 / rounds)
    return max(out) if out else None
