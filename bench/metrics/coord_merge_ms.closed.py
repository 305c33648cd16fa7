"""Per batch, the coordinator's own time: its ``coord.batch`` span less
the ``coord.segment`` spans inside it, from the program's
``obs.trace.Tracer`` on the wall clock; the mean over the window's
batches, ms."""


def read(run):
    batches = [e for e in run.spans if e.name == "coord.batch"]
    segs = [e for e in run.spans if e.name == "coord.segment"]
    if not batches:
        return None
    own = []
    for b in batches:
        end = b.ts_us + b.dur_us
        inner = sum(s.dur_us for s in segs
                    if s.ts_us >= b.ts_us and s.ts_us + s.dur_us <= end)
        own.append(b.dur_us - inner)
    return sum(own) / len(own) / 1e3
