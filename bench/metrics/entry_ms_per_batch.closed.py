"""Device time of stage ``search_entry`` (the navigation graph's entry
search) per batch, the largest over devices (ms, from the trace's op
paths)."""
import scope_reduce


def read(run):
    return scope_reduce.stage_ms(run, ("search_entry",), per_round=False)
