"""Device time of stage ``round_expand`` (the expansion of the fetched
blocks' neighbours) per round each device ran, the largest over devices
(ms, from the trace's op paths)."""
import scope_reduce


def read(run):
    return scope_reduce.stage_ms(run, ("round_expand",))
