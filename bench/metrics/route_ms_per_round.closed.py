"""Device time of stage ``round_route`` (the PQ-ADC lookup, the
visited filter and the PQ codes of the block-search loop) per round
each device ran, the largest over devices (ms, from the trace's op
paths)."""
import scope_reduce


def read(run):
    return scope_reduce.stage_ms(run, ("round_route",))
