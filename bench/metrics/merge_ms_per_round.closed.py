"""Device time of stages ``round_candidates`` and ``round_results``
(the loop's merges into the candidates and into the results) per round
each device ran, the largest over devices (ms, from the trace's op
paths)."""
import scope_reduce


def read(run):
    return scope_reduce.stage_ms(run, ("round_candidates", "round_results"))
