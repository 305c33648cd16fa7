"""Device time of stage ``round_fetch`` (``fused_round``: the
``gather_blocks`` and ``rank_tiles`` kernels and their glue) per round
each device ran, the largest over devices (ms, from the trace's op
paths)."""
import scope_reduce


def read(run):
    return scope_reduce.stage_ms(run, ("round_fetch",))
