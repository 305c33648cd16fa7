"""Per batch, the largest less the smallest device time of the
router's stage ``shard_exchange`` over devices (ms, from the trace's op
paths): how long the first rank to reach the exchange waits there for
the slowest."""
import router_stages


def read(run):
    ms = router_stages.ms_per_batch(run, "shard_exchange")
    return max(ms) - min(ms) if ms else None
