"""Device time of the router's stage ``shard_merge``
(``merge_shard_topk``: the on-device merge of the gathered top-k
lists) per batch, the largest over devices (ms, from the trace's op
paths)."""
import router_stages


def read(run):
    ms = router_stages.ms_per_batch(run, "shard_merge")
    return max(ms) if ms else None
