"""Device time of the router's stage ``shard_exchange`` (the
all-gathers of every rank's top-k over the mesh) per batch, the
smallest over devices (ms, from the trace's op paths): the rank that
arrives last at the exchange waits least, so its time is the
collective's own."""
import router_stages


def read(run):
    ms = router_stages.ms_per_batch(run, "shard_exchange")
    return min(ms) if ms else None
