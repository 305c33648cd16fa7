"""Device time under the router's named stages in a traced window.

The mesh router's step runs its all-gathers under the named scope
``shard_exchange`` and its on-device top-k merge under ``shard_merge``
(``ROUTER_STAGES`` of the program's ``core/device_search``, named here
again so that a program without them reads nothing and raises
nothing)."""
from typing import List

import scope_reduce

ROUTER_STAGES = ("shard_exchange", "shard_merge")


def ms_per_batch(run, stage: str) -> List[float]:
    """Per device that ran an op under ``stage``: the window's device
    self time under it (ms) per batch; empty where no op is under it,
    or the run has no trace or op paths."""
    if run.trace is None or not run.op_paths or not run.batches:
        return []
    by_device = scope_reduce.stage_ns(run.trace, run.op_paths,
                                      ROUTER_STAGES)
    return [d[stage] / 1e6 / len(run.batches)
            for d in by_device if stage in d]
