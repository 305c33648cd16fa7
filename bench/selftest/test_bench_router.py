"""The router layer's readers on hand counts: device time under the
router's named stages per batch, over the ranks, and the jit-cache
misses its ``router.route`` spans count; and no reading, not a zero,
on a run of a program without the stages or the counts."""
import dataclasses

import pytest

import harness
import router_stages
import tiny
import trace_reduce

# per device d of three: ms under each path in the window
OPS = {"gather": ("jit(local)/shard_exchange/all_gather",
                  lambda d: (3 - d) * 2.0),
       "sort": ("jit(local)/shard_merge/jit(lexsort)/sort",
                lambda d: 0.5 + 0.25 * d),
       "route": ("jit(local)/jit(device_anns)/while/body/round_route/"
                 "gather", lambda d: 40.0),
       "loop": ("jit(local)/jit(device_anns)/while", lambda d: 7.0)}


def _router_run(batches=4, paths=OPS):
    """A traced run on three devices: ``shard_exchange`` 6, 4 and 2 ms,
    ``shard_merge`` 0.5, 0.75 and 1 ms, and ops under no router stage,
    over ``batches`` batches."""
    devs, op_paths = [], {}
    for d in range(3):
        dev = trace_reduce.DeviceTrace(
            name=f"/device:TPU:{d}", busy_ns=0,
            self_ns={op: ms(d) * 1e6 for op, (_, ms) in OPS.items()},
            count={}, intervals=[])
        devs.append(dev)
        op_paths[dev.name] = {op: p for op, (p, _) in paths.items()}
    return harness.Run(
        config={}, op_paths=op_paths,
        trace=trace_reduce.TraceSummary(window=(0, 1), devices=devs,
                                        host_spans=[]),
        batches=[harness.Batch(0, 1, 8, 8, [10, 12, 9], 0)
                 for _ in range(batches)])


def test_ms_per_batch_hand_count():
    run = _router_run()
    assert router_stages.ms_per_batch(run, "shard_exchange") \
        == pytest.approx([6 / 4, 4 / 4, 2 / 4], rel=1e-12)
    assert router_stages.ms_per_batch(run, "shard_merge") \
        == pytest.approx([0.5 / 4, 0.75 / 4, 1 / 4], rel=1e-12)


@pytest.mark.parametrize("metric,want", [
    # the rank that waits least gives the collective's own time
    ("exchange_ms_per_batch.closed", 2 / 4),
    # the first rank to arrive waits for the slowest
    ("rank_wait_ms_per_batch.closed", (6 - 2) / 4),
    ("shard_merge_ms_per_batch.closed", 1 / 4),
])
def test_router_stage_readers_hand_count(metric, want):
    read = harness.load_reader(tiny.ROOT, metric)
    assert read(_router_run()) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("metric", [
    "exchange_ms_per_batch.closed", "rank_wait_ms_per_batch.closed",
    "shard_merge_ms_per_batch.closed"])
def test_router_stage_readers_read_nothing_without_their_stage(metric):
    """A program whose step carries no router scope (one segment, or a
    step from before the scopes), no op paths, or no trace: no
    reading."""
    read = harness.load_reader(tiny.ROOT, metric)
    unnamed = {op: (p.replace("shard_exchange/", "")
                    .replace("shard_merge/", ""), ms)
               for op, (p, ms) in OPS.items()}
    assert read(_router_run(paths=unnamed)) is None
    assert read(dataclasses.replace(_router_run(), op_paths={})) is None
    assert read(dataclasses.replace(_router_run(), trace=None)) is None


def _span(name, **args):
    from repro.obs.trace import TraceEvent
    return TraceEvent(name=name, cat="serve", ph="X", ts_us=0.0,
                      dur_us=10.0, args=args)


def test_router_compiles_hand_count():
    read = harness.load_reader(tiny.ROOT, "router_compiles.closed")
    run = harness.Run(config={}, spans=[
        _span("router.route", traces=0, compiles=0),
        _span("coord.shard", rounds=5),
        _span("router.route", traces=2, compiles=1),
        _span("server.dispatch", traces=7, compiles=7)])
    assert read(run) == 2
    # the router's spans without the count (a program from before it),
    # or no router at all: no reading
    assert read(harness.Run(config={}, spans=[
        _span("router.route", block_reads=3)])) is None
    assert read(harness.Run(config={}, spans=[
        _span("server.dispatch", traces=1, compiles=1)])) is None
