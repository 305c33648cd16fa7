"""The op-path reduction (``scope_reduce``) and the segment server's
readers: on the recorded v5e trace of a program without named stages
(``tiny.xplane.pb.gz``), on one recorded with them
(``staged.xplane.pb.gz``: the same two searches of 8 queries over a
2,000-vector segment, 15 rounds each, with the traced server), and on
hand counts."""
import dataclasses
import gzip
import importlib.util
import os

import pytest

import harness
import scope_reduce
import tiny
import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
STAGES = scope_reduce.STAGES
ADC = "fusion(f32[8,16,256], s32["     # the PQ-ADC lookup's signature


def _load(tmp_path_factory, name):
    path = tmp_path_factory.mktemp("trace") / name
    with gzip.open(os.path.join(DATA, name + ".gz")) as f:
        path.write_bytes(f.read())
    return (trace_reduce.load(str(path)),
            scope_reduce.load_op_paths(str(path)))


@pytest.fixture(scope="module")
def plain(tmp_path_factory):
    return _load(tmp_path_factory, "tiny.xplane.pb")


@pytest.fixture(scope="module")
def staged(tmp_path_factory):
    return _load(tmp_path_factory, "staged.xplane.pb")


def _adc_op(d):
    """The loop's ADC lookup: the [Q, M, K] table gather with the most
    self time."""
    return max((n for n in d.self_ns if ADC in trace_reduce.short_name(n)),
               key=d.self_ns.get)


@pytest.mark.parametrize("trace,path", [
    ("plain", "jit(device_anns)/while/body/jit(take_along_axis)/gather"),
    ("staged",
     "jit(device_anns)/while/body/round_route/jit(take_along_axis)/gather"),
])
def test_op_paths_name_the_adc_gather(request, trace, path):
    summary, paths = request.getfixturevalue(trace)
    d = summary.devices[0]
    assert paths[d.name][_adc_op(d)] == path


@pytest.mark.parametrize("trace", ["plain", "staged"])
def test_op_paths_cover_the_window(request, trace):
    summary, paths = request.getfixturevalue(trace)
    d = summary.devices[0]
    named = sum(t for n, t in d.self_ns.items() if n in paths[d.name])
    assert named >= 0.99 * sum(d.self_ns.values())


def test_a_program_without_stages_has_no_stage_time(plain):
    assert scope_reduce.stage_ns(*plain, STAGES) == [{}]


def test_named_stages_explain_the_window(staged):
    summary, paths = staged
    by_stage, = scope_reduce.stage_ns(summary, paths, STAGES)
    assert set(by_stage) == set(STAGES)
    total = sum(summary.devices[0].self_ns.values())
    assert sum(by_stage.values()) >= 0.95 * total


def _reader_module(name):
    path = os.path.join(tiny.BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("kernel", ["gather_blocks", "rank_tiles"])
def test_named_kernels_still_match_the_roofline_readers(staged, kernel):
    """The kernels' names reach the trace as their instructions' names,
    and the roofline readers' signatures still find them."""
    summary, paths = staged
    d = summary.devices[0]
    named = [n for n in d.self_ns if n.startswith(f"%{kernel}.")]
    assert len(named) == 1
    rx = _reader_module(f"{kernel}_roofline.closed").KERNEL
    assert summary.op_ns(rx)[0] == d.self_ns[named[0]] > 0
    assert paths[d.name][named[0]].endswith(f"/{kernel}/pallas_call")


def test_server_spans_split_the_idle_gaps(staged):
    """The program's spans are on the trace's host plane, on its clock:
    each idle gap is named by the server or coordinator span it falls
    in."""
    summary, _ = staged
    names = {s[0] for s in summary.host_spans}
    assert {"coord.batch", "coord.segment", "server.dispatch",
            "server.wait"} <= names
    gaps = dict(trace_reduce.idle_gaps(
        summary, phases=("server.", "coord.", "bench.")))
    assert "coord.segment" in gaps and "server.dispatch" in gaps
    idle = summary.window_ns - summary.devices[0].busy_ns
    assert sum(gaps.values()) == pytest.approx(idle / 1e9, rel=1e-6)


# ----------------------------------------------------------- hand counts

def test_stage_ns_hand_count():
    """Self time by the first stage on each op's path, per device; ops
    under no stage, or with no path, are left out."""
    ops = {"a": ("jit(f)/while/body/round_route/jit(_take)/gather", 4e6),
           "b": ("jit(f)/while/body/round_results/jit(lexsort)/sort", 2e6),
           "c": ("jit(f)/while/body/round_results/gather", 5e5),
           "d": ("jit(f)/search_entry/while/body/gather", 8e5),
           "e": ("jit(f)/while", 1e5),
           "f": (None, 3e5)}
    dev = trace_reduce.DeviceTrace(
        name="/device:TPU:0", busy_ns=0,
        self_ns={op: t for op, (_, t) in ops.items()}, count={},
        intervals=[])
    summary = trace_reduce.TraceSummary(window=(0, 1), devices=[dev],
                                        host_spans=[])
    paths = {dev.name: {op: p for op, (p, _) in ops.items() if p}}
    assert scope_reduce.stage_ns(summary, paths, STAGES) == [
        {"round_route": 4e6, "round_results": 2.5e6, "search_entry": 8e5}]
    assert scope_reduce.stage_ns(summary, paths, ("round_results",)) == [
        {"round_results": 2.5e6}]


def _stage_run(rounds, batches=2):
    """A run on two devices whose ops fall under the stages: per device
    d, route (2d+1) * 6 ms, candidates 3 ms, results 1 ms, fetch 0.5 ms,
    entry 0.8 ms, and 7 ms under no stage; ``rounds`` per device per
    batch."""
    devs, paths = [], {}
    for d in range(2):
        ops = {"route": ("jit(f)/while/body/round_route/gather",
                         (2 * d + 1) * 6e6),
               "cand": ("jit(f)/while/body/round_candidates/sort", 3e6),
               "res": ("jit(f)/while/body/round_results/sort", 1e6),
               "fetch": ("jit(f)/while/body/round_fetch/gather_blocks/"
                         "pallas_call", 5e5),
               "entry": ("jit(f)/search_entry/while/body/gather", 8e5),
               "loop": ("jit(f)/while", 7e6)}
        dev = trace_reduce.DeviceTrace(
            name=f"/device:TPU:{d}", busy_ns=0,
            self_ns={op: t for op, (_, t) in ops.items()}, count={},
            intervals=[])
        devs.append(dev)
        paths[dev.name] = {op: p for op, (p, _) in ops.items()}
    return harness.Run(
        config={}, op_paths=paths,
        trace=trace_reduce.TraceSummary(window=(0, 1), devices=devs,
                                        host_spans=[]),
        batches=[harness.Batch(0, 1, 8, 8, list(rounds), 0)
                 for _ in range(batches)])


@pytest.mark.parametrize("metric,want", [
    # the largest over devices of the stage's ms per round it ran
    ("route_ms_per_round.closed", max(6 / 20, 18 / 40)),
    ("merge_ms_per_round.closed", max(4 / 20, 4 / 40)),
    ("fetch_ms_per_round.closed", 0.5 / 20),
    ("entry_ms_per_batch.closed", 0.8 / 2),
])
def test_stage_readers_hand_count(metric, want):
    read = harness.load_reader(tiny.ROOT, metric)
    assert read(_stage_run([10, 20])) == pytest.approx(want, rel=1e-12)
    # the device that ran more rounds is read per its own rounds
    assert read(_stage_run([20, 10])) >= read(_stage_run([10, 20]))


@pytest.mark.parametrize("metric", [
    "route_ms_per_round.closed", "merge_ms_per_round.closed",
    "expand_ms_per_round.closed", "fetch_ms_per_round.closed",
    "entry_ms_per_batch.closed"])
def test_stage_readers_read_nothing_without_their_stage(metric):
    """No op under the stage, no op paths, or no trace: no reading,
    not a zero."""
    read = harness.load_reader(tiny.ROOT, metric)
    run = _stage_run([10, 20])
    run.op_paths = {d: {op: "jit(f)/while" for op in ops}
                    for d, ops in run.op_paths.items()}
    assert read(run) is None
    assert read(dataclasses.replace(_stage_run([10, 20]),
                                    op_paths={})) is None
    assert read(dataclasses.replace(_stage_run([10, 20]),
                                    trace=None)) is None
    if metric.startswith("expand"):
        assert read(_stage_run([10, 20])) is None


def test_stage_readers_on_the_recorded_trace(staged):
    """On the recorded chip trace (two batches of 15 rounds), each stage
    reader reads the stage's self time over the rounds or batches."""
    summary, paths = staged
    run = harness.Run(config={}, trace=summary, op_paths=paths,
                      batches=[harness.Batch(0, 1, 8, 8, [15], 0)] * 2)
    by_stage, = scope_reduce.stage_ns(summary, paths, STAGES)
    for metric, stages, per in [
            ("route_ms_per_round.closed", ["round_route"], 30),
            ("merge_ms_per_round.closed",
             ["round_candidates", "round_results"], 30),
            ("expand_ms_per_round.closed", ["round_expand"], 30),
            ("fetch_ms_per_round.closed", ["round_fetch"], 30),
            ("entry_ms_per_batch.closed", ["search_entry"], 2)]:
        want = sum(by_stage[s] for s in stages) / 1e6 / per
        got = harness.load_reader(tiny.ROOT, metric)(run)
        assert got == pytest.approx(want, rel=1e-12) and got > 0, metric


def _span(name, dur_us, **args):
    from repro.obs.trace import TraceEvent
    return TraceEvent(name=name, cat="serve", ph="X", ts_us=0.0,
                      dur_us=dur_us, args=args)


@pytest.mark.parametrize("metric,want", [
    ("dispatch_ms_per_batch.closed", (1500 + 2500) / 2 / 1e3),
    ("compiles.closed", 0 + 2),
])
def test_server_readers_hand_count(metric, want):
    run = harness.Run(config={}, spans=[
        _span("server.dispatch", 1500, traces=0, compiles=0),
        _span("server.wait", 9000),
        _span("server.dispatch", 2500, traces=2, compiles=1),
        _span("coord.batch", 12000)])
    read = harness.load_reader(tiny.ROOT, metric)
    assert read(run) == pytest.approx(want, rel=1e-12)
    # a program without the server's spans gives no reading, not a zero
    assert read(harness.Run(config={}, spans=[_span("coord.batch", 1)])) \
        is None
