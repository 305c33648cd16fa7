"""The op-path reduction (``scope_reduce``) and the segment server's
readers: on the recorded v5e trace of a program without named stages
(``tiny.xplane.pb.gz``), on one recorded with them
(``staged.xplane.pb.gz``: the same two searches of 8 queries over a
2,000-vector segment, 15 rounds each, with the traced server), and on
hand counts."""
import gzip
import importlib.util
import os

import pytest

import harness
import scope_reduce
import tiny
import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
STAGES = ("search_entry", "round_pick", "round_fetch", "round_results",
          "round_expand", "round_route", "round_candidates")
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
