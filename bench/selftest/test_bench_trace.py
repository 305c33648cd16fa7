"""The trace reduction on a small trace recorded on a TPU v5e: two
searches of 8 queries over a 2,000-vector segment inside a
``bench.window`` span, gzipped."""
import gzip
import os

import pytest

import trace_reduce

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "tiny.xplane.pb.gz")


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    with gzip.open(FIXTURE) as f:
        path.write_bytes(f.read())
    return str(path)


@pytest.fixture(scope="module")
def summary(xplane):
    return trace_reduce.load(xplane)


def _raw_ops(xplane):
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    return [(e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
    raise AssertionError("no XLA Ops line")


def test_window_and_device(summary):
    assert [d.name for d in summary.devices] == ["/device:TPU:0"]
    assert summary.window_ns > 0
    d = summary.devices[0]
    assert 0 < d.busy_ns <= summary.window_ns


def test_busy_is_the_union_of_op_intervals(summary, xplane):
    lo_w, hi_w = summary.window
    # a plain sweep over every instant an op starts or ends
    edges = sorted({x for iv in _raw_ops(xplane) for x in iv
                    if lo_w <= x <= hi_w} | {lo_w, hi_w})
    busy = 0.0
    ops = _raw_ops(xplane)
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        if any(s <= mid < e for s, e in ops):
            busy += b - a
    assert summary.devices[0].busy_ns == pytest.approx(busy, rel=1e-9)


def test_self_times_add_up_to_busy(summary):
    d = summary.devices[0]
    assert sum(d.self_ns.values()) == pytest.approx(d.busy_ns, rel=1e-6)


def test_both_round_kernels_found(summary):
    import importlib.util
    counts = []
    for name in ("gather_blocks_roofline.closed",
                 "rank_tiles_roofline.closed"):
        path = os.path.join(os.path.dirname(os.path.dirname(FIXTURE)),
                            "..", "metrics", name + ".py")
        spec = importlib.util.spec_from_file_location("reader", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        counts.append(summary.op_count(mod.KERNEL)[0])
        assert summary.op_ns(mod.KERNEL)[0] > 0
    # one call of each per round
    assert counts[0] == counts[1] > 0


def test_breakdown_names(summary):
    ops = trace_reduce.top_ops(summary, 10)
    assert 0 < len(ops) <= 10
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    assert all("{" not in n and len(n) <= 160 for n, _ in ops)
    gaps = trace_reduce.idle_gaps(summary)
    assert {n for n, _ in gaps} <= {"bench.window", "bench.search", "idle"}
    idle = summary.window_ns - summary.devices[0].busy_ns
    assert sum(s for _, s in gaps) == pytest.approx(idle / 1e9, rel=1e-6)
