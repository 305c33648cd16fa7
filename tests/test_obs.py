"""Unit tests for the observability plane (repro.obs, DESIGN.md §6):
clocks, tracer, compile counter, metrics registry, perf artifacts, and
the measured-vs-modeled cost calibration. All fast — no segment
builds; the device round-log integration lives in
tests/test_trace_roundlog.py."""
import dataclasses
import json

import numpy as np
import pytest

from repro.core.iostats import IOStats, NVME_SEGMENT, TPU_HBM_SEGMENT
from repro.obs import (CalibrationPreset, CalibrationSample, Counter,
                       Gauge, Histogram, ManualClock, MetricsRegistry,
                       RoundRecord, Tracer, WallClock, calibrate,
                       compile_counts, fit_cost_model, fold_round_log,
                       manual_tracer, round_log_totals)


# ------------------------------------------------------------------ clocks
def test_wall_clock_monotone():
    c = WallClock()
    ts = [c.now_us() for _ in range(100)]
    assert all(b >= a for a, b in zip(ts, ts[1:]))


def test_manual_clock_advance_and_set():
    c = ManualClock(start_us=10.0)
    assert c.now_us() == 10.0
    c.advance(5.0)
    assert c.now_us() == 15.0
    c.set(100.0)
    assert c.now_us() == 100.0
    with pytest.raises(ValueError):
        c.advance(-1.0)
    with pytest.raises(ValueError):
        c.set(0.0)                        # clocks only move forward


def test_manual_clock_auto_tick():
    c = ManualClock(auto_tick_us=2.0)
    assert (c.now_us(), c.now_us(), c.now_us()) == (0.0, 2.0, 4.0)


# ------------------------------------------------------------------ tracer
def test_tracer_span_records_duration_and_outcome_args():
    tr = Tracer(clock=ManualClock())
    with tr.span("host.search", cat="serve", track="seg0", k=10) as sp:
        tr.clock.advance(7.0)
        sp["block_reads"] = 42
    (ev,) = tr.events
    assert ev.name == "host.search" and ev.ph == "X"
    assert ev.ts_us == 0.0 and ev.dur_us == 7.0
    assert ev.args == {"k": 10, "block_reads": 42}
    assert ev.track == "seg0"


def test_tracer_span_records_on_exception():
    tr = Tracer(clock=ManualClock())
    with pytest.raises(RuntimeError):
        with tr.span("coord.batch"):
            tr.clock.advance(3.0)
            raise RuntimeError("boom")
    assert len(tr) == 1 and tr.events[0].dur_us == 3.0


def test_tracer_event_and_slice():
    tr = manual_tracer(auto_tick_us=1.0)
    tr.event("sched.repack", cat="sched", target="seg0")
    inst, = tr.events
    assert inst.ph == "i" and inst.args == {"target": "seg0"}
    assert inst.ts_us == 0.0 and inst.cat == "sched"


def test_tracer_span_enters_profiler_annotation(monkeypatch):
    """Every span is also a ``jax.profiler.TraceAnnotation`` of its
    name, entered around the span's body, so the program's spans reach
    the profiler's trace; instant events are not annotated."""
    import jax
    seen = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    tr = manual_tracer()
    with tr.span("coord.batch"):
        with tr.span("server.dispatch"):
            tr.event("io.read")
        seen.append(("body", None))
    assert seen == [("enter", "coord.batch"), ("enter", "server.dispatch"),
                    ("exit", "server.dispatch"), ("body", None),
                    ("exit", "coord.batch")]
    assert [e.name for e in tr.events] == ["io.read", "server.dispatch",
                                           "coord.batch"]


def test_compile_counts_count_outermost_traces_and_compiles():
    """A jit-cache miss counts one trace however many jitted functions
    it traces inside, and one compile; a warm call counts neither."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        return jnp.take_along_axis(x, jnp.argsort(x, axis=1), axis=1)

    compile_counts()                       # registers the listeners
    t0, c0 = compile_counts()
    f(np.ones((3, 5), np.float32)).block_until_ready()
    t1, c1 = compile_counts()
    f(np.zeros((3, 5), np.float32)).block_until_ready()
    t2, c2 = compile_counts()
    assert (t1 - t0, c1 - c0) == (1, 1)
    assert (t2, c2) == (t1, c1)


def test_tracer_head_capture_drops_past_max_events():
    tr = Tracer(clock=ManualClock(auto_tick_us=1.0), max_events=3)
    for i in range(10):
        tr.event("e", i=i)
    assert len(tr) == 3 and tr.dropped == 7
    assert [e.args["i"] for e in tr.events] == [0, 1, 2]  # head, not ring
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


def test_tracer_by_name():
    tr = manual_tracer()
    tr.event("a")
    tr.event("b")
    tr.event("a")
    assert len(tr.by_name("a")) == 2 and len(tr.by_name("c")) == 0


# ----------------------------------------------------------------- metrics
def test_counter_monotone():
    c = Counter()
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_histogram_window_quantiles():
    h = Histogram(window=4)
    for v in (1.0, 2.0, 3.0, 4.0, 100.0):  # 1.0 fell out of the window
        h.observe(v)
    assert h.count == 5 and h.total == 110.0
    assert h.quantile(0.0) == 2.0
    assert h.quantile(0.99) == 100.0
    s = h.summary()
    assert s["count"] == 5 and s["window"] == 4
    assert s["mean"] == 22.0 and s["max"] == 100.0
    assert s["p50"] == 4.0                 # nearest-rank over [2,3,4,100]
    assert Histogram().quantile(0.5) == 0.0


def test_registry_create_on_first_use_and_per_target():
    m = MetricsRegistry()
    m.counter("serve.block_reads", "seg0").inc(10)
    m.counter("serve.block_reads", "seg1").inc(20)
    m.gauge("serve.cache_hit_rate").set(0.5)
    m.histogram("serve.batch_block_reads").observe(30)
    assert m.value("serve.block_reads", "seg0") == 10
    assert m.value("serve.block_reads", "seg1") == 20
    assert m.value("nope") is None
    assert m.targets("serve.block_reads") == ["seg0", "seg1"]
    snap = m.snapshot()
    assert snap["serve.block_reads"] == {"seg0": 10, "seg1": 20}
    assert snap["serve.cache_hit_rate"][""] == 0.5
    assert snap["serve.batch_block_reads"][""]["count"] == 1


def test_registry_kind_mismatch_raises():
    m = MetricsRegistry()
    m.counter("serve.batches")
    with pytest.raises(TypeError):
        m.gauge("serve.batches")
    with pytest.raises(TypeError):
        m.histogram("serve.batches")
    # same name under a DIFFERENT target is a separate instrument
    assert isinstance(m.gauge("serve.batches", "segX"), Gauge)


# ---------------------------------------------------------- round-log fold
def test_fold_round_log_drops_padding_and_validates_shape():
    log = np.zeros((6, 8), np.int32)
    log[0] = [8, 10, 2, 3, 1, 0, 2, 1]
    log[1] = [4, 6, 1, 1, 0, 1, 0, 3]
    recs = fold_round_log(log, rounds=2)
    assert len(recs) == 2
    assert recs[1] == RoundRecord(1, 4, 6, 1, 1, 0, True,
                                  spec_hits=0, spec_wasted=3)
    tot = round_log_totals(recs)
    assert tot == {"rounds": 2, "hops": 12, "io": 16, "tier0_hits": 3,
                   "dedup_saved": 4, "dedup_cross": 1, "compactions": 1,
                   "spec_hits": 2, "spec_wasted": 4,
                   "live_weight": 12}
    with pytest.raises(ValueError):
        fold_round_log(np.zeros((6, 6), np.int32), 2)


# ----------------------------------------------------------- perf artifact
def test_perf_artifact_schema_round_trip(tmp_path, monkeypatch):
    from benchmarks import common as C
    monkeypatch.setattr(C, "ARTIFACT_DIR", str(tmp_path))
    path = C.perf_artifact(
        "t_bench", [{"name": "lat", "value": 1.5, "units": "us"},
                    {"name": "hits", "value": 3, "units": "blocks",
                     "measured": True}],
        config={"n": 10}, measured=False)
    with open(path) as f:
        payload = json.load(f)
    assert C.validate_perf_artifact(payload) == []
    assert payload["bench"] == "t_bench"
    assert payload["config_hash"] == C.config_hash({"n": 10})
    assert payload["metrics"][0]["measured"] is False
    assert payload["metrics"][1]["measured"] is True   # per-row override


def test_validate_perf_artifact_catches_problems():
    from benchmarks import common as C
    assert C.validate_perf_artifact({}) != []
    bad = {"schema": C.ARTIFACT_SCHEMA, "bench": "b", "config": {},
           "config_hash": "x", "measured": False,
           "metrics": [{"name": "m", "value": "NaNstr", "units": "",
                        "measured": False}]}
    assert any("number" in p for p in C.validate_perf_artifact(bad))


def test_config_hash_stable_and_order_independent():
    from benchmarks import common as C
    assert C.config_hash({"a": 1, "b": 2}) == C.config_hash({"b": 2,
                                                             "a": 1})
    assert C.config_hash({"a": 1}) != C.config_hash({"a": 2})


# ------------------------------------------------------------- calibration
def _device_stats(io, t0, hops, saved, rounds):
    return IOStats.from_device(io, t0, hops, saved, rounds)


def test_calibration_recovers_known_device_constants():
    truth = dataclasses.replace(TPU_HBM_SEGMENT, t_batch_block=0.7,
                                t_round=2.5, t_round_comp=0.3)
    rng = [(40, 5, 30, 4, 12), (80, 9, 55, 10, 20), (25, 2, 18, 1, 9),
           (60, 7, 44, 6, 16)]
    samples = [CalibrationSample(_device_stats(*r),
                                 truth.latency_us(_device_stats(*r)))
               for r in rng]
    fields = ("t_batch_block", "t_round", "t_round_comp")
    model, report = fit_cost_model(TPU_HBM_SEGMENT, samples, fields)
    for f in fields:
        assert getattr(model, f) == pytest.approx(getattr(truth, f),
                                                  abs=1e-6)
    assert report["unfit"] == []
    assert report["error_after"]["mean_abs_rel_err"] < 1e-9


def test_calibration_reports_unidentifiable_fields():
    # host-regime samples never exercise the round chain: t_round /
    # t_round_comp columns are all-zero and must come back unfit with
    # base values, never silently "fitted"
    samples = [CalibrationSample(IOStats(block_reads=r, cache_misses=r,
                                         hops=r), float(100 * r))
               for r in (5, 11, 23)]
    model, report = fit_cost_model(
        NVME_SEGMENT, samples,
        fields=("t_block_io", "t_round", "t_round_comp"))
    assert set(report["unfit"]) == {"t_round", "t_round_comp"}
    assert model.t_round == NVME_SEGMENT.t_round
    assert "t_block_io" in report["fitted"]
    assert model.t_block_io >= 0.0


def test_calibration_clips_negative_constants_and_needs_samples():
    with pytest.raises(ValueError):
        fit_cost_model(NVME_SEGMENT, [])
    s = [CalibrationSample(IOStats(block_reads=r, cache_misses=r),
                           0.0)           # measured 0 → raw fit < base
         for r in (3, 7)]
    model, _ = fit_cost_model(NVME_SEGMENT, s, fields=("t_block_io",))
    assert model.t_block_io >= 0.0


def test_preset_save_load_apply(tmp_path):
    truth = dataclasses.replace(TPU_HBM_SEGMENT, t_round=4.0)
    stats = [_device_stats(40, 5, 30, 4, 12), _device_stats(70, 6, 50,
                                                            8, 18)]
    samples = [CalibrationSample(s, truth.latency_us(s)) for s in stats]
    path = tmp_path / "preset.json"
    model, preset, report = calibrate(
        TPU_HBM_SEGMENT, samples, fields=("t_round",),
        source="unit test", preset_path=str(path))
    loaded = CalibrationPreset.load(path)
    assert loaded == preset
    applied = loaded.apply(TPU_HBM_SEGMENT)
    assert applied.t_round == pytest.approx(4.0, abs=1e-6)
    assert applied.t_block_io == TPU_HBM_SEGMENT.t_block_io  # untouched
    with pytest.raises(ValueError):
        loaded.apply(NVME_SEGMENT)         # backend mismatch


def test_load_calibrated_applies_stored_preset(tmp_path):
    """ISSUE 7 satellite: the scheduler/router/mesh-bench default
    pricing path — a stored CALIB_<backend>.json overlays its fitted
    constants on the shipped base model."""
    from repro.obs import load_calibrated
    preset = CalibrationPreset(
        backend=TPU_HBM_SEGMENT.name, constants={"t_round": 9.5},
        unfit=[], n_samples=4, error={})
    preset.save(tmp_path / f"CALIB_{TPU_HBM_SEGMENT.name}.json")
    cm = load_calibrated(TPU_HBM_SEGMENT, results_dir=str(tmp_path))
    assert cm.t_round == pytest.approx(9.5)
    assert cm.t_block_io == TPU_HBM_SEGMENT.t_block_io   # unfit kept


def test_load_calibrated_falls_back_on_mismatch_or_garbage(tmp_path):
    """Any way the preset cannot be honored falls back to the base
    model — missing file, wrong backend, unparseable JSON — so callers
    can default to calibrated pricing unconditionally."""
    from repro.obs import load_calibrated
    # missing file
    assert load_calibrated(NVME_SEGMENT,
                           results_dir=str(tmp_path)) == NVME_SEGMENT
    # preset fitted for a different backend than the file name claims
    wrong = CalibrationPreset(
        backend=TPU_HBM_SEGMENT.name, constants={"t_round": 9.5},
        unfit=[], n_samples=4, error={})
    wrong.save(tmp_path / f"CALIB_{NVME_SEGMENT.name}.json")
    assert load_calibrated(NVME_SEGMENT,
                           results_dir=str(tmp_path)) == NVME_SEGMENT
    # unparseable file
    (tmp_path / f"CALIB_{NVME_SEGMENT.name}.json").write_text("{nope")
    assert load_calibrated(NVME_SEGMENT,
                           results_dir=str(tmp_path)) == NVME_SEGMENT
    # unknown constant name inside an otherwise valid preset
    bad = CalibrationPreset(
        backend=NVME_SEGMENT.name, constants={"t_warp_drive": 1.0},
        unfit=[], n_samples=1, error={})
    bad.save(tmp_path / f"CALIB_{NVME_SEGMENT.name}.json")
    assert load_calibrated(NVME_SEGMENT,
                           results_dir=str(tmp_path)) == NVME_SEGMENT


# -------------------------------------------- coordinator stats/obs wiring
class _FakeServer:
    """Duck-typed device-less server: fixed results, zero traffic."""

    def __init__(self, offset=0):
        self.offset = offset

    def search(self, queries, k):
        n = queries.shape[0]
        ids = np.tile(np.arange(k, dtype=np.int64), (n, 1))
        dists = np.ones((n, k), np.float32)
        return ids, dists, np.zeros(n, np.int64)


def test_coordinator_stats_schema_complete_on_cold_batch():
    """Every STATS_SCHEMA key is present with zeros included — a
    downstream consumer must never KeyError on a batch that hit no
    cache and saved no dedup (the PR 6 stats-shape fix)."""
    from repro.serving import QueryCoordinator
    coord = QueryCoordinator([_FakeServer()])
    q = np.zeros((4, 8), np.float32)
    _, _, stats = coord.search(q, k=3)
    for key in QueryCoordinator.STATS_SCHEMA:
        assert key in stats, f"stats dict missing {key!r}"
    assert stats["total_tier0_hits"] == 0
    assert stats["total_dedup_saved"] == 0
    assert stats["deduped_block_reads"] == 0
    assert stats["cache_hits"] == 0 and stats["cache_misses"] == 0
    assert stats["cache_hit_rate"] == 0.0
    assert stats["segments_searched"] == 1


def test_coordinator_emits_spans_and_metrics():
    from repro.serving import QueryCoordinator
    tr = manual_tracer()
    m = MetricsRegistry()
    coord = QueryCoordinator([_FakeServer(0), _FakeServer(100)],
                             tracer=tr, metrics=m)
    q = np.zeros((4, 8), np.float32)
    coord.search(q, k=3)
    coord.search(q, k=3)
    assert len(tr.by_name("coord.batch")) == 2
    assert len(tr.by_name("coord.segment")) == 4   # 2 segments x 2
    batch = tr.by_name("coord.batch")[0]
    assert batch.args["n_queries"] == 4 and "block_reads" in batch.args
    assert m.value("serve.batches") == 2
    assert m.value("serve.queries") == 8
    assert m.value("serve.block_reads", "seg0") == 0
    assert m.snapshot()["serve.batch_block_reads"][""]["count"] == 2
    # registry view and stats dict can never disagree
    assert m.value("serve.total_block_reads") == 0
