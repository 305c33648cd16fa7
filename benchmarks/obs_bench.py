"""repro.obs benchmarks: the trace smoke (a served batch recorded
end-to-end) and the measured-vs-modeled cost calibration harness
(ROADMAP adaptive-plane v2 items 3+4).

``obs_trace_smoke`` runs a real coordinator batch under a wall-clock
tracer + metrics registry and checks that the ``trace_rounds`` buffer
folds exactly to the batch counters.

``cost_calibration`` fits ``CostModel`` constants per backend regime:

  * host/NVMe — replay host search batches under wall-clock timing
    (``measured=True``: real clock on this container's CPU, so the
    fitted ``t_block_io`` prices a *Python block visit*, not NVMe —
    the artifact's measured flag plus the preset's ``source`` say so);
  * device/TPU — recover known constants from synthetically priced
    device traffic (``measured=False``): real searches produce the
    counters, a perturbed ground-truth model prices them, and the fit
    must recover that model near-exactly (asserted) — the
    identifiability check that makes the wall-clock fit trustworthy.

Both presets land in ``results/CALIB_<backend>.json`` and a
``BENCH_cost_calibration.json`` perf artifact carries the residuals.
"""
from __future__ import annotations

import dataclasses
import os

import numpy as np

from benchmarks import common as C
from repro.core.iostats import IOStats, NVME_SEGMENT, TPU_HBM_SEGMENT
from repro.core.search import anns
from repro.obs import (CalibrationSample, MetricsRegistry, Tracer,
                       WallClock, calibrate, fold_round_log,
                       round_log_totals)


def obs_trace_smoke():
    """One served batch, fully traced: coordinator and server spans,
    host-path io.read spans, metrics registry. Asserts the
    round-granular trace buffer folds exactly to the batch counters."""
    import jax
    from repro.core import device_search as DS
    try:
        jax.devices()
    except RuntimeError as e:           # no backend: record the skip
        C.record("obs_trace_smoke", skipped=str(e))
        return
    from repro.configs.starling_segment import (DEVICE_SEARCH_BATCH,
                                                SEGMENT_BENCH_CACHED)
    from repro.core.segment import build_segment
    from repro.serving import (HostSegmentServer, QueryCoordinator,
                               SegmentServer)

    x = C.base_data()
    seg = build_segment(x, SEGMENT_BENCH_CACHED)  # cache-fronted host view
    q = C.queries()[:8]

    tracer = Tracer(clock=WallClock())
    metrics = MetricsRegistry()

    # serving plane: a device server (round-granular tracing on) behind
    # the coordinator, plus a traced host server for the io.read spans
    p = dataclasses.replace(DEVICE_SEARCH_BATCH, trace_rounds=True)
    server = SegmentServer(segment=DS.from_segment(seg, tier0_frac=0.1),
                           offset=0, num_vectors=x.shape[0], host=seg,
                           params=p)
    hserver = HostSegmentServer.from_segment(seg, 0)
    coord = QueryCoordinator([server], tracer=tracer, metrics=metrics)
    hserver.tracer = tracer
    hserver.view.store.attach_obs(tracer, metrics, target="seg0-host")

    hserver.search(q)                     # host spans + io.read spans
    _, _, stats = coord.search(q, k=10)   # coord spans + device columns

    # the round-granular buffer must fold EXACTLY to the batch counters
    records = fold_round_log(server.last_round_log, server.last_rounds)
    tot = round_log_totals(records)
    assert tot["io"] == int(server.last_io.sum())
    assert tot["hops"] == int(server.last_hops.sum())
    assert tot["tier0_hits"] == int(server.last_tier0_hits.sum())
    assert tot["dedup_saved"] == int(server.last_dedup_saved.sum())

    snap = metrics.snapshot()
    C.record("obs_trace_smoke",
             events=len(tracer), dropped=tracer.dropped,
             tracks=len({e.track for e in tracer.events}),
             device_rounds=tot["rounds"],
             metric_names=len(snap),
             serve_batches=metrics.value("serve.batches"),
             total_block_reads=stats["total_block_reads"])
    C.perf_artifact(
        "obs_trace_smoke", [
            {"name": "trace_events", "value": len(tracer),
             "units": "events", "measured": True},
            {"name": "device_rounds", "value": tot["rounds"],
             "units": "rounds"},
            {"name": "trace_dropped", "value": tracer.dropped,
             "units": "events", "measured": True}],
        config={"n": C.N_BASE, "dim": C.DIM, "batch": int(q.shape[0]),
                "tier0_frac": 0.1},
        measured=False)


def _host_samples():
    """Replay host search batches under wall-clock timing, varying the
    beam width so the sample matrix has rank (different counter mixes
    identify different constants)."""
    seg = C.bench_segment()
    clk = WallClock()
    samples = []
    for gamma in (24, 48, 64, 96):
        sp = dataclasses.replace(seg.params.search, candidate_size=gamma)
        for nq in (8, 16, 32):
            q = C.queries()[:nq]
            t0 = clk.now_us()
            _, _, stats = anns(seg.view, q, 10, sp)
            t1 = clk.now_us()
            tot = IOStats()
            for s in stats:
                tot.merge(s)
            samples.append(CalibrationSample(tot, t1 - t0))
    return samples


def _device_samples(ground_truth):
    """Real device searches priced by a known perturbed model — the
    recovery target the fit must reproduce."""
    import jax.numpy as jnp
    from repro.configs.starling_segment import DEVICE_SEARCH_BATCH
    from repro.core import device_search as DS
    from repro.data.vectors import query_set
    seg = C.bench_segment(shuffle="bnf")
    ds = DS.from_segment(seg, tier0_frac=0.05)
    x = C.base_data()
    samples = []
    for b in (4, 8, 16, 32):
        q = query_set(x, 32, seed=5)[:b]
        r = DS.device_anns(ds, jnp.asarray(q), DEVICE_SEARCH_BATCH)
        batch = IOStats.from_device_batch(
            np.asarray(r.io), np.asarray(r.tier0_hits),
            np.asarray(r.hops), np.asarray(r.dedup_saved),
            int(r.rounds))
        samples.append(CalibrationSample(
            batch, ground_truth.latency_us(batch)))
    return samples


def cost_calibration():
    """Fit, store, and report per-backend calibration presets."""
    # --- host/NVMe regime: wall-clock measured on THIS container
    host_samples = _host_samples()
    _, preset_h, rep_h = calibrate(
        NVME_SEGMENT, host_samples,
        source="host anns replay, wall-clock, CPU container",
        preset_path=os.path.join(C.ARTIFACT_DIR, "CALIB_nvme.json"))
    C.record("cost_calibration", backend="nvme", measured=True,
             n_samples=len(host_samples),
             fitted=",".join(sorted(preset_h.constants)) or "none",
             unfit=",".join(preset_h.unfit) or "none",
             err_before=rep_h["error_before"]["mean_abs_rel_err"],
             err_after=rep_h["error_after"]["mean_abs_rel_err"])
    # the fit must not make the model WORSE on its own samples
    assert rep_h["error_after"]["mean_abs_rel_err"] <= \
        rep_h["error_before"]["mean_abs_rel_err"] + 1e-9

    # --- device/TPU regime: recover a known perturbed model
    try:
        import jax
        jax.devices()
    except RuntimeError as e:
        C.record("cost_calibration", backend="tpu-hbm", skipped=str(e))
        return
    truth = dataclasses.replace(TPU_HBM_SEGMENT, t_block_io=2.4,
                                t_batch_block=0.6, t_round=3.0,
                                t_round_comp=0.4)
    dev_samples = _device_samples(truth)
    fitted, preset_d, rep_d = calibrate(
        TPU_HBM_SEGMENT, dev_samples,
        source="device anns replay, synthetic ground-truth pricing",
        preset_path=os.path.join(C.ARTIFACT_DIR, "CALIB_tpu-hbm.json"))
    err_d = rep_d["error_after"]["mean_abs_rel_err"]
    assert err_d < 0.05, (
        f"calibration must recover the known device model "
        f"(residual {err_d:.3f})")
    C.record("cost_calibration", backend="tpu-hbm", measured=False,
             n_samples=len(dev_samples),
             fitted=",".join(sorted(preset_d.constants)) or "none",
             unfit=",".join(preset_d.unfit) or "none",
             err_before=rep_d["error_before"]["mean_abs_rel_err"],
             err_after=err_d)
    C.perf_artifact(
        "cost_calibration", [
            {"name": "nvme_mean_abs_rel_err",
             "value": rep_h["error_after"]["mean_abs_rel_err"],
             "units": "ratio", "measured": True},
            {"name": "nvme_mean_measured_us",
             "value": rep_h["error_after"]["mean_measured_us"],
             "units": "us", "measured": True},
            {"name": "tpu_recovery_mean_abs_rel_err", "value": err_d,
             "units": "ratio"}],
        config={"n": C.N_BASE, "dim": C.DIM,
                "host_samples": len(host_samples),
                "device_samples": len(dev_samples)},
        measured=False)
