"""The benchmark's harness: finds a cell's configuration, traffic and
metric readers by name, builds the served index from the configuration, drives
the measured window through the program's serving path, checks what the
window served against the plain reference, and returns the result line.

A configuration's ``segments`` (1 when absent) cuts its data into that
many equal contiguous segments, one per chip of the cell, served by one
``MeshQueryRouter`` under the ``QueryCoordinator``; one segment is
served by its ``SegmentServer`` alone.

Everything that belongs to one configuration, traffic mix or metric is
data or a reader of its own, found by the name ``BENCHMARK.json`` gives:

* ``<file>`` of the configuration entry: a JSON file of sizes and knobs;
* ``bench/traffic/<traffic>.json``: the traffic mix's parameters, read
  by the one generator here (a closed loop of whole batches);
* ``bench/metrics/<metric>.py``: a reader ``read(run) -> float | None``
  of one metric, end to end or per layer, from the ``Run`` record.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import data as bench_data  # noqa: E402
import reference  # noqa: E402
import scope_reduce  # noqa: E402
import trace_reduce  # noqa: E402


# ------------------------------------------------------------ discovery

def load_spec(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _read_json(root: str, rel: str) -> dict:
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


def find_cell(spec: dict, root: str, name: str):
    """(cell entry, configuration dict, traffic dict) of cell ``name``."""
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = _read_json(root, configs[cell["config"]]["file"])
    traffic = _read_json(root, os.path.join(
        "bench", "traffic", cell["traffic"] + ".json"))
    return cell, cfg, traffic


def cell_metrics(spec: dict, cell: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in spec[kind]
            if cell in m.get("workloads", [cell])]


def load_reader(root: str, metric: str) -> Callable:
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------ run record

@dataclasses.dataclass
class Batch:
    """One served batch: host times (s, perf_counter), rows sent, valid
    rows, and the program's counts for it."""
    t0: float
    t1: float
    rows: int
    valid: int
    device_rounds: List[int]    # rounds each rank ran, in mesh order
    paying_dmas: int            # io - dedup_saved, summed over ranks


@dataclasses.dataclass
class Run:
    """What a metric reader can read about one run."""
    config: dict
    setup_s: float = 0.0
    build_graph_s: float = 0.0
    window: tuple = (0.0, 0.0)          # perf_counter start, end
    batches: List[Batch] = dataclasses.field(default_factory=list)
    index_hbm_bytes: int = 0
    shapes: Dict[str, int] = dataclasses.field(default_factory=dict)
    checks: Dict[str, dict] = dataclasses.field(default_factory=dict)
    spans: list = dataclasses.field(default_factory=list)  # obs.Tracer
    trace: Optional[trace_reduce.TraceSummary] = None
    # device plane name -> op event name -> the op's ``op_name`` path
    op_paths: Dict[str, Dict[str, str]] = dataclasses.field(
        default_factory=dict)
    peaks: object = None

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def served(self) -> int:
        return sum(b.valid for b in self.batches)


# ------------------------------------------------------------- the index

def segment_params(cfg: dict):
    from repro.core.params import (CacheParams, GraphParams,
                                   LayoutParams, NavGraphParams,
                                   PQParams, SearchParams,
                                   SegmentParams)
    s = cfg["segment"]
    return SegmentParams(graph=GraphParams(**s["graph"]),
                         layout=LayoutParams(**s["layout"]),
                         pq=PQParams(**s["pq"]),
                         nav=NavGraphParams(**s["nav"]),
                         search=SearchParams(**s["search"]),
                         cache=CacheParams(**s["cache"]),
                         metric=s["metric"])


def segments(cfg: dict, cell: dict) -> int:
    """The configuration's ``segments`` (1 when absent), checked against
    the cell: several segments are served one per chip, so the cell
    asks for as many chips, and they share ``n`` equally."""
    count = int(cfg.get("segments", 1))
    if count < 1:
        raise ValueError(f"segments must be >= 1, not {count}")
    if count > 1 and cell["chips"] != count:
        raise ValueError(
            f"{count} segments are served one per chip, but cell "
            f"{cell['name']!r} asks for {cell['chips']} chips")
    if cfg["n"] % count:
        raise ValueError(f"n = {cfg['n']} does not split into {count} "
                         f"equal segments")
    return count


def build_target(cfg: dict, x: np.ndarray, devices, run: Run):
    """Build and pack the served index of ``x`` as the configuration's
    ``segments`` contiguous segments, segment ``s`` on ``devices[s]``
    (rows ``[s*n/S, (s+1)*n/S)``, ids offset by ``s*n/S``), each wrapped
    in a ``SegmentServer``. One segment is served by its server; several
    by a ``MeshQueryRouter`` over a 1 x S mesh of ``devices``. Returns
    (the target, its servers)."""
    import jax
    from jax.sharding import Mesh

    from repro.core.device_search import from_segment
    from repro.core.graph import build_graph
    from repro.core.params import DeviceSearchParams
    from repro.core.segment import build_segment
    from repro.serving.coordinator import SegmentServer
    from repro.serving.router import MeshQueryRouter

    params = segment_params(cfg)
    serve = DeviceSearchParams(**cfg["serve"])
    count = len(devices)
    rows = x.shape[0] // count
    servers = []
    for s, device in enumerate(devices):
        part = x[s * rows:(s + 1) * rows]
        with jax.default_device(device):
            with _span("bench.build_graph"):
                t = time.perf_counter()
                graph = build_graph(part, params.graph, params.metric)
                run.build_graph_s += time.perf_counter() - t
            with _span("bench.build_segment"):
                seg = build_segment(part, params, graph=graph)
                ds = from_segment(seg)
                jax.block_until_ready(ds)
        servers.append(SegmentServer(segment=ds, offset=s * rows,
                                     num_vectors=rows, params=serve,
                                     metric=params.metric))
    ds = servers[0].segment
    run.shapes = {"eps": int(ds.vid.shape[1]),
                  "dim": int(ds.vecs.shape[2]),
                  "itemsize": int(ds.vecs.dtype.itemsize)}
    if count == 1:
        return servers[0], servers
    mesh = Mesh(np.asarray(devices).reshape(1, count), ("data", "model"))
    return MeshQueryRouter(servers, mesh=mesh), servers


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def index_bytes(servers) -> int:
    """Bytes of the served device index arrays, each served segment
    counted once."""
    import jax
    return int(sum(a.nbytes for s in servers
                   for a in jax.tree.leaves(s.segment)))


def batch_counts(target):
    """(rounds per rank in mesh order, paying DMAs summed over ranks) of
    the batch just served, through the ``SegmentTarget`` protocol."""
    from repro.serving.target import batch_stats
    stats = batch_stats(target)
    paying = int(stats["io"].sum()) - int(stats["dedup_saved"].sum())
    per_rank = getattr(target, "last_per_rank", None)
    if per_rank:
        return [int(per_rank[r].batch_rounds)
                for r in range(len(per_rank))], paying
    return [int(stats["rounds"])], paying


# -------------------------------------------------------------- traffic

class Served:
    """Every answer the window served, by query index."""

    def __init__(self, n: int, k: int):
        self.ids = np.full((n, k), -1, np.int64)
        self.dists = np.full((n, k), np.inf, np.float32)
        self.answered = np.zeros(n, bool)

    def put(self, qidx, ids, dists):
        self.ids[qidx] = ids
        self.dists[qidx] = dists
        self.answered[qidx] = True


def _serve_batch(ctx, batcher, qidx_of):
    """Send the batcher's next batch through the coordinator, record its
    counts, and return (query indices, ids, dists)."""
    coord, run, target, k = ctx
    with _span("bench.next_batch"):
        qb, rids, valid = batcher.next_batch()
    t0 = time.perf_counter()
    with _span("bench.search"):
        gi, gd, _ = coord.search(qb, k)
    t1 = time.perf_counter()
    rounds, paying = batch_counts(target)
    run.batches.append(Batch(t0, t1, qb.shape[0], valid, rounds, paying))
    qidx = np.asarray([qidx_of.pop(r) for r in rids], np.int64)
    return qidx, gi[:valid], gd[:valid]


def _batcher(pool, batch):
    from repro.kernels.ops import round_tile
    from repro.serving.batcher import RequestBatcher
    return RequestBatcher(pool.shape[1], buckets=(batch,),
                          tile=round_tile(batch))


def warm_up(ctx, pool, traffic):
    """One batch of the size the traffic sends, so that every program
    the window runs is compiled (or loaded from the cache) in set-up."""
    coord, k, b = ctx[0], ctx[3], traffic["batch"]
    batcher = _batcher(pool, b)
    for q in pool[:b]:
        batcher.submit(q)
    coord.search(batcher.next_batch()[0], k)


def closed_loop(ctx, pool, traffic, seconds):
    """One client sending back-to-back batches of ``batch`` queries for
    ``seconds``, in whole passes over the pool: a pass that starts
    before the time is up is finished and counted, so every window
    serves each of the pool's batches equally often. Returns (queries,
    served)."""
    run, b = ctx[1], traffic["batch"]
    batcher = _batcher(pool, b)
    sent = 0
    answers = []
    qidx_of: Dict[int, int] = {}
    t_start = time.perf_counter()
    while sent % len(pool) or time.perf_counter() - t_start < seconds:
        with _span("bench.admit"):
            for _ in range(b):
                qidx_of[batcher.submit(pool[sent % len(pool)])] = sent
                sent += 1
        answers.append(_serve_batch(ctx, batcher, qidx_of))
    run.window = (t_start, run.batches[-1].t1)
    served = Served(sent, ctx[3])
    for answer in answers:
        served.put(*answer)
    return pool[np.arange(sent) % len(pool)], served


# --------------------------------------------------------------- a run

def _memory_peak(device) -> int:
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", 0))


def _find_xplane(d: str) -> str:
    for dirpath, _, files in os.walk(d):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(dirpath, f)
    raise FileNotFoundError(f"no .xplane.pb under {d}")


def _window(ctx, pool, traffic, seconds, trace_dir):
    """The measured window, under the profiler when ``trace_dir``."""
    import jax
    profiler = contextlib.nullcontext()
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        profiler = jax.profiler.trace(trace_dir, profiler_options=opts)
    with profiler:
        with _span("bench.window"):
            return closed_loop(ctx, pool, traffic, seconds)


def run_cell(root: str, cell_name: str, seed: int, seconds: float,
             trace: bool, t_process: float, *, peaks=None) -> dict:
    """One run of one cell; returns the result line's object. The
    caller has checked the devices; ``t_process`` is the process's
    start on ``time.perf_counter``."""
    import shutil

    import jax

    from repro.obs.clock import WallClock
    from repro.obs.trace import Tracer
    from repro.serving.coordinator import QueryCoordinator

    spec = load_spec(root)
    cell, cfg, traffic = find_cell(spec, root, cell_name)
    count = segments(cfg, cell)
    run = Run(config=cfg, peaks=peaks)
    devices = jax.devices()[:cell["chips"]]

    with _span("bench.data"):
        x, pool = bench_data.cell_inputs(cfg, traffic, seed)
    target, servers = build_target(cfg, x, devices[:count], run)
    tracer = Tracer(clock=WallClock()) if trace else None
    coord = QueryCoordinator([target], tracer=tracer)
    run.index_hbm_bytes = index_bytes(servers)
    ctx = (coord, run, target, traffic["k"])
    with _span("bench.warm"):
        warm_up(ctx, pool, traffic)
    if tracer is not None:
        tracer.clear()
    run.setup_s = time.perf_counter() - t_process

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        queries, served = _window(ctx, pool, traffic, seconds, trace_dir)
        memory_peaks = [_memory_peak(d) for d in devices]
        if tracer is not None:
            run.spans = list(tracer.events)
        # the program's state goes before the reference runs
        del ctx, coord, target, servers, tracer
        gc.collect()
        run.checks = check(x, queries, served, cfg)
        if trace:
            xplane = _find_xplane(trace_dir)
            run.trace = trace_reduce.load(xplane)
            run.op_paths = scope_reduce.load_op_paths(xplane)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": max(memory_peaks)}
    per_device = {"memory_peak_bytes": memory_peaks,
                  "rounds_per_batch": [
                      sum(b.device_rounds[r] for b in run.batches)
                      / len(run.batches)
                      for r in range(len(run.batches[0].device_rounds))]}
    if trace:
        busy = [d.busy_ns / 1e9 for d in run.trace.devices]
        device["busy_s"] = float(np.mean(busy)) if busy else 0.0
        device["window_s"] = run.trace.window_ns / 1e9
        per_device["busy_s"] = busy
    metrics = {}
    for m in cell_metrics(spec, cell_name,
                          "per_layer" if trace else "end_to_end"):
        value = load_reader(root, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    out = {"correct": all(c["pass"] for c in run.checks.values()),
           "attempted": int(len(queries)),
           "failed": int(run.checks["bad_rows"]["value"]
                         + run.checks["unanswered"]["value"]),
           "metrics": metrics, "device": device}
    if trace and run.trace.devices:
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in
                           trace_reduce.top_ops(run.trace, 10)],
            "idle_gaps": [[n, s] for n, s in
                          trace_reduce.idle_gaps(run.trace)[:10]]}
    out["per_device"] = per_device
    out["checks"] = run.checks
    return out


def check(x, queries, served, cfg) -> Dict[str, dict]:
    """The numbers that decide ``correct``, each with its limit."""
    lim = cfg["limits"]
    k = served.ids.shape[1]
    ans = served.answered
    ref_ids, _ = reference.exact_knn(x, queries[ans], k)
    got = reference.compare(x, queries[ans], served.ids[ans],
                            served.dists[ans], ref_ids)
    unanswered = int((~ans).sum())
    return {
        "recall_at_10": {"value": got["recall_at_10"],
                         "limit": lim["recall_at_10_min"], "op": ">=",
                         "pass": got["recall_at_10"]
                         >= lim["recall_at_10_min"]},
        "dist_gap": {"value": got["dist_gap"],
                     "limit": lim["dist_gap_max"], "op": "<=",
                     "pass": got["dist_gap"] <= lim["dist_gap_max"]},
        "bad_rows": {"value": got["bad_rows"], "limit": 0, "op": "<=",
                     "pass": got["bad_rows"] == 0},
        "unanswered": {"value": unanswered, "limit": 0, "op": "<=",
                       "pass": unanswered == 0},
    }
