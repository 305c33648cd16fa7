"""A tiny copy of the benchmark for CPU tests: the same files, cells and
metrics, with each configuration and traffic mix cut to a size the
Pallas interpreter serves in seconds."""
from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

TINY_CONFIG = {"n": 1600}
TINY_SERVE = {"candidates": 24, "max_hops": 96}
TINY_GRAPH = {"build_beam": 32}
TINY_TRAFFIC = {"batch": 16, "query_pool": 64}


def make_root(dest: str) -> str:
    """Copy ``BENCHMARK.json`` and ``bench/`` (not ``selftest``) into
    ``dest`` and cut every configuration and traffic mix there to a
    tiny size. Returns ``dest``."""
    shutil.copytree(BENCH, os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("selftest", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dest)
    spec = json.load(open(os.path.join(dest, "BENCHMARK.json")))
    for c in spec["configs"]:
        path = os.path.join(dest, c["file"])
        cfg = json.load(open(path))
        cfg.update(TINY_CONFIG)
        cfg["serve"].update(TINY_SERVE)
        cfg["segment"]["graph"].update(TINY_GRAPH)
        json.dump(cfg, open(path, "w"))
    tdir = os.path.join(dest, "bench", "traffic")
    for f in os.listdir(tdir):
        t = json.load(open(os.path.join(tdir, f)))
        t.update(TINY_TRAFFIC)
        json.dump(t, open(os.path.join(tdir, f), "w"))
    return dest


def run(root: str, cell: str, seed: int = 2 ** 31 + 11,
        trace: bool = False, seconds: float = 1.0) -> dict:
    """One run of ``cell`` in the tiny copy at ``root``, on whatever
    devices JAX has (the caller skips the look for a chip)."""
    import time

    import harness
    return harness.run_cell(root, cell, seed, seconds, trace,
                            time.perf_counter())


def check_line(out: dict, spec: dict, cell: str, trace: bool) -> None:
    """The result line's schema: the contract's keys, the cell's
    metrics with their units, and the checks last."""
    import harness
    keys = list(out)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics",
                        "device"], keys
    assert keys[-1] == "checks"
    assert isinstance(out["correct"], bool)
    assert out["attempted"] > 0 and out["failed"] >= 0
    dev = out["device"]
    for key in ("platform", "kind", "count", "memory_peak_bytes"):
        assert key in dev
    if trace:
        assert "busy_s" in dev and "window_s" in dev
    kind = "per_layer" if trace else "end_to_end"
    units = {m["name"]: m["unit"]
             for m in harness.cell_metrics(spec, cell, kind)}
    for name, m in out["metrics"].items():
        assert name in units, name
        assert m["unit"] == units[name]
        assert isinstance(m["value"], float)
    if not trace:
        assert set(out["metrics"]) == set(units)
    for name, c in out["checks"].items():
        assert {"value", "limit", "pass"} <= set(c), name
