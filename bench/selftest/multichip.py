"""Runs of a tiny benchmark copy on several virtual CPU devices, in a
process of their own: one CPU process holds one device unless
``XLA_FLAGS`` asks for more before JAX starts, so a cell on several
chips (a mesh of segments behind the router) is run here.

    python bench/selftest/multichip.py <root> < jobs.json

Each job is ``{"cell", "seed", "trace", "fault"}`` (a run of the cell,
with the fault of ``faults.plant`` planted when one is named) or
``{"compare": cell, "seed"}`` (the cell's first batch served through
its router and through a ``QueryCoordinator`` over the same segment
servers). Prints one JSON list of the jobs' results as the last line of
standard output."""
from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
SRC = os.path.join(os.path.dirname(BENCH), "src")


def run(root: str, jobs: list, chips: int, timeout: float = 600) -> list:
    """The results of ``jobs`` on ``chips`` virtual CPU devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                        f"platform_device_count={chips}").strip()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), root],
        input=json.dumps(jobs), capture_output=True, text=True,
        env=env, timeout=timeout)
    if proc.returncode:
        raise RuntimeError(f"multichip run failed ({proc.returncode}):\n"
                           + proc.stderr[-4000:])
    return json.loads(proc.stdout.strip().splitlines()[-1])


def compare(root: str, cell_name: str, seed: int) -> dict:
    """The router's answers against the segment servers' under a
    ``QueryCoordinator``, on the cell's first batch."""
    import jax
    import numpy as np

    import data as bench_data
    import harness
    from repro.serving.coordinator import QueryCoordinator

    spec = harness.load_spec(root)
    cell, cfg, traffic = harness.find_cell(spec, root, cell_name)
    count = harness.segments(cfg, cell)
    x, pool = bench_data.cell_inputs(cfg, traffic, seed)
    run_rec = harness.Run(config=cfg)
    target, servers = harness.build_target(
        cfg, x, jax.devices()[:count], run_rec)
    queries, k = pool[:traffic["batch"]], traffic["k"]
    ri, rd, _ = QueryCoordinator([target]).search(queries, k)
    rounds, _ = harness.batch_counts(target)
    ci, cd, _ = QueryCoordinator(servers).search(queries, k)
    return {"ids_equal": bool(np.array_equal(ri, ci)),
            "dists_equal": bool(np.array_equal(rd, cd)),
            "rounds": rounds,
            "index_bytes": harness.index_bytes(servers),
            "one_segment_bytes": harness.index_bytes(servers[:1])}


def main() -> int:
    for p in (HERE, BENCH, SRC):
        if p not in sys.path:
            sys.path.insert(0, p)
    import pytest

    import faults
    import tiny

    root = sys.argv[1]
    out = []
    for job in json.load(sys.stdin):
        if "compare" in job:
            out.append(compare(root, job["compare"], job["seed"]))
            continue
        with pytest.MonkeyPatch.context() as mp:
            if job.get("fault"):
                faults.plant(job["fault"], mp.setattr)
            out.append(tiny.run(root, job["cell"], seed=job["seed"],
                                trace=job["trace"]))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
