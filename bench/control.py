"""The lower-precision control: the plain reference put in the
program's place, ranking in bfloat16 (the step below the f32 the
configurations state), checked by the same comparison as a run.

    python bench/control.py --workload <cell> --seeds 1 2 3

For each seed it makes the cell's data and the queries one pass of its
window serves, ranks them by the bf16 brute force, and prints the
checked numbers and ``correct`` as one JSON line. ``correct`` must come out false: the
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import data as bench_data  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402


def control_checks(root: str, cell: str, seed: int) -> dict:
    spec = harness.load_spec(root)
    _, cfg, traffic = harness.find_cell(spec, root, cell)
    x, queries = bench_data.cell_inputs(cfg, traffic, seed)
    n, k = len(queries), traffic["k"]
    ids, dists = reference.exact_knn(x, queries, k, precision="bf16")
    served = harness.Served(n, k)
    served.put(list(range(n)), ids, dists)
    checks = harness.check(x, queries, served, cfg)
    return {"seed": seed, "queries": n,
            "correct": all(c["pass"] for c in checks.values()),
            "checks": {name: c["value"] for name, c in checks.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 1
    for seed in args.seeds:
        print(json.dumps(control_checks(ROOT, args.workload, seed)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
