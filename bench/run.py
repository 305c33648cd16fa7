"""The benchmark's one command.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips JAX finds: builds the
cell's data and index from ``--seed`` (set-up), measures ``--seconds``
of its traffic through the program's serving path, checks every answer
the window served against the plain reference, and prints one JSON
object as the last line of standard output. ``--trace 1`` runs the
window under the profiler and reports the cell's per-layer metrics in
place of its end-to-end ones. Exits non-zero, printing no result, when
JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import harness
    from peaks import peaks_for

    spec = harness.load_spec(ROOT)
    cell, _, _ = harness.find_cell(spec, ROOT, args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chips; JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    out = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                           bool(args.trace), T_PROCESS,
                           peaks=peaks_for(devices[0].device_kind))
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} {c['op']} {c['limit']!r} "
              f"{'ok' if c['pass'] else 'FAILED'}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
