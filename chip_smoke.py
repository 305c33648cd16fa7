"""Bring-up check: the served segment search, end to end, on a TPU.

    python chip_smoke.py                 # one chip: build, serve, check
    python chip_smoke.py --four-chips    # the mesh router on four chips

One chip: builds one segment in the run from ``--seed`` (clustered
vectors, D=128, the ``SEGMENT_BIGANN_F32`` configuration: NSG graph
whose exact-kNN seed runs on the chip, BNF block layout, PQ, navigation
graph), packs it with ``from_segment``, and serves four batches of 256
queries through ``RequestBatcher`` -> ``QueryCoordinator`` ->
``SegmentServer``. It fails unless recall@10 against exact search
(brute force on the chip) reaches 0.90, the served ids agree with the
plain-XLA fetch stage (``fetch_impl="jnp"``) on one batch up to distance
ties, and the served step's lowered program holds the compiled Pallas
kernel (``tpu_custom_call``).

Four chips (``--four-chips``, only this phase): the same n vectors as
four segments of n/4, served by ``MeshQueryRouter`` on a 1x4 mesh and by
``QueryCoordinator`` over the same four servers on chip 0; the ids must
agree up to ties, and each chip must hold only its own shard.

Exits non-zero, printing no result line, when JAX finds no TPU. The last
line of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
DIM = 128
K = 10
BATCH = 256
RECALL_FLOOR = 0.90
TIE_RTOL = 1e-5
HOPS_PER_GAMMA = 4
# BIGANN-1M is the scale this configuration stands for; the default is
# cut to a quarter of it so that the in-run build, the served batches and
# the reference path finish well inside one chip call
N_DEFAULT = 250_000


def _say(key: str, value) -> None:
    print(f"{key}: {value}", flush=True)


def _use_compile_cache(jax) -> None:
    """Keep compiled programs where ``JAX_COMPILATION_CACHE_DIR`` says;
    without it, at a fixed path in the checkout (the path is part of the
    cache key, so it must not move)."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))


def _agree(ids_a, d_a, ids_b, d_b):
    """(ok, differing slots): ids equal slot by slot, except where the
    two distances at that slot tie within ``TIE_RTOL`` relative."""
    import numpy as np
    same = np.asarray(ids_a) == np.asarray(ids_b)
    tie = np.isclose(np.asarray(d_a), np.asarray(d_b), rtol=TIE_RTOL,
                     atol=0.0)
    return bool(np.all(same | tie)), int((~same).sum())


def _recall(ids, truth) -> float:
    import numpy as np
    hits = [len(set(a.tolist()) & set(b.tolist()))
            for a, b in zip(np.asarray(ids), np.asarray(truth))]
    return float(np.mean(hits)) / truth.shape[1]


def _build(x, params, tag: str):
    """Build + pack one segment; print its phase times and bytes."""
    import jax
    from repro.core.device_search import from_segment
    from repro.core.graph import build_graph
    from repro.core.segment import build_segment
    t = time.perf_counter()
    # the disk graph first, so that its time is on the output even when
    # a later phase does not finish
    graph = build_graph(x, params.graph, params.metric)
    _say(f"{tag}build_disk_graph_s", f"{time.perf_counter() - t:.3f}")
    seg = build_segment(x, params, graph=graph)
    for phase, s in seg.build_times.items():
        if phase != "disk_graph_s":
            _say(f"{tag}build_{phase}", f"{s:.3f}")
    _say(f"{tag}build_total_s", f"{time.perf_counter() - t:.3f}")
    t = time.perf_counter()
    ds = from_segment(seg)
    jax.block_until_ready(ds)
    _say(f"{tag}pack_s", f"{time.perf_counter() - t:.3f}")
    _say(f"{tag}device_segment_bytes",
         sum(a.nbytes for a in jax.tree.leaves(ds)))
    return seg, ds


def _batches(queries, tile: int):
    """Queries -> the padded batches ``RequestBatcher`` emits."""
    from repro.serving.batcher import RequestBatcher
    batcher = RequestBatcher(DIM, buckets=(BATCH,), tile=tile)
    for q in queries:
        batcher.submit(q)
    out = []
    while batcher.queue:
        qb, _, valid = batcher.next_batch()
        out.append((qb, valid))
    return out


def _serve(coord, batches, tag: str):
    """Serve every batch once after a warm-up call; per-batch seconds
    end when the results are on the host (which waits for the
    device)."""
    import numpy as np
    t = time.perf_counter()
    coord.search(batches[0][0], K)
    first = time.perf_counter() - t
    ids, dists, secs, reads = [], [], [], []
    for qb, valid in batches:
        t = time.perf_counter()
        gi, gd, stats = coord.search(qb, K)
        secs.append(time.perf_counter() - t)
        ids.append(gi[:valid])
        dists.append(gd[:valid])
        reads.append(stats["mean_block_reads_per_query"])
    for i, s in enumerate(secs):
        _say(f"{tag}batch{i}_s", f"{s:.6f}")
    _say(f"{tag}compile_s", f"{first - float(np.median(secs)):.3f}")
    _say(f"{tag}mean_block_reads_per_query", f"{np.mean(reads):.2f}")
    return np.concatenate(ids), np.concatenate(dists)


def _serve_params(gamma: int):
    """The serving knobs at candidate-set size Γ. A wider beam needs
    more rounds to converge: the round cap grows with Γ so that it
    stays a safety valve and never cuts a search short."""
    from repro.serving.coordinator import SERVE_DEVICE_SEARCH
    return dataclasses.replace(
        SERVE_DEVICE_SEARCH, candidates=gamma,
        max_hops=max(SERVE_DEVICE_SEARCH.max_hops, HOPS_PER_GAMMA * gamma))


def one_chip(args, jax) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.starling_segment import SEGMENT_BIGANN_F32
    from repro.core import distances as D
    from repro.core.device_search import device_anns
    from repro.data.vectors import clustered_vectors, query_set
    from repro.kernels.ops import round_tile
    from repro.serving.coordinator import QueryCoordinator, SegmentServer

    t = time.perf_counter()
    x = clustered_vectors(args.n, DIM, seed=args.seed)
    queries = query_set(x, args.batches * BATCH, seed=args.seed + 1)
    _say("data_s", f"{time.perf_counter() - t:.3f}")
    seg, ds = _build(x, SEGMENT_BIGANN_F32, "")

    t = time.perf_counter()
    truth = D.brute_force_knn(x, queries, K)
    _say("exact_knn_s", f"{time.perf_counter() - t:.3f}")

    batches = _batches(queries, round_tile(BATCH))
    params = _serve_params(args.gamma)
    server = SegmentServer(segment=ds, offset=0, num_vectors=args.n,
                           params=params, host=seg)
    ids, dists = _serve(QueryCoordinator([server]), batches, "")
    recall = _recall(ids, truth)
    _say("gamma", args.gamma)
    _say("last_batch_rounds", f"{server.last_rounds} of max_hops "
         f"{params.max_hops}")
    _say("recall_at_10", f"{recall:.4f}")

    # the served step's program: the kernel must be compiled into it
    p = dataclasses.replace(params, k=K)
    lowered = device_anns.lower(ds, jnp.asarray(batches[0][0]), p)
    has_kernel = "tpu_custom_call" in lowered.as_text()
    _say("served_step_tpu_custom_call", has_kernel)

    ref = SegmentServer(segment=ds, offset=0, num_vectors=args.n,
                        params=dataclasses.replace(params,
                                                   fetch_impl="jnp"))
    qb, valid = batches[0]
    ri, rd, _ = QueryCoordinator([ref]).search(qb, K)
    agree, differ = _agree(ids[:valid], dists[:valid], ri[:valid],
                           rd[:valid])
    _say("jnp_fetch_agrees", agree)
    _say("jnp_fetch_differing_slots_at_ties", differ)
    _say("peak_bytes_in_use",
         (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use"))

    failed = [name for name, ok in (
        ("recall", recall >= RECALL_FLOOR), ("jnp agreement", agree),
        ("tpu_custom_call", has_kernel)) if not ok]
    if failed:
        raise SystemExit(f"chip smoke failed: {', '.join(failed)}")


def four_chips(args, jax) -> None:
    import numpy as np

    from repro.configs.starling_segment import SEGMENT_BIGANN_F32
    from repro.data.vectors import clustered_vectors, query_set
    from repro.kernels.ops import round_tile
    from repro.serving import MeshQueryRouter
    from repro.serving.coordinator import QueryCoordinator, SegmentServer

    if len(jax.devices()) < 4:
        raise SystemExit(f"--four-chips needs 4 devices, found "
                         f"{len(jax.devices())}")
    n_seg = args.n // 4
    x = clustered_vectors(4 * n_seg, DIM, seed=args.seed)
    queries = query_set(x, args.batches * BATCH, seed=args.seed + 1)
    params = _serve_params(args.gamma)
    _say("gamma", params.candidates)
    servers = []
    for s in range(4):
        seg, ds = _build(x[s * n_seg:(s + 1) * n_seg], SEGMENT_BIGANN_F32,
                         f"seg{s}_")
        servers.append(SegmentServer(segment=ds, offset=s * n_seg,
                                     num_vectors=n_seg, params=params,
                                     host=seg))
    batches = _batches(queries, round_tile(BATCH))
    ci, cd = _serve(QueryCoordinator(servers), batches, "chip0_coord_")

    router = MeshQueryRouter(
        servers, mesh=jax.make_mesh((1, 4), ("data", "model")))
    per_dev = {}
    for leaf in jax.tree.leaves(router._seg_stack):
        for shard in leaf.addressable_shards:
            if shard.data.shape[0] != 1:
                raise SystemExit(f"device {shard.device} holds "
                                 f"{shard.data.shape[0]} shards")
            per_dev[shard.device.id] = (per_dev.get(shard.device.id, 0)
                                        + shard.data.nbytes)
    for dev, nbytes in sorted(per_dev.items()):
        _say(f"router_shard_bytes_device{dev}", nbytes)
    ri, rd = _serve(QueryCoordinator([router]), batches, "router_")
    for d in jax.devices():
        _say(f"bytes_in_use_device{d.id}",
             (d.memory_stats() or {}).get("bytes_in_use"))
    agree, differ = _agree(ri, rd, ci, cd)
    _say("router_agrees_with_coordinator", agree)
    _say("router_differing_slots_at_ties", differ)
    _say("router_queries", int(np.asarray(ri).shape[0]))
    if not agree or len(per_dev) != 4:
        raise SystemExit("chip smoke failed: router disagrees with the "
                         "coordinator or does not span four devices")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=N_DEFAULT,
                    help=f"vectors in the served data (default {N_DEFAULT})")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--gamma", type=int, default=1024,
                    help="candidate-set size Γ of the served search")
    ap.add_argument("--batches", type=int, default=4)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip router phase")
    args = ap.parse_args()

    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip smoke needs a TPU; JAX found {platform}",
              file=sys.stderr)
        return 1
    _use_compile_cache(jax)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    dev = jax.devices()[0]
    _say("platform", f"{dev.platform} {dev.device_kind} x"
         f"{len(jax.devices())}")
    _say("n", args.n)
    _say("dim", DIM)
    (four_chips if args.four_chips else one_chip)(args, jax)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
