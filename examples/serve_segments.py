"""End-to-end serving driver (the paper's kind of workload): a machine
hosting multiple Starling segments behind a query coordinator + request
batcher, serving batched ANNS requests with the device-side (jit'd,
batched while_loop) search path.

  PYTHONPATH=src python examples/serve_segments.py
"""
import dataclasses
import sys
import time

sys.path.insert(0, "src")

import jax
import numpy as np

from repro.configs.starling_segment import SEGMENT_BENCH
from repro.core import device_search as DS
from repro.core import distances as D
from repro.core.search import recall_at_k
from repro.core.segment import build_segment
from repro.data.vectors import clustered_vectors, query_set
from repro.serving import QueryCoordinator, RequestBatcher, SegmentServer
from repro.serving.coordinator import SERVE_DEVICE_SEARCH


def main():
    print("== multi-segment serving demo ==")
    num_segments, n_per, dim = 3, 2000, 48
    servers, xs, off = [], [], 0
    for s in range(num_segments):
        x = clustered_vectors(n_per, dim, num_clusters=16, seed=s)
        print(f"building segment {s} ({n_per} vectors) ...")
        seg = build_segment(x, SEGMENT_BENCH)
        servers.append(SegmentServer(
            segment=DS.from_segment(seg), offset=off, num_vectors=n_per,
            params=dataclasses.replace(SERVE_DEVICE_SEARCH,
                                       candidates=48)))
        xs.append(x)
        off += n_per
    union = np.concatenate(xs, axis=0)
    coord = QueryCoordinator(servers)
    batcher = RequestBatcher(dim=dim, buckets=(8, 32))

    # clients submit single-query requests
    queries = query_set(union, 24, seed=9)
    rids = [batcher.submit(qq) for qq in queries]
    print(f"submitted {len(rids)} requests")

    results = {}
    t0 = time.perf_counter()
    while batcher.queue:
        qbatch, ids, n = batcher.next_batch()
        gi, gd, stats = coord.search(qbatch[:n], k=10)
        for i, rid in enumerate(ids):
            results[rid] = (gi[i], gd[i])
        print(f"  served batch of {n} "
              f"(segments={stats['segments_searched']}, "
              f"mean block reads/query="
              f"{stats['mean_block_reads_per_query']:.1f})")
    wall = time.perf_counter() - t0

    got = np.stack([results[r][0] for r in rids])
    truth = D.brute_force_knn(union, queries, 10)
    print(f"recall@10 over {num_segments} segments: "
          f"{recall_at_k(got, truth):.3f}")
    dev = jax.devices()[0]
    mode = "interpreted" if dev.platform == "cpu" else "compiled"
    print(f"wall ({dev.platform} {dev.device_kind}, {mode} kernels): "
          f"{wall:.2f}s")


if __name__ == "__main__":
    main()
