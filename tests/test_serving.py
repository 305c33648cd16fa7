"""Serving plane: multi-segment coordinator, merge, batcher (Fig. 1(b),
§6.7 scalability structure)."""
import numpy as np
import pytest

import dataclasses

from repro.core import device_search as DS
from repro.core import distances as D
from repro.core.segment import build_segment
from repro.core.search import recall_at_k
from repro.data.vectors import clustered_vectors, query_set
from repro.serving import QueryCoordinator, RequestBatcher, SegmentServer
from repro.serving.coordinator import SERVE_DEVICE_SEARCH, merge_topk
from tests.conftest import SMALL_SEGMENT


@pytest.fixture(scope="module")
def two_segments():
    xs = [clustered_vectors(1200, 32, num_clusters=12, seed=s)
          for s in (0, 1)]
    servers = []
    off = 0
    for si, x in enumerate(xs):
        seg = build_segment(x, SMALL_SEGMENT)
        # second segment carries a tier-0 hot-tile pack — results must
        # merge identically either way, the pack only moves touches off
        # the DMA counter
        servers.append(SegmentServer(
            segment=DS.from_segment(seg, tier0_frac=0.1 * si),
            offset=off, num_vectors=x.shape[0],
            params=dataclasses.replace(SERVE_DEVICE_SEARCH,
                                       candidates=48)))
        off += x.shape[0]
    return xs, servers


def test_merge_topk_correct():
    ids = [np.asarray([[0, 1]]), np.asarray([[0, -1]])]
    dd = [np.asarray([[0.5, 2.0]]), np.asarray([[1.0, np.inf]])]
    gi, gd = merge_topk(ids, dd, offsets=[0, 100], k=3)
    np.testing.assert_array_equal(gi[0], [0, 100, 1])
    np.testing.assert_allclose(gd[0], [0.5, 1.0, 2.0])


def test_merge_topk_duplicate_distance_tiebreak():
    """ISSUE 7 satellite regression: equal distances break ties by
    ascending GLOBAL id — the same (dist, id) total order the on-device
    ``merge_shard_topk`` sorts by, so host and mesh merges agree no
    matter which segment a duplicate lands in."""
    ids = [np.asarray([[3, 1]]), np.asarray([[2, 0]])]
    dd = [np.asarray([[1.0, 1.0]]), np.asarray([[1.0, 1.0]])]
    gi, gd = merge_topk(ids, dd, offsets=[0, 100], k=4)
    np.testing.assert_array_equal(gi[0], [1, 3, 100, 102])
    np.testing.assert_allclose(gd[0], [1.0, 1.0, 1.0, 1.0])
    # invalid slots (-1) always sort last, even against inf distances
    ids = [np.asarray([[5, -1]]), np.asarray([[7, -1]])]
    dd = [np.asarray([[2.0, np.inf]]), np.asarray([[2.0, np.inf]])]
    gi, gd = merge_topk(ids, dd, offsets=[0, 100], k=4)
    np.testing.assert_array_equal(gi[0], [5, 107, -1, -1])


def test_merge_topk_matches_device_merge_on_ties():
    """Host merge == device merge on the same duplicate-heavy inputs:
    both sort the shared (dist, global id) key, so the mesh router's
    on-device merge is bit-identical to the coordinator's."""
    import jax.numpy as jnp

    from repro.core.device_search import merge_shard_topk
    rng = np.random.default_rng(5)
    s, qn, kk, k = 3, 6, 8, 5
    ids = [rng.integers(0, 40, (qn, kk)) for _ in range(s)]
    # quantized dists force plenty of cross-segment ties
    dd = [rng.integers(0, 4, (qn, kk)).astype(np.float64)
          for _ in range(s)]
    for i, d in zip(ids, dd):                     # some invalid slots
        mask = rng.random((qn, kk)) < 0.2
        i[mask] = -1
        d[mask] = np.inf
    offsets = [0, 100, 200]
    hi, hd = merge_topk(ids, dd, offsets, k)
    gids = np.stack([np.where(i >= 0, i + off, -1)
                     for i, off in zip(ids, offsets)])
    di, dv = merge_shard_topk(jnp.asarray(gids),
                              jnp.asarray(np.stack(dd)), k)
    np.testing.assert_array_equal(hi, np.asarray(di))
    np.testing.assert_array_equal(hd, np.asarray(dv))


@pytest.mark.slow
def test_coordinator_recall_over_union(two_segments):
    xs, servers = two_segments
    union = np.concatenate(xs, axis=0)
    q = query_set(union, 16, seed=3)
    coord = QueryCoordinator(servers)
    gi, gd, stats = coord.search(q, k=10)
    truth = D.brute_force_knn(union, q, 10)
    assert recall_at_k(gi, truth) >= 0.75
    assert stats["segments_searched"] == 2
    assert stats["total_block_reads"] > 0
    # the tier-0-packed segment absorbed some touches into VMEM
    assert stats.get("total_tier0_hits", 0) > 0


@pytest.mark.slow
def test_server_k_above_beam_widens(two_segments):
    """A per-request k above the configured candidate beam widens Γ
    instead of tripping DeviceSearchParams validation."""
    xs, servers = two_segments
    q = query_set(xs[0], 4, seed=7)
    ids, dists, io = servers[0].search(q, k=96)
    assert ids.shape == (4, 96) and dists.shape == (4, 96)
    assert (io > 0).all()


@pytest.mark.slow
def test_server_spans_count_traces_and_compiles(two_segments,
                                                monkeypatch):
    """A traced server records ``server.dispatch`` and ``server.wait``
    per batch; the dispatch counts one trace and one compile on a new
    batch shape and none on the next call of that shape. Untraced, the
    server makes no profiler or monitoring call and returns the same
    answers and counters."""
    import jax

    from repro.obs import compiles, manual_tracer

    xs, servers = two_segments
    q = query_set(xs[0], 6, seed=5)          # a batch shape of its own
    tr = manual_tracer()
    traced = dataclasses.replace(servers[0], tracer=tr)
    traced.search(q, k=10)
    want = traced.search(q, k=10)
    assert [e.name for e in tr.events] == ["server.dispatch",
                                           "server.wait"] * 2
    first, _, again, _ = tr.events
    assert (first.args["traces"], first.args["compiles"]) == (1, 1)
    assert (again.args["traces"], again.args["compiles"]) == (0, 0)
    assert first.track == "seg0"

    def forbidden(*args, **kwargs):
        raise AssertionError("untraced search called the profiler")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", forbidden)
    monkeypatch.setattr(compiles, "compile_counts", forbidden)
    plain = dataclasses.replace(servers[0])
    assert plain.tracer is None
    got = plain.search(q, k=10)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    for key, value in traced.batch_stats().items():
        np.testing.assert_array_equal(plain.batch_stats()[key], value)


@pytest.mark.slow
def test_coordinator_attaches_its_tracer_to_servers(two_segments):
    from repro.obs import manual_tracer

    xs, servers = two_segments
    tr = manual_tracer()
    fresh = [dataclasses.replace(s) for s in servers]
    coord = QueryCoordinator(fresh, tracer=tr)
    coord.search(query_set(xs[0], 4, seed=4), k=5)
    assert all(s.tracer is tr for s in fresh)
    assert [e.name for e in tr.events].count("server.dispatch") == 2
    assert {e.track for e in tr.by_name("server.wait")} == {
        f"seg{s.offset}" for s in fresh}


@pytest.mark.slow
def test_coordinator_pruning_hook(two_segments):
    xs, servers = two_segments
    q = query_set(xs[0], 4, seed=4)
    coord = QueryCoordinator(servers, prune_fn=lambda queries: [0])
    _, _, stats = coord.search(q, k=5)
    assert stats["segments_searched"] == 1


def test_batcher_buckets():
    # tile=1 opts out of kernel-tile coercion: buckets used verbatim
    b = RequestBatcher(dim=8, buckets=(4, 16), tile=1)
    for _ in range(6):
        b.submit(np.zeros(8))
    q, ids, n = b.next_batch()
    assert n == 6 and q.shape == (16, 8) and len(ids) == 6
    q, ids, n = b.next_batch() if b.queue else (None, [], 0)
    assert n == 0


def test_batcher_buckets_align_to_kernel_tiles():
    """ISSUE 4 satellite: bucket sizes are coerced up to multiples of
    the fused round kernel's tile granularity, so a padded batch fills
    whole kernel tiles (and the coerced sizes dedupe)."""
    b = RequestBatcher(dim=4, buckets=(3, 5, 8, 30), tile=8)
    assert b.buckets == (8, 32)          # 3,5,8 -> 8 (deduped), 30 -> 32
    from repro.kernels import round_tile
    # every bucket is a whole number of kernel tiles
    assert all(x % round_tile(x) == 0 for x in b.buckets)
    with pytest.raises(ValueError):
        RequestBatcher(dim=4, buckets=(4,), tile=0)


@pytest.mark.slow
def test_ragged_batch_padding_is_result_invariant(two_segments):
    """ISSUE 4 satellite regression: a ragged final batch padded up to
    its bucket returns bit-identical per-request results to singleton
    searches — zero-padded rows converge on their own and (with the
    serving preset's compaction) drop out of the rounds; they never
    leak into real rows."""
    xs, servers = two_segments
    q5 = query_set(xs[0], 5, seed=11)         # ragged: 5 of bucket 8
    batcher = RequestBatcher(dim=q5.shape[1], buckets=(8, 32))
    for row in q5:
        batcher.submit(row)
    padded, ids, n = batcher.next_batch()
    assert padded.shape[0] == 8 and n == 5
    ib, db, _ = servers[0].search(padded, k=10)
    for row in range(n):
        i1, d1, _ = servers[0].search(q5[row: row + 1], k=10)
        np.testing.assert_array_equal(i1[0], ib[row])
        np.testing.assert_array_equal(d1[0], db[row])


def test_batcher_single_request_pads_to_smallest_bucket():
    b = RequestBatcher(dim=4, buckets=(8, 32))
    b.submit(np.ones(4))
    q, ids, n = b.next_batch()
    assert q.shape == (8, 4) and n == 1
    assert np.allclose(q[0], 1.0) and np.allclose(q[1:], 0.0)


def test_batcher_ready_waits_for_deadline():
    """A partial batch is NOT ready until max_wait polls elapse."""
    b = RequestBatcher(dim=4, buckets=(4, 8), max_wait=3)
    assert not b.ready()                  # empty queue: never ready
    b.submit(np.zeros(4))
    assert not b.ready() and not b.ready()
    assert b.ready()                      # deadline flush on 3rd poll
    _, _, n = b.next_batch()
    assert n == 1
    assert not b.ready()                  # wait counter reset


def test_batcher_ready_immediate_on_full_bucket():
    b = RequestBatcher(dim=4, buckets=(4, 8), max_wait=1000)
    for _ in range(8):
        b.submit(np.zeros(4))
    assert b.ready()                      # largest bucket full: no wait
