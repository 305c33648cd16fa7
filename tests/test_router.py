"""Mesh router (DESIGN.md §7): shard_map fan-out over sharded
segments, replica-slice routing, per-rank accounting, elastic
rebalance.

The mesh-dependent tests run in the ``make test-mesh`` lane
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) and skip on
smaller worlds; the planning/validation tests, and those of the step on
one rank, run everywhere."""
import dataclasses
import re

import numpy as np
import pytest

import jax

from repro.core import device_search as DS
from repro.core.iostats import IOStats
from repro.core.params import RouterParams
from repro.core.segment import build_segment
from repro.data.vectors import clustered_vectors, query_set
from repro.serving import MeshQueryRouter, QueryCoordinator, SegmentServer
from repro.serving.coordinator import SERVE_DEVICE_SEARCH, merge_topk
from repro.serving.target import BATCH_STAT_KEYS, SegmentTarget, is_target
from tests.conftest import SMALL_SEGMENT

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(the make test-mesh lane)")

N_SEG = 4
N_PER_SEG = 600


@pytest.fixture(scope="module")
def mesh_servers():
    """Four shape-identical segments (stack_segments requires it) with
    global id bases, plus queries drawn over their union."""
    if jax.device_count() < 8:
        pytest.skip("mesh fixture needs 8 host devices")
    p = dataclasses.replace(SERVE_DEVICE_SEARCH, candidates=48,
                            fetch_impl="jnp")
    servers, xs, off = [], [], 0
    for s in range(N_SEG):
        x = clustered_vectors(N_PER_SEG, 32, num_clusters=8, seed=30 + s)
        seg = build_segment(x, SMALL_SEGMENT)
        servers.append(SegmentServer(
            segment=DS.from_segment(seg, tier0_frac=0.1),
            offset=off, num_vectors=x.shape[0], params=p, host=seg))
        xs.append(x)
        off += x.shape[0]
    q = query_set(np.concatenate(xs), 16, seed=7)
    return servers, q


@pytest.fixture()
def router(mesh_servers):
    servers, _ = mesh_servers
    return MeshQueryRouter(
        servers, params=RouterParams(window_batches=8,
                                     rebalance_interval=4, min_window=2,
                                     skew_threshold=1.2))


# ------------------------------------------------------ acceptance core

@needs_mesh
def test_route_bit_identical_to_single_target(router, mesh_servers):
    """THE mesh invariant: routed + device-merged (ids, dists) ==
    merge_topk over the per-segment single-target paths. Exact
    equality — both merges sort the same (dist, global id) key."""
    servers, q = mesh_servers
    assert len(servers) >= 4 and router.world >= 8
    ri, rd, stats = router.route(q, k=10)

    ids, dd, offs = [], [], []
    for s in servers:
        i, d, _ = s.search(q, 10)
        ids.append(i)
        dd.append(d)
        offs.append(s.offset)
    gi, gd = merge_topk(ids, dd, offs, 10)
    np.testing.assert_array_equal(ri, gi)
    np.testing.assert_array_equal(rd, gd)
    assert stats["segments"] == N_SEG and stats["ranks"] == 8


@needs_mesh
def test_per_rank_fold_is_exact(router, mesh_servers):
    """Per-rank IOStats fold to the router totals exactly:
    merge_ranks(per_rank) == stats['total'], and the additive counters
    sum across ranks (rounds_active_weight deliberately does not —
    totals are DEFINED as the merge, nothing else)."""
    _, q = mesh_servers
    _, _, stats = router.route(q, k=10)
    per_rank = stats["per_rank"]
    assert set(per_rank) == set(range(router.world))
    assert IOStats.merge_ranks(per_rank) == stats["total"]
    for field in ("cache_misses", "tier0_hits", "dedup_saved_fetches"):
        assert sum(getattr(r, field) for r in per_rank.values()) \
            == getattr(stats["total"], field)
    # slowest-rank gating: batch_rounds merges by max
    assert stats["rounds_max"] == max(
        r.batch_rounds for r in per_rank.values())
    assert stats["modeled_step_us"] == max(
        stats["per_rank_modeled_us"].values())
    assert stats["total_block_reads"] > 0


@needs_mesh
def test_replica_slices_partition_batch(router):
    """Every segment's replica group partitions [0, q) into disjoint
    contiguous slices — each (query, segment) pair owned exactly
    once."""
    for q in (1, 7, 16, 33):
        meta = router._rank_meta(q)
        for si, ranks in router._seg_ranks().items():
            lo = 0
            for r in ranks:
                assert meta[r, 1] == lo
                assert meta[r, 2] >= meta[r, 1]
                lo = int(meta[r, 2])
            assert lo == q


@needs_mesh
def test_routed_speculation_is_bit_identical(router, mesh_servers):
    """ISSUE 9 mesh acceptance: the cross-round speculative pipeline
    under shard_map fan-out changes nothing the user sees — routed
    (ids, dists) and every shared counter are bit-identical — while
    the spec counters flow through the per-rank fold, the batch_stats
    schema and the dma_speculative flag."""
    servers, q = mesh_servers
    spec_servers = [dataclasses.replace(
        s, params=dataclasses.replace(s.params, speculate=True))
        for s in servers]
    spec_router = MeshQueryRouter(spec_servers, params=router.params)
    ri, rd, stats = router.route(q, k=10)
    si, sd, sstats = spec_router.route(q, k=10)
    np.testing.assert_array_equal(ri, si)
    np.testing.assert_array_equal(rd, sd)
    for field in ("cache_misses", "tier0_hits", "hops",
                  "dedup_saved_fetches", "dedup_cross_tile"):
        assert getattr(stats["total"], field) \
            == getattr(sstats["total"], field), field
    assert stats["rounds_max"] == sstats["rounds_max"]
    # off-run counters are zero; on-run counters fold rank-additively
    assert stats["total_spec_hits"] == 0
    assert stats["total_spec_wasted"] == 0
    assert stats["total"].dma_speculative == 0
    assert sstats["total"].dma_speculative == 1
    assert sstats["total_spec_hits"] == sum(
        r.spec_hits for r in sstats["per_rank"].values())
    assert sstats["total_spec_hits"] > 0, \
        "this workload should speculate successfully"
    # the schema rides batch_stats: spec columns sum to the totals
    bs = spec_router.batch_stats()
    assert int(np.sum(bs["spec_hits"])) == sstats["total_spec_hits"]
    assert int(np.sum(bs["spec_wasted"])) == sstats["total_spec_wasted"]
    assert bs["dma_speculative"] is True
    assert spec_router.batch_stats() is not None


@needs_mesh
def test_router_is_segment_target(router, mesh_servers):
    """The router IS a SegmentTarget: protocol surface + batch_stats
    schema + per-query io that sums each (query, segment) once."""
    servers, q = mesh_servers
    assert isinstance(router, SegmentTarget) and is_target(router)
    assert router.offset == 0
    assert router.num_vectors == sum(s.num_vectors for s in servers)
    ids, dists, io = router.search(q, k=10)
    assert ids.shape == (q.shape[0], 10) and io.shape == (q.shape[0],)
    bs = router.batch_stats()
    assert set(BATCH_STAT_KEYS) <= set(bs)
    assert int(np.sum(bs["io"])) == router.last_stats.cache_misses
    np.testing.assert_array_equal(np.asarray(bs["io"], np.int64), io)


@needs_mesh
def test_router_through_coordinator(router, mesh_servers):
    """The coordinator speaks only the protocol, so a mesh router drops
    in as a single target — ids already global (offset 0)."""
    _, q = mesh_servers
    ri, rd, _ = router.route(q, k=10)
    coord = QueryCoordinator([router])
    ci, cd, stats = coord.search(q, k=10)
    np.testing.assert_array_equal(ci, ri)
    np.testing.assert_array_equal(cd, rd)
    assert stats["segments_searched"] == 1
    assert stats["total_block_reads"] == router.last_stats.cache_misses


# --------------------------------------------------------- rebalance

@needs_mesh
def test_rebalance_quiet_on_settled_stream(router, mesh_servers):
    """A settled stream (same batch over and over) must NOT fire: the
    rank loads stay proportional, the re-plan keeps the placement."""
    _, q = mesh_servers
    before = router.placement
    fired = []
    for _ in range(router.params.rebalance_interval * 2):
        _, _, stats = router.route(q, k=10)
        if "rebalance" in stats:
            fired.append(stats["rebalance"]["fired"])
    assert fired and not any(fired)
    assert router.placement == before and router.rebalances == 0


@needs_mesh
def test_rebalance_fires_on_skew_then_settles(router, mesh_servers):
    """A sustained skewed window fires a rebalance that grants the hot
    segment extra replicas; re-planning from the settled post-move
    loads is idempotent (zero moves)."""
    _, q = mesh_servers
    _, _, _ = router.route(q, k=10)      # populate shapes/window
    hot = 0
    w = router.world
    skewed_rank = np.asarray(
        [40.0 if router.placement[r] == hot else 1.0 for r in range(w)])
    seg = np.zeros(N_SEG)
    for r in range(w):
        seg[router.placement[r]] += skewed_rank[r]
    router._window.clear()
    for _ in range(router.params.min_window):
        router._window.append((skewed_rank, seg, np.ones(w)))
    plan = router.maybe_rebalance(force=True)
    assert plan is not None and plan.fired and len(plan.moves) > 0
    assert plan.skew >= router.params.skew_threshold
    counts = np.bincount(router.placement, minlength=N_SEG)
    assert counts[hot] > counts[1:].max()     # hot segment gained ranks
    assert counts.min() >= 1                  # every segment still held
    assert router.rebalances == 1
    assert len(router._window) == 0           # stale attribution dropped

    # idempotence: balanced per-rank loads under the new placement
    settled = np.ones(w)
    seg2 = np.bincount(router.placement, minlength=N_SEG).astype(float)
    for _ in range(router.params.min_window):
        router._window.append((settled, seg2, np.ones(w)))
    plan2 = router.maybe_rebalance(force=True)
    assert plan2 is not None and not plan2.fired


@needs_mesh
def test_rebalanced_placement_serves_identically(router, mesh_servers):
    """Placement changes must not change results: after a forced move
    the restacked tree serves the same (ids, dists) — same compiled
    step, different shard contents."""
    servers, q = mesh_servers
    ri, rd, _ = router.route(q, k=10)
    new = [0, 0, 0, 0, 1, 1, 2, 3][: router.world]
    router._placement = list(new)
    router._restack()
    ri2, rd2, _ = router.route(q, k=10)
    np.testing.assert_array_equal(ri2, ri)
    np.testing.assert_array_equal(rd2, rd)


# ---------------------------------------------- unguarded validation

def test_router_params_validation():
    with pytest.raises(ValueError):
        RouterParams(window_batches=0)
    with pytest.raises(ValueError):
        RouterParams(rebalance_interval=0)
    with pytest.raises(ValueError):
        RouterParams(min_window=32, window_batches=16)
    with pytest.raises(ValueError):
        RouterParams(skew_threshold=0.5)


class _Stub:
    def __init__(self, params, metric="l2", num_vectors=10, offset=0):
        self.params = params
        self.metric = metric
        self.num_vectors = num_vectors
        self.offset = offset


def test_router_rejects_mismatched_members():
    p = SERVE_DEVICE_SEARCH
    other = dataclasses.replace(p, candidates=p.candidates * 2)
    with pytest.raises(ValueError, match="share DeviceSearchParams"):
        MeshQueryRouter([_Stub(p), _Stub(other)])
    with pytest.raises(ValueError, match="share DeviceSearchParams"):
        MeshQueryRouter([_Stub(p, metric="l2"), _Stub(p, metric="mips")])
    with pytest.raises(ValueError, match="at least one"):
        MeshQueryRouter([])


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_router_rejects_undersized_world():
    p = SERVE_DEVICE_SEARCH
    with pytest.raises(ValueError, match="cannot hold"):
        MeshQueryRouter([_Stub(p), _Stub(p)],
                        mesh=_FakeMesh({"data": 1, "model": 1}))


def test_router_rejects_nonmodel_sharding():
    p = SERVE_DEVICE_SEARCH
    with pytest.raises(ValueError, match="'model' only"):
        MeshQueryRouter([_Stub(p)],
                        mesh=_FakeMesh({"data": 2, "model": 2}))


# ------------------------------------------- the step's stages, its compiles
#
# On one rank (a 1 x 1 mesh of this process's first device), so these
# run in every lane: the step's exchange and merge lower the same way
# whatever the mesh's size.

def _one_rank_mesh():
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))


@pytest.fixture(scope="module")
def one_rank():
    """One small segment's server and queries over it."""
    p = dataclasses.replace(SERVE_DEVICE_SEARCH, candidates=24,
                            fetch_impl="jnp")
    x = clustered_vectors(N_PER_SEG, 32, num_clusters=8, seed=30)
    server = SegmentServer(
        segment=DS.from_segment(build_segment(x, SMALL_SEGMENT)),
        offset=0, num_vectors=x.shape[0], params=p)
    return server, query_set(x, 8, seed=7)


def _op_names(compiled_text: str, op: str):
    """The ``op_name`` metadata of every ``op`` instruction."""
    return [re.search(r'op_name="([^"]*)"', line).group(1)
            for line in compiled_text.splitlines()
            if f" {op}(" in line and "op_name=" in line]


def _assert_stages_named(compiled_text: str) -> None:
    gathers = _op_names(compiled_text, "all-gather")
    assert gathers and all("/shard_exchange/" in n for n in gathers), \
        gathers
    assert any("/shard_merge/" in n
               for n in _op_names(compiled_text, "sort"))


def test_router_step_names_its_exchange_and_merge(one_rank):
    """The router's all-gathers carry ``shard_exchange`` and its merge
    ``shard_merge`` in their HLO ``op_name``: what the trace's op paths
    read per stage."""
    server, q = one_rank
    router = MeshQueryRouter([server], mesh=_one_rank_mesh())
    meta = router._rank_meta(q.shape[0])
    compiled = router._get_step(10).lower(
        router._seg_stack, jax.numpy.asarray(q),
        jax.numpy.asarray(meta)).compile()
    _assert_stages_named(compiled.as_text())


def test_search_step_names_its_exchange_and_merge():
    from repro.core.params import DeviceSearchParams
    from repro.distributed.sharding import SEGMENT_SERVE_RULES
    fn, args = DS.make_search_step(
        _one_rank_mesh(), SEGMENT_SERVE_RULES, n_local=1 << 10, dim=32,
        eps=4, lam=8, q_global=8, pq_m=4, nav_frac=16, nav_deg=4,
        search=DeviceSearchParams(candidates=16, max_hops=16,
                                  fetch_impl="jnp"))
    _assert_stages_named(jax.jit(fn).lower(*args).compile().as_text())


def test_route_span_counts_the_step_compiles(one_rank):
    """``router.route``'s ``traces`` counts the step's jit-cache misses:
    one or more on the first batch of a new k, none on the next."""
    from repro.obs.trace import Tracer
    server, q = one_rank
    tracer = Tracer()
    router = MeshQueryRouter([server], mesh=_one_rank_mesh(),
                             tracer=tracer)
    for k in (10, 10, 5, 5):
        router.route(q, k=k)
    spans = [e for e in tracer.events if e.name == "router.route"]
    traces = [e.args["traces"] for e in spans]
    assert traces[0] >= 1 and traces[1] == 0, traces
    assert traces[2] >= 1 and traces[3] == 0, traces
    assert all(e.args["compiles"] >= 0 for e in spans)
