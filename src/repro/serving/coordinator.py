"""Vector-DB serving plane (Fig. 1(b)): segment servers + coordinator.

A machine holds multiple independent segments (each with its own
Starling index); the coordinator scatters a query batch to the relevant
segments (all by default; a partition-pruning hook mirrors the
query-dispatch optimizations of Pyramid/LANNS), gathers per-segment
top-k and merges hierarchically — exactly the structure the on-mesh
``make_search_step`` reproduces with shard_map (segments <-> model
ranks, merge <-> all-gather).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.device_search import DeviceSegment, device_anns
from repro.core.iostats import IOStats
from repro.core.params import DeviceSearchParams, SearchParams
from repro.core.search import SegmentView, anns
from repro.io.async_fetch import AsyncFetchQueue
from repro.io.cached_store import CachedBlockStore
from repro.io.hottier import merge_hot_cold
from repro.serving import target as tgt

# serving default: the divergence-aware batched preset (wide fetch +
# cross-query dedup + active-query compaction) at the paper's Γ;
# tier-0 budget rides on the segment arrays themselves
# (``from_segment``), not on these search knobs
from repro.configs.starling_segment import DEVICE_SEARCH_BATCH

SERVE_DEVICE_SEARCH = dataclasses.replace(DEVICE_SEARCH_BATCH,
                                          candidates=64)


def merge_topk(ids: Sequence[np.ndarray], dists: Sequence[np.ndarray],
               offsets: Sequence[int], k: int
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Merge per-segment results into global top-k.

    ids[i]/dists[i]: [Q, k_i] from segment i; offsets[i]: id-space base
    of segment i. Invalid slots: id < 0 / dist inf.

    Ordering is (dist, global id) — ties broken by the smaller global
    id, with invalid slots keyed past every real id. This matches the
    device-side shard merge (``device_search.merge_shard_topk``)
    exactly, so a host-merged and a device-merged fan-out over the
    same shards return bit-identical ``(ids, dists)`` regardless of
    segment arrival order or placement."""
    gids = np.concatenate(
        [np.where(i >= 0, i + off, -1) for i, off in zip(ids, offsets)],
        axis=1).astype(np.int64)
    gd = np.concatenate(dists, axis=1)
    gd = np.where(gids >= 0, gd, np.inf)
    key_id = np.where(gids >= 0, gids, np.iinfo(np.int64).max)
    # lexsort: last key is primary -> sort by dist, break ties by id
    order = np.lexsort((key_id, gd), axis=1)[:, :k]
    return (np.take_along_axis(gids, order, axis=1),
            np.take_along_axis(gd, order, axis=1))


@dataclasses.dataclass
class SegmentServer:
    """One segment + its device arrays + search knobs.

    ``params`` bundles every online knob (``DeviceSearchParams``); a
    per-request ``k`` override replaces just that field. When the
    segment was packed with a tier-0 budget (``from_segment``), hot
    touches land in ``last_tier0_hits`` instead of the io column;
    cold touches that joined another query's same-round gather (the
    batched path's cross-query dedup) land in ``last_dedup_saved`` —
    actual DMAs for the batch = io - dedup_saved.

    ``host`` (optional) keeps the host ``Segment`` the device arrays
    were packed from; the serving ``RepackScheduler`` needs it to
    rebuild the tier-0 pack online (``repack``). Servers without it
    simply cannot be repack targets.

    ``hot_tier`` (optional, a ``repro.io.hottier.HotTier``) turns the
    server hybrid: queries route hot-first on the host, the device
    search is seeded from the exit frontier (``device_anns``'s
    ``seeds`` override), results merge by ``(dist, id)`` with
    ``tombstones`` masked from both sides, and the memory work lands
    in the ``hot_tier_hits`` batch column.

    ``tracer`` (optional, a ``repro.obs.trace.Tracer``; ``attach_obs``
    wires the coordinator's) records each batch's ``server.dispatch``
    span — the ``device_anns`` call: host enqueue, plus trace and
    compile on a jit-cache miss, counted in its ``traces`` and
    ``compiles`` args — and ``server.wait``, until the device has
    finished. Without one the search makes no profiler or monitoring
    call."""
    segment: DeviceSegment
    offset: int                   # base of this segment's id space
    num_vectors: int
    k_default: int = 10
    params: DeviceSearchParams = SERVE_DEVICE_SEARCH
    metric: str = "l2"
    host: Optional[object] = None  # the host Segment (repack source)
    hot_tier: Optional[object] = None   # repro.io.hottier.HotTier
    tombstones: Optional[np.ndarray] = None  # [num_vectors] bool
    tracer: Optional[object] = None  # repro.obs.trace.Tracer (optional)

    def search(self, queries: np.ndarray, k: Optional[int] = None
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        import jax.numpy as jnp
        k = k or self.k_default
        queries = np.ascontiguousarray(queries, np.float32)
        n_dead = (int(self.tombstones.sum())
                  if self.tombstones is not None else 0)
        route = None
        seeds = None
        k_cold = k
        candidates = max(self.params.candidates, k)
        if self.hot_tier is not None:
            route = self.hot_tier.route(queries, k)
            exits = route.exits.astype(np.int32)
            # union with the nav entries: the exits start the device
            # beam where memory converged, the nav entries keep basin
            # diversity; the hot tier absorbed the early exploration so
            # the cold beam narrows (cold_gamma_frac) at equal recall
            nav = self.host.view.nav if self.host is not None else None
            if nav is not None:
                nav_seeds = nav.entry_points(
                    queries, beam=self.params.nav_beam,
                    num=self.params.entry_points).astype(np.int32)
                exits = np.concatenate([exits, nav_seeds], axis=1)
            seeds = jnp.asarray(exits, jnp.int32)
            # over-fetch so the cold top-k survives the tombstone mask
            k_cold = k + min(n_dead, k)
            candidates = max(k_cold, int(round(
                self.params.candidates
                * self.hot_tier.params.cold_gamma_frac)))
        # a per-request k above the configured beam widens Γ with it
        # (DeviceSearchParams requires candidates >= k)
        p = dataclasses.replace(
            self.params, k=k_cold, candidates=max(candidates, k_cold))
        q = jnp.asarray(queries, jnp.float32)
        if self.tracer is not None:
            r = self._traced_dispatch(q, p, seeds)
        else:
            r = device_anns(self.segment, q, p, metric=self.metric,
                            seeds=seeds)
        self.last_io = np.asarray(r.io)
        self.last_tier0_hits = np.asarray(r.tier0_hits)
        self.last_hops = np.asarray(r.hops)
        self.last_dedup_saved = np.asarray(r.dedup_saved)
        self.last_dedup_cross = np.asarray(r.dedup_cross)
        self.last_spec_hits = np.asarray(r.spec_hits)
        self.last_spec_wasted = np.asarray(r.spec_wasted)
        self.last_rounds = int(r.rounds)
        # per-round trace buffer (params.trace_rounds; repro.obs) —
        # None when tracing is off
        self.last_round_log = (np.asarray(r.round_log)
                               if r.round_log is not None else None)
        cold_ids = np.asarray(r.ids)
        cold_dists = np.asarray(r.dists)
        if route is None:
            self.last_hot_tier_hits = np.zeros(queries.shape[0], np.int64)
            return cold_ids, cold_dists, np.asarray(r.io)
        self.last_hot_tier_hits = route.hot_hits.astype(np.int64)
        ci = cold_ids.astype(np.int64)
        cd = cold_dists.astype(np.float32)
        if self.tombstones is not None:
            dead = (ci >= 0) & self.tombstones[np.maximum(ci, 0)]
            ci = np.where(dead, -1, ci)
            cd = np.where(dead, np.inf, cd)
        out_i = np.full((queries.shape[0], k), -1, np.int64)
        out_d = np.full((queries.shape[0], k), np.inf, np.float32)
        for qi in range(queries.shape[0]):
            out_i[qi], out_d[qi] = merge_hot_cold(
                k, route.ids[qi], route.dists[qi], ci[qi], cd[qi])
        return out_i, out_d, np.asarray(r.io)

    def _traced_dispatch(self, queries, p, seeds):
        """``device_anns`` under ``server.dispatch``, then
        ``server.wait`` until its results are ready."""
        import jax
        from repro.obs.compiles import compile_counts
        track = f"seg{self.offset}"
        traces, compiles = compile_counts()
        with self.tracer.span("server.dispatch", track=track) as sp:
            r = device_anns(self.segment, queries, p, metric=self.metric,
                            seeds=seeds)
            traces_now, compiles_now = compile_counts()
            sp["traces"] = traces_now - traces
            sp["compiles"] = compiles_now - compiles
        with self.tracer.span("server.wait", track=track):
            jax.block_until_ready(r)
        return r

    def repack(self, observed, plan=None) -> int:
        """Swap the tier-0 pack for one re-ranked by ``observed``
        per-block demand counts (same budget, same compiled
        executable; results stay bit-identical — exact copies either
        way). ``plan`` short-circuits selection when the caller (the
        scheduler) already planned the pack to price its drift.
        Returns the number of pack slots that changed."""
        if self.host is None:
            raise ValueError("SegmentServer.host is unset — build the "
                             "server with its host Segment to repack")
        from repro.core.device_search import repack_tier0
        self.segment, changed = repack_tier0(self.segment, self.host,
                                             observed, plan=plan)
        return changed

    # ------------------------------------- SegmentTarget capability hooks
    def batch_stats(self) -> Dict[str, object]:
        """Device columns of the last served batch (the exact
        ``IOStats.from_device_batch`` inputs); {} before any batch."""
        if getattr(self, "last_tier0_hits", None) is None:
            return {}
        return {"io": self.last_io, "tier0_hits": self.last_tier0_hits,
                "hops": self.last_hops,
                "dedup_saved": self.last_dedup_saved,
                "dedup_cross": self.last_dedup_cross,
                "spec_hits": self.last_spec_hits,
                "spec_wasted": self.last_spec_wasted,
                "hot_tier_hits": self.last_hot_tier_hits,
                "rounds": self.last_rounds,
                "dma_pipelined": (self.params.pipeline_dma
                                  and self.params.fetch_impl == "fused"),
                "dma_speculative": self.params.speculate}

    def repack_source(self):
        return self.host

    def attach_obs(self, tracer, metrics) -> None:
        if tracer is not None and self.tracer is None:
            self.tracer = tracer
        if self.hot_tier is not None and \
                (tracer is not None or metrics is not None):
            self.hot_tier.attach_obs(tracer, metrics,
                                     target=f"seg{self.offset}")


@dataclasses.dataclass
class HostSegmentServer:
    """Host-path segment server with ONE block cache shared across all
    queries it serves (repro.io deployment, Fig. 1(b)).

    ``view.store`` should be a ``CachedBlockStore`` (build the segment
    with ``SegmentParams.cache`` enabled); because the store object is
    shared, residency survives between requests and the hit rate comes
    from inter-query locality on the entry neighborhood. With an
    uncached view this degrades gracefully to the seed behavior.
    """
    view: SegmentView
    params: SearchParams
    offset: int                   # base of this segment's id space
    num_vectors: int
    k_default: int = 10
    tracer: Optional[object] = None  # repro.obs.trace.Tracer (optional)

    @classmethod
    def from_segment(cls, seg, offset: int) -> "HostSegmentServer":
        return cls(view=seg.view, params=seg.params.search, offset=offset,
                   num_vectors=seg.num_vectors)

    def search(self, queries: np.ndarray, k: Optional[int] = None
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        if self.tracer is not None:
            with self.tracer.span("host.search", cat="serve",
                                  track=f"seg{self.offset}",
                                  n_queries=int(queries.shape[0]),
                                  k=int(k or self.k_default)) as sp:
                ids, dists, io = self._search(queries, k)
                sp["block_reads"] = int(io.sum())
            return ids, dists, io
        return self._search(queries, k)

    def _search(self, queries: np.ndarray, k: Optional[int]
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        ids, dists, stats = anns(self.view, queries,
                                 k or self.k_default, self.params)
        self.last_stats = stats
        io = np.asarray([s.block_reads for s in stats], np.int64)
        return ids, dists, io

    def cache_stats(self) -> Dict[str, float]:
        """Lifetime cache counters of the shared store (empty if
        uncached). When the store carries a metrics registry
        (``CachedBlockStore.attach_obs``), the same counters are
        republished through it first, so this dict is a view of what
        the registry reports."""
        store = self.view.store
        if not isinstance(store, CachedBlockStore):
            return {}
        store.publish_metrics()
        t = store.total
        return {"cache_hits": t.cache_hits,
                "tier2_hits": t.tier2_hits,
                "cache_misses": t.cache_misses,
                "io_round_trips": t.io_round_trips,
                "prefetched_blocks": t.prefetched_blocks,
                "queue_fetches": t.queue_fetches,
                "inflight_peak": t.inflight_peak,
                "inflight_joins": t.inflight_joins,
                "completion_reorders": t.completion_reorders,
                "hit_rate": t.cache_hit_rate}

    # ------------------------------------- SegmentTarget capability hooks
    def lifetime_stats(self) -> Dict[str, float]:
        return self.cache_stats()

    def demand_feed(self):
        store = self.view.store
        return store if isinstance(store, CachedBlockStore) else None

    def attach_obs(self, tracer, metrics) -> None:
        if tracer is not None and self.tracer is None:
            self.tracer = tracer
        store = self.view.store
        if isinstance(store, CachedBlockStore) and \
                (tracer is not None or metrics is not None):
            store.attach_obs(tracer, metrics, target=f"seg{self.offset}")


def attach_shared_fetch_queue(servers: Sequence["HostSegmentServer"],
                              depth: int = 8,
                              scheduler=None) -> AsyncFetchQueue:
    """Share ONE AsyncFetchQueue across every cache-fronted server view.

    This is the serving-plane half of the async subsystem: with a
    common queue, concurrent queries (and co-located segments backed by
    the same store) dedup in-flight fetches of the same block — a
    demand read arriving while the block is still in flight joins the
    existing ticket (``IOStats.inflight_joins``) instead of issuing a
    new round trip. Returns the queue so callers can inspect its
    lifetime counters (``submitted``/``delivered``/``reorders``/
    ``inflight_peak``).

    ``scheduler`` (a ``repro.serving.RepackScheduler``) additionally
    registers every attached store as a demand feed, so a shared-queue
    deployment's tier-0 repacks select from the *union* of what all
    its stores observed — the same cross-query scope the queue dedups
    fetches in.

    Discovery goes through the ``SegmentTarget`` protocol: any target
    whose ``demand_feed()`` yields a ``CachedBlockStore`` is attached,
    so routers and future remote proxies participate without this
    function knowing their concrete type."""
    q = AsyncFetchQueue(depth=depth)
    attached = 0
    for s in servers:
        store = tgt.demand_feed(s)
        if isinstance(store, CachedBlockStore):
            # drains any private queue first so its in-flight fetches
            # are delivered, not orphaned
            store.attach_queue(q)
            if scheduler is not None:
                scheduler.attach_feed(store)
            attached += 1
    if attached == 0:
        raise ValueError("no cache-fronted serving targets to attach "
                         "the shared fetch queue to")
    return q


class QueryCoordinator:
    """Scatter -> per-segment search -> hierarchical merge.

    ``scheduler`` (a ``repro.serving.RepackScheduler``) turns the
    coordinator into the adaptive serving plane's control point: any
    target whose ``repack_source()`` yields a host ``Segment``
    registers as a repack target, any whose ``demand_feed()`` yields a
    cached store as a demand feed, and after every served batch the
    coordinator notes the device columns and lets the scheduler
    evaluate — so tier-0 packs follow the query stream with no extra
    plumbing at call sites.

    The coordinator speaks ONLY the ``SegmentTarget`` protocol (via
    the ``serving.target`` adapters): host servers, device servers and
    the mesh ``MeshQueryRouter`` are interchangeable entries of
    ``servers``."""

    def __init__(self, servers: List[tgt.SegmentTarget],
                 prune_fn: Optional[Callable] = None,
                 scheduler=None, tracer=None, metrics=None):
        self.servers = servers
        self.prune_fn = prune_fn          # (queries) -> segment indices
        self.scheduler = scheduler
        self.tracer = tracer              # repro.obs: coord.batch /
        #                                   coord.segment spans
        self.metrics = metrics            # repro.obs.MetricsRegistry the
        #                                   stats dict is re-expressed
        #                                   through (same keys, same
        #                                   values — snapshot() is the
        #                                   dashboard view of it)
        self._cache_seen: Dict[int, Tuple[int, int]] = {}  # per-server
        #   (hits, misses) lifetime watermark for per-call delta reporting
        for s in servers:
            if scheduler is not None:
                if tgt.repack_source(s) is not None:
                    scheduler.attach_target(s)
                feed = tgt.demand_feed(s)
                if feed is not None:
                    scheduler.attach_feed(feed)
            # wire the target (its store, fetch queue, ranks, ...) into
            # the same observability plane the coordinator reports
            # through
            if tracer is not None or metrics is not None:
                tgt.attach_obs(s, tracer, metrics)
        if scheduler is not None and tracer is not None and \
                getattr(scheduler, "tracer", None) is None:
            scheduler.tracer = tracer

    # every search() stats dict carries ALL of these keys, zeros
    # included — downstream consumers (dashboards, the obs bench) must
    # never KeyError on a cold batch. "repack" additionally appears on
    # batches where the scheduler evaluated.
    STATS_SCHEMA = ("segments_searched", "total_block_reads",
                    "mean_block_reads_per_query", "total_tier0_hits",
                    "total_dedup_saved", "total_dedup_cross",
                    "total_spec_hits", "total_spec_wasted",
                    "total_hot_tier_hits", "deduped_block_reads",
                    "cache_hits", "cache_misses", "cache_hit_rate")

    def search(self, queries: np.ndarray, k: int = 10
               ) -> Tuple[np.ndarray, np.ndarray, Dict]:
        if self.tracer is not None:
            with self.tracer.span("coord.batch", cat="serve",
                                  track="coord",
                                  n_queries=int(queries.shape[0]),
                                  k=int(k)) as sp:
                gi, gd, stats = self._search(queries, k)
                sp["block_reads"] = stats["total_block_reads"]
                sp["segments"] = stats["segments_searched"]
            return gi, gd, stats
        return self._search(queries, k)

    def _search(self, queries: np.ndarray, k: int
                ) -> Tuple[np.ndarray, np.ndarray, Dict]:
        targets = (self.prune_fn(queries) if self.prune_fn
                   else list(range(len(self.servers))))
        ids, dists, offs = [], [], []
        total_io, total_t0, total_saved, total_cross = 0, 0, 0, 0
        total_spec_h, total_spec_w, total_hot = 0, 0, 0
        for si in targets:
            s = self.servers[si]
            if self.tracer is not None:
                with self.tracer.span("coord.segment", cat="serve",
                                      track="coord",
                                      target=f"seg{s.offset}") as sp:
                    i, d, io = s.search(queries, k)
                    sp["block_reads"] = int(io.sum())
            else:
                i, d, io = s.search(queries, k)
            ids.append(i)
            dists.append(d)
            offs.append(s.offset)
            seg_io = int(io.sum())
            total_io += seg_io
            bs = tgt.batch_stats(s)
            if bs:
                total_t0 += int(np.asarray(bs["tier0_hits"]).sum())
                total_saved += int(np.asarray(bs["dedup_saved"]).sum())
                total_cross += int(np.asarray(bs["dedup_cross"]).sum())
                total_spec_h += int(np.asarray(bs["spec_hits"]).sum())
                total_spec_w += int(np.asarray(bs["spec_wasted"]).sum())
                total_hot += int(np.asarray(bs["hot_tier_hits"]).sum())
            if self.metrics is not None:
                # per-target attribution: which segment the reads hit
                self.metrics.counter("serve.block_reads",
                                     f"seg{s.offset}").inc(seg_io)
        gi, gd = merge_topk(ids, dists, offs, k)
        stats = {"segments_searched": len(targets),
                 "total_block_reads": total_io,
                 "mean_block_reads_per_query":
                     total_io / max(queries.shape[0], 1),
                 # device tier-0: block touches the VMEM hot-tile pack
                 # absorbed (they are not in total_block_reads)
                 "total_tier0_hits": total_t0,
                 # cross-query dedup: cold touches that rode another
                 # query's same-round gather — deduped_block_reads is
                 # what the device actually issued
                 "total_dedup_saved": total_saved,
                 # the cross-tile subset of the joins — what batch-scope
                 # dedup saved beyond the old per-tile kernel's scope
                 "total_dedup_cross": total_cross,
                 # cross-round speculation (DESIGN.md §9): paying
                 # gathers the previous round pre-fetched, and
                 # speculative gathers nothing consumed — zeros
                 # whenever no target speculates
                 "total_spec_hits": total_spec_h,
                 "total_spec_wasted": total_spec_w,
                 # hybrid hot tier (DESIGN.md §10): vertex visits the
                 # in-memory answering graph absorbed before the block
                 # search even started — memory-priced, never I/O
                 "total_hot_tier_hits": total_hot,
                 "deduped_block_reads": total_io - total_saved}
        # repro.io: aggregate shared-cache counters from servers that
        # expose them, as deltas so every key in the dict is per-call
        # (the cache itself stays warm across calls — only the
        # reporting is scoped to this batch)
        hits = misses = 0
        for si in targets:
            cs = tgt.lifetime_stats(self.servers[si])
            before = self._cache_seen.get(si, (0, 0))
            # tier-2 summary hits count as hits: they avoid the disk trip
            now = (cs.get("cache_hits", 0) + cs.get("tier2_hits", 0),
                   cs.get("cache_misses", 0))
            self._cache_seen[si] = now
            hits += now[0] - before[0]
            misses += now[1] - before[1]
        stats["cache_hits"] = hits
        stats["cache_misses"] = misses
        stats["cache_hit_rate"] = (hits / (hits + misses)
                                   if hits or misses else 0.0)
        if self.metrics is not None:
            self._publish_metrics(queries.shape[0], stats)
        # adaptive serving plane: fold this batch's device columns into
        # the scheduler window and let it evaluate on its own cadence.
        # The repack (if any) lands AFTER this batch returned, so a
        # request never observes a pack swap mid-flight.
        if self.scheduler is not None:
            self.scheduler.note_batch([self.servers[si] for si in targets])
            decision = self.scheduler.maybe_repack()
            if decision is not None:
                stats["repack"] = {
                    "repacked": decision.repacked,
                    "changed_slots": decision.changed_slots,
                    "max_drift": decision.max_drift,
                    "tier0_hit_rate": decision.tier0_hit_rate,
                    "modeled_step_us": decision.modeled_step_us}
                if self.metrics is not None:
                    self.metrics.counter("sched.evals").inc()
                    self.metrics.counter("sched.repacks").inc(
                        decision.repacked)
        return gi, gd, stats

    def _publish_metrics(self, n_queries: int, stats: Dict) -> None:
        """Re-express the batch stats through the metrics registry —
        the same numbers the stats dict returns, under ``serve.*``
        names, so a dashboard scraping ``metrics.snapshot()`` and a
        caller reading the dict can never disagree."""
        m = self.metrics
        m.counter("serve.batches").inc()
        m.counter("serve.queries").inc(n_queries)
        m.counter("serve.total_block_reads").inc(
            stats["total_block_reads"])
        m.counter("serve.total_tier0_hits").inc(
            stats["total_tier0_hits"])
        m.counter("serve.total_dedup_saved").inc(
            stats["total_dedup_saved"])
        m.counter("serve.total_dedup_cross").inc(
            stats["total_dedup_cross"])
        m.counter("serve.total_spec_hits").inc(
            stats["total_spec_hits"])
        m.counter("serve.total_spec_wasted").inc(
            stats["total_spec_wasted"])
        m.counter("serve.total_hot_tier_hits").inc(
            stats["total_hot_tier_hits"])
        m.counter("serve.cache_hits").inc(stats["cache_hits"])
        m.counter("serve.cache_misses").inc(stats["cache_misses"])
        m.gauge("serve.cache_hit_rate").set(stats["cache_hit_rate"])
        m.histogram("serve.batch_block_reads").observe(
            stats["total_block_reads"])
        m.histogram("serve.batch_mean_reads_per_query").observe(
            stats["mean_block_reads_per_query"])
