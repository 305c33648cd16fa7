"""Mesh-scale sharded serving: the shard_map fan-out router
(DESIGN.md §7).

``MeshQueryRouter`` turns a set of single-segment device servers into
ONE ``SegmentTarget``: a query batch fans out over mesh ranks (one
``DeviceSegment`` shard per rank on the ``model`` axis, the Fig. 1(b)
segments <-> ranks layout of ``core.device_search.make_search_step``),
each rank runs the batched block search on its shard, and per-shard
top-k merges **on device** via ``merge_shard_topk`` — the same
(dist, global id) total order the host ``merge_topk`` sorts by, so a
routed+merged batch is bit-identical to the concatenated single-target
path over the same segments.

Replica groups: with more ranks than segments, hot segments get extra
replicas (``distributed.elastic.plan_placement`` — load-proportional,
largest remainder, every segment >= 1 rank). Each replica group
partitions the query batch into contiguous slices sized inversely to
the windowed per-rank load (``rounds_active_weight`` occupancy fold),
so a lagging replica is handed fewer rows next batch. Every (query,
segment) pair is owned by exactly ONE rank — non-owned rows mask to
the -1/inf sentinels before the all-gather — which keeps accounting
exact and the merge bit-identical: a replica runs the identical
batched search its siblings run, so its owned rows equal the
single-target rows no matter how the slices are drawn.

Elastic rebalance: the router keeps a sliding window of per-rank
``IOStats`` folds (``IOStats.fold_rank_batches`` — THE shared fold
``mesh_qps_estimate`` and the ``RepackScheduler`` price). When the
windowed rank-load skew sustains past ``RouterParams.skew_threshold``,
``elastic.plan_rebalance`` re-plans placement and the router restacks
the shard tree — same shapes, so the step reuses the same compiled
executable (the mesh analogue of ``repack_tier0``'s in-place pack
swap). A settled or balanced stream plans zero moves (idempotence).

Observability: ``router.route`` spans per batch (with the step call's
jit-cache misses and XLA compiles in their ``traces`` and ``compiles``
args), ``coord.shard`` spans per rank (a per-rank row in the
profiler's trace), ``router.rebalance`` spans on firing evaluations,
and ``(name, target="rank<r>")`` metrics through ``repro.obs``. The
step's all-gathers run under the named scope ``shard_exchange`` and
its merge under ``shard_merge`` (``device_search.ROUTER_STAGES``).
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.iostats import IOStats, TPU_HBM_SEGMENT, CostModel
from repro.core.params import DeviceSearchParams, RouterParams
from repro.distributed import elastic
from repro.distributed.sharding import SEGMENT_SERVE_RULES, logical_spec


def _make_mesh(model: int):
    import jax
    return jax.make_mesh((1, model), ("data", "model"))


class MeshQueryRouter:
    """Fan a query batch over sharded ``DeviceSegment``s; one
    ``SegmentTarget`` whose id space is the union of its members'.

    ``servers``: single-segment device targets (``SegmentServer``-like:
    ``segment``/``offset``/``num_vectors``; ``host`` optional, needed
    only to repack). All member segments must be shape-identical
    (``stack_segments`` enforces it) and share search params + metric —
    one compiled step serves every placement. Member ``offset``s are
    GLOBAL bases; the router's own ``offset`` is 0 because its results
    already carry global ids (the coordinator's merge adds ``offset``,
    which must be a no-op here)."""

    def __init__(self, servers: Sequence, *, mesh=None,
                 params: RouterParams = RouterParams(),
                 cost_model: Optional[CostModel] = None,
                 tracer=None, metrics=None):
        import jax
        if not servers:
            raise ValueError("MeshQueryRouter needs at least one "
                             "segment server")
        self.servers = list(servers)
        p0 = self.servers[0].params
        m0 = getattr(self.servers[0], "metric", "l2")
        for s in self.servers[1:]:
            if s.params != p0 or getattr(s, "metric", "l2") != m0:
                raise ValueError(
                    "mesh members must share DeviceSearchParams and "
                    "metric — one compiled step serves every rank")
        self.params = params
        self.search_params: DeviceSearchParams = p0
        self.metric = m0
        self.k_default = getattr(self.servers[0], "k_default", 10)
        self.offset = 0
        self.num_vectors = sum(s.num_vectors for s in self.servers)
        if cost_model is None:
            from repro.obs.calibrate import load_calibrated
            cost_model = load_calibrated(TPU_HBM_SEGMENT)
        self.cost_model = cost_model
        self.tracer = tracer
        self.metrics = metrics

        self.mesh = mesh if mesh is not None else _make_mesh(
            jax.device_count())
        self.world = int(self.mesh.shape["model"])
        for ax, n in self.mesh.shape.items():
            if ax != "model" and n != 1:
                raise ValueError(
                    f"router meshes shard segments over 'model' only; "
                    f"axis {ax!r} has size {n} (want 1)")
        if self.world < len(self.servers):
            raise ValueError(
                f"{self.world} mesh ranks cannot hold "
                f"{len(self.servers)} segments at >= 1 replica each")

        # initial placement: uniform loads -> round-robin-ish replicas
        self._placement: List[int] = elastic.plan_placement(
            [1.0] * len(self.servers), self.world)
        self._restack()
        self._steps: Dict[int, object] = {}     # k -> compiled step
        # sliding window of (rank_loads [W], seg_loads [S], rank_queries
        # [W]) — the rebalance evidence and the replica-slice weights
        self._window = deque(maxlen=params.window_batches)
        self._since_eval = 0
        self.batches = 0
        self.rebalances = 0
        self.last_per_rank: Dict[int, IOStats] = {}
        self.last_stats: Optional[IOStats] = None
        self.last_plan: Optional[elastic.PlacementPlan] = None

    # ------------------------------------------------------------ stacking
    def _restack(self) -> None:
        """(Re)build the [W, ...] shard tree + per-rank offsets from the
        current placement, each rank's shard placed on its own device.
        Shapes never change across restacks, so the compiled step
        executable is reused."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.core.device_search import stack_segments
        self._seg_stack = stack_segments(
            [self.servers[si].segment for si in self._placement],
            NamedSharding(self.mesh, P("model")))
        self._offsets = np.asarray(
            [self.servers[si].offset for si in self._placement],
            np.int32)

    @property
    def placement(self) -> Tuple[int, ...]:
        return tuple(self._placement)

    def _seg_ranks(self) -> Dict[int, List[int]]:
        """segment index -> its replica ranks (ascending)."""
        out: Dict[int, List[int]] = {}
        for r, si in enumerate(self._placement):
            out.setdefault(si, []).append(r)
        return out

    # ------------------------------------------------------------- the step
    def _build_step(self, k: int):
        import jax
        import jax.numpy as jnp

        from repro.core.device_search import (device_anns,
                                              merge_shard_topk)

        mesh = self.mesh
        p = dataclasses.replace(
            self.search_params, k=k,
            candidates=max(self.search_params.candidates, k))
        metric = self.metric

        def local(seg, queries, meta):
            seg = jax.tree.map(lambda a: a[0], seg)  # strip shard dim
            meta = meta[0]                           # [3] this rank's
            #                                          (offset, lo, hi)
            r = device_anns(seg, queries, p, metric=metric)
            q = queries.shape[0]
            qidx = jnp.arange(q, dtype=jnp.int32)
            own = (qidx >= meta[1]) & (qidx < meta[2])   # [Q]
            # non-owned rows mask to the invalid sentinels BEFORE the
            # gather: every (query, segment) pair then reaches the
            # merge from exactly one rank — replica slices never
            # double-count and never change the merged result (each
            # replica ran the identical batch, so owned rows equal the
            # single-target rows)
            gid = jnp.where((r.ids >= 0) & own[:, None],
                            r.ids + meta[0], -1)
            gd = jnp.where(gid >= 0, r.dists, jnp.inf)
            with jax.named_scope("shard_exchange"):
                gids = jax.lax.all_gather(gid, "model")  # [W, Q, k]
                gds = jax.lax.all_gather(gd, "model")
            mi, md = merge_shard_topk(gids, gds, k)
            owni = own.astype(jnp.int32)
            col = jnp.ones((1, 1), jnp.int32)
            # per-rank device columns, masked to owned rows — the
            # fold_rank_batches inputs (rounds stays whole-batch: the
            # rank's loop really ran that many rounds)
            return (mi, md,
                    (r.io * owni)[:, None] * col,
                    (r.hops * owni)[:, None] * col,
                    (r.tier0_hits * owni)[:, None] * col,
                    (r.dedup_saved * owni)[:, None] * col,
                    (r.dedup_cross * owni)[:, None] * col,
                    (r.spec_hits * owni)[:, None] * col,
                    (r.spec_wasted * owni)[:, None] * col,
                    r.rounds[None])

        def leaf_spec(a):
            axes = ("segment",) + (None,) * (a.ndim - 1)
            return logical_spec((self.world,) + a.shape[1:], axes,
                                SEGMENT_SERVE_RULES, mesh)

        seg_specs = jax.tree.map(leaf_spec, self._seg_stack)
        from jax.sharding import PartitionSpec as P
        in_specs = (seg_specs, P(), P("model"))
        out_specs = (P(), P(), P(None, "model"), P(None, "model"),
                     P(None, "model"), P(None, "model"),
                     P(None, "model"), P(None, "model"),
                     P(None, "model"), P("model"))
        fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        return jax.jit(fn)

    def _get_step(self, k: int):
        if k not in self._steps:
            self._steps[k] = self._build_step(k)
        return self._steps[k]

    # ------------------------------------------------------------- routing
    def _rank_weights(self) -> np.ndarray:
        """Inverse windowed per-rank load — the slice weights. Uniform
        until the window has data."""
        w = np.ones(self.world)
        if self._window:
            load = np.zeros(self.world)
            for rank_loads, _, _ in self._window:
                load += rank_loads
            w = 1.0 / (1.0 + load)
        return w

    def _rank_meta(self, q: int) -> np.ndarray:
        """[W, 3] int32 (offset, q_lo, q_hi) per rank: each segment's
        replica group partitions [0, q) into contiguous slices sized by
        the inverse-load weights (largest remainder, rank order)."""
        meta = np.zeros((self.world, 3), np.int32)
        meta[:, 0] = self._offsets
        weights = self._rank_weights()
        for si, ranks in self._seg_ranks().items():
            w = weights[ranks]
            quota = w / w.sum() * q
            sizes = np.floor(quota).astype(np.int64)
            short = q - int(sizes.sum())
            order = sorted(range(len(ranks)),
                           key=lambda i: (-(quota[i] - sizes[i]), i))
            for i in order[:short]:
                sizes[i] += 1
            lo = 0
            for r, size in zip(ranks, sizes):
                meta[r, 1], meta[r, 2] = lo, lo + size
                lo += int(size)
            assert lo == q, (lo, q)
        return meta

    def route(self, queries: np.ndarray, k: Optional[int] = None
              ) -> Tuple[np.ndarray, np.ndarray, Dict]:
        """Serve one batch across the mesh. Returns global ``(ids
        [Q, k], dists [Q, k], stats)`` — stats carries the rank-keyed
        ``IOStats`` fold, their ``merge_ranks`` total, and (when due)
        the rebalance plan."""
        import jax.numpy as jnp
        k = k or self.k_default
        q = np.asarray(queries, np.float32)
        meta = self._rank_meta(q.shape[0])
        step = self._get_step(k)
        if self.tracer is not None:
            from repro.obs.compiles import compile_counts
            with self.tracer.span("router.route", cat="serve",
                                  track="router",
                                  n_queries=int(q.shape[0]), k=int(k),
                                  ranks=self.world) as sp:
                traces, compiles = compile_counts()
                out = step(self._seg_stack, jnp.asarray(q),
                           jnp.asarray(meta))
                traces_now, compiles_now = compile_counts()
                sp["traces"] = traces_now - traces
                sp["compiles"] = compiles_now - compiles
                ids, dists, stats = self._account(out, meta)
                sp["block_reads"] = stats["total_block_reads"]
                sp["rounds_max"] = stats["rounds_max"]
        else:
            out = step(self._seg_stack, jnp.asarray(q),
                       jnp.asarray(meta))
            ids, dists, stats = self._account(out, meta)
        plan = self.maybe_rebalance()
        if plan is not None:
            stats["rebalance"] = {
                "fired": plan.fired, "moves": len(plan.moves),
                "skew": plan.skew,
                "placement": list(plan.placement)}
        return ids, dists, stats

    def _account(self, out, meta) -> Tuple[np.ndarray, np.ndarray, Dict]:
        (ids, dists, io_c, hops_c, t0_c, sv_c, cx_c, sh_c, sw_c,
         rounds) = [np.asarray(x) for x in out]
        w = self.world
        # THE shared mesh fold (DESIGN.md §7): per-rank IOStats from
        # the masked device columns; totals are defined ONLY as the
        # merge of the per-rank folds (rounds_active_weight is not
        # additive across ranks with different round counts)
        pipelined = (self.search_params.pipeline_dma
                     and self.search_params.fetch_impl == "fused")
        speculative = self.search_params.speculate
        per_rank = IOStats.fold_rank_batches(
            {r: (io_c[:, r], t0_c[:, r], hops_c[:, r], sv_c[:, r],
                 int(rounds[r]), cx_c[:, r], pipelined,
                 sh_c[:, r], sw_c[:, r], speculative)
             for r in range(w)})
        total = IOStats.merge_ranks(per_rank)
        self.last_per_rank = per_rank
        self.last_stats = total
        self._last_cols = (io_c, t0_c, hops_c, sv_c, cx_c, sh_c, sw_c,
                           rounds)
        self.batches += 1
        self._since_eval += 1

        rank_loads = np.asarray(
            [per_rank[r].rounds_active_weight for r in range(w)])
        rank_queries = np.asarray(
            [int(meta[r, 2] - meta[r, 1]) for r in range(w)], float)
        seg_loads = np.zeros(len(self.servers))
        for r, si in enumerate(self._placement):
            seg_loads[si] += rank_loads[r]
        self._window.append((rank_loads, seg_loads, rank_queries))

        per_rank_us = {r: self.cost_model.latency_us(per_rank[r])
                       for r in range(w)}
        if self.tracer is not None or self.metrics is not None:
            for r in range(w):
                s = per_rank[r]
                if self.tracer is not None:
                    with self.tracer.span(
                            "coord.shard", cat="serve", track="router",
                            target=f"rank{r}",
                            segment=int(self._placement[r])) as sp:
                        sp["block_reads"] = s.block_reads
                        sp["rounds"] = s.batch_rounds
                        sp["occupancy"] = s.rounds_active_weight
                        sp["modeled_step_us"] = per_rank_us[r]
                if self.metrics is not None:
                    m = self.metrics
                    m.counter("router.block_reads", f"rank{r}").inc(
                        s.block_reads)
                    m.counter("router.tier0_hits", f"rank{r}").inc(
                        s.tier0_hits)
                    m.gauge("router.occupancy", f"rank{r}").set(
                        s.rounds_active_weight)
                    m.gauge("router.modeled_step_us", f"rank{r}").set(
                        per_rank_us[r])
            if self.metrics is not None:
                self.metrics.counter("router.batches").inc()

        stats = {
            "ranks": w,
            "segments": len(self.servers),
            "placement": list(self._placement),
            "per_rank": per_rank,
            "total": total,
            "total_block_reads": total.block_reads,
            "total_tier0_hits": total.tier0_hits,
            "total_dedup_saved": total.dedup_saved_fetches,
            "total_dedup_cross": total.dedup_cross_tile,
            "total_spec_hits": total.spec_hits,
            "total_spec_wasted": total.spec_wasted,
            "rounds_max": total.batch_rounds,
            "per_rank_modeled_us": per_rank_us,
            # the mesh step is gated by its slowest rank — exactly the
            # figure mesh_qps_estimate models from the same fold
            "modeled_step_us": max(per_rank_us.values()),
        }
        return ids, dists, stats

    # ----------------------------------------------------------- rebalance
    def window_rank_loads(self) -> np.ndarray:
        load = np.zeros(self.world)
        for rank_loads, _, _ in self._window:
            load += rank_loads
        return load

    def window_seg_loads(self) -> np.ndarray:
        load = np.zeros(len(self.servers))
        for _, seg_loads, _ in self._window:
            load += seg_loads
        return load

    def maybe_rebalance(self, force: bool = False
                        ) -> Optional[elastic.PlacementPlan]:
        """Evaluate placement once per ``rebalance_interval`` routed
        batches (or on ``force``), with at least ``min_window`` steps
        of evidence. Returns the plan (fired or not), or None when not
        yet due. A firing plan restacks the shard tree in place —
        same shapes, same compiled executable."""
        p = self.params
        if not force and (self._since_eval < p.rebalance_interval
                          or len(self._window) < p.min_window):
            return None
        self._since_eval = 0
        plan = elastic.plan_rebalance(
            self._placement, self.window_seg_loads().tolist(),
            self.window_rank_loads().tolist(),
            skew_threshold=p.skew_threshold)
        self.last_plan = plan
        if plan.fired:
            if self.tracer is not None:
                with self.tracer.span("router.rebalance", cat="serve",
                                      track="router",
                                      moves=len(plan.moves),
                                      skew=float(plan.skew)) as sp:
                    self._placement = list(plan.placement)
                    self._restack()
                    sp["placement"] = ",".join(
                        str(s) for s in plan.placement)
            else:
                self._placement = list(plan.placement)
                self._restack()
            self.rebalances += 1
            # moved segments invalidate the window's rank attribution
            self._window.clear()
            if self.metrics is not None:
                self.metrics.counter("router.rebalances").inc()
        return plan

    # ------------------------------------- SegmentTarget capability hooks
    def search(self, queries: np.ndarray, k: Optional[int] = None
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """``SegmentTarget`` surface: global ids (offset 0), merged
        dists, per-query cold block touches summed across ranks."""
        ids, dists, _ = self.route(queries, k)
        # per-query cold touches: the owned-row columns sum across
        # ranks to exactly one contribution per (query, segment)
        io = self._last_cols[0].sum(axis=1).astype(np.int64)
        return ids, dists, io

    def batch_stats(self) -> Dict[str, object]:
        """The last routed step's device columns summed across ranks,
        with the slowest rank's round count — the slowest-rank-gated
        view a mesh step presents to per-batch pricing consumers
        (``RepackScheduler.note_batch``). Exact per-rank folds live in
        ``last_per_rank``; totals in ``last_stats`` (their
        ``merge_ranks``)."""
        if self._last_cols is None:
            return {}
        (io_c, t0_c, hops_c, sv_c, cx_c, sh_c, sw_c,
         rounds) = self._last_cols
        return {"io": io_c.sum(axis=1), "tier0_hits": t0_c.sum(axis=1),
                "hops": hops_c.sum(axis=1),
                "dedup_saved": sv_c.sum(axis=1),
                "dedup_cross": cx_c.sum(axis=1),
                "spec_hits": sh_c.sum(axis=1),
                "spec_wasted": sw_c.sum(axis=1),
                # the mesh step searches the block graph only: no
                # member's hot tier runs behind the router
                "hot_tier_hits": np.zeros(io_c.shape[0], np.int64),
                "rounds": int(rounds.max()),
                "dma_pipelined": (self.search_params.pipeline_dma
                                  and self.search_params.fetch_impl
                                  == "fused"),
                "dma_speculative": self.search_params.speculate}

    _last_cols = None

    def lifetime_stats(self) -> Dict[str, float]:
        out: Dict[str, float] = {"batches": float(self.batches),
                                 "rebalances": float(self.rebalances)}
        for r, load in enumerate(self.window_rank_loads()):
            out[f"rank{r}_window_load"] = float(load)
        return out

    def repack_source(self):
        return None          # member packs are repacked via repack()

    def repack(self, observed, plan=None) -> int:
        """Repack every member's tier-0 pack from ``observed`` demand
        and restack the shard tree (same shapes, same executable).
        Members without a host ``Segment`` are skipped."""
        changed = 0
        for s in self.servers:
            if getattr(s, "host", None) is not None:
                changed += s.repack(observed, plan=plan)
        self._restack()
        return changed

    def demand_feed(self):
        return None

    def attach_obs(self, tracer, metrics) -> None:
        if tracer is not None and self.tracer is None:
            self.tracer = tracer
        if metrics is not None and self.metrics is None:
            self.metrics = metrics
