"""Parameter dataclasses for the Starling segment index.

Notation follows the paper (§4.1):
  Λ (max_degree)   — max neighbor IDs stored per vertex
  λ                — actual neighbor count (stored inline, padded to Λ)
  γ (vertex_kb)    — KB per vertex on "disk" = D·dtype + 4 + Λ·4 bytes
  η (block_kb)     — block size in KB (smallest I/O unit)
  ε (verts_per_block) — ⌊η/γ⌋
  ρ (num_blocks)   — ⌈|V|/ε⌉
  σ (pruning_ratio)   — block-pruning ratio (§5.1), paper optimum 0.3
  μ (sample_ratio)    — navigation-graph sample ratio (§4.2)
  φ (rs_ratio)        — range-search doubling threshold (§5.3), paper 0.5
  Γ (candidate_size)  — search candidate-set size (App. M)
  β, τ             — shuffling iteration cap / OR-gain threshold (App. C)
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional


@dataclasses.dataclass(frozen=True)
class GraphParams:
    """Graph-index construction parameters (Vamana/NSG/HNSW-flavour)."""
    max_degree: int = 32          # Λ
    build_beam: int = 64          # L (candidate list during construction)
    alpha: float = 1.2            # Vamana robust-prune slack
    algo: str = "vamana"          # vamana | nsg | hnsw
    insert_batch: int = 256       # batched-insert chunk during build
    seed: int = 0

    def __post_init__(self):
        assert self.build_beam >= self.max_degree, "L must be >= Λ (App. L)"
        assert self.algo in ("vamana", "nsg", "hnsw")


@dataclasses.dataclass(frozen=True)
class LayoutParams:
    """Block-level layout parameters (§4.1)."""
    block_kb: float = 4.0         # η
    shuffle: str = "bnf"          # none | bnp | bnf | bns
    bnf_iters: int = 8            # β  (paper default 8, App. C)
    bns_iters: int = 2            # β for BNS (expensive; App. F)
    gain_tau: float = 0.01        # τ  (paper default 0.01, App. C)

    def verts_per_block(self, dim: int, max_degree: int,
                        dtype_bytes: int = 4) -> int:
        """ε = ⌊η/γ⌋ with γ = D·b + 4 (λ) + Λ·4 bytes (Example 2)."""
        gamma = dim * dtype_bytes + 4 + max_degree * 4
        eps = int(self.block_kb * 1024) // gamma
        if eps < 1:
            raise ValueError(
                f"vertex ({gamma}B) does not fit a {self.block_kb}KB block")
        return eps

    def num_blocks(self, n: int, dim: int, max_degree: int,
                   dtype_bytes: int = 4) -> int:
        eps = self.verts_per_block(dim, max_degree, dtype_bytes)
        return math.ceil(n / eps)


@dataclasses.dataclass(frozen=True)
class PQParams:
    """Product-quantization parameters for in-memory routing (§5.1)."""
    num_subspaces: int = 8        # M
    num_centroids: int = 256      # K (uint8 codes)
    train_iters: int = 12
    train_sample: int = 16384
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class NavGraphParams:
    """In-memory navigation graph (§4.2)."""
    sample_ratio: float = 0.1     # μ
    max_degree: int = 20          # Λ' (smaller than disk graph; Tab. 17)
    build_beam: int = 64
    search_beam: int = 16         # beam when finding entry points
    num_entry_points: int = 4     # entry points handed to the disk search
    seed: int = 1


@dataclasses.dataclass(frozen=True)
class HotTierParams:
    """The in-memory hot tier above the block hierarchy (DESIGN.md §10).

    A navigable graph over the hot-set *vectors* (selected by the
    shared ``repro.io.hotset`` ranking, same prior as tiers 0/1/2) that
    *answers* at memory latency; the cold block search is seeded from
    its exit frontier. Also the home of the mutable delta: inserts land
    in the hot tier's append region until ``compact()`` folds them into
    a fresh disk layout."""
    budget_frac: float = 0.10     # share of segment vectors resident hot
    max_degree: int = 16          # hot-graph degree (HNSW-style, small)
    build_beam: int = 48
    search_beam: int = 16         # beam for the hot route (to convergence)
    exit_width: int = 4           # exit-frontier seeds handed to cold search
    cold_gamma_frac: float = 0.85  # hybrid's cold Γ as a share of the
    #                                configured candidate size — the hot
    #                                tier absorbs the early exploration,
    #                                so the seeded block search runs a
    #                                narrower beam at equal recall
    append_slack: float = 0.5     # append-region capacity / built size
    hops: int = 1                 # BFS depth of the hot-set ranking
    seed: int = 1

    def __post_init__(self):
        if not 0.0 < self.budget_frac <= 1.0:
            raise ValueError("budget_frac must be in (0, 1]")
        if not 0.0 < self.cold_gamma_frac <= 1.0:
            raise ValueError("cold_gamma_frac must be in (0, 1]")
        if self.exit_width < 1:
            raise ValueError("exit_width must be >= 1")
        if self.append_slack < 0.0:
            raise ValueError("append_slack must be >= 0")
        if self.search_beam < self.exit_width:
            raise ValueError("search_beam must cover exit_width")


@dataclasses.dataclass(frozen=True)
class SearchParams:
    """Online search parameters (§5)."""
    candidate_size: int = 64      # Γ
    pruning_ratio: float = 0.3    # σ
    use_pq_routing: bool = True
    use_nav_graph: bool = True
    use_block_search: bool = True  # False → vertex-at-a-time (baseline strat)
    pipeline: bool = True          # I/O–compute overlap (modeled on CPU)
    rs_ratio: float = 0.5          # φ
    rs_max_rounds: int = 6         # cap on candidate-set doublings
    max_hops: int = 4096           # safety valve


@dataclasses.dataclass(frozen=True)
class CacheParams:
    """Block-cache + prefetch knobs (the repro.io subsystem).

    The cache budget is memory reserved for block residency and is
    charged as C_cache against the Eq. 10 segment memory budget. Either
    give an absolute ``budget_bytes`` or a ``budget_frac`` of the block
    file (``BlockStore.disk_bytes()``); both zero disables caching and
    the search path behaves exactly as the seed.

    ``tier2_frac`` carves a share of the budget into a second tier of
    compressed PQ-space block summaries at ``block_bytes //
    tier2_compression`` each (a tier-2 hit re-ranks without a disk
    trip); ``queue_depth`` > 0 switches the fetch path from
    synchronous-coalesced to the event-clock ``AsyncFetchQueue`` with
    that many fetches in flight.

    ``tier0_bytes`` / ``tier0_frac`` budget the *device* tier 0 — the
    VMEM-resident hot-tile pack of ``device_search.DeviceSegment``.
    Tier-0 bytes are separate from the host budget (they live on the
    accelerator, not in segment DRAM) but are charged into Eq. 10 all
    the same (``Segment.memory_bytes``) and capped by
    ``SegmentBudget.tier0_vmem_bytes``: reserved memory is reserved
    memory, whichever tier holds it.
    """
    budget_bytes: int = 0         # absolute cache budget
    budget_frac: float = 0.0      # fraction of disk_bytes (if bytes == 0)
    policy: str = "lru"           # lru | lfu
    pin_fraction: float = 0.25    # share of tier-1 capacity pinned to the
    #                               build-time entry-neighborhood hot set
    prefetch_width: int = 4       # speculative blocks per demand read:
    #                               coalesced into the round trip (sync)
    #                               or put in flight (async); 0 → none
    tier2_frac: float = 0.0       # share of the budget reserved for the
    #                               compressed summary tier (0 → 1 tier)
    tier2_compression: int = 16   # full-block bytes per summary byte
    queue_depth: int = 0          # max in-flight fetches on the async
    #                               queue (0 → synchronous fetch path)
    tier0_bytes: int = 0          # absolute device hot-tile (VMEM) budget
    tier0_frac: float = 0.0       # fraction of disk_bytes (if bytes == 0)

    def __post_init__(self):
        # ValueError (not assert) so invalid configs fail under -O too,
        # matching BlockCache's own validation
        if self.policy not in ("lru", "lfu"):
            raise ValueError(
                f"unknown eviction policy {self.policy!r} (lru | lfu)")
        if not (0.0 <= self.pin_fraction <= 1.0
                and 0.0 <= self.budget_frac <= 1.0
                and self.budget_bytes >= 0 and self.prefetch_width >= 0):
            raise ValueError(
                "CacheParams out of range: pin_fraction/budget_frac in "
                "[0, 1], budget_bytes/prefetch_width >= 0")
        if not (0.0 <= self.tier2_frac < 1.0):
            raise ValueError("tier2_frac must be in [0, 1): tier 1 "
                             "needs a non-empty share of the budget")
        if self.tier2_compression < 1 or self.queue_depth < 0:
            raise ValueError(
                "tier2_compression must be >= 1 and queue_depth >= 0")
        if not (0.0 <= self.tier0_frac <= 1.0) or self.tier0_bytes < 0:
            raise ValueError(
                "tier0_frac must be in [0, 1] and tier0_bytes >= 0")

    @property
    def enabled(self) -> bool:
        return self.budget_bytes > 0 or self.budget_frac > 0.0

    @property
    def tier0_enabled(self) -> bool:
        return self.tier0_bytes > 0 or self.tier0_frac > 0.0

    def resolve_budget(self, disk_bytes: int) -> int:
        if self.budget_bytes > 0:
            return self.budget_bytes
        return int(self.budget_frac * disk_bytes)

    def resolve_tier0_budget(self, disk_bytes: int) -> int:
        """Device hot-tile budget in bytes (Eq. 10's C_tier0 charge)."""
        if self.tier0_bytes > 0:
            return self.tier0_bytes
        return int(self.tier0_frac * disk_bytes)


@dataclasses.dataclass(frozen=True)
class RepackParams:
    """Knobs of the serving-plane tier-0 repack scheduler
    (``repro.serving.scheduler.RepackScheduler``, DESIGN.md §5).

    The scheduler folds the host stores' observed per-block demand
    (``CachedBlockStore.block_freq``) and the device search's tier-0 /
    dedup columns into a periodic repack decision for the VMEM hot-tile
    pack. ``hysteresis`` is the control-loop damper: a repack fires
    only when at least that fraction of the pack's slots would change,
    so a below-threshold drift costs nothing (the no-op invariant the
    property tests pin down) and the loop cannot oscillate between two
    near-equal packs.
    """
    interval_batches: int = 8     # evaluate every N served batches
    hysteresis: float = 0.25      # min fraction of pack slots that must
    #                               change for a repack to fire (0 =
    #                               repack on any drift)
    min_observed: int = 1         # ignore blocks with fewer demand reads
    #                               (noise floor of the drift signal)
    hit_rate_ceiling: float = 0.95  # skip repacks while the observed
    #                               tier-0 hit rate is already above
    #                               this (the pack absorbs the stream;
    #                               churn buys nothing)

    def __post_init__(self):
        if self.interval_batches < 1:
            raise ValueError("interval_batches must be >= 1")
        if not (0.0 <= self.hysteresis <= 1.0
                and 0.0 <= self.hit_rate_ceiling <= 1.0):
            raise ValueError(
                "hysteresis and hit_rate_ceiling must be in [0, 1]")
        if self.min_observed < 1:
            raise ValueError("min_observed must be >= 1")


@dataclasses.dataclass(frozen=True)
class RouterParams:
    """Knobs of the mesh serving router
    (``repro.serving.router.MeshQueryRouter``, DESIGN.md §7).

    The router keeps a sliding window of per-rank load folds (the
    ``rounds_active_weight`` occupancy of each rank's served step) and
    every ``rebalance_interval`` routed batches compares the windowed
    per-segment loads against the current placement. A rebalance fires
    only when the window holds at least ``min_window`` steps AND the
    rank-load skew (max/mean) reaches ``skew_threshold`` AND the
    re-planned placement actually moves a segment — so a settled,
    balanced stream never restacks (the idempotence invariant the mesh
    tests pin down), mirroring the ``RepackParams`` hysteresis for
    tier 0.
    """
    window_batches: int = 16      # per-rank load folds kept in the
    #                               sliding window (older steps age out)
    rebalance_interval: int = 8   # evaluate placement every N batches
    min_window: int = 4           # steps the window must hold before a
    #                               rebalance may fire (cold-start guard)
    skew_threshold: float = 1.5   # min max/mean windowed rank load for
    #                               a rebalance to fire (1.0 = any skew)

    def __post_init__(self):
        if self.window_batches < 1 or self.rebalance_interval < 1 \
                or self.min_window < 1:
            raise ValueError("window_batches, rebalance_interval and "
                             "min_window must be >= 1")
        if self.min_window > self.window_batches:
            raise ValueError("min_window cannot exceed window_batches")
        if self.skew_threshold < 1.0:
            raise ValueError("skew_threshold must be >= 1.0 "
                             "(max/mean load is never below 1)")


@dataclasses.dataclass(frozen=True)
class SegmentBudget:
    """Per-segment space budget (§2.2: ≤2 GB DRAM, ≤10 GB disk;
    DESIGN.md §3: plus a device VMEM cap for the tier-0 hot-tile pack —
    VMEM is ~16 MB/core and the search step needs most of it for
    working tiles, so tier 0 gets a small carve-out)."""
    memory_bytes: int = 2 << 30
    disk_bytes: int = 10 << 30
    tier0_vmem_bytes: int = 4 << 20


@dataclasses.dataclass(frozen=True)
class DeviceSearchParams:
    """Batched device-search knobs (``device_search.device_anns`` /
    ``make_search_step``) — the TPU analogue of ``SearchParams``.

    Frozen and hashable, so it rides through ``jax.jit`` as a static
    argument: one compiled executable per distinct parameter set.

    ``fetch_width`` (F) fetches the F best unvisited candidates' blocks
    per DMA round trip (beyond-paper: the Central Assumption prices a
    few random reads per round-trip like one). ``tier0_frac`` sizes the
    VMEM hot-tile pack for ``make_search_step``'s specs; segments built
    through ``from_segment`` take the (equivalent) budget from
    ``CacheParams`` so host and device agree. ``fetch_impl`` picks the
    fused Pallas round kernel (probe + deduped gather + rank) or the
    pure-jnp reference fetch stage — both bit-identical.

    ``compact_frac`` > 0 enables active-query compaction: when the live
    fraction of the batch drops below the threshold, the round repacks
    live queries to the front (a stable permutation, inverted on exit)
    so converged queries cluster into whole kernel tiles the fused
    round kernel skips. 0 disables compaction; results are identical
    either way — only which tile a query lands in (and thus the dedup
    grouping of its block requests) moves.
    """
    k: int = 10                   # results per query
    candidates: int = 64          # Γ (candidate-set size)
    sigma: float = 0.3            # σ (block-pruning ratio)
    max_hops: int = 128           # round-trip cap (safety valve)
    fetch_width: int = 1          # F: blocks fetched per round trip
    nav_beam: int = 8             # navigation-graph beam width
    nav_hops: int = 12            # navigation-graph beam iterations
    entry_points: int = 4         # entries handed to the block search
    tier0_frac: float = 0.0       # VMEM hot-tile share of the block file
    fetch_impl: str = "fused"     # fused (Pallas kernel) | jnp (reference)
    compact_frac: float = 0.0     # repack live queries to the front when
    #                               the active fraction falls below this
    #                               (0 = never compact)
    trace_rounds: bool = False    # carry the per-round trace buffer
    #                               (repro.obs.roundlog) through the loop
    #                               and return it on the result; (ids,
    #                               dists) and every counter are
    #                               bit-identical on or off
    pipeline_dma: bool = True     # double-buffer the fused kernel's
    #                               cold-block gather (make_async_copy
    #                               two-slot schedule); off, one block
    #                               copy is in flight at a time. The jnp
    #                               fetch stage ignores it. Payloads are
    #                               bit-identical on or off — only the
    #                               DMA schedule (and the cost model's
    #                               max(dma, compute) overlap pricing,
    #                               via IOStats.dma_pipelined) moves.
    round_tile_cap: int = 0       # cap on the round kernel's query-tile
    #                               size (0 = the kernel's BQ ceiling).
    #                               Dedup is batch-scope regardless; the
    #                               tile is the idle-skip/compaction
    #                               granularity and the intra- vs
    #                               cross-tile accounting boundary —
    #                               tests/benches shrink it to exercise
    #                               multi-tile batches cheaply.
    speculate: bool = False       # cross-round speculative pipeline
    #                               (DESIGN.md §9): predict round i+1's
    #                               cold-block union from round i's
    #                               ranked expansion candidates and
    #                               issue its gather while round i's
    #                               top-M maintenance runs. Never wrong,
    #                               only late — a mis-speculated block
    #                               is re-gathered by the authoritative
    #                               round fetch, so (ids, dists) and
    #                               every existing counter are
    #                               bit-identical on or off; only the
    #                               spec_hits/spec_wasted accounting
    #                               (and the CostModel's speculative
    #                               overlap pricing) move.

    def __post_init__(self):
        if self.k < 1 or self.candidates < self.k:
            raise ValueError("need candidates >= k >= 1")
        if not (0.0 <= self.sigma <= 1.0
                and 0.0 <= self.tier0_frac <= 1.0):
            raise ValueError("sigma and tier0_frac must be in [0, 1]")
        if self.fetch_width < 1 or self.max_hops < 1:
            raise ValueError("fetch_width and max_hops must be >= 1")
        if self.fetch_impl not in ("fused", "jnp"):
            raise ValueError(
                f"unknown fetch_impl {self.fetch_impl!r} (fused | jnp)")
        if not (0.0 <= self.compact_frac <= 1.0):
            raise ValueError("compact_frac must be in [0, 1]")
        if self.round_tile_cap < 0:
            raise ValueError("round_tile_cap must be >= 0 (0 = BQ)")


@dataclasses.dataclass(frozen=True)
class SegmentParams:
    graph: GraphParams = dataclasses.field(default_factory=GraphParams)
    layout: LayoutParams = dataclasses.field(default_factory=LayoutParams)
    pq: PQParams = dataclasses.field(default_factory=PQParams)
    nav: NavGraphParams = dataclasses.field(default_factory=NavGraphParams)
    search: SearchParams = dataclasses.field(default_factory=SearchParams)
    cache: CacheParams = dataclasses.field(default_factory=CacheParams)
    budget: SegmentBudget = dataclasses.field(default_factory=SegmentBudget)
    metric: str = "l2"            # l2 | ip

    def __post_init__(self):
        assert self.metric in ("l2", "ip")
