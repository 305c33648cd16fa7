"""Device-side batched Starling search (the TPU product of DESIGN.md §2).

The host implementation (``core/search.py``) is the per-query oracle; this
module is the batched, jit'd production path:

  * one ``lax.while_loop`` over hops for a whole query batch, carrying
    an explicit *active-query* view (``open_key``): converged queries
    stop contributing to the loop condition, request no blocks (their
    fetch slots carry the -1 sentinel the round kernel skips), and are
    excluded from every DMA/tier-0 counter;
  * each hop runs the fused *round stage* (``kernels.fused_round``):
    probe the tier-0 VMEM hot-tile pack first (a hit serves the block
    without the HBM->VMEM DMA that models one 4 KB disk read; counted
    in ``tier0_hits``), union the round's block requests across the
    query batch so each distinct cold block is gathered from HBM once
    and broadcast to all requesters (joins counted in ``dedup_saved``;
    the GoVector-style shared-I/O win, on device), exact-rank all
    resident vertices and order the sigma-pruned expansion targets —
    one kernel pass — then route new candidates by memory-resident
    PQ-ADC;
  * ``compact_frac`` > 0 adds divergence compaction: when the live
    fraction of the batch drops below the threshold, live queries are
    stably repacked to the front so converged queries cluster into
    whole kernel tiles the round kernel skips (the permutation is
    carried and inverted on exit — results are order-identical);
  * ``speculate`` (DESIGN.md §9) pipelines rounds: while round i's
    expansion/top-M maintenance still runs, the loop predicts round
    i+1's cold-block union from the candidates round i just PQ-routed
    and stages it in carried speculation state — the modeled
    speculative DMAs overlap round i's compute. The next round's
    authoritative fetch re-gathers anything mis-predicted (speculation
    is never wrong, only late), so (ids, dists) are bit-identical to
    speculation-off; consumed predictions land in ``spec_hits`` (DMAs
    off the critical path), dead ones in ``spec_wasted`` (bandwidth
    surcharge the cost model prices);
  * entry points come from an in-memory navigation-graph beam search;
  * per-query DMA / tier-0-hit / dedup-join / round-trip counters are
    carried exactly (the paper's "mean I/Os" splits across the
    hierarchy; actual DMAs issued = ``io - dedup_saved``).

Tier 0 (DESIGN.md §3): ``DeviceSegment`` carries a packed copy of the
hottest blocks — selected at build time from the same
``repro.io.hotset`` ranking that pins the host tier-1 cache — plus a
block->hot-slot index map. The pack holds exact copies, so tier-0
budget never changes (ids, dists); it only moves block touches from
the DMA counter to the tier-0 counter. Its bytes charge into the
Eq. 10 segment budget (``CacheParams.tier0_*``,
``SegmentBudget.tier0_vmem_bytes``).

Distribution (``make_search_step``): segment-parallel over the ``model``
mesh axis (each rank owns an independent sub-segment, Fig. 1(b)),
query-parallel over ``data`` (+ ``pod``); a top-k merge (all-gather +
sort over ``model``) combines per-segment results — the only collective
in the step.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.params import DeviceSearchParams

Tree = dict

# per-round trace-buffer columns (p.trace_rounds) — the device-side
# twin of repro.obs.roundlog.ROUND_LOG_COLS (kept import-free here so
# core never depends on the obs plane; equality is pinned by a test).
# ``joins`` is ALL dedup joins in the round (batch scope, the kernel's
# union pass); ``joins_x`` is the cross-tile subset of them.
# ``spec_hits``/``spec_wasted`` are the round's consumed speculation
# outcomes (DESIGN.md §9) — always present, zero when ``p.speculate``
# is off, so the fold schema never varies with the knob.
_ROUND_LOG_COLS = ("live", "cold", "tier0", "joins", "joins_x",
                   "compacted", "spec_hits", "spec_wasted")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DeviceSegment:
    """One segment shard, fully device-resident.

    ``hot_*`` is the tier-0 hot-tile pack: exact copies of the ``H``
    most-traversed blocks (``repro.io.hotset`` ranking), VMEM-resident
    in the TPU regime; ``hot_slot_of[b]`` maps block -> hot slot (-1 =
    cold). ``H >= 1`` always — a disabled tier 0 is one zeroed sentinel
    slot that ``hot_slot_of`` never points at."""
    vecs: jnp.ndarray          # [rho, eps, D]
    vid: jnp.ndarray           # [rho, eps] i32 (-1 pad)
    deg: jnp.ndarray           # [rho, eps] i32
    nbrs: jnp.ndarray          # [rho, eps, Lam] i32 (-1 pad)
    block_of: jnp.ndarray      # [N] i32
    pq_codes: jnp.ndarray      # [N, M] u8
    pq_cent: jnp.ndarray       # [M, K, dsub] f32
    nav_vecs: jnp.ndarray      # [n', D]
    nav_adj: jnp.ndarray       # [n', deg'] i32 (-1 pad)
    nav_ids: jnp.ndarray       # [n'] i32 global ids
    nav_entry: jnp.ndarray     # scalar i32 (nav-local)
    hot_vecs: jnp.ndarray      # [H, eps, D] tier-0 packed tiles
    hot_vid: jnp.ndarray       # [H, eps] i32
    hot_nbrs: jnp.ndarray      # [H, eps, Lam] i32
    hot_slot_of: jnp.ndarray   # [rho] i32 block -> hot slot (-1 = cold)


class DeviceSearchResult(NamedTuple):
    """Per-query outputs of ``device_anns``."""
    ids: jnp.ndarray           # [Q, k]
    dists: jnp.ndarray         # [Q, k]
    io: jnp.ndarray            # [Q] cold block touches (pre-dedup DMAs)
    hops: jnp.ndarray          # [Q] DMA round trips (fetch_width blocks each)
    tier0_hits: jnp.ndarray    # [Q] block touches served by the VMEM pack
    dedup_saved: jnp.ndarray   # [Q] cold touches that joined another
    #                            request's same-round gather — BATCH
    #                            scope, the union the kernel actually
    #                            dedups across (actual DMAs issued for
    #                            this query = io - dedup_saved)
    dedup_cross: jnp.ndarray   # [Q] the cross-tile subset of
    #                            ``dedup_saved``: joins onto a gather
    #                            first requested in ANOTHER round-kernel
    #                            query tile — what the batch-scope
    #                            rework (DESIGN.md §8) wins over
    #                            per-tile dedup (whose modeled DMAs =
    #                            io - (dedup_saved - dedup_cross))
    spec_hits: jnp.ndarray     # [Q] paying cold gathers (io -
    #                            dedup_saved) whose block the previous
    #                            round's speculative prediction already
    #                            put in flight (p.speculate, DESIGN.md
    #                            §9) — the DMA left the critical path;
    #                            zero when speculation is off
    spec_wasted: jnp.ndarray   # [Q] speculative gathers no request of
    #                            the next round consumed — extra DMA
    #                            bandwidth the cost model surcharges,
    #                            never a correctness event (the
    #                            authoritative round fetch re-gathers
    #                            misses: "never wrong, only late")
    rounds: jnp.ndarray        # scalar: loop rounds the batch ran
    #                            (hops / rounds = a query's occupancy)
    round_log: Optional[jnp.ndarray] = None
    #                            [max_hops, 8] i32 per-round trace buffer
    #                            (p.trace_rounds; repro.obs.roundlog —
    #                            cols live/cold/tier0/joins/joins_x/
    #                            compacted/spec_hits/spec_wasted; rows
    #                            >= ``rounds`` are unwritten). None when
    #                            tracing is off.


class DeviceRangeResult(NamedTuple):
    """Per-query outputs of ``device_range_search``."""
    ids: jnp.ndarray           # [Q, k_cap]
    dists: jnp.ndarray         # [Q, k_cap]
    in_range: jnp.ndarray      # [Q, k_cap] bool
    io: jnp.ndarray            # [Q] cold block touches across all rounds
    tier0_hits: jnp.ndarray    # [Q] tier-0 hits across all rounds
    dedup_saved: jnp.ndarray   # [Q] same-round dedup joins (batch
    #                            scope), all rounds
    dedup_cross: jnp.ndarray   # [Q] cross-tile subset of dedup_saved
    spec_hits: jnp.ndarray     # [Q] speculative pre-gathers consumed,
    #                            all RS rounds (the speculation state
    #                            drains at each RS re-entry)
    spec_wasted: jnp.ndarray   # [Q] speculative gathers never consumed
    rounds: jnp.ndarray        # scalar: total loop rounds, all RS rounds


def _tier0_pack(seg, num_blocks: int, observed=None, plan=None):
    """Select + pack the tier-0 hot set (host side, build time).

    ``observed`` (block id -> demand-read count, e.g. a serving
    ``CachedBlockStore.block_freq``) re-ranks the build-time selection
    by what the query stream actually touched — the dynamic-admission
    repack of a drifting workload. Selection goes through
    ``hotset.plan_tier0``, the same planner the serving scheduler
    prices drift with, so a plan and the pack it becomes can never
    diverge; a caller that already planned (the scheduler did, to gate
    on drift) passes ``plan`` and skips re-deriving the ranking."""
    from repro.io import hotset

    v = seg.view
    vecs = np.asarray(v.store.vecs)
    vid = np.asarray(v.store.vid)
    meta = np.asarray(v.store.meta)
    rho, eps = vid.shape
    hot: list = []
    if num_blocks > 0:
        if plan is not None:
            if len(plan) != min(num_blocks, rho):
                raise ValueError(
                    f"tier-0 plan selects {len(plan)} blocks for a "
                    f"{min(num_blocks, rho)}-slot budget")
            hot = [int(b) for b in plan]
        else:
            ranking = hotset.hot_block_ranking(
                v.layout.block_of, seg.graph.adj, seg.graph.deg,
                hotset.view_seed_ids(v))
            hot = hotset.plan_tier0(ranking, observed or {}, num_blocks,
                                    rho)
    slot_of = np.full(rho, -1, np.int32)
    if hot:
        hb = np.asarray(hot, np.int64)
        slot_of[hb] = np.arange(len(hot), dtype=np.int32)
        return (vecs[hb], vid[hb], meta[hb, :, 1:], slot_of)
    # sentinel pack: one zeroed slot the map never points at
    return (np.zeros((1,) + vecs.shape[1:], vecs.dtype),
            np.full((1, eps), -1, vid.dtype),
            np.full((1, eps, meta.shape[2] - 1), -1, meta.dtype),
            slot_of)


def from_segment(seg, tier0_blocks: Optional[int] = None,
                 tier0_frac: Optional[float] = None,
                 observed=None) -> DeviceSegment:
    """Host ``Segment`` -> device arrays.

    The tier-0 hot-tile budget comes from, in precedence order:
    ``tier0_blocks`` (explicit block count), ``tier0_frac`` (fraction
    of the block file), else ``seg.params.cache`` (the Eq. 10-charged
    configuration). Budget 0 packs the sentinel slot only — the search
    is then bit-identical to the seed's uncached device path *and* to
    any budgeted pack (the pack holds exact copies).

    ``observed`` re-ranks the pack from observed per-block demand
    frequencies (``hotset.repack_from_frequencies``) — dynamic tier-0
    admission for workloads that drifted away from the build-time
    entry-neighborhood prior. Results stay bit-identical for any pack
    (exact copies); only the io/tier0_hits split moves."""
    v = seg.view
    nav = v.nav
    if tier0_blocks is None:
        block_bytes = max(int(v.store.block_kb * 1024), 1)
        if tier0_frac is not None:
            tier0_blocks = int(tier0_frac * v.store.num_blocks)
        else:
            tier0_blocks = (seg.params.cache.resolve_tier0_budget(
                v.store.disk_bytes()) // block_bytes)
    hot_vecs, hot_vid, hot_nbrs, slot_of = _tier0_pack(
        seg, tier0_blocks, observed=observed)
    return DeviceSegment(
        vecs=jnp.asarray(v.store.vecs),
        vid=jnp.asarray(v.store.vid),
        deg=jnp.asarray(v.store.meta[:, :, 0]),
        nbrs=jnp.asarray(v.store.meta[:, :, 1:]),
        block_of=jnp.asarray(v.layout.block_of),
        pq_codes=jnp.asarray(v.pq_codes),
        pq_cent=jnp.asarray(v.pq_cb.centroids),
        nav_vecs=jnp.asarray(nav.vectors),
        nav_adj=jnp.asarray(nav.graph.adj),
        nav_ids=jnp.asarray(nav.sample_ids),
        nav_entry=jnp.asarray(nav.graph.entry, jnp.int32),
        hot_vecs=jnp.asarray(hot_vecs),
        hot_vid=jnp.asarray(hot_vid, jnp.int32),
        hot_nbrs=jnp.asarray(hot_nbrs, jnp.int32),
        hot_slot_of=jnp.asarray(slot_of, jnp.int32),
    )


def hot_pack_blocks(ds: DeviceSegment) -> set:
    """The block ids currently in the tier-0 pack (empty when tier 0 is
    disabled) — the one way every consumer (scheduler drift, repack,
    benches, tests) reads the pack, so the ``hot_slot_of`` sentinel
    encoding has a single point of truth."""
    return set(np.flatnonzero(np.asarray(ds.hot_slot_of) >= 0).tolist())


def repack_tier0(ds: DeviceSegment, seg, observed,
                 plan=None) -> Tuple[DeviceSegment, int]:
    """Rebuild ONLY the hot-tile pack of ``ds`` at its current budget,
    re-ranked by ``observed`` per-block demand counts (the serving
    scheduler's online repack, DESIGN.md §5). A caller that already
    ran ``hotset.plan_tier0`` (the scheduler, pricing drift) passes
    the ``plan`` to skip re-deriving the build ranking — an avoidable
    host-side BFS on the online path.

    Every other device array is reused as-is — a repack moves H block
    tiles, not the segment. Returns ``(new_ds, changed)`` where
    ``changed`` is the number of pack slots whose block differs from
    the old pack (the realized drift; 0 means the repack was a no-op
    and the returned segment holds the identical selection). The pack
    is exact copies either way, so results are bit-identical before
    and after — only the io/tier0_hits split moves."""
    old = hot_pack_blocks(ds)
    hot_vecs, hot_vid, hot_nbrs, slot_of = _tier0_pack(
        seg, len(old), observed=observed, plan=plan)
    new = set(np.flatnonzero(slot_of >= 0).tolist())
    out = dataclasses.replace(
        ds, hot_vecs=jnp.asarray(hot_vecs, ds.hot_vecs.dtype),
        hot_vid=jnp.asarray(hot_vid, jnp.int32),
        hot_nbrs=jnp.asarray(hot_nbrs, jnp.int32),
        hot_slot_of=jnp.asarray(slot_of, jnp.int32))
    return out, len(new - old)


def tier0_bytes(ds: DeviceSegment) -> int:
    """Bytes the hot-tile pack reserves on device (0 when disabled) —
    the C_tier0 the Eq. 10 accounting charges."""
    packed = int((np.asarray(ds.hot_slot_of) >= 0).sum())
    if packed == 0:
        return 0
    per_block = (ds.hot_vecs.nbytes + ds.hot_vid.nbytes
                 + ds.hot_nbrs.nbytes) // ds.hot_vecs.shape[0]
    return packed * int(per_block)


# ------------------------------------------------------------- utilities

def _dists(q: jnp.ndarray, x: jnp.ndarray, metric: str) -> jnp.ndarray:
    """q [Q, D] vs x [Q, E, D] -> [Q, E] (f32)."""
    q32, x32 = q.astype(jnp.float32), x.astype(jnp.float32)
    if metric == "ip":
        return -jnp.einsum("qd,qed->qe", q32, x32)
    return jnp.sum(jnp.square(x32 - q32[:, None, :]), axis=-1)


def _adc_lut(q: jnp.ndarray, cent: jnp.ndarray, metric: str) -> jnp.ndarray:
    """q [Q, D], cent [M, K, dsub] -> [Q, M, K]."""
    m, k, dsub = cent.shape
    qs = q.reshape(q.shape[0], m, 1, dsub).astype(jnp.float32)
    if metric == "ip":
        return -jnp.sum(cent[None] * qs, axis=-1)
    return jnp.sum(jnp.square(cent[None] - qs), axis=-1)


def _adc(lut: jnp.ndarray, codes: jnp.ndarray) -> jnp.ndarray:
    """lut [Q, M, K], codes [Q, I, M] -> [Q, I]."""
    idx = jnp.swapaxes(codes.astype(jnp.int32), 1, 2)      # [Q, M, I]
    got = jnp.take_along_axis(lut, idx, axis=2)            # [Q, M, I]
    return jnp.sum(got, axis=1)


def _merge_top(keys, ids, new_keys, new_ids, size: int, extra=None,
               new_extra=None):
    """Merge sorted-ish lists, dedupe by id, keep `size` smallest keys.

    keys/ids [Q, A], new_* [Q, B] -> [Q, size]. Invalid slots: id < 0,
    key = +inf. ``extra`` (optional int32 payload, e.g. visited flags)
    rides along."""
    k = jnp.concatenate([keys, new_keys], axis=1)
    i = jnp.concatenate([ids, new_ids], axis=1)
    e = (jnp.concatenate([extra, new_extra], axis=1)
         if extra is not None else None)
    # dedupe: sort by (id asc); duplicates adjacent; keep the first
    # occurrence with the *smallest key* -> sort by (id, key)
    order = jnp.lexsort((k, i))
    k = jnp.take_along_axis(k, order, axis=1)
    i = jnp.take_along_axis(i, order, axis=1)
    if e is not None:
        # keep the max extra among duplicates (visited wins): approximate
        # by taking the flag of the kept (first) occurrence after lexsort
        # with visited as secondary key desc would be ideal; visited
        # entries also carry +inf keys in our usage, so (id, key) order
        # already puts the live entry first.
        e = jnp.take_along_axis(e, order, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros((i.shape[0], 1), bool), i[:, 1:] == i[:, :-1]], axis=1)
    dup |= i < 0
    k = jnp.where(dup, jnp.inf, k)
    i = jnp.where(dup, -1, i)
    order2 = jnp.argsort(k, axis=1)[:, :size]
    k = jnp.take_along_axis(k, order2, axis=1)
    i = jnp.take_along_axis(i, order2, axis=1)
    if e is not None:
        e = jnp.where(dup, 0, e)
        e = jnp.take_along_axis(e, order2, axis=1)
        return k, i, e
    return k, i


def _bit_get(mask: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """mask [Q, NB] u32, ids [Q, I] (>=0) -> [Q, I] bool."""
    word = jnp.take_along_axis(mask, (ids >> 5).astype(jnp.int32), axis=1)
    return ((word >> (ids & 31).astype(jnp.uint32)) & 1).astype(bool)


def _bit_set(mask: jnp.ndarray, ids: jnp.ndarray,
             on: jnp.ndarray) -> jnp.ndarray:
    """Set bits for ids [Q] where on [Q] (ids >= 0)."""
    q = mask.shape[0]
    word_idx = (ids >> 5).astype(jnp.int32)
    bit = (jnp.uint32(1) << (ids & 31).astype(jnp.uint32))
    bit = jnp.where(on, bit, 0).astype(jnp.uint32)
    cur = mask[jnp.arange(q), word_idx]
    return mask.at[jnp.arange(q), word_idx].set(cur | bit)


# -------------------------------------------------- navigation graph beam

def nav_entry_points(ds: DeviceSegment, queries: jnp.ndarray,
                     beam: int = 8, hops: int = 12, num: int = 4,
                     metric: str = "l2") -> jnp.ndarray:
    """Batched beam search on the in-memory navigation graph.
    Returns [Q, num] *global* entry ids (no block I/O involved)."""
    qn = queries.shape[0]
    d0 = _dists(queries, ds.nav_vecs[ds.nav_entry][None, None, :].repeat(
        qn, axis=0), metric)[:, 0]
    ids = jnp.full((qn, beam), -1, jnp.int32).at[:, 0].set(ds.nav_entry)
    keys = jnp.full((qn, beam), jnp.inf).at[:, 0].set(d0)
    expanded = jnp.zeros((qn, beam), bool)

    def body(_, state):
        ids, keys, expanded = state
        open_key = jnp.where(expanded | (ids < 0), jnp.inf, keys)
        pick = jnp.argmin(open_key, axis=1)                  # [Q]
        has_open = jnp.isfinite(
            jnp.take_along_axis(open_key, pick[:, None], axis=1))[:, 0]
        u = jnp.take_along_axis(ids, pick[:, None], axis=1)[:, 0]
        u_safe = jnp.maximum(u, 0)
        expanded = expanded.at[jnp.arange(qn), pick].set(
            expanded[jnp.arange(qn), pick] | has_open)
        nb = ds.nav_adj[u_safe]                              # [Q, deg']
        valid = (nb >= 0) & has_open[:, None]
        nb_safe = jnp.maximum(nb, 0)
        nd = _dists(queries, ds.nav_vecs[nb_safe], metric)
        nd = jnp.where(valid, nd, jnp.inf)
        nb_m = jnp.where(valid, nb, -1)
        keys, ids, expanded = _merge_top(
            keys, ids, nd, nb_m, beam,
            extra=expanded.astype(jnp.int32),
            new_extra=jnp.zeros(nb.shape, jnp.int32))
        return ids, keys, expanded.astype(bool)

    ids, keys, _ = jax.lax.fori_loop(0, hops, body, (ids, keys, expanded))
    top = ids[:, :num]
    return ds.nav_ids[jnp.maximum(top, 0)] * (top >= 0) + (-1) * (top < 0)


# ------------------------------------------------------ main block search

def _round_stage(ds: DeviceSegment, queries: jnp.ndarray, u: jnp.ndarray,
                 metric: str, impl: str, n_expand: int, tile: int,
                 pipeline_dma: bool):
    """The fused per-round fetch pipeline (DR): tier-0 probe,
    batch-scope-deduped block gather, exact rank, and the per-query
    top-``n_expand`` expansion order — one pass.

    u [Q, F] picked candidate ids (-1 = converged/empty slot) ->
    (vid [Q, F*eps], nbrs [Q, F*eps, Lam], dists [Q, F*eps],
    hit [Q, F] i32, order [Q, n_expand]). ``impl='fused'`` runs the
    ``fused_round`` Pallas kernel (whole-batch deduped gather —
    double-buffered cold DMAs when ``pipeline_dma`` and compiled,
    idle-tile skip at the ``tile`` granularity); ``'jnp'`` is the
    pure-jnp reference with straight per-request gathers —
    bit-identical payloads (dedup only changes which gather produced a
    tile, never its value; same f32 distance form, same stable-argsort
    tie-breaking)."""
    from repro import kernels as K

    if impl == "fused":
        dd, vid, nbrs, hit, order = K.fused_round(
            queries, u, ds.block_of, ds.hot_slot_of, ds.hot_vecs,
            ds.hot_vid, ds.hot_nbrs, ds.vecs, ds.vid, ds.nbrs,
            n_expand, metric=metric, bq=tile,
            pipeline_dma=pipeline_dma)
    else:
        from repro.kernels import ref
        dd, vid, nbrs, hit, order = ref.fused_round_ref(
            queries, u, ds.block_of, ds.hot_slot_of, ds.hot_vecs,
            ds.hot_vid, ds.hot_nbrs, ds.vecs, ds.vid, ds.nbrs,
            n_expand, metric=metric)
    return vid, nbrs, dd, hit, order


def _open_keys(cand_id: jnp.ndarray, cand_key: jnp.ndarray,
               visited: jnp.ndarray) -> jnp.ndarray:
    """Candidate keys with visited/invalid entries masked to +inf — the
    carried what's-still-expandable view; a query is *active* iff any
    entry is finite. Carrying it means the loop ``cond`` reads it for
    free instead of re-gathering the visited bitmask every round."""
    vis = _bit_get(visited, jnp.maximum(cand_id, 0)) | (cand_id < 0)
    return jnp.where(vis, jnp.inf, cand_key)


def _dedup_joins(b: jnp.ndarray, cold: jnp.ndarray, tile: int):
    """Mark cold block requests that join an earlier request's gather.

    b, cold [Q, F] -> (joined, joined_x) [Q, F] bool. ``joined`` is
    True where this round already gathers the block for an earlier
    (flat-order) cold request ANYWHERE in the batch — the whole-batch
    union scope the fused kernel's pass 1 dedups across; the first
    requester pays the DMA (stays in ``io``), joiners land in
    ``dedup_saved``. ``joined_x`` is the cross-tile subset: joins whose
    paying requester sits in a DIFFERENT round-kernel query tile
    (``kernels.round_tile``) — what batch scope wins over the old
    per-tile dedup. Both masks come from the same sentinel-keyed flat
    array through the shared ``kernels.dedup.join_mask`` (one row per
    tile for the intra mask, one whole-batch row for the total), so
    joined_x = joined & ~intra and intra ⊆ joined by the stable flat
    order — the accounting can never disagree with the kernel's union
    pass, which uses the same module."""
    from repro.kernels import dedup

    qn, fw = b.shape
    pad = (-qn) % tile
    bp = jnp.pad(b, ((0, pad), (0, 0)))
    cp = jnp.pad(cold, ((0, pad), (0, 0)))
    t = bp.shape[0] // tile
    r = tile * fw
    # non-cold slots get globally unique negative sentinels so they
    # never form duplicate groups in either scope
    flat = jnp.where(cp.reshape(-1), bp.reshape(-1),
                     -1 - jnp.arange(t * r, dtype=jnp.int32))
    intra = dedup.join_mask(flat.reshape(t, r)).reshape(-1)
    batch = dedup.join_mask(flat.reshape(1, t * r)).reshape(-1)
    cross = batch & ~intra
    return (batch[: qn * fw].reshape(qn, fw),
            cross[: qn * fw].reshape(qn, fw))


def _block_search_loop(ds: DeviceSegment, queries: jnp.ndarray, lut,
                       state, *, res_size: int, candidates: int,
                       sigma: float, max_hops: int, metric: str,
                       fetch_width: int, fetch_impl: str,
                       compact_frac: float = 0.0, trace: bool = False,
                       pipeline_dma: bool = False,
                       round_tile_cap: int = 0,
                       speculate: bool = False):
    """The batched best-first block search from a given carried state.

    ``state`` = (cand_id, cand_key, open_key, visited, res_id, res_key,
    io, t0, hops, saved, saved_x, t); the range-search driver re-enters with the
    previous round's ``visited``/result arrays so already-expanded
    vertices are never re-fetched (PR 2's host RS resume fix, device
    formulation). ``open_key`` (``_open_keys``) is the carried active
    view: the loop condition and the pick stage read it directly
    instead of re-probing the visited bitmask every round.

    ``compact_frac`` > 0 (jit-static) turns on divergence compaction:
    rounds whose live fraction fell below the threshold stably repack
    live queries to the front — converged queries then fill whole
    round-kernel tiles, which the fused kernel skips. The permuted
    ``queries``/``lut`` rows are *carried* in the loop state and every
    permutation gather lives behind a ``lax.cond`` on the compaction
    trigger, so a round with no repack does zero extra gathers (idle
    rounds are free — ROADMAP (a)); only the round that actually
    compacts pays the sort + re-gather. The permutation is inverted
    before returning, so callers see original query order either
    way.

    ``trace`` (jit-static) carries a ``[max_hops, 8] i32`` per-round
    buffer (``repro.obs.roundlog`` columns: live, cold, tier0, joins,
    joins_x, compacted, spec_hits, spec_wasted) written once per round
    from the same masks the counters
    sum — a lossless refinement, so the log's column sums equal the
    counter totals by construction. The buffer's round axis is never
    permuted by compaction (its rows are batch-level sums, which are
    permutation-invariant). Returns ``(state, round_log)``; the log is
    ``None`` when tracing is off, and the counters/results are
    bit-identical either way (the trace writes are pure additions to
    the dataflow).

    ``speculate`` (jit-static, DESIGN.md §9) carries two-slot
    speculation state in the loop — per-query hit/wasted counters plus
    the ``[Q, F]`` block prediction staged by the previous round. Each
    round first *consumes* the staged prediction against its
    authoritative requests (a paying cold gather whose block was
    predicted is a ``spec_hit``: its DMA was already in flight during
    the previous round's expansion/top-M maintenance; a predicted
    block no cold request of the query consumes is ``spec_wasted``),
    then *stages* the next round's prediction from the neighbors it
    just PQ-routed — before the merged candidate pool resolves, which
    is exactly why the prediction is fallible and why it overlaps the
    maintenance stage. Every speculation branch is pure accounting
    over the same masks the counters already sum: the authoritative
    fetch is untouched, so (ids, dists) and every other counter are
    bit-identical to ``speculate=False``, and the loop jaxpr without
    the knob is unchanged. The final round's staged prediction is
    dropped unconsumed (modeled as issued at the consume boundary —
    a search that ends never issues it, so it is not wasted DMA)."""
    qn = queries.shape[0]
    eps = ds.vid.shape[1]
    fw = max(fetch_width, 1)
    n_expand = fw * (1 + max(int(np.ceil((eps - 1) * sigma)), 0))
    from repro import kernels as K
    tile = K.round_tile(qn, round_tile_cap)
    compact = compact_frac > 0.0

    def cond(st):
        open_key, t = st[2], st[-1]
        return jnp.isfinite(open_key).any() & (t < max_hops)

    def body(st):
        (cand_id, cand_key, open_key, visited, res_id, res_key,
         io, t0, hops, saved, saved_x) = st[:11]
        pos = 11
        if speculate:
            spec_h, spec_w, spec_blk = st[11:14]
            pos = 14
        if compact:
            perm, q_r, lut_r = st[pos:pos + 3]
            pos += 3
        if trace:
            rlog = st[pos]
            pos += 1
        t = st[-1]

        # --- active mask + optional live-query compaction
        live = jnp.isfinite(open_key).any(axis=1)            # [Q]
        fired = jnp.asarray(False)
        if compact:
            frac = live.astype(jnp.float32).mean()
            # repack only when the live rows are no longer front-packed
            # (a dead row sits before a live one): once compacted, the
            # carried order STAYS compacted until another query
            # converges mid-front, so the sort + permutation gathers
            # run only on rounds that actually change the packing —
            # every other round takes the identity branch for free
            unpacked = (jnp.any(jnp.logical_not(live[:-1]) & live[1:])
                        if qn > 1 else jnp.asarray(False))
            fired = (frac < compact_frac) & unpacked
            # every carried array is per-query along axis 0 — the
            # speculation trio (when on) rides the same permutation,
            # so a staged prediction follows its query through a repack
            carried = (cand_id, cand_key, open_key, visited, res_id,
                       res_key, io, t0, hops, saved, saved_x) \
                + ((spec_h, spec_w, spec_blk) if speculate else ()) \
                + (perm, q_r, lut_r)

            def _repack(arrs):
                # stable: live first, original order within each group;
                # the carried q_r/lut_r rows ride the same permutation,
                # so no later round ever re-gathers queries[perm]
                ordr = jnp.argsort(jnp.logical_not(live))
                return tuple(jnp.take(a, ordr, axis=0) for a in arrs)

            carried = jax.lax.cond(fired, _repack,
                                   lambda arrs: arrs, carried)
            (cand_id, cand_key, open_key, visited, res_id, res_key,
             io, t0, hops, saved, saved_x) = carried[:11]
            if speculate:
                spec_h, spec_w, spec_blk = carried[11:14]
            perm, q_r, lut_r = carried[-3:]
        else:
            q_r, lut_r = queries, lut

        # --- pick the F best open candidates per query (converged
        # queries pick nothing: every slot carries the -1 sentinel)
        neg_top, picks = jax.lax.top_k(-open_key, fw)        # [Q, F]
        f_active = jnp.isfinite(-neg_top)                    # [Q, F]
        active = f_active[:, 0]
        u = jnp.take_along_axis(cand_id, picks, axis=1)      # [Q, F]
        u = jnp.where(f_active, u, -1)
        b = ds.block_of[jnp.maximum(u, 0)]                   # [Q, F]

        # --- DR round stage: probe tier 0, dedup + gather the round's
        # block union, rank, and order expansions — one fused pass
        vid, nbrs, dd, hit, order = _round_stage(
            ds, q_r, u, metric, fetch_impl, n_expand, tile,
            pipeline_dma)
        hot = hit.astype(bool) & f_active
        cold = f_active & ~hot
        joined, joined_x = _dedup_joins(b, cold, tile)       # [Q, F]
        io = io + cold.sum(axis=1).astype(jnp.int32)
        t0 = t0 + hot.sum(axis=1).astype(jnp.int32)
        saved = saved + joined.sum(axis=1).astype(jnp.int32)
        saved_x = saved_x + joined_x.sum(axis=1).astype(jnp.int32)
        hops = hops + active.astype(jnp.int32)               # round trips

        if speculate:
            # --- consume the prediction the previous round staged,
            # against this round's authoritative requests. A PAYING
            # cold gather (cold & ~joined — the DMAs the cost model
            # prices) whose block was predicted is a hit: its copy was
            # already in flight while the previous round's expansion /
            # top-M maintenance ran, so the DMA left the critical
            # path. A predicted block that matches NO cold request of
            # its query is wasted bandwidth (a matched-but-joined
            # request is neither: its gather was already someone
            # else's). Charged at consume time, so the trace row below
            # sums to exactly these per-query increments.
            pred_match = (b[:, :, None]
                          == spec_blk[:, None, :]).any(-1)   # [Q, F]
            hit_spec = cold & ~joined & pred_match
            used = ((spec_blk[:, :, None]
                     == jnp.where(cold, b, -1)[:, None, :]).any(-1)
                    & (spec_blk >= 0))                       # [Q, F]
            sh_r = hit_spec.sum(axis=1).astype(jnp.int32)
            sw_r = ((spec_blk >= 0) & ~used).sum(
                axis=1).astype(jnp.int32)
            spec_h = spec_h + sh_r
            spec_w = spec_w + sw_r

        if trace:
            # the round's row is the batch-level sum of exactly the
            # masks the per-query counters just accumulated, so the
            # log's column sums equal the counter totals identically
            # (the fold invariant tests/test_trace_roundlog.py pins);
            # sums are permutation-invariant, so compaction is moot
            spec_cols = ((sh_r.sum().astype(jnp.int32),
                          sw_r.sum().astype(jnp.int32)) if speculate
                         else (jnp.zeros((), jnp.int32),
                               jnp.zeros((), jnp.int32)))
            rlog = rlog.at[t].set(jnp.stack([
                active.sum().astype(jnp.int32),
                cold.sum().astype(jnp.int32),
                hot.sum().astype(jnp.int32),
                joined.sum().astype(jnp.int32),
                joined_x.sum().astype(jnp.int32),
                fired.astype(jnp.int32), *spec_cols]))

        # --- DC: fold the exact-ranked residents into results
        f_valid = jnp.repeat(f_active, eps, axis=1)
        slot_valid = (vid >= 0) & f_valid
        dd_m = jnp.where(slot_valid, dd, jnp.inf)
        res_key, res_id = _merge_top(res_key, res_id, dd_m,
                                     jnp.where(slot_valid, vid, -1),
                                     res_size)

        # --- block pruning: targets + top-((eps-1)*sigma), in the
        # expansion order the round stage already ranked
        is_target = (vid[:, :, None] == u[:, None, :]).any(-1) \
            & (vid >= 0)
        sel_key = jnp.where(is_target, -jnp.inf, dd_m)
        ex_id = jnp.take_along_axis(vid, order, axis=1)
        ex_valid = (jnp.take_along_axis(sel_key, order, axis=1)
                    < jnp.inf) & active[:, None] & (ex_id >= 0)
        ex_new = ex_valid & ~_bit_get(visited, jnp.maximum(ex_id, 0))
        for j in range(n_expand):                            # mark expanded
            visited = _bit_set(visited, jnp.maximum(ex_id[:, j], 0),
                               ex_new[:, j])

        # --- collect neighbors of expanded slots, route by PQ
        ex_nbrs = jnp.take_along_axis(
            nbrs, order[:, :, None], axis=1)                 # [Q, X, Lam]
        flat = ex_nbrs.reshape(qn, -1)
        f_valid = (flat >= 0) & ex_new.repeat(
            ex_nbrs.shape[2], axis=1) & active[:, None]
        f_safe = jnp.maximum(flat, 0)
        f_valid &= ~_bit_get(visited, f_safe)                # skip expanded
        f_codes = ds.pq_codes[f_safe]                        # [Q, F, M]
        f_key = jnp.where(f_valid, _adc(lut_r, f_codes), jnp.inf)
        f_id = jnp.where(f_valid, flat, -1)
        if speculate:
            # --- stage the NEXT round's prediction from the neighbors
            # this round just PQ-routed — before they merge into the
            # candidate pool, which is why the speculative gather can
            # overlap the top-M maintenance below (and why it can
            # miss: the merged pool may still prefer an older
            # candidate). Hot-pack blocks never issue a speculative
            # DMA (a tier-0 hit needs none), and duplicate slot
            # predictions collapse so one block never double-counts.
            neg_p, p_pick = jax.lax.top_k(-f_key, fw)        # [Q, F]
            p_id = jnp.take_along_axis(f_id, p_pick, axis=1)
            p_ok = (jnp.isfinite(-neg_p) & (p_id >= 0)
                    & active[:, None])
            p_blk = jnp.where(p_ok,
                              ds.block_of[jnp.maximum(p_id, 0)], -1)
            p_hot = ds.hot_slot_of[jnp.maximum(p_blk, 0)] >= 0
            p_blk = jnp.where(p_hot & (p_blk >= 0), -1, p_blk)
            dup = ((p_blk[:, :, None] == p_blk[:, None, :])
                   & (jnp.arange(fw)[None, :, None]
                      > jnp.arange(fw)[None, None, :])).any(-1)
            spec_blk = jnp.where(dup & (p_blk >= 0), -1,
                                 p_blk).astype(jnp.int32)

        cand_key, cand_id = _merge_top(cand_key, cand_id, f_key, f_id,
                                       candidates)
        open_key = _open_keys(cand_id, cand_key, visited)
        out = (cand_id, cand_key, open_key, visited, res_id, res_key,
               io, t0, hops, saved, saved_x)
        if speculate:
            out = out + (spec_h, spec_w, spec_blk)
        if compact:
            out = out + (perm, q_r, lut_r)
        if trace:
            out = out + (rlog,)
        return out + (t + 1,)

    # extended state: core11 + (spec_h, spec_w, spec_blk | speculate)
    #                        + (perm, queries, lut | compact)
    #                        + (round log | trace) + (t,)
    st = state[:-1]
    if speculate:
        st = st + (jnp.zeros((qn,), jnp.int32),
                   jnp.zeros((qn,), jnp.int32),
                   jnp.full((qn, fw), -1, jnp.int32))
    if compact:
        st = st + (jnp.arange(qn, dtype=jnp.int32), queries, lut)
    if trace:
        st = st + (jnp.zeros((max_hops, len(_ROUND_LOG_COLS)),
                             jnp.int32),)
    out = jax.lax.while_loop(cond, body, st + (state[-1],))
    nper = 14 if speculate else 11           # per-query carried arrays
    arrs = out[:nper]
    pos = nper
    if compact:
        perm = out[nper]
        pos = nper + 3
        inv = jnp.argsort(perm)              # undo the compaction order
        arrs = tuple(jnp.take(a, inv, axis=0) for a in arrs)
    rlog = None
    if trace:
        rlog = out[pos]                      # round axis: never permuted
    if speculate:
        # drop the final round's staged-but-unconsumed prediction (its
        # DMA is modeled as issued at the consume boundary, which a
        # finished search never reaches); keep the hit/wasted counters
        arrs = arrs[:13]
    return arrs + (out[-1],), rlog


DEFAULT_DEVICE_SEARCH = DeviceSearchParams()


@functools.partial(jax.jit, static_argnames=("p", "metric"))
def device_anns(ds: DeviceSegment, queries: jnp.ndarray,
                p: DeviceSearchParams = DEFAULT_DEVICE_SEARCH,
                metric: str = "l2",
                seeds: Optional[jnp.ndarray] = None
                ) -> DeviceSearchResult:
    """Batched Starling ANNS on one segment shard.

    ``p.fetch_width`` > 1 fetches the F best unvisited candidates'
    blocks per round-trip (beyond-paper: the paper's Central Assumption
    notes a few random reads per SSD/DMA round-trip cost about the same
    as one — this trades block-bandwidth for round-trip latency).

    ``seeds`` [Q, S] int32 (−1-padded) is the seed-override path
    (hot/cold hybrid routing, DESIGN.md §10): when given, the
    navigation-graph entry pick is skipped entirely and the search
    seeds from these vertex ids instead — the hot tier hands its exit
    frontier here, so the cold search resumes where the memory tier
    converged. Rows that are all −1 fall back to nowhere (the caller
    guarantees at least one live seed per query).

    Returns ``DeviceSearchResult(ids [Q, k], dists [Q, k], io [Q] cold
    block touches, hops [Q] round trips, tier0_hits [Q], dedup_saved
    [Q], rounds)``. Tier-0 budget moves touches from ``io`` to
    ``tier0_hits``; cross-query dedup moves actual DMAs from ``io`` to
    ``dedup_saved`` (``io`` still counts every cold touch, so its
    semantics — and the io+tier0 block-touch total — are unchanged);
    neither changes (ids, dists) — asserted in tests and the
    device_bench sweeps."""
    qn, d = queries.shape
    eps = ds.vid.shape[1]
    n = ds.block_of.shape[0]
    nb_words = -(-n // 32)
    fw = max(p.fetch_width, 1)
    res_size = p.k + 2 * eps * fw
    queries = queries.astype(jnp.float32)

    lut = _adc_lut(queries, ds.pq_cent, metric)              # [Q, M, K]
    if seeds is not None:
        entry = seeds.astype(jnp.int32)
    else:
        entry = nav_entry_points(ds, queries, beam=p.nav_beam,
                                 hops=p.nav_hops, num=p.entry_points,
                                 metric=metric)
    e_codes = ds.pq_codes[jnp.maximum(entry, 0)]
    e_key = jnp.where(entry >= 0, _adc(lut, e_codes), jnp.inf)

    cand_id = jnp.full((qn, p.candidates), -1, jnp.int32)
    cand_key = jnp.full((qn, p.candidates), jnp.inf)
    cand_key, cand_id = _merge_top(cand_key, cand_id, e_key, entry,
                                   p.candidates)
    visited = jnp.zeros((qn, nb_words), jnp.uint32)          # expanded set
    state = (cand_id, cand_key,
             _open_keys(cand_id, cand_key, visited),
             visited,
             jnp.full((qn, res_size), -1, jnp.int32),
             jnp.full((qn, res_size), jnp.inf),
             jnp.zeros((qn,), jnp.int32),                    # io
             jnp.zeros((qn,), jnp.int32),                    # tier-0 hits
             jnp.zeros((qn,), jnp.int32),                    # hops
             jnp.zeros((qn,), jnp.int32),                    # dedup joins
             jnp.zeros((qn,), jnp.int32),                    # cross-tile
             jnp.zeros((), jnp.int32))
    state, rlog = _block_search_loop(
        ds, queries, lut, state, res_size=res_size,
        candidates=p.candidates, sigma=p.sigma, max_hops=p.max_hops,
        metric=metric, fetch_width=fw, fetch_impl=p.fetch_impl,
        compact_frac=p.compact_frac, trace=p.trace_rounds,
        pipeline_dma=p.pipeline_dma,
        round_tile_cap=p.round_tile_cap,
        speculate=p.speculate)
    if p.speculate:
        (_, _, _, _, res_id, res_key, io, t0, hops, saved, saved_x,
         spec_h, spec_w, t) = state
    else:
        (_, _, _, _, res_id, res_key, io, t0, hops, saved, saved_x,
         t) = state
        spec_h = jnp.zeros((qn,), jnp.int32)
        spec_w = jnp.zeros((qn,), jnp.int32)
    return DeviceSearchResult(res_id[:, : p.k], res_key[:, : p.k], io,
                              hops, t0, saved, saved_x, spec_h, spec_w,
                              t, rlog)


# --------------------------------------------- production mesh search step

def merge_shard_topk(gids: jnp.ndarray, gd: jnp.ndarray,
                     k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Merge stacked per-shard results on device: ``gids``/``gd``
    [S, Q, kk] (global ids, -1 = invalid; dists, inf on invalid) ->
    ([Q, k], [Q, k]) global top-k.

    Ordering is (dist, global id) with invalid ids keyed past every
    real id — the SAME total order the host ``serving.merge_topk``
    sorts by, so a device-merged mesh fan-out and a host-merged concat
    over the same shards are bit-identical, independent of shard
    arrival order or placement (an argsort by position is NOT: moving
    a segment to another rank would reorder equal-distance ties)."""
    s, q, kk = gids.shape
    flat_i = jnp.moveaxis(gids, 0, 1).reshape(q, s * kk)
    flat_d = jnp.moveaxis(gd, 0, 1).reshape(q, s * kk)
    flat_d = jnp.where(flat_i >= 0, flat_d, jnp.inf)
    key_id = jnp.where(flat_i >= 0, flat_i,
                       jnp.iinfo(flat_i.dtype).max)
    # lexsort: last key is primary -> (dist, then id on ties)
    order = jnp.lexsort((key_id, flat_d))[:, :k]
    return (jnp.take_along_axis(flat_i, order, axis=1),
            jnp.take_along_axis(flat_d, order, axis=1))


def stack_segments(segments, sharding) -> DeviceSegment:
    """Stack same-shape segment shards along a new leading axis — the
    [W, ...] tree ``make_search_step``/the mesh router shard over the
    ``model`` axis (one shard per rank; replicas are repeated
    entries). All shards must agree on every array's shape and dtype
    so a restack after a rebalance reuses the same compiled
    executable (the mesh analogue of ``repack_tier0``'s same-shape
    in-place swap).

    ``sharding`` (a ``NamedSharding`` splitting the leading axis) places
    each shard straight on its own device: the stack is never gathered
    onto one device, so each device holds only its own shard."""
    if not segments:
        raise ValueError("stack_segments needs at least one shard")
    first = segments[0]
    for idx, seg in enumerate(segments[1:], 1):
        for f in dataclasses.fields(DeviceSegment):
            a, b = getattr(first, f.name), getattr(seg, f.name)
            if a.shape != b.shape or a.dtype != b.dtype:
                raise ValueError(
                    f"segment shard {idx} field {f.name!r} is "
                    f"{b.shape}/{b.dtype}, shard 0 has "
                    f"{a.shape}/{a.dtype} — mesh shards must be "
                    "shape-identical (pad segments to a common size)")

    def place(*xs):
        return jax.make_array_from_callback(
            (len(xs),) + xs[0].shape, sharding,
            lambda idx: jnp.stack(xs[idx[0]]))

    return jax.tree.map(place, *segments)


def make_search_step(mesh, rules, *,
                     n_local: int = 1 << 21, dim: int = 128,
                     eps: int = 16, lam: int = 31, q_global: int = 4096,
                     pq_m: int = 16, pq_k: int = 256,
                     nav_frac: int = 64, nav_deg: int = 12,
                     search: Optional[DeviceSearchParams] = None):
    """Build (fn, arg ShapeDtypeStructs) for the segment-search dry-run.

    Layout: every ``model`` rank owns an independent sub-segment of
    ``n_local`` vectors (16 ranks x 2M = 33M vectors per pod row — the
    paper's segment scale); queries are sharded over ``data`` (x ``pod``)
    and replicated over ``model``. The step runs the local block search
    via shard_map and merges per-segment top-k with one all-gather over
    ``model``.

    ``search`` carries every online knob (today's production defaults
    when omitted): Γ, σ, fetch width, nav beam, compaction — and the
    tier-0 budget, which sizes the per-rank hot-tile pack in the
    argument specs. The step returns (gid, dists, io, hops,
    tier0_hits, dedup_saved, dedup_cross, spec_hits, spec_wasted); the
    per-rank io/hops/tier-0/dedup/speculation columns land in the
    ``(data, model)``-sharded
    outputs — the mesh-level QPS fold in ``benchmarks/paper_tables.py``
    consumes exactly these."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    if search is None:
        search = DeviceSearchParams(candidates=64, max_hops=128)
    model_n = mesh.shape["model"]
    data_axes = tuple(a for a in mesh.axis_names if a != "model")
    rho = n_local // eps
    hot_n = max(int(search.tier0_frac * rho), 1)
    nav_n = n_local // nav_frac
    dsub = dim // pq_m

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec))

    seg_specs = DeviceSegment(
        vecs=sds((model_n, rho, eps, dim), jnp.bfloat16, P("model")),
        vid=sds((model_n, rho, eps), jnp.int32, P("model")),
        deg=sds((model_n, rho, eps), jnp.int32, P("model")),
        nbrs=sds((model_n, rho, eps, lam), jnp.int32, P("model")),
        block_of=sds((model_n, n_local), jnp.int32, P("model")),
        pq_codes=sds((model_n, n_local, pq_m), jnp.uint8, P("model")),
        pq_cent=sds((model_n, pq_m, pq_k, dsub), jnp.float32, P("model")),
        nav_vecs=sds((model_n, nav_n, dim), jnp.float32, P("model")),
        nav_adj=sds((model_n, nav_n, nav_deg), jnp.int32, P("model")),
        nav_ids=sds((model_n, nav_n), jnp.int32, P("model")),
        nav_entry=sds((model_n,), jnp.int32, P("model")),
        hot_vecs=sds((model_n, hot_n, eps, dim), jnp.bfloat16,
                     P("model")),
        hot_vid=sds((model_n, hot_n, eps), jnp.int32, P("model")),
        hot_nbrs=sds((model_n, hot_n, eps, lam), jnp.int32, P("model")),
        hot_slot_of=sds((model_n, rho), jnp.int32, P("model")),
    )
    q_specs = sds((q_global, dim), jnp.float32, P(data_axes))

    in_specs = (DeviceSegment(
        vecs=P("model"), vid=P("model"), deg=P("model"), nbrs=P("model"),
        block_of=P("model"), pq_codes=P("model"), pq_cent=P("model"),
        nav_vecs=P("model"), nav_adj=P("model"), nav_ids=P("model"),
        nav_entry=P("model"), hot_vecs=P("model"), hot_vid=P("model"),
        hot_nbrs=P("model"), hot_slot_of=P("model")), P(data_axes))
    out_specs = (P(data_axes), P(data_axes), P(data_axes, "model"),
                 P(data_axes, "model"), P(data_axes, "model"),
                 P(data_axes, "model"), P(data_axes, "model"),
                 P(data_axes, "model"), P(data_axes, "model"))

    def local_search(seg: DeviceSegment, queries):
        seg = jax.tree.map(lambda a: a[0], seg)      # strip shard dim
        seg = dataclasses.replace(
            seg, vecs=seg.vecs.astype(jnp.float32),
            hot_vecs=seg.hot_vecs.astype(jnp.float32))
        r = device_anns(seg, queries, search)
        ids, dists = r.ids, r.dists
        # hierarchical top-k merge over segment ranks: all-gather k
        # results per rank (O(k) bytes cross-rank, not O(Gamma)),
        # merged in the shared (dist, global id) order so the result
        # is placement-invariant and bit-identical to the host
        # ``serving.merge_topk`` concat over the same shards
        gids = jax.lax.all_gather(ids, "model")      # [S, Q, k]
        gd = jax.lax.all_gather(dists, "model")
        s, _, kk = gids.shape
        # global id = segment rank * n_local + local id
        seg_of = jnp.arange(s, dtype=jnp.int32)[:, None, None]
        glob = jnp.where(gids >= 0, seg_of * n_local + gids, -1)
        gid, out_d = merge_shard_topk(glob, gd, kk)
        col = jnp.ones((1, 1), jnp.int32)
        return (gid, out_d, r.io[:, None] * col, r.hops[:, None] * col,
                r.tier0_hits[:, None] * col,
                r.dedup_saved[:, None] * col,
                r.dedup_cross[:, None] * col,
                r.spec_hits[:, None] * col,
                r.spec_wasted[:, None] * col)

    fn = jax.shard_map(local_search, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn, (seg_specs, q_specs)


# ---------------------------------------------------------- range search

@functools.partial(jax.jit, static_argnames=(
    "radius", "k_cap", "p", "metric", "rounds", "ratio"))
def device_range_search(ds: DeviceSegment, queries: jnp.ndarray,
                        radius: float, k_cap: int = 256,
                        p: DeviceSearchParams = DEFAULT_DEVICE_SEARCH,
                        metric: str = "l2",
                        rounds: int = 3, ratio: float = 0.5
                        ) -> DeviceRangeResult:
    """Batched RS (§5.3 semantics, device formulation): ANNS rounds with
    a doubling candidate set; stop growing a query's set once the
    in-range fraction of its results drops below ``ratio`` (handled by
    the ratio mask on the host serving layer — rounds are compile-time
    unrolled here).

    The ``visited`` bitmask and result arrays thread through the rounds
    (the device analogue of the host RS resume fix): a later round
    re-seeds its candidate set from the previous round's results but
    never re-expands — so never re-fetches, and never re-counts in
    ``io`` — a block whose vertex an earlier round already expanded.

    ``p.speculate`` carries through each inner ANNS loop; the staged
    prediction drains at every RS re-entry (the pipeline has a hard
    barrier at the doubling boundary — the next round's candidate set
    is re-seeded host-side), while the hit/wasted counters accumulate
    across rounds.
    """
    qn = queries.shape[0]
    n = ds.block_of.shape[0]
    eps = ds.vid.shape[1]
    nb_words = -(-n // 32)
    fw = max(p.fetch_width, 1)
    queries = queries.astype(jnp.float32)
    lut = _adc_lut(queries, ds.pq_cent, metric)

    entry = nav_entry_points(ds, queries, beam=p.nav_beam,
                             hops=p.nav_hops, num=p.entry_points,
                             metric=metric)
    e_codes = ds.pq_codes[jnp.maximum(entry, 0)]
    e_key = jnp.where(entry >= 0, _adc(lut, e_codes), jnp.inf)

    visited = jnp.zeros((qn, nb_words), jnp.uint32)
    res_id = jnp.zeros((qn, 0), jnp.int32)
    res_key = jnp.zeros((qn, 0), jnp.float32)
    io = jnp.zeros((qn,), jnp.int32)
    t0 = jnp.zeros((qn,), jnp.int32)
    hops = jnp.zeros((qn,), jnp.int32)
    saved = jnp.zeros((qn,), jnp.int32)
    saved_x = jnp.zeros((qn,), jnp.int32)
    spec_h = jnp.zeros((qn,), jnp.int32)
    spec_w = jnp.zeros((qn,), jnp.int32)
    total_rounds = jnp.zeros((), jnp.int32)
    seed_id, seed_key = entry, e_key

    c = p.candidates
    for rnd in range(rounds):
        k_r = min(k_cap, c)
        res_size = k_r + 2 * eps * fw
        cand_id = jnp.full((qn, c), -1, jnp.int32)
        cand_key = jnp.full((qn, c), jnp.inf)
        cand_key, cand_id = _merge_top(cand_key, cand_id, seed_key,
                                       seed_id, c)
        r_id = jnp.full((qn, res_size), -1, jnp.int32)
        r_key = jnp.full((qn, res_size), jnp.inf)
        if res_id.shape[1]:
            r_key, r_id = _merge_top(r_key, r_id, res_key, res_id,
                                     res_size)
        state = (cand_id, cand_key,
                 _open_keys(cand_id, cand_key, visited), visited,
                 r_id, r_key, io, t0, hops, saved, saved_x,
                 jnp.zeros((), jnp.int32))
        # trace stays off here: RS re-enters the loop per round, so a
        # stitched multi-round log has no single ``rounds`` to fold
        # against — the ANNS path is the traced one
        state, _ = _block_search_loop(
            ds, queries, lut, state, res_size=res_size, candidates=c,
            sigma=p.sigma, max_hops=p.max_hops, metric=metric,
            fetch_width=fw, fetch_impl=p.fetch_impl,
            compact_frac=p.compact_frac, trace=False,
            pipeline_dma=p.pipeline_dma,
            round_tile_cap=p.round_tile_cap,
            speculate=p.speculate)
        if p.speculate:
            (_, _, _, visited, res_id, res_key, io, t0, hops, saved,
             saved_x, sh_r, sw_r, t) = state
            spec_h = spec_h + sh_r
            spec_w = spec_w + sw_r
        else:
            (_, _, _, visited, res_id, res_key, io, t0, hops, saved,
             saved_x, t) = state
        total_rounds = total_rounds + t
        if c * 2 > k_cap:
            break
        c *= 2
        # next round resumes from this round's frontier: results whose
        # vertices were ranked but never expanded are live candidates
        # under the carried ``visited`` mask (expanded ones mask out)
        seed_id, seed_key = res_id, res_key

    ids, dists = res_id[:, :k_cap], res_key[:, :k_cap]
    pad = k_cap - ids.shape[1]
    if pad > 0:
        ids = jnp.pad(ids, ((0, 0), (0, pad)), constant_values=-1)
        dists = jnp.pad(dists, ((0, 0), (0, pad)),
                        constant_values=jnp.inf)
    return DeviceRangeResult(ids, dists, dists <= radius, io, t0,
                             saved, saved_x, spec_h, spec_w,
                             total_rounds)
