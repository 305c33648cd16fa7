"""Block gather + rank kernels of the device search's fetch stage
(DESIGN.md §3.2, §4, §8).

The cold block store and the tier-0 hot pack stay in HBM
(``memory_space=pl.ANY``): at segment scale (1M f32 vectors of 128 dims
is 512 MB of cold store) neither fits VMEM. Every kernel reads block
ids as SMEM scalars and copies whole blocks with ``make_async_copy`` —
no kernel indexes a vector with a vector, so all of them lower with
Mosaic.

``fused_round`` — the per-round fetch pipeline of the *divergence-aware
batched* search:

  * **pass 1** (plain jnp, traced into the surrounding jit): derive the
    target blocks from the picked candidates and union them into the
    whole-batch sorted-unique block list via ``dedup.sorted_unique_ranks``
    — one list for ALL Q x F requests — plus the flat-slot -> unique-rank
    map;
  * **pass 2a** (``gather_blocks``, grid over unique-block chunks, block
    ids scalar-prefetched into SMEM): copy each distinct block's vector
    tile out of the HBM block store exactly ONCE batch-wide — the
    modeled DMAs. The block's ids and neighbor rows, whose minor
    dimensions (eps, Lam) are narrower than a 128-lane tile and so
    cannot be sliced by a Mosaic DMA, are gathered by XLA.
    ``pipeline_dma`` runs the two-slot ``make_async_copy`` schedule
    (block j+1's copy is in flight while block j's lands); off, each
    copy starts and finishes before the next one starts. Payloads are
    identical either way;
  * **select + broadcast** (plain jnp): probe the tier-0 hot-slot map for
    the unique list, take each distinct block's payload from the hot
    pack (hit) or the pass-2a copy, and broadcast it to the requesting
    slots through the rank map;
  * **pass 2b** (``_rank_kernel``, grid over query tiles): exact
    distances and the per-query top-``n_expand`` expansion order by
    iterative masked argmin (stable-argsort tie order). A tile whose
    queries are all converged (every ``u`` slot is -1 — what active-query
    compaction clusters) skips the body and writes masked sentinels; the
    tile's liveness reaches the kernel as a scalar-prefetched flag.

Distances use the same f32 sum-of-squared-differences (or negated IP)
form as the pure-jnp fetch stage; the hot pack holds exact copies of the
packed blocks, so neither tier-0 budget nor dedup scope ever changes
(ids, dists) — only which source tier served a tile and which counter
(``io`` / ``tier0_hits`` / ``dedup_saved``) a touch lands in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import dedup

BQ = 128   # query-tile size of the rank pass
RB = 128   # block chunk size of the gather pass


# ------------------------------------------------ pass 2a: block gather

def _dma_gather(block_id, rb, srcs, dsts, sems, pipeline: bool):
    """Copy ``rb`` whole blocks out of the HBM stores ``srcs`` into rows
    0..rb-1 of the VMEM tiles ``dsts``; ``block_id(j)`` is row j's block
    id, read as an SMEM scalar. ``pipeline`` runs the two-slot schedule:
    while block j's copies complete, block j+1's are already in flight
    on the other semaphore slot. Off, one block is in flight at a time.
    The payload is identical either way; only the schedule differs."""

    def copies(slot, j):
        blk = block_id(j)
        return [pltpu.make_async_copy(src.at[pl.ds(blk, 1)],
                                      dst.at[pl.ds(j, 1)], sems.at[slot, a])
                for a, (src, dst) in enumerate(zip(srcs, dsts))]

    if pipeline:
        for c in copies(0, 0):                    # warm up slot 0
            c.start()

    def body(j, carry):
        slot = jax.lax.rem(j, 2)
        if pipeline:
            @pl.when(j + 1 < rb)
            def _start_next():
                for c in copies(1 - slot, j + 1):
                    c.start()
        else:
            for c in copies(slot, j):
                c.start()
        for c in copies(slot, j):
            c.wait()
        return carry

    jax.lax.fori_loop(0, rb, body, 0)


def _gather_kernel(idx_ref, *refs, rb: int, pipeline: bool):
    n = (len(refs) - 1) // 2
    srcs, dsts, sems = refs[:n], refs[n:2 * n], refs[2 * n]
    base = pl.program_id(0) * rb
    _dma_gather(lambda j: idx_ref[base + j], rb, srcs, dsts, sems,
                pipeline)


def gather_blocks(idx: jnp.ndarray, *stores: jnp.ndarray, interpret: bool,
                  pipeline_dma: bool = True, rb: int = RB):
    """Pass 2a: ``idx`` [R] i32 block ids (R % rb == 0) -> one
    ``[R, ...]`` array per HBM store in ``stores`` (each [rho, ...]),
    row i holding block ``idx[i]``. Block ids are scalar-prefetched into
    SMEM; each block is one ``make_async_copy`` per store. Compiled,
    a store's minor dimension must be a multiple of 128 lanes: Mosaic
    cannot slice a block out of a narrower HBM array."""
    r = idx.shape[0]
    assert r % rb == 0, (r, rb)

    def out_spec(a):
        zeros = (0,) * (a.ndim - 1)
        return pl.BlockSpec((rb,) + a.shape[1:],
                            lambda i, idx_ref: (i,) + zeros)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(r // rb,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY) for _ in stores],
        out_specs=[out_spec(a) for a in stores],
        scratch_shapes=[pltpu.SemaphoreType.DMA((2, len(stores)))])
    return tuple(pl.pallas_call(
        functools.partial(_gather_kernel, rb=rb, pipeline=pipeline_dma),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((r,) + a.shape[1:], a.dtype)
                   for a in stores],
        interpret=interpret,
        name="gather_blocks",
    )(idx, *stores))


# ------------------------------------------------ pass 2b: rank a tile

def _tile_dists(q, t, metric: str):
    """q [BQ, D] f32 vs t [BQ, E, D] -> [BQ, E] f32, the jnp fetch
    stage's form."""
    t32 = t.astype(jnp.float32)
    if metric == "ip":
        return -jnp.sum(q[:, None, :] * t32, axis=-1)
    return jnp.sum(jnp.square(t32 - q[:, None, :]), axis=-1)


def _rank_kernel(live_ref, q_ref, u_ref, t_ref, vid_ref, d_ref, ord_ref,
                 *, metric: str, n_expand: int, eps: int):
    live = live_ref[pl.program_id(0)] > 0

    @pl.when(live)
    def _live_tile():
        q = q_ref[...].astype(jnp.float32)        # [BQ, D]
        u = u_ref[...]                            # [BQ, F] i32, -1 idle
        vid = vid_ref[...]                        # [BQ, F*eps]
        dd = _tile_dists(q, t_ref[...], metric)   # [BQ, F*eps]
        bq, fe = vid.shape
        col = jax.lax.broadcasted_iota(jnp.int32, (bq, fe), 1)
        f_valid = jnp.zeros((bq, fe), jnp.bool_)
        is_target = jnp.zeros((bq, fe), jnp.bool_)
        for f in range(u.shape[1]):
            uf = u[:, f:f + 1]
            in_f = (col >= f * eps) & (col < (f + 1) * eps)
            f_valid = f_valid | (in_f & (uf >= 0))
            is_target = is_target | (vid == uf)
        slot_valid = (vid >= 0) & f_valid
        dd_m = jnp.where(slot_valid, dd, jnp.inf)
        sel_key = jnp.where(is_target & (vid >= 0), -jnp.inf, dd_m)
        # per-query top-n_expand in stable-argsort order: the smallest
        # untaken key, ties to the lowest column (targets first, then
        # nearest residents, then invalid slots in column order)
        taken = jnp.zeros((bq, fe), jnp.bool_)
        ocol = jax.lax.broadcasted_iota(jnp.int32, (bq, n_expand), 1)
        order = jnp.zeros((bq, n_expand), jnp.int32)
        for m in range(n_expand):
            kmin = jnp.min(jnp.where(taken, jnp.inf, sel_key), axis=1,
                           keepdims=True)
            cand = (~taken) & (sel_key == kmin)
            pick = jnp.min(jnp.where(cand, col, fe), axis=1,
                           keepdims=True)
            taken = taken | (col == pick)
            order = jnp.where(ocol == m, pick, order)
        d_ref[...] = dd
        ord_ref[...] = order

    @pl.when(jnp.logical_not(live))
    def _idle_tile():
        # a fully-converged tile (what compaction clusters): skip the
        # rank entirely, emit masked sentinels the search loop never
        # consumes (every downstream use is gated on u >= 0)
        d_ref[...] = jnp.zeros(d_ref.shape, jnp.float32)
        ord_ref[...] = jnp.zeros(ord_ref.shape, jnp.int32)


def rank_tiles(live, queries, u, tiles, vid, n_expand: int, *,
               metric: str, interpret: bool, bq: int = BQ):
    """Pass 2b: live [Q/bq] i32 tile flags, queries [Q, D], u [Q, F],
    tiles [Q, F*eps, D], vid [Q, F*eps] -> (dists [Q, F*eps] f32,
    order [Q, n_expand] i32)."""
    qn, d = queries.shape
    f = u.shape[1]
    fe = vid.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(qn // bq,),
        in_specs=[pl.BlockSpec((bq, d), lambda i, lv: (i, 0)),
                  pl.BlockSpec((bq, f), lambda i, lv: (i, 0)),
                  pl.BlockSpec((bq, fe, d), lambda i, lv: (i, 0, 0)),
                  pl.BlockSpec((bq, fe), lambda i, lv: (i, 0))],
        out_specs=[pl.BlockSpec((bq, fe), lambda i, lv: (i, 0)),
                   pl.BlockSpec((bq, n_expand), lambda i, lv: (i, 0))])
    return pl.pallas_call(
        functools.partial(_rank_kernel, metric=metric, n_expand=n_expand,
                          eps=fe // f),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((qn, fe), jnp.float32),
                   jax.ShapeDtypeStruct((qn, n_expand), jnp.int32)],
        interpret=interpret,
        name="rank_tiles",
    )(live, queries, u, tiles, vid)


# ------------------------------------------------------ the whole round

def fused_round(queries: jnp.ndarray, u: jnp.ndarray,
                block_of: jnp.ndarray, hot_slot_of: jnp.ndarray,
                hot_vecs: jnp.ndarray, hot_vid: jnp.ndarray,
                hot_nbrs: jnp.ndarray, vecs: jnp.ndarray,
                vid: jnp.ndarray, nbrs: jnp.ndarray, n_expand: int,
                metric: str = "l2", *, interpret: bool, bq: int = BQ,
                pipeline_dma: bool = True):
    """One search round's fetch pipeline, batch-scope (see module
    docstring).

    queries [Q, D]; u [Q, F] i32 picked candidate ids (-1 = converged /
    empty slot); block_of [N]; hot_slot_of [rho]; hot pack [H, eps, ...];
    cold store [rho, eps, ...] ->
    (dists [Q, F*eps] f32, vid [Q, F*eps] i32, nbrs [Q, F*eps, Lam] i32,
    hit [Q, F] i32, order [Q, n_expand] i32).

    Dedup scope is the WHOLE batch: every distinct block across all
    Q x F requests is gathered once and broadcast. Q % bq == 0."""
    qn, d = queries.shape
    _, f = u.shape
    assert qn % bq == 0, (qn, bq)
    eps = vecs.shape[1]

    # --- pass 1: whole-batch sorted-unique union + slot -> rank map.
    # Idle slots (u = -1) fold onto block 0's rank — harmless, their
    # outputs are masked downstream; ranks past the distinct count keep
    # the 0 placeholder no slot maps to.
    b = block_of[jnp.maximum(u, 0)]               # [Q, F] target blocks
    uniq, req_rank = dedup.sorted_unique_ranks(b.reshape(-1))
    rank2d = req_rank.reshape(qn, f)
    # --- pass 2a: copy each distinct block's cold payload once
    r = uniq.shape[0]
    rb = min(RB, r)
    pad = (-r) % rb
    uniq_p = uniq if pad == 0 else jnp.pad(uniq, (0, pad))
    (tv,) = gather_blocks(uniq_p, vecs, interpret=interpret,
                          pipeline_dma=pipeline_dma, rb=rb)
    # ids and neighbor rows are narrower than a lane tile (eps and Lam <
    # 128), which a Mosaic DMA cannot slice: XLA gathers them
    ti, tn = vid[uniq], nbrs[uniq]

    # --- tier-0 probe + hot/cold select, then broadcast each distinct
    # block to its requesting slots through the rank map
    s = hot_slot_of[uniq]                         # [R] hot slot, -1 cold
    hot_u = s >= 0
    ss = jnp.maximum(s, 0)
    tiles_u = jnp.where(hot_u[:, None, None], hot_vecs[ss], tv[:r])
    vid_u = jnp.where(hot_u[:, None], hot_vid[ss], ti[:r])
    nbrs_u = jnp.where(hot_u[:, None, None], hot_nbrs[ss], tn[:r])
    tiles = tiles_u[rank2d].reshape(qn, f * eps, d)
    vid_q = vid_u[rank2d].reshape(qn, f * eps)
    nbrs_q = nbrs_u[rank2d].reshape(qn, f * eps, -1)
    hit = hot_u[rank2d]

    # --- pass 2b: exact rank + expansion order per live query tile
    live = (u.reshape(qn // bq, bq * f) >= 0).any(axis=1)
    dd, order = rank_tiles(live.astype(jnp.int32), queries, u, tiles,
                           vid_q, n_expand, metric=metric,
                           interpret=interpret, bq=bq)
    row_live = jnp.repeat(live, bq)[:, None]
    return (dd, jnp.where(row_live, vid_q, -1),
            jnp.where(row_live[:, :, None], nbrs_q, -1),
            jnp.where(row_live, hit, False).astype(jnp.int32), order)


def tier0_fetch_rank(queries: jnp.ndarray, blocks: jnp.ndarray,
                     hot_slot_of: jnp.ndarray, hot_vecs: jnp.ndarray,
                     cold_vecs: jnp.ndarray, metric: str = "l2", *,
                     interpret: bool):
    """queries [Q, D]; blocks [Q, F] i32; hot_slot_of [rho] i32 (-1 =
    not packed); hot_vecs [H, eps, D]; cold_vecs [rho, eps, D] ->
    (dists [Q, F*eps] f32, hit [Q, F] i32). The cold tiles come from
    the block-gather kernel; the probe, the hot select and the
    distances are jnp."""
    qn, f = blocks.shape
    _, eps, d = cold_vecs.shape
    flat = blocks.reshape(-1)
    rb = min(RB, flat.shape[0])
    pad = (-flat.shape[0]) % rb
    (cold,) = gather_blocks(jnp.pad(flat, (0, pad)), cold_vecs,
                            interpret=interpret, rb=rb)
    slot = hot_slot_of[flat]
    hit = slot >= 0
    t = jnp.where(hit[:, None, None], hot_vecs[jnp.maximum(slot, 0)],
                  cold[:flat.shape[0]])
    dd = _tile_dists(queries.astype(jnp.float32),
                     t.reshape(qn, f * eps, d), metric)
    return dd, hit.reshape(qn, f).astype(jnp.int32)
