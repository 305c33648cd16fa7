"""jit'd dispatch wrappers around the Pallas kernels.

The kernel mode follows the platform a call is lowered for: on the CPU
the kernels run under the Pallas interpreter (``interpret=True``), on a
TPU they are compiled by Mosaic — a kernel that does not lower there
raises, it never falls back. ``interpret=`` pins the mode explicitly
(tests use it to run either side). Wrappers pad inputs to tile
multiples and strip the padding from outputs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import block_topk as _bt
from repro.kernels import l2_tile as _l2
from repro.kernels import pq_adc as _adc
from repro.kernels import tier0_fetch as _t0


def _call(kernel, interpret, *args):
    """``kernel(*args, interpret=...)``: interpreted where the call runs
    on the CPU, compiled everywhere else — unless ``interpret`` pins
    the mode."""
    if interpret is not None:
        return kernel(*args, interpret=interpret)
    return jax.lax.platform_dependent(
        *args, cpu=functools.partial(kernel, interpret=True),
        default=functools.partial(kernel, interpret=False))


def _pad_rows(a: jnp.ndarray, mult: int) -> jnp.ndarray:
    n = a.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return a
    return jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))


@functools.partial(jax.jit,
                   static_argnames=("metric", "interpret", "bq", "bn"))
def pairwise_l2(q: jnp.ndarray, x: jnp.ndarray, metric: str = "l2",
                interpret: bool = None, bq: int = None, bn: int = None
                ) -> jnp.ndarray:
    """[Q, D] x [N, D] -> [Q, N] distances via the l2_tile kernel."""
    bq = bq or min(_l2.BQ, max(8, q.shape[0]))
    bn = bn or min(_l2.BN, max(8, x.shape[0]))
    qp, xp = _pad_rows(q, bq), _pad_rows(x, bn)
    out = _call(functools.partial(_l2.l2_tile, metric=metric, bq=bq,
                                  bn=bn), interpret, qp, xp)
    # padded base rows are zero vectors (-0.0 for ip); sliced off
    return out[: q.shape[0], : x.shape[0]]


@functools.partial(jax.jit, static_argnames=("interpret", "bn"))
def pq_adc_batch(codes: jnp.ndarray, luts: jnp.ndarray,
                 interpret: bool = None, bn: int = None) -> jnp.ndarray:
    """codes [N, M] uint8 x luts [B, M, K] -> [B, N] ADC distances."""
    bn = bn or min(_adc.BN, max(8, codes.shape[0]))
    cp = _pad_rows(codes, bn)
    out = _call(functools.partial(_adc.pq_adc, bn=bn), interpret, cp,
                luts.astype(jnp.float32))
    return jnp.moveaxis(out, 0, 1)[:, : codes.shape[0]]


@functools.partial(jax.jit,
                   static_argnames=("top_m", "metric", "interpret", "bq"))
def block_rank(queries: jnp.ndarray, tiles: jnp.ndarray, top_m: int,
               metric: str = "l2", interpret: bool = None,
               bq: int = None):
    """queries [Q, D] x gathered tiles [Q, eps, D] ->
    (dists [Q, eps], top_idx [Q, top_m])."""
    bq = bq or min(_bt.BQ, max(8, queries.shape[0]))
    qp = _pad_rows(queries, bq)
    tp = _pad_rows(tiles, bq)
    d, idx = _call(functools.partial(_bt.block_topk, top_m=top_m,
                                     metric=metric, bq=bq),
                   interpret, qp, tp)
    return d[: queries.shape[0]], idx[: queries.shape[0]]


def round_tile(qn: int, cap: int = 0) -> int:
    """The query-tile size the fused round kernel's rank pass runs at
    for a batch of ``qn`` (``cap`` > 0 overrides the ``BQ`` ceiling —
    ``DeviceSearchParams.round_tile_cap``, the knob the cross-tile
    sweeps/tests force multi-tile batches with). Since the batch-scope
    rework (DESIGN.md §8) dedup spans the WHOLE batch; the tile is only
    the idle-skip / compaction granularity and the intra- vs cross-tile
    boundary of the split ``dedup_saved`` accounting."""
    lim = cap if cap > 0 else _t0.BQ
    return min(lim, max(8, qn))


@functools.partial(jax.jit,
                   static_argnames=("n_expand", "metric", "interpret",
                                    "bq", "pipeline_dma"))
def fused_round(queries: jnp.ndarray, u: jnp.ndarray,
                block_of: jnp.ndarray, hot_slot_of: jnp.ndarray,
                hot_vecs: jnp.ndarray, hot_vid: jnp.ndarray,
                hot_nbrs: jnp.ndarray, vecs: jnp.ndarray,
                vid: jnp.ndarray, nbrs: jnp.ndarray, n_expand: int,
                metric: str = "l2", interpret: bool = None,
                bq: int = None, pipeline_dma: bool = True):
    """Fused per-round fetch pipeline of the batched device search:
    whole-batch sorted-unique dedup (pass 1), once-per-distinct-
    block DMA gather from the HBM store (pass 2a; two-slot schedule
    when ``pipeline_dma``), tier-0 select and broadcast, then per-tile
    exact distances + per-query top-``n_expand`` expansion order
    (pass 2b). Padded query rows carry ``u = -1`` (converged), so
    all-pad tiles take the rank kernel's skip path; their outputs are
    sliced off."""
    bq = bq or round_tile(queries.shape[0])
    qp = _pad_rows(queries, bq)
    pad = (-u.shape[0]) % bq
    up = u if pad == 0 else jnp.pad(u, ((0, pad), (0, 0)),
                                    constant_values=-1)
    outs = _call(functools.partial(_t0.fused_round, n_expand=n_expand,
                                   metric=metric, bq=bq,
                                   pipeline_dma=pipeline_dma),
                 interpret, qp, up, block_of, hot_slot_of, hot_vecs,
                 hot_vid, hot_nbrs, vecs, vid, nbrs)
    return tuple(o[: queries.shape[0]] for o in outs)


@functools.partial(jax.jit, static_argnames=("metric", "interpret"))
def tier0_rank(queries: jnp.ndarray, blocks: jnp.ndarray,
               hot_slot_of: jnp.ndarray, hot_vecs: jnp.ndarray,
               cold_vecs: jnp.ndarray, metric: str = "l2",
               interpret: bool = None):
    """Tier-0 probe + block gather + rank: queries [Q, D] x target
    blocks [Q, F] -> (dists [Q, F*eps] over the gathered tiles, hit
    [Q, F] tier-0 mask)."""
    return _call(functools.partial(_t0.tier0_fetch_rank, metric=metric),
                 interpret, queries, blocks, hot_slot_of, hot_vecs,
                 cold_vecs)
