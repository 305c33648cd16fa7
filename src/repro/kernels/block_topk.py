"""Fused block-tile ranking kernel (the §5.1 block-search inner loop).

Input is the gathered block tile per query — exactly what one HBM->VMEM
DMA delivers in the TPU mapping of a 4 KB disk read. The kernel
exact-ranks all eps resident vertices against the query and selects the
top-m slots (block pruning keeps the (eps-1)*sigma closest) without
leaving VMEM: distances via dot, selection via m iterations of
masked-argmin (eps is small, ~4-16, so iterative select beats a sort).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BQ = 128


def _rank_kernel(q_ref, t_ref, d_ref, i_ref, *, top_m: int, metric: str):
    q = q_ref[...].astype(jnp.float32)              # [BQ, D]
    t = t_ref[...].astype(jnp.float32)              # [BQ, eps, D]
    # one query per tile row: a multiply-reduce, not a batched matmul
    # (Mosaic has no lowering for a dot batched over the query axis)
    dot = jnp.sum(q[:, None, :] * t, axis=-1)
    if metric == "ip":
        d = -dot
    else:
        tt = jnp.sum(t * t, axis=-1)
        qq = jnp.sum(q * q, axis=-1, keepdims=True)
        d = jnp.maximum(tt + qq - 2.0 * dot, 0.0)
    d_ref[...] = d

    work = d
    eps = d.shape[1]
    cols = jax.lax.broadcasted_iota(jnp.int32, (d.shape[0], eps), 1)
    for m in range(top_m):
        bidx = jnp.argmin(work, axis=1)
        i_ref[:, m] = bidx.astype(jnp.int32)
        work = jnp.where(cols == bidx[:, None], 3.0e38, work)


def block_topk(queries: jnp.ndarray, tiles: jnp.ndarray, top_m: int,
               metric: str = "l2", *, interpret: bool, bq: int = BQ):
    """queries [Q, D]; tiles [Q, eps, D] -> (dists [Q, eps] f32,
    top_idx [Q, top_m] int32)."""
    qn, d = queries.shape
    _, eps, _ = tiles.shape
    assert qn % bq == 0, (qn, bq)
    grid = (qn // bq,)
    return pl.pallas_call(
        functools.partial(_rank_kernel, top_m=top_m, metric=metric),
        grid=grid,
        in_specs=[pl.BlockSpec((bq, d), lambda i: (i, 0)),
                  pl.BlockSpec((bq, eps, d), lambda i: (i, 0, 0))],
        out_specs=[pl.BlockSpec((bq, eps), lambda i: (i, 0)),
                   pl.BlockSpec((bq, top_m), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((qn, eps), jnp.float32),
                   jax.ShapeDtypeStruct((qn, top_m), jnp.int32)],
        interpret=interpret,
    )(queries, tiles)
