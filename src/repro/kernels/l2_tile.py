"""Tiled exact-distance kernel (MXU).

Grid over (query tiles, base tiles); each program computes one
[BQ, BN] distance tile from VMEM-resident [BQ, D] and [BN, D] blocks.
The -2*q@x.T term is the MXU matmul; the norms ride along on the VPU.
BQ/BN default to 128/512 — MXU-aligned (multiples of 128) and well under
VMEM (~128 KiB + 256 KiB + 256 KiB at D=128 f32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BQ = 128
BN = 512


def _l2_kernel(q_ref, x_ref, o_ref, *, metric: str):
    q = q_ref[...].astype(jnp.float32)          # [BQ, D]
    x = x_ref[...].astype(jnp.float32)          # [BN, D]
    dot = jax.lax.dot_general(q, x, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    if metric == "ip":
        o_ref[...] = -dot
    else:
        qq = jnp.sum(q * q, axis=1, keepdims=True)
        xx = jnp.sum(x * x, axis=1)[None, :]
        o_ref[...] = jnp.maximum(qq + xx - 2.0 * dot, 0.0)


def l2_tile(q: jnp.ndarray, x: jnp.ndarray, metric: str = "l2", *,
            interpret: bool, bq: int = BQ, bn: int = BN) -> jnp.ndarray:
    """[Q, D] x [N, D] -> [Q, N] (f32). Q % bq == 0 and N % bn == 0 is
    handled by padding in ops.pairwise_l2."""
    qn, d = q.shape
    n = x.shape[0]
    assert qn % bq == 0 and n % bn == 0, (qn, n, bq, bn)
    grid = (qn // bq, n // bn)
    return pl.pallas_call(
        functools.partial(_l2_kernel, metric=metric),
        grid=grid,
        in_specs=[pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
                  pl.BlockSpec((bn, d), lambda i, j: (j, 0))],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qn, n), jnp.float32),
        interpret=interpret,
    )(q, x)
