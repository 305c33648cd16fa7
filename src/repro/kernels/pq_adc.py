"""Batched PQ asymmetric-distance (ADC) kernel.

TPU adaptation (DESIGN.md §2): the CPU implementation is a per-subspace
table *gather*, which the TPU vector unit does poorly. Instead each code
tile is expanded to a one-hot [BN, M*K] matrix in VMEM and multiplied
against the flattened LUTs [M*K, B] on the MXU — one matmul scores a tile
of database codes against *all* queries in the batch.

Grid over code tiles; LUTs stay VMEM-resident across the grid
(index_map pins block (0, 0)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BN = 256


def _adc_kernel(codes_ref, luts_ref, o_ref, *, num_centroids: int):
    codes = codes_ref[...].astype(jnp.int32)        # [BN, M]
    luts = luts_ref[...]                            # [M*K, B] f32
    bn, m = codes.shape
    k = num_centroids
    # one-hot over the flattened (M, K) axis: row i has ones at
    # positions j*K + codes[i, j]
    flat_idx = codes + (jnp.arange(m, dtype=jnp.int32) * k)[None, :]
    iota = jax.lax.broadcasted_iota(jnp.int32, (bn, m, k), 2) \
        + (jnp.arange(m, dtype=jnp.int32) * k)[None, :, None]
    onehot = (iota == flat_idx[:, :, None]).astype(jnp.float32)
    onehot = onehot.reshape(bn, m * k)
    o_ref[...] = jax.lax.dot_general(
        onehot, luts, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # [BN, B]


def pq_adc(codes: jnp.ndarray, luts: jnp.ndarray, *,
           interpret: bool, bn: int = BN) -> jnp.ndarray:
    """codes [N, M] uint8, luts [B, M, K] f32 -> [N, B] distances."""
    n, m = codes.shape
    b, m2, k = luts.shape
    assert m == m2 and n % bn == 0, (n, m, m2, bn)
    luts_flat = jnp.moveaxis(luts.reshape(b, m * k), 0, 1)  # [M*K, B]
    grid = (n // bn,)
    return pl.pallas_call(
        functools.partial(_adc_kernel, num_centroids=k),
        grid=grid,
        in_specs=[pl.BlockSpec((bn, m), lambda i: (i, 0)),
                  pl.BlockSpec((m * k, b), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bn, b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, b), jnp.float32),
        interpret=interpret,
    )(codes, luts_flat)
