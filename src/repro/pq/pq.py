"""Product quantization (Jégou et al. [36]) for memory-resident routing.

Starling (like DiskANN) keeps PQ short codes of *all* vectors in memory and
ranks the candidate queue by asymmetric-distance computation (ADC), saving
full-precision disk reads (§5.1 "PQ-based approximate distance").

Pipeline:
  train_pq   — per-subspace Lloyd k-means (jit'd) on a training sample
  encode_pq  — [N, M] uint8 codes
  adc_lut    — per-query [M, K] lookup table of subspace distances
  adc_distance — sum LUT entries along codes (the Pallas kernel
                 ``repro.kernels.pq_adc`` is the TPU version of this)
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.params import PQParams


@dataclasses.dataclass
class PQCodebook:
    centroids: np.ndarray     # [M, K, dsub] float32
    dim: int
    metric: str = "l2"

    @property
    def num_subspaces(self) -> int:
        return self.centroids.shape[0]

    @property
    def num_centroids(self) -> int:
        return self.centroids.shape[1]

    @property
    def dsub(self) -> int:
        return self.centroids.shape[2]

    def memory_bytes(self) -> int:
        return self.centroids.nbytes


# full f32 products: an accelerator's default matmul precision rounds
# inputs to bf16, which flips nearest-centroid picks (|x|^2 + |c|^2 -
# 2 x.c cancels) and leaves the codebook coarser than the f32 config says
_HIGHEST = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnames=("iters",))
def _lloyd(x: jnp.ndarray, init: jnp.ndarray, iters: int) -> jnp.ndarray:
    """x [N, d], init [K, d] -> [K, d]. Empty clusters keep their centroid."""
    def step(cent, _):
        d = (jnp.sum(x * x, 1, keepdims=True) + jnp.sum(cent * cent, 1)
             - 2.0 * jnp.matmul(x, cent.T, precision=_HIGHEST))
        a = jnp.argmin(d, axis=1)
        one = jax.nn.one_hot(a, cent.shape[0], dtype=x.dtype)   # [N, K]
        cnt = one.sum(0)
        tot = jnp.matmul(one.T, x, precision=_HIGHEST)
        new = jnp.where(cnt[:, None] > 0, tot / jnp.maximum(cnt[:, None], 1),
                        cent)
        return new, None
    cent, _ = jax.lax.scan(step, init, None, length=iters)
    return cent


def train_pq(x: np.ndarray, p: PQParams, metric: str = "l2") -> PQCodebook:
    n, d = x.shape
    m = p.num_subspaces
    assert d % m == 0, f"dim {d} not divisible by M={m}"
    dsub = d // m
    k = min(p.num_centroids, n)
    rng = np.random.default_rng(p.seed)
    sample = x[rng.choice(n, size=min(p.train_sample, n), replace=False)]
    cent = np.empty((m, p.num_centroids, dsub), np.float32)
    for j in range(m):
        sub = sample[:, j * dsub:(j + 1) * dsub].astype(np.float32)
        init = sub[rng.choice(sub.shape[0], size=k, replace=False)]
        c = np.asarray(_lloyd(jnp.asarray(sub), jnp.asarray(init),
                              p.train_iters))
        if k < p.num_centroids:   # tiny datasets: tile to K
            reps = -(-p.num_centroids // k)
            c = np.tile(c, (reps, 1))[: p.num_centroids]
        cent[j] = c
    return PQCodebook(centroids=cent, dim=d, metric=metric)


@jax.jit
def _encode(x: jnp.ndarray, cent: jnp.ndarray) -> jnp.ndarray:
    """x [N, M, dsub], cent [M, K, dsub] -> codes [N, M] uint8."""
    d = (jnp.sum(x * x, -1)[:, :, None]
         + jnp.sum(cent * cent, -1)[None]
         - 2.0 * jnp.einsum("nmd,mkd->nmk", x, cent, precision=_HIGHEST))
    return jnp.argmin(d, axis=-1).astype(jnp.uint8)


def encode_pq(x: np.ndarray, cb: PQCodebook, chunk: int = 65536
              ) -> np.ndarray:
    n, d = x.shape
    m, dsub = cb.num_subspaces, cb.dsub
    out = np.empty((n, m), np.uint8)
    cent = jnp.asarray(cb.centroids)
    for s in range(0, n, chunk):
        xs = x[s:s + chunk].astype(np.float32).reshape(-1, m, dsub)
        out[s:s + chunk] = np.asarray(_encode(jnp.asarray(xs), cent))
    return out


def adc_lut(q: np.ndarray, cb: PQCodebook) -> np.ndarray:
    """Query LUT [M, K]: subspace distance from q's sub-vector to each
    centroid. For IP the LUT holds negated partial inner products so that
    summation stays 'smaller is better'."""
    m, k, dsub = cb.centroids.shape
    qs = q.astype(np.float32).reshape(m, 1, dsub)
    if cb.metric == "ip":
        return -(cb.centroids * qs).sum(-1)
    diff = cb.centroids - qs
    return np.einsum("mkd,mkd->mk", diff, diff)


def adc_lut_batch(q: np.ndarray, cb: PQCodebook) -> np.ndarray:
    """[Q, D] -> [Q, M, K]."""
    m, k, dsub = cb.centroids.shape
    qs = q.astype(np.float32).reshape(q.shape[0], m, 1, dsub)
    if cb.metric == "ip":
        return -(cb.centroids[None] * qs).sum(-1)
    diff = cb.centroids[None] - qs
    return np.einsum("qmkd,qmkd->qmk", diff, diff)


def adc_distance(lut: np.ndarray, codes: np.ndarray) -> np.ndarray:
    """lut [M, K], codes [n, M] -> [n] approximate distances (numpy ref)."""
    m = lut.shape[0]
    return lut[np.arange(m)[None, :], codes.astype(np.int64)].sum(axis=1)


def reconstruct(codes: np.ndarray, cb: PQCodebook) -> np.ndarray:
    """Decode codes back to vectors (for error bounds in tests)."""
    m, _, dsub = cb.centroids.shape
    parts = [cb.centroids[j, codes[:, j].astype(np.int64)]
             for j in range(m)]
    return np.concatenate(parts, axis=1)
