"""Distance primitives.

Host-side (numpy, BLAS-backed) helpers for index construction and the
reference search implementation, plus jit'd chunked brute force used for
ground truth and KNN-graph seeding.

Conventions: ``l2`` returns *squared* Euclidean distance (monotone in the
true metric, as in DiskANN/Starling implementations); ``ip`` returns the
negated inner product so that smaller is always better.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def pairwise(a: np.ndarray, b: np.ndarray, metric: str = "l2") -> np.ndarray:
    """[Na, D] x [Nb, D] -> [Na, Nb] distance matrix (numpy, float32)."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    dot = a @ b.T
    if metric == "ip":
        return -dot
    na = np.sum(a * a, axis=1, keepdims=True)
    nb = np.sum(b * b, axis=1, keepdims=True)
    d = na + nb.T - 2.0 * dot
    return np.maximum(d, 0.0)


@functools.partial(jax.jit, static_argnames=("metric",))
def pairwise_jit(a: jnp.ndarray, b: jnp.ndarray, metric: str = "l2"):
    # full f32 products: an accelerator's default matmul precision
    # rounds inputs to bf16, which would make "exact" ground truth
    # approximate
    dot = jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)
    if metric == "ip":
        return -dot
    na = jnp.sum(a * a, axis=1, keepdims=True)
    nb = jnp.sum(b * b, axis=1, keepdims=True)
    return jnp.maximum(na + nb.T - 2.0 * dot, 0.0)


def point_to_points(q: np.ndarray, x: np.ndarray, metric: str = "l2"
                    ) -> np.ndarray:
    """[D] x [N, D] -> [N]."""
    q = np.asarray(q, np.float32)
    x = np.asarray(x, np.float32)
    if metric == "ip":
        return -(x @ q)
    diff = x - q[None, :]
    return np.einsum("nd,nd->n", diff, diff)


# bytes of one [chunk, N] f32 distance block: 1 GiB keeps a block and
# its top-k working set well inside one accelerator's HBM at N = 1M
BLOCK_BYTES = 1 << 30


def _rows(chunk: int, n: int) -> int:
    """Query rows per distance block: ``chunk``, capped so that a
    [rows, n] f32 block stays within ``BLOCK_BYTES``."""
    return max(1, min(chunk, BLOCK_BYTES // (4 * max(n, 1))))


def point_pairs(a: np.ndarray, b: np.ndarray, metric: str = "l2"
                ) -> np.ndarray:
    """Row-wise distances: [N, D] x [N, D] -> [N]."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    if metric == "ip":
        return -np.einsum("nd,nd->n", a, b)
    diff = a - b
    return np.einsum("nd,nd->n", diff, diff)


# columns per group of the two-stage top-k: one lane tile
GROUP = 128


def smallest_k(d: jnp.ndarray, k: int) -> jnp.ndarray:
    """Ids of the ``k`` smallest entries of each row of ``d`` [r, n], in
    ``jax.lax.top_k(-d, k)`` order (value, then lower index) — the same
    ids, found without a top-k over rows of n.

    The columns are cut into groups of ``GROUP``. The k groups with the
    smallest minima hold every one of the row's k smallest entries
    (each of those lies in a group whose minimum is no larger than the
    k-th smallest entry, and at most k groups have such a minimum; the
    group top-k takes the lower group at a tie, as the plain top-k takes
    the lower index). Taken in ascending group order, their columns keep
    index order, so the final top-k over k * GROUP candidates breaks
    ties as the plain one does."""
    r, n = d.shape
    if n <= 2 * k * GROUP:
        return jax.lax.top_k(-d, k)[1]
    pad = (-n) % GROUP
    grp = jnp.pad(d, ((0, 0), (0, pad)),
                  constant_values=jnp.inf).reshape(r, -1, GROUP)
    _, gi = jax.lax.top_k(-grp.min(axis=2), k)
    gi = jnp.sort(gi, axis=1)                                   # [r, k]
    cand = jnp.take_along_axis(grp, gi[:, :, None], axis=1)
    _, ci = jax.lax.top_k(-cand.reshape(r, k * GROUP), k)
    return (jnp.take_along_axis(gi, ci // GROUP, axis=1) * GROUP
            + ci % GROUP)


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _knn_block(q: jnp.ndarray, x: jnp.ndarray, k: int, metric: str):
    return smallest_k(pairwise_jit(q, x, metric=metric), k)


def brute_force_knn(x: np.ndarray, q: np.ndarray, k: int,
                    metric: str = "l2", chunk: int = 4096) -> np.ndarray:
    """Exact top-k ids for each query row (ground truth). [Nq, k] int32."""
    x = np.asarray(x, np.float32)
    q = np.asarray(q, np.float32)
    out = np.empty((q.shape[0], k), np.int32)
    xj = jnp.asarray(x)
    chunk = _rows(chunk, x.shape[0])
    for s in range(0, q.shape[0], chunk):
        idx = _knn_block(jnp.asarray(q[s:s + chunk]), xj, k=k,
                         metric=metric)
        out[s:s + chunk] = np.asarray(idx, np.int32)
    return out


def brute_force_range(x: np.ndarray, q: np.ndarray, radius: float,
                      metric: str = "l2", chunk: int = 2048):
    """Exact range-search ground truth: list of id arrays per query."""
    x = np.asarray(x, np.float32)
    out = []
    chunk = _rows(chunk, x.shape[0])
    for s in range(0, q.shape[0], chunk):
        d = np.asarray(pairwise_jit(jnp.asarray(q[s:s + chunk]),
                                    jnp.asarray(x), metric=metric))
        for row in d:
            out.append(np.where(row <= radius)[0].astype(np.int32))
    return out


def knn_graph(x: np.ndarray, k: int, metric: str = "l2",
              chunk: int = 2048) -> np.ndarray:
    """Exact KNN graph over x (excluding self). [N, k] int32."""
    n = x.shape[0]
    ids = brute_force_knn(x, x, min(k + 1, n), metric=metric, chunk=chunk)
    if n > k:
        # each row's k+1 ids are distinct: dropping self (or, where a
        # duplicate pushed self out, the last id) leaves k, in order
        keep = ids != np.arange(n)[:, None]
        pos = np.argsort(~keep, axis=1, kind="stable")[:, :k]
        return np.take_along_axis(ids, pos, axis=1)
    out = np.empty((n, k), np.int32)
    for i in range(n):
        row = ids[i]
        row = row[row != i][:k]
        if row.shape[0] < k:  # degenerate duplicates; pad with self-exclusions
            pad = np.setdiff1d(np.arange(min(n, k + 2)), np.append(row, i))
            row = np.append(row, pad)[:k]
        out[i] = row
    return out
