"""The plain reference: exact top-k by brute force, and the comparison
that decides a run's ``correct``.

``exact_knn`` ranks every base row for every query in ``jax.numpy``, in
f32 at ``Precision.HIGHEST`` (a float32 matmul on a TPU runs at bfloat16
otherwise), in blocks of queries and of base rows so that it fits next
to nothing else on the device. Ties go to the lower id. It imports
nothing of the program under test.

``compare`` holds served answers against it:

* ``recall_at_10``: the mean share of the exact top-k ids that were
  served, against the floor the configuration states;
* ``dist_gap``: the widest gap between a served distance and the exact
  distance of the id it was served with (recomputed in float64), as a
  share of the query's exact k-th distance;
* ``bad_rows``: rows with an id outside the data, a repeated id, a
  distance that is not finite, or distances out of order (limit 0).
"""
from __future__ import annotations

import functools

import jax
import numpy as np

PRECISIONS = ("highest", "bf16")


@functools.partial(jax.jit,
                   static_argnames=("k", "precision"))
def _knn_block(q, xb, valid, best_d, best_i, base, *, k: int,
               precision: str):
    """Fold one block of base rows ``xb`` [B, D] (ids base..base+B-1,
    ``valid`` [B]) into the running top-k of queries ``q`` [M, D]."""
    import jax
    import jax.numpy as jnp
    if precision == "bf16":
        q = q.astype(jnp.bfloat16)
        xb = xb.astype(jnp.bfloat16)
        prec = jax.lax.Precision.DEFAULT
    else:
        prec = jax.lax.Precision.HIGHEST
    q2 = jnp.sum(jnp.square(q.astype(jnp.float32)), axis=1)
    x2 = jnp.sum(jnp.square(xb.astype(jnp.float32)), axis=1)
    qx = jnp.dot(q, xb.T, precision=prec,
                 preferred_element_type=jnp.float32)
    d = jnp.maximum(q2[:, None] - 2.0 * qx + x2[None, :], 0.0)
    d = jnp.where(valid[None, :], d, jnp.inf)
    ids = base + jnp.arange(xb.shape[0], dtype=jnp.int32)
    # the running best holds lower ids than this block, and top_k keeps
    # the earlier of equal keys: ties go to the lower id
    cat_d = jnp.concatenate([best_d, d], axis=1)
    cat_i = jnp.concatenate(
        [best_i, jnp.broadcast_to(ids, d.shape)], axis=1)
    neg, pos = jax.lax.top_k(-cat_d, k)
    return -neg, jnp.take_along_axis(cat_i, pos, axis=1)


def exact_knn(x: np.ndarray, queries: np.ndarray, k: int, *,
              precision: str = "highest", query_block: int = 1024,
              row_block: int = 65536):
    """(ids [M, k] int64, dists [M, k] f32) of the exact top-k of every
    query over all rows of ``x``, nearest first. ``precision="bf16"``
    is the lower-precision control: the same ranking from bfloat16
    inputs."""
    import jax.numpy as jnp
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    n, dim = x.shape
    rb = min(row_block, n)
    nb = -(-n // rb)
    xp = np.zeros((nb * rb, dim), np.float32)
    xp[:n] = x
    valid = np.arange(nb * rb) < n
    xd = jnp.asarray(xp)
    vd = jnp.asarray(valid)
    out_i, out_d = [], []
    m = queries.shape[0]
    qb = min(query_block, m)
    for lo in range(0, m, qb):
        q = np.zeros((qb, dim), np.float32)
        rows = min(qb, m - lo)
        q[:rows] = queries[lo:lo + rows]
        qd = jnp.asarray(q)
        best_d = jnp.full((qb, k), jnp.inf, jnp.float32)
        best_i = jnp.full((qb, k), -1, jnp.int32)
        for b in range(nb):
            best_d, best_i = _knn_block(
                qd, xd[b * rb:(b + 1) * rb], vd[b * rb:(b + 1) * rb],
                best_d, best_i, jnp.int32(b * rb), k=k,
                precision=precision)
        out_i.append(np.asarray(best_i)[:rows].astype(np.int64))
        out_d.append(np.asarray(best_d)[:rows])
    return np.concatenate(out_i), np.concatenate(out_d)


def _true_dists(x: np.ndarray, queries: np.ndarray,
                ids: np.ndarray) -> np.ndarray:
    """float64 squared L2 distance of each query to each of its ids
    (ids must be valid rows)."""
    diff = (x[ids].astype(np.float64)
            - queries[:, None, :].astype(np.float64))
    return np.einsum("qkd,qkd->qk", diff, diff)


def compare(x: np.ndarray, queries: np.ndarray, served_ids: np.ndarray,
            served_d: np.ndarray, ref_ids: np.ndarray) -> dict:
    """The numbers that decide ``correct`` for served answers
    (``served_ids``/``served_d`` [M, k], global ids) against the exact
    top-k ``ref_ids`` [M, k] of the same queries."""
    n = x.shape[0]
    m, k = ref_ids.shape
    served_ids = np.asarray(served_ids, np.int64)
    served_d = np.asarray(served_d, np.float64)
    in_range = (served_ids >= 0) & (served_ids < n)
    srt = np.sort(served_ids, axis=1)
    repeated = (srt[:, 1:] == srt[:, :-1]).any(axis=1)
    finite = np.isfinite(served_d).all(axis=1)
    with np.errstate(invalid="ignore"):
        ordered = (np.diff(served_d, axis=1) >= 0).all(axis=1)
    bad = ~in_range.all(axis=1) | repeated | ~finite | ~ordered
    hits = [len(set(a.tolist()) & set(b.tolist()))
            for a, b in zip(served_ids, ref_ids)]
    kth = _true_dists(x, queries, ref_ids[:, -1:])[:, 0]
    safe = np.where(in_range, served_ids, 0)
    true = _true_dists(x, queries, safe)
    gap = np.where(in_range, np.abs(np.nan_to_num(
        served_d, posinf=np.finfo(np.float64).max) - true), 0.0)
    gap = gap / np.maximum(kth, np.finfo(np.float32).tiny)[:, None]
    return {"recall_at_10": float(np.mean(hits)) / k if m else 0.0,
            "dist_gap": float(gap.max()) if m else 0.0,
            "bad_rows": int(bad.sum())}
