"""Inputs of the benchmark, made from ``--seed``.

``clustered_vectors`` and ``query_set`` are copies of the program's
generators (``repro.data.vectors``) as they stood when the benchmark was
written, kept here so that a change to the program's generator cannot
move the yardstick; ``selftest/test_bench_units.py`` pins the two equal.
The program under test receives only the arrays these functions return.
"""
from __future__ import annotations

import numpy as np


def clustered_vectors(n: int, dim: int, num_clusters: int = 64,
                      seed: int = 0, background: float = 0.05,
                      dtype=np.float32) -> np.ndarray:
    """Gaussian clusters with gamma-distributed scales plus a uniform
    background share: BIGANN/DEEP-like structure."""
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((num_clusters, dim)).astype(np.float32)
    centers *= 4.0
    scales = (0.5 + rng.gamma(2.0, 0.5, size=num_clusters)).astype(
        np.float32)
    weights = rng.dirichlet(np.ones(num_clusters) * 2.0)
    assign = rng.choice(num_clusters, size=n, p=weights)
    x = (centers[assign]
         + rng.standard_normal((n, dim)).astype(np.float32)
         * scales[assign][:, None])
    nb = int(n * background)
    if nb:
        idx = rng.choice(n, size=nb, replace=False)
        x[idx] = rng.standard_normal((nb, dim)).astype(np.float32) * 6.0
    return x.astype(dtype)


def query_set(x: np.ndarray, num: int, in_db: bool = False,
              seed: int = 1, jitter: float = 0.1) -> np.ndarray:
    """``num`` base rows, each jittered by Gaussian noise scaled to
    ``jitter`` times the mean absolute coordinate (``in_db`` keeps the
    exact rows)."""
    rng = np.random.default_rng(seed)
    idx = rng.choice(x.shape[0], size=num, replace=False)
    q = x[idx].astype(np.float32).copy()
    if not in_db:
        q += rng.standard_normal(q.shape).astype(np.float32) * (
            jitter * np.abs(q).mean())
    return q


def cell_inputs(cfg: dict, traffic: dict, seed: int):
    """(base vectors [n, dim] f32, query pool [pool, dim] f32) of one
    run. The base vectors and the pool are the configuration's own,
    drawn from its ``data.seed`` as a deployment's data set is fixed.
    The pool is cut into fixed batches of ``traffic["batch"]``; ``seed``
    orders the batches and the rows inside each, so every seed serves
    the same batches, and so the same work, in another order."""
    data = cfg["data"]
    x = clustered_vectors(cfg["n"], cfg["dim"],
                          num_clusters=data["clusters"], seed=data["seed"],
                          background=data["background"])
    pool = query_set(x, traffic["query_pool"], seed=data["seed"] + 1,
                     jitter=traffic["jitter"])
    batch = traffic["batch"]
    if len(pool) % batch:
        raise ValueError(f"query_pool {len(pool)} is not a whole number "
                         f"of batches of {batch}")
    rng = np.random.default_rng([seed % 2 ** 64, 1])
    batches = pool.reshape(-1, batch, pool.shape[1])
    order = rng.permutation(len(batches))
    rows = np.stack([rng.permutation(batch) for _ in order])
    return x, batches[order[:, None], rows].reshape(pool.shape)
