"""Graph-index construction (Vamana / NSG-flavour / HNSW-flavour).

The paper deliberately reuses existing graph algorithms (§4: "We do not focus
on developing a specific graph index algorithm") — Starling's contribution is
the *layout* and *search strategy* around them. We therefore implement the
standard constructions:

  * ``vamana`` — DiskANN's graph [35]: iterative insertion, greedy search for
    candidates, RobustPrune(α), reverse-edge insertion. Insertions are batched
    (as in the parallel DiskANN build) for single-core throughput.
  * ``nsg``    — NSG-flavour [25]: exact KNN seed graph + MRNG-style prune
    (RobustPrune with α=1) + reverse-edge fill + connectivity fix from the
    medoid.
  * ``hnsw``   — HNSW-flavour [49]: geometric level assignment; each level is
    a pruned KNN graph over its subset; level 0 is the disk graph and upper
    levels form the in-memory multi-layer navigation structure (Fig. 16(b)).

Adjacency is stored dense: ``adj [N, Λ] int32`` padded with -1 and
``deg [N] int32`` — exactly the on-disk vertex format (vector ‖ λ ‖ Λ ids).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import breadth_first_order, connected_components

from repro.core import distances as D
from repro.core.params import GraphParams


@dataclasses.dataclass
class Graph:
    adj: np.ndarray          # [N, Λ] int32, -1 padded
    deg: np.ndarray          # [N] int32
    entry: int               # medoid / entry vertex id
    metric: str = "l2"

    @property
    def num_vertices(self) -> int:
        return self.adj.shape[0]

    @property
    def max_degree(self) -> int:
        return self.adj.shape[1]

    def neighbors(self, u: int) -> np.ndarray:
        return self.adj[u, : self.deg[u]]

    def avg_degree(self) -> float:
        return float(self.deg.mean())

    def edges(self) -> np.ndarray:
        """[(u, v)] edge list, [E, 2] int32 (deg-masked: slots past
        deg[u] are ignored even if non-negative)."""
        mask = (np.arange(self.max_degree)[None, :] < self.deg[:, None])
        mask &= self.adj >= 0
        u = np.repeat(np.arange(self.num_vertices, dtype=np.int32),
                      mask.sum(axis=1))
        v = self.adj[mask]
        return np.stack([u, v.astype(np.int32)], axis=1)


def medoid(x: np.ndarray, metric: str = "l2") -> int:
    mean = x.mean(axis=0)
    return int(np.argmin(D.point_to_points(mean, x, metric)))


def robust_prune(u: int, cand_ids: np.ndarray, cand_dist: np.ndarray,
                 x: np.ndarray, max_degree: int, alpha: float,
                 metric: str = "l2") -> np.ndarray:
    """DiskANN RobustPrune: keep v only if no kept w has
    α·dist(w, v) <= dist(u, v). Returns selected ids (≤ Λ)."""
    order = np.argsort(cand_dist, kind="stable")
    ids = cand_ids[order]
    dist_u = cand_dist[order]
    keep = ~(ids == u)
    ids, dist_u = ids[keep], dist_u[keep]
    # dedupe, stable
    _, first = np.unique(ids, return_index=True)
    sel_mask = np.zeros(ids.shape[0], bool)
    sel_mask[np.sort(first)] = True
    ids, dist_u = ids[sel_mask], dist_u[sel_mask]

    selected: List[int] = []
    alive = np.ones(ids.shape[0], bool)
    for i in range(ids.shape[0]):
        if not alive[i]:
            continue
        v = int(ids[i])
        selected.append(v)
        if len(selected) >= max_degree:
            break
        rest = np.where(alive)[0]
        rest = rest[rest > i]
        if rest.size:
            dv = D.point_to_points(x[v], x[ids[rest]], metric)
            alive[rest[alpha * dv <= dist_u[rest]]] = False
    return np.asarray(selected, np.int32)


def greedy_search_batch(x: np.ndarray, adj: np.ndarray, deg: np.ndarray,
                        entry: int, queries: np.ndarray, beam: int,
                        metric: str = "l2", max_hops: int = 512,
                        ) -> Tuple[np.ndarray, np.ndarray, List[dict]]:
    """Batched best-first (beam) search on the current graph.

    Returns (ids [B, beam], dists [B, beam], visited list-of-dicts
    {id: dist}) — visited sets feed RobustPrune during construction.
    Vectorized across the batch; the hop loop is host-level (as in any
    CPU graph build).
    """
    B = queries.shape[0]
    INF = np.float32(np.inf)
    cand_ids = np.full((B, beam), -1, np.int64)
    cand_dist = np.full((B, beam), INF, np.float32)
    expanded = np.zeros((B, beam), bool)
    d0 = D.pairwise(queries, x[entry][None, :], metric)[:, 0]
    cand_ids[:, 0] = entry
    cand_dist[:, 0] = d0
    visited = [{int(entry): float(d0[b])} for b in range(B)]

    for _ in range(max_hops):
        # pick first unexpanded candidate per query
        open_mask = (~expanded) & (cand_ids >= 0)
        has_open = open_mask.any(axis=1)
        if not has_open.any():
            break
        first_open = np.where(open_mask, np.arange(beam)[None, :], beam)
        pick = first_open.min(axis=1)          # [B]
        rows = np.where(has_open)[0]
        picks = pick[rows]
        expanded[rows, picks] = True
        cur = cand_ids[rows, picks].astype(np.int64)

        nbr = adj[cur]                          # [R, Λ]
        valid = nbr >= 0
        # distances for all (row, neighbor) pairs in one BLAS call
        flat_ids = nbr[valid]
        if flat_ids.size == 0:
            continue
        row_of = np.repeat(np.arange(rows.size), valid.sum(axis=1))
        dists = np.einsum(
            "nd,nd->n",
            x[flat_ids] - queries[rows][row_of],
            x[flat_ids] - queries[rows][row_of]) if metric == "l2" else \
            -np.einsum("nd,nd->n", x[flat_ids], queries[rows][row_of])

        # merge per row (python loop over batch rows; candidate arrays tiny)
        ptr = 0
        counts = valid.sum(axis=1)
        for ri, b in enumerate(rows):
            cnt = counts[ri]
            ids_r = flat_ids[ptr:ptr + cnt]
            d_r = dists[ptr:ptr + cnt]
            ptr += cnt
            vb = visited[b]
            new_mask = np.fromiter((int(i) not in vb for i in ids_r),
                                   bool, cnt)
            if not new_mask.any():
                continue
            ids_n, d_n = ids_r[new_mask], d_r[new_mask]
            for i, dd in zip(ids_n, d_n):
                vb[int(i)] = float(dd)
            merged_ids = np.concatenate([cand_ids[b], ids_n])
            merged_d = np.concatenate([cand_dist[b], d_n])
            merged_e = np.concatenate([expanded[b],
                                       np.zeros(ids_n.shape[0], bool)])
            order = np.argsort(merged_d, kind="stable")[:beam]
            cand_ids[b] = merged_ids[order]
            cand_dist[b] = merged_d[order]
            expanded[b] = merged_e[order]
    return cand_ids, cand_dist, visited


def _add_reverse_edges(x, adj, deg, batch_ids, max_degree, alpha, metric):
    """After inserting batch vertices, add reverse edges u->v => v->u with
    RobustPrune on overflow (DiskANN insert step 3)."""
    pending: dict = {}
    for u in batch_ids:
        for v in adj[u, : deg[u]]:
            pending.setdefault(int(v), []).append(int(u))
    for v, new_in in pending.items():
        room = max_degree - deg[v]
        uniq = [w for w in dict.fromkeys(new_in)
                if w not in set(adj[v, : deg[v]].tolist())]
        if not uniq:
            continue
        if len(uniq) <= room:
            adj[v, deg[v]: deg[v] + len(uniq)] = uniq
            deg[v] += len(uniq)
        else:
            cand = np.concatenate([adj[v, : deg[v]],
                                   np.asarray(uniq, np.int32)])
            cd = D.point_to_points(x[v], x[cand], metric)
            sel = robust_prune(v, cand, cd, x, max_degree, alpha, metric)
            adj[v] = -1
            adj[v, : sel.shape[0]] = sel
            deg[v] = sel.shape[0]


def build_vamana(x: np.ndarray, p: GraphParams, metric: str = "l2") -> Graph:
    """Batched-insertion Vamana (DiskANN §Algorithm 1–3)."""
    n = x.shape[0]
    L, R, alpha = p.build_beam, p.max_degree, p.alpha
    rng = np.random.default_rng(p.seed)
    adj = np.full((n, R), -1, np.int32)
    deg = np.zeros(n, np.int32)
    ep = medoid(x, metric)

    order = rng.permutation(n)
    # seed: connect a small bootstrap clique around the medoid
    boot = order[: min(R + 1, n)]
    for i, u in enumerate(boot):
        others = np.delete(boot, i)[: R]
        dd = D.point_to_points(x[u], x[others], metric)
        sel = robust_prune(int(u), others.astype(np.int32),
                           dd.astype(np.float32), x, R, alpha, metric)
        adj[u, : sel.shape[0]] = sel
        deg[u] = sel.shape[0]

    done = set(int(b) for b in boot)
    todo = [int(u) for u in order if int(u) not in done]
    for s in range(0, len(todo), p.insert_batch):
        batch = np.asarray(todo[s: s + p.insert_batch], np.int64)
        _, _, visited = greedy_search_batch(
            x, adj, deg, ep, x[batch], beam=L, metric=metric)
        for bi, u in enumerate(batch):
            vis = visited[bi]
            ids = np.fromiter(vis.keys(), np.int32, len(vis))
            dd = np.fromiter(vis.values(), np.float32, len(vis))
            # fold in any reverse edges already attached to u so they
            # survive its own insertion prune
            if deg[u]:
                prev = adj[u, : deg[u]]
                ids = np.concatenate([ids, prev])
                dd = np.concatenate(
                    [dd, D.point_to_points(x[u], x[prev], metric)])
            sel = robust_prune(int(u), ids, dd, x, R, alpha, metric)
            adj[u] = -1                       # clear stale slots
            adj[u, : sel.shape[0]] = sel
            deg[u] = sel.shape[0]
        _add_reverse_edges(x, adj, deg, batch, R, alpha, metric)
    g = Graph(adj=adj, deg=deg, entry=ep, metric=metric)
    _ensure_reachable(x, g)
    return g


@functools.partial(jax.jit, static_argnames=("max_degree", "metric"))
def _prune_rows(xu, xc, alpha, max_degree: int, metric: str):
    """RobustPrune for a batch of vertices at once (the device form of
    ``robust_prune``): xu [B, D] vertices, xc [B, K, D] their distinct
    candidates (self excluded) -> (order [B, K] candidate positions
    sorted by distance to the vertex, keep [B, K] bool in that order)."""
    dot = jnp.einsum("bkd,bjd->bkj", xc, xc,
                     precision=jax.lax.Precision.HIGHEST)
    if metric == "ip":
        du = -jnp.sum(xu[:, None, :] * xc, axis=-1)
        pd = -dot
    else:
        du = jnp.sum(jnp.square(xc - xu[:, None, :]), axis=-1)
        sq = jnp.sum(xc * xc, axis=-1)
        pd = jnp.maximum(sq[:, :, None] + sq[:, None, :] - 2.0 * dot, 0.0)
    order = jnp.argsort(du, axis=1)                   # stable
    du = jnp.take_along_axis(du, order, axis=1)
    pd = jnp.take_along_axis(
        jnp.take_along_axis(pd, order[:, :, None], axis=1),
        order[:, None, :], axis=2)
    b, k = du.shape
    later = jnp.arange(k)[None, :]

    def body(i, st):
        alive, keep, cnt = st
        take = alive[:, i] & (cnt < max_degree)
        keep = keep.at[:, i].set(take)
        kill = take[:, None] & (alpha * pd[:, i, :] <= du) & (later > i)
        return alive & ~kill, keep, cnt + take

    _, keep, _ = jax.lax.fori_loop(
        0, k, body, (jnp.ones((b, k), bool), jnp.zeros((b, k), bool),
                     jnp.zeros((b,), jnp.int32)))
    return order, keep


def prune_knn(x: np.ndarray, knn: np.ndarray, max_degree: int,
              alpha: float, metric: str = "l2",
              chunk: int = 8192) -> Tuple[np.ndarray, np.ndarray]:
    """RobustPrune every vertex's kNN row (``knn`` [N, K], self
    excluded, distinct ids) in device batches of ``chunk`` rows ->
    (adj [N, Λ] -1 padded, deg [N])."""
    n, k = knn.shape
    adj = np.full((n, max_degree), -1, np.int32)
    xj = jnp.asarray(x, jnp.float32)
    for s in range(0, n, chunk):
        rows = jnp.asarray(knn[s:s + chunk])
        order, keep = _prune_rows(xj[s:s + rows.shape[0]], xj[rows],
                                  alpha, max_degree, metric)
        ids = np.take_along_axis(knn[s:s + chunk], np.asarray(order), 1)
        keep = np.asarray(keep)
        # compact the kept ids to the front of each row, in order
        pos = np.argsort(~keep, axis=1, kind="stable")[:, :max_degree]
        sel = np.take_along_axis(ids, pos, axis=1)
        adj[s:s + chunk, :pos.shape[1]] = np.where(
            np.take_along_axis(keep, pos, axis=1), sel, -1)
    return adj, (adj >= 0).sum(axis=1).astype(np.int32)


def _fill_reverse_edges(x: np.ndarray, adj: np.ndarray, deg: np.ndarray,
                        metric: str = "l2", chunk: int = 1 << 20) -> None:
    """For every edge u->v, add v->u into v's spare slots, nearest u
    first (the reverse-edge insertion of NSG's link step, without the
    prune). Pruned kNN graphs are directed and local; the reverse
    edges make them close to symmetric, so nearly every vertex is
    reachable from the entry before ``_ensure_reachable`` runs."""
    n, big_r = adj.shape
    mask = np.arange(big_r)[None, :] < deg[:, None]
    u = np.repeat(np.arange(n, dtype=np.int64), deg)
    v = adj[mask].astype(np.int64)
    have = np.sort(u * n + v)
    rev = v * n + u
    pos = np.minimum(np.searchsorted(have, rev), have.shape[0] - 1)
    new = have[pos] != rev                     # v->u not already there
    u, v = u[new], v[new]
    d = np.concatenate([
        D.point_pairs(x[u[s:s + chunk]], x[v[s:s + chunk]], metric)
        for s in range(0, u.shape[0], chunk)]) if u.size else np.zeros(0)
    order = np.lexsort((d, v))
    u, v = u[order], v[order]
    rank = np.arange(v.shape[0]) - np.searchsorted(v, v, side="left")
    take = rank < big_r - deg[v]
    u, v, rank = u[take], v[take], rank[take]
    adj[v, deg[v] + rank] = u
    deg += np.bincount(v, minlength=n).astype(deg.dtype)


def build_nsg(x: np.ndarray, p: GraphParams, metric: str = "l2") -> Graph:
    """NSG-flavour: exact KNN seed + α=1 prune (device-batched) +
    reverse-edge fill + connectivity fix. The reverse edges make the
    graph denser than the pruned kNN graph alone (mean degree 13.6
    against 10.6 at Λ=31 on 20k clustered 128-d vectors), at equal or
    higher recall for a given Γ."""
    n = x.shape[0]
    R = p.max_degree
    k = min(max(2 * R, p.build_beam), n - 1)
    knn = D.knn_graph(x, k, metric)
    adj, deg = prune_knn(x, knn, R, 1.0, metric)
    _fill_reverse_edges(x, adj, deg, metric)
    g = Graph(adj=adj, deg=deg, entry=medoid(x, metric), metric=metric)
    _ensure_reachable(x, g)
    return g


def _reachable(g: Graph) -> np.ndarray:
    """Vertices reachable from ``g.entry`` over deg-masked edges."""
    e = g.edges()
    n = g.num_vertices
    m = csr_matrix((np.ones(e.shape[0], np.int8), (e[:, 0], e[:, 1])),
                   shape=(n, n))
    seen = np.zeros(n, bool)
    seen[breadth_first_order(m, g.entry, directed=True,
                             return_predecessors=False)] = True
    return seen


def _unreachable_roots(g: Graph, missing: np.ndarray) -> np.ndarray:
    """One vertex (the lowest id) of each source component of the
    unreachable subgraph: its strongly connected components that no
    other unreachable component has an edge into. Every unreachable
    vertex is reachable from one of them, so attaching these makes the
    whole graph reachable."""
    n = g.num_vertices
    e = g.edges()
    e = e[missing[e[:, 0]] & missing[e[:, 1]]]
    m = csr_matrix((np.ones(e.shape[0], np.int8), (e[:, 0], e[:, 1])),
                   shape=(n, n))
    _, lab = connected_components(m, directed=True, connection="strong")
    fed = np.zeros(n, bool)                    # component has an in-edge
    cross = lab[e[:, 0]] != lab[e[:, 1]]
    fed[lab[e[cross, 1]]] = True
    ids = np.flatnonzero(missing)
    ids = ids[~fed[lab[ids]]]
    _, first = np.unique(lab[ids], return_index=True)
    return ids[np.sort(first)]


def _ensure_reachable(x: np.ndarray, g: Graph, max_rounds: int = 16
                      ) -> None:
    """Attach unreachable vertices to their nearest reachable vertex
    (NSG spanning-tree fix). Only one vertex per source component of
    the unreachable subgraph is attached; the rest become reachable
    through it. Hosts with spare degree get a new edge; full hosts
    sacrifice their last slot — which can orphan a previously
    reachable vertex, so reachability is re-verified until it
    converges.
    """
    for _ in range(max_rounds):
        seen = _reachable(g)
        missing = ~seen
        if not missing.any():
            return
        reach = np.where(seen)[0]
        used_slots: set = set()
        roots = _unreachable_roots(g, missing)
        # the 8 nearest reachable hosts of every root, in one batch
        near = reach[D.brute_force_knn(x[reach], x[roots],
                                       min(8, reach.size), g.metric)]
        for u, hosts in zip(roots, near):
            placed = False
            for h in hosts:                    # prefer a near host w/room
                h = int(h)
                if g.deg[h] < g.max_degree:
                    g.adj[h, g.deg[h]] = u
                    g.deg[h] += 1
                    placed = True
                    break
            if not placed:                     # any reachable host w/room
                room = g.deg[reach] < g.max_degree
                if room.any():
                    cand = reach[room]
                    h = int(cand[np.argmin(
                        D.point_to_points(x[u], x[cand], g.metric))])
                    g.adj[h, g.deg[h]] = u
                    g.deg[h] += 1
                    placed = True
            if not placed:                     # overwrite a full host's
                order = np.argsort(             # last slot (once/round)
                    D.point_to_points(x[u], x[reach], g.metric))
                for oi in order:
                    h = int(reach[oi])
                    slot = g.deg[h] - 1
                    if (h, slot) not in used_slots:
                        g.adj[h, slot] = u
                        used_slots.add((h, slot))
                        break
    assert _reachable(g).all(), "connectivity fix did not converge"


@dataclasses.dataclass
class HNSWGraph:
    """Multi-layer structure; ``layers[0]`` is the (disk) base graph and
    ``layers[1:]`` + ``level_ids`` form the in-memory upper layers."""
    layers: List[Graph]
    level_ids: List[np.ndarray]   # global ids of vertices on each level
    metric: str = "l2"

    @property
    def base(self) -> Graph:
        return self.layers[0]


def build_hnsw(x: np.ndarray, p: GraphParams, metric: str = "l2",
               level_mult: Optional[float] = None) -> HNSWGraph:
    n = x.shape[0]
    rng = np.random.default_rng(p.seed)
    m = p.max_degree
    level_mult = level_mult or 1.0 / np.log(max(m, 2))
    levels = np.minimum(
        (-np.log(rng.uniform(size=n) + 1e-12) * level_mult).astype(np.int32),
        6)
    max_level = int(levels.max())
    layers: List[Graph] = []
    level_ids: List[np.ndarray] = []
    for lv in range(max_level + 1):
        ids = np.where(levels >= lv)[0].astype(np.int32)
        if ids.size < 2:
            break
        sub = x[ids]
        deg_cap = m if lv == 0 else max(m // 2, 4)
        gp = dataclasses.replace(p, max_degree=deg_cap,
                                 build_beam=max(p.build_beam, deg_cap))
        g = (build_vamana(sub, gp, metric) if lv == 0 and ids.size > 512
             else build_nsg(sub, gp, metric))
        layers.append(g)
        level_ids.append(ids)
    return HNSWGraph(layers=layers, level_ids=level_ids, metric=metric)


def build_graph(x: np.ndarray, p: GraphParams, metric: str = "l2") -> Graph:
    if p.algo == "vamana":
        return build_vamana(x, p, metric)
    if p.algo == "nsg":
        return build_nsg(x, p, metric)
    if p.algo == "hnsw":
        return build_hnsw(x, p, metric).base
    raise ValueError(p.algo)
