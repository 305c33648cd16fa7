"""Block-level graph layout and block shuffling (§4.1).

A layout assigns |V| vertices to ρ blocks of capacity ε. The objective is to
maximize the overlap ratio

    OR(u) = |B(u) ∩ N(u)| / (|B(u)| − 1)        (Eq. 5)
    OR(G) = mean_u OR(u)

which Theorem 4.1 shows is NP-hard to optimize (no finite-factor poly-time
approximation unless P=NP). We implement the paper's three heuristics:

  * BNP — Block Neighbor Padding (one pass, Example 4)
  * BNF — Block Neighbor Frequency (Algorithm 1)
  * BNS — Block Neighbor Swap (Algorithm 3, Lemma 4.2 monotone)

plus the DiskANN baseline (ID-contiguous), a k-means packer (the §7
"naive strategy" comparison), and a GP3-style prioritized-gain restreaming
variant (App. G) for the graph-partitioning comparison.

All of these are pure integer/statistics passes over the adjacency — no
vector-distance computation — exactly as the paper stresses for its
"Time cost" analysis.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

from repro.core.graph import Graph


@dataclasses.dataclass
class BlockLayout:
    """blocks[b] lists vertex ids in block b (-1 padded);
    block_of[u] / slot_of[u] invert the map (the C_mapping of Eq. 10)."""
    blocks: np.ndarray        # [ρ, ε] int32, -1 padded
    block_of: np.ndarray      # [N] int32
    slot_of: np.ndarray       # [N] int32

    @property
    def num_blocks(self) -> int:
        return self.blocks.shape[0]

    @property
    def verts_per_block(self) -> int:
        return self.blocks.shape[1]

    def validate(self) -> None:
        """Layout must be a bijection V -> (block, slot)."""
        n = self.block_of.shape[0]
        flat = self.blocks[self.blocks >= 0]
        assert flat.shape[0] == n, "every vertex assigned exactly once"
        assert np.array_equal(np.sort(flat), np.arange(n)), "permutation"
        assert np.all(
            self.blocks[self.block_of, self.slot_of] == np.arange(n))

    def mapping_bytes(self) -> int:
        """C_mapping memory charge (Eq. 10): block id + slot per vertex."""
        return self.block_of.nbytes + self.slot_of.nbytes


def _from_block_of(block_of: np.ndarray, rho: int, eps: int) -> BlockLayout:
    """Each block's vertices in ascending id order, in slots 0, 1, ..."""
    n = block_of.shape[0]
    blocks = np.full((rho, eps), -1, np.int32)
    order = np.argsort(block_of, kind="stable")
    b = block_of[order]
    slot = np.arange(n) - np.searchsorted(b, b, side="left")
    blocks[b, slot] = order
    slot_of = np.empty(n, np.int32)
    slot_of[order] = slot
    return BlockLayout(blocks=blocks, block_of=block_of.astype(np.int32),
                       slot_of=slot_of)


def _block_counts(src: np.ndarray, blk: np.ndarray, rho: int):
    """Edges ``src`` -> a neighbor in block ``blk`` (``src`` ascending) ->
    (u, b, count) of each distinct (vertex, block) pair, ordered by
    vertex, then count descending, then block id."""
    key = np.unique(src.astype(np.int64) * rho + blk, return_counts=True)
    u, b = np.divmod(key[0], rho)
    order = np.lexsort((b, -key[1], u))
    return u[order], b[order], key[1][order]


def _neighbor_keys(g: Graph) -> np.ndarray:
    """Sorted u*N+v keys of all directed edges, for O(log E) membership."""
    e = g.edges().astype(np.int64)
    return np.sort(e[:, 0] * g.num_vertices + e[:, 1])


def overlap_ratio(g: Graph, layout: BlockLayout,
                  keys: Optional[np.ndarray] = None) -> float:
    """OR(G) (Eq. 5), fully vectorized."""
    return float(per_vertex_overlap(g, layout, keys).mean())


def per_vertex_overlap(g: Graph, layout: BlockLayout,
                       keys: Optional[np.ndarray] = None) -> np.ndarray:
    n = g.num_vertices
    keys = _neighbor_keys(g) if keys is None else keys
    members = layout.blocks[layout.block_of]          # [N, ε]
    valid = (members >= 0) & (members != np.arange(n)[:, None])
    pair = np.arange(n, dtype=np.int64)[:, None] * n + members
    idx = np.searchsorted(keys, pair.ravel())
    idx = np.minimum(idx, keys.shape[0] - 1)
    hit = (keys[idx] == pair.ravel()).reshape(n, -1) & valid
    sizes = (members >= 0).sum(axis=1)
    denom = np.maximum(sizes - 1, 1)
    orr = hit.sum(axis=1) / denom
    orr[sizes <= 1] = 0.0
    return orr.astype(np.float32)


# ---------------------------------------------------------------- baseline

def layout_sequential(g: Graph, eps: int) -> BlockLayout:
    """DiskANN baseline: ID-contiguous vertices per block (Fig. 2(a))."""
    n = g.num_vertices
    rho = -(-n // eps)
    block_of = (np.arange(n) // eps).astype(np.int32)
    return _from_block_of(block_of, rho, eps)


# --------------------------------------------------------------------- BNP

def layout_bnp(g: Graph, eps: int) -> BlockLayout:
    """Block Neighbor Padding: scan ids ascending; place each unassigned
    vertex then pad the block with its unassigned neighbors."""
    n = g.num_vertices
    rho = -(-n // eps)
    block_of = np.full(n, -1, np.int32)
    cur, fill = 0, 0
    for u in range(n):
        if block_of[u] >= 0:
            continue
        if fill >= eps:
            cur, fill = cur + 1, 0
        block_of[u] = cur
        fill += 1
        for v in g.adj[u, : g.deg[u]]:
            if fill >= eps:
                break
            if block_of[v] < 0:
                block_of[v] = cur
                fill += 1
        if fill >= eps:
            cur, fill = cur + 1, 0
    return _from_block_of(block_of, rho, eps)


# --------------------------------------------------------------------- BNF

def layout_bnf(g: Graph, eps: int, iters: int = 8, tau: float = 0.01,
               init: Optional[BlockLayout] = None,
               gain_order: bool = False) -> Tuple[BlockLayout, list]:
    """Block Neighbor Frequency (Algorithm 1).

    Each round: snapshot D = vertex→block; clear blocks; re-stream vertices,
    assigning each to the non-full block holding most of its neighbors
    (under D); overflow goes to the emptiest block. Stops when the OR(G)
    gain between rounds falls below τ or after β rounds.

    ``gain_order=True`` re-streams vertices by descending best-block
    neighbor count — the GP3 prioritized-restreaming variant of App. G.
    Otherwise vertices are re-streamed grouped by their previous block
    (cohorts arrive together, so a cohesive block can re-claim its slots
    before filling up with strangers — the restreaming-partitioner order).

    Returns (best_layout, [OR(G) after each round]).
    """
    n = g.num_vertices
    rho = -(-n // eps)
    layout = init if init is not None else layout_bnp(g, eps)
    keys = _neighbor_keys(g)
    history = [overlap_ratio(g, layout, keys)]
    best, best_or = layout, history[0]
    prev = layout.block_of.copy()

    # Symmetrized adjacency: placing u with a vertex w improves OR through
    # *either* direction (u→w raises OR(u); w→u raises OR(w)), so the
    # neighbor-frequency signal must count in- and out-edges. CSR form.
    e = g.edges().astype(np.int64)
    sym = np.concatenate([e, e[:, ::-1]], axis=0)
    sym = sym[np.argsort(sym[:, 0], kind="stable")]
    starts = np.searchsorted(sym[:, 0], np.arange(n + 1))
    sym_dst = sym[:, 1].astype(np.int32)

    src = sym[:, 0]

    for _ in range(iters):
        # every vertex's neighbor blocks under the snapshot, most
        # neighbors first, ties to the lower block id — fixed for the
        # whole round, since the snapshot is
        cand_u, cand_b, cnt = _block_counts(src, prev[sym_dst], rho)
        cstarts = np.searchsorted(cand_u, np.arange(n + 1)).tolist()
        if gain_order:
            gains = np.zeros(n, np.int64)
            has = np.diff(cstarts) > 0
            gains[has] = cnt[np.asarray(cstarts[:-1])[has]]
            order = np.argsort(-gains, kind="stable")
        else:
            order = np.argsort(prev, kind="stable")
        cand_b = cand_b.tolist()
        new = [-1] * n
        fill = [0] * rho
        spill_ptr = 0
        for u in order.tolist():
            for j in range(cstarts[u], cstarts[u + 1]):
                b = cand_b[j]
                if fill[b] < eps:
                    break
            else:                                # lines 13–14: spill
                while fill[spill_ptr] >= eps:
                    spill_ptr += 1
                b = spill_ptr
            new[u] = b
            fill[b] += 1
        new = np.asarray(new, np.int32)
        layout = _from_block_of(new, rho, eps)
        cur = overlap_ratio(g, layout, keys)
        gain = cur - history[-1]
        history.append(cur)
        prev = new
        if cur > best_or:
            best, best_or = layout, cur
        if gain < tau:
            break
    return best, history


# --------------------------------------------------------------------- BNS

def layout_bns(g: Graph, eps: int, iters: int = 2, tau: float = 0.01,
               init: Optional[BlockLayout] = None,
               rng_seed: int = 0) -> Tuple[BlockLayout, list]:
    """Block Neighbor Swap (Algorithm 3).

    For each vertex u and each pair (a, e) of its neighbors living in
    different blocks, swap the min-OR vertices of B(a) and B(e) iff the
    summed OR of the two blocks strictly increases — hence OR(G) is
    monotone non-decreasing in β (Lemma 4.2).

    O(β·o³·ε·|V|): intended for small/medium segments (App. F runs it on
    1M vectors with hours of budget; we keep it exact and let callers
    choose scale).
    """
    n = g.num_vertices
    rho = -(-n // eps)
    layout = init if init is not None else layout_bnp(g, eps)
    keys = _neighbor_keys(g)
    block_of = layout.block_of.copy()
    blocks = [list(layout.blocks[b][layout.blocks[b] >= 0])
              for b in range(rho)]
    nbr_sets = [set(g.adj[u, : g.deg[u]].tolist())
                for u in range(n)]

    def or_of_vertex(u: int, members) -> float:
        others = [m for m in members if m != u]
        if not others:
            return 0.0
        return sum(1 for m in others if m in nbr_sets[u]) / len(others)

    def or_of_block(members) -> float:
        if not members:
            return 0.0
        return sum(or_of_vertex(u, members) for u in members) / len(members)

    history = [overlap_ratio(g, layout, keys)]
    for _ in range(iters):
        improved = 0.0
        for u in range(n):
            nb = g.adj[u, : g.deg[u]]
            for i in range(nb.shape[0]):
                for j in range(i + 1, nb.shape[0]):
                    a, e = int(nb[i]), int(nb[j])
                    ba, be = block_of[a], block_of[e]
                    if ba == be:
                        continue
                    ma, me = blocks[ba], blocks[be]
                    x = min(ma, key=lambda v: or_of_vertex(v, ma))
                    y = min(me, key=lambda v: or_of_vertex(v, me))
                    old = or_of_block(ma) + or_of_block(me)
                    ma2 = [v for v in ma if v != x] + [y]
                    me2 = [v for v in me if v != y] + [x]
                    new = or_of_block(ma2) + or_of_block(me2)
                    if new > old + 1e-12:
                        blocks[ba], blocks[be] = ma2, me2
                        block_of[x], block_of[y] = be, ba
                        improved += new - old
        lay = _pack(blocks, rho, eps, n)
        cur = overlap_ratio(g, lay, keys)
        history.append(cur)
        if cur - history[-2] < tau:
            break
    return _pack(blocks, rho, eps, n), history


def _pack(block_lists, rho, eps, n) -> BlockLayout:
    blocks = np.full((rho, eps), -1, np.int32)
    block_of = np.empty(n, np.int32)
    slot_of = np.empty(n, np.int32)
    for b, mem in enumerate(block_lists):
        for s, u in enumerate(mem):
            blocks[b, s] = u
            block_of[u] = b
            slot_of[u] = s
    return BlockLayout(blocks=blocks, block_of=block_of, slot_of=slot_of)


# ----------------------------------------------------- comparison packers

def layout_kmeans(x: np.ndarray, g: Graph, eps: int, iters: int = 8,
                  seed: int = 0) -> BlockLayout:
    """§7 'naive strategy that assigns vertices to blocks by k-means':
    balanced k-means packer — cluster, then greedily fill blocks from
    cluster-ordered vertices."""
    from repro.core import distances as D
    n = x.shape[0]
    rho = -(-n // eps)
    rng = np.random.default_rng(seed)
    k = max(rho // 4, 1)
    cent = x[rng.choice(n, size=k, replace=False)].astype(np.float32)
    for _ in range(iters):
        assign = np.argmin(D.pairwise(x, cent), axis=1)
        for c in range(k):
            m = assign == c
            if m.any():
                cent[c] = x[m].mean(axis=0)
    order = np.argsort(assign, kind="stable")
    block_of = np.empty(n, np.int32)
    block_of[order] = (np.arange(n) // eps).astype(np.int32)
    return _from_block_of(block_of, rho, eps)


def make_layout(g: Graph, eps: int, scheme: str,
                x: Optional[np.ndarray] = None,
                bnf_iters: int = 8, bns_iters: int = 2,
                tau: float = 0.01) -> BlockLayout:
    if scheme == "none":
        return layout_sequential(g, eps)
    if scheme == "bnp":
        return layout_bnp(g, eps)
    if scheme == "bnf":
        return layout_bnf(g, eps, iters=bnf_iters, tau=tau)[0]
    if scheme == "bns":
        init, _ = layout_bnf(g, eps, iters=bnf_iters, tau=tau)
        return layout_bns(g, eps, iters=bns_iters, tau=tau, init=init)[0]
    if scheme == "kmeans":
        assert x is not None
        return layout_kmeans(x, g, eps)
    if scheme == "gp3":
        return layout_bnf(g, eps, iters=bnf_iters, tau=tau,
                          gain_order=True)[0]
    raise ValueError(scheme)
