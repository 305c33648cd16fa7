"""Graph-index construction invariants (Vamana/NSG/HNSW flavours)."""
import numpy as np
import pytest

from repro.core import distances as D
from repro.core import graph as G
from repro.core.params import GraphParams
from repro.data.vectors import clustered_vectors


@pytest.fixture(scope="module")
def vecs():
    return clustered_vectors(600, 16, num_clusters=8, seed=7)


@pytest.mark.parametrize("algo", ["vamana", "nsg", "hnsw"])
def test_build_invariants(vecs, algo):
    p = GraphParams(max_degree=12, build_beam=24, algo=algo)
    g = G.build_graph(vecs, p)
    n = g.num_vertices
    assert n == vecs.shape[0]
    assert (g.deg >= 0).all() and (g.deg <= g.max_degree).all()
    valid = g.adj[g.adj >= 0]
    assert valid.max() < n
    # no self loops
    rows = np.repeat(np.arange(n), g.deg)
    assert not (g.adj[g.adj >= 0] == rows).any()
    assert g.deg.mean() >= 2


def test_greedy_search_finds_near_neighbor(vecs):
    p = GraphParams(max_degree=16, build_beam=32, algo="vamana")
    g = G.build_graph(vecs, p)
    q = vecs[:8] + 0.01
    ids, dists, _ = G.greedy_search_batch(
        vecs, g.adj, g.deg, g.entry, q, beam=24)
    truth = D.brute_force_knn(vecs, q, 1)
    hits = sum(int(truth[i, 0]) in set(ids[i].tolist()) for i in range(8))
    assert hits >= 7


def test_robust_prune_degree_bound(vecs):
    cand = np.arange(1, 100, dtype=np.int32)
    cd = D.point_to_points(vecs[0], vecs[cand]).astype(np.float32)
    sel = G.robust_prune(0, cand, cd, vecs, max_degree=8, alpha=1.2)
    assert sel.shape[0] <= 8
    assert 0 not in sel.tolist()
    assert len(set(sel.tolist())) == sel.shape[0]


def test_nsg_reachability(vecs):
    p = GraphParams(max_degree=10, build_beam=20, algo="nsg")
    g = G.build_graph(vecs, p)
    seen = np.zeros(g.num_vertices, bool)
    stack = [g.entry]
    seen[g.entry] = True
    while stack:
        u = stack.pop()
        for v in g.adj[u, : g.deg[u]]:
            if not seen[v]:
                seen[v] = True
                stack.append(int(v))
    assert seen.all()


def test_hnsw_layers(vecs):
    p = GraphParams(max_degree=12, build_beam=24, algo="hnsw")
    h = G.build_hnsw(vecs, p)
    assert len(h.layers) >= 1
    sizes = [ids.size for ids in h.level_ids]
    assert sizes == sorted(sizes, reverse=True)


@pytest.mark.parametrize("alpha", [1.0, 1.2])
def test_prune_knn_matches_robust_prune(vecs, alpha):
    """The device-batched prune keeps, row for row, the edges the
    per-vertex host RobustPrune keeps."""
    knn = D.knn_graph(vecs, 24)
    adj, deg = G.prune_knn(vecs, knn, 10, alpha, chunk=256)
    for u in range(vecs.shape[0]):
        cd = D.point_to_points(vecs[u], vecs[knn[u]])
        want = G.robust_prune(u, knn[u], cd, vecs, 10, alpha)
        np.testing.assert_array_equal(adj[u, :deg[u]], want)
        assert (adj[u, deg[u]:] == -1).all()


def test_fill_reverse_edges_nearest_first(vecs):
    """Every edge u->v gains its reverse v->u unless v is full, no edge
    is duplicated, and the reverse edges fill v's spare slots nearest
    first."""
    knn = D.knn_graph(vecs, 24)
    adj, deg = G.prune_knn(vecs, knn, 10, 1.0)
    before = adj.copy(), deg.copy()
    G._fill_reverse_edges(vecs, adj, deg)
    edges = {(u, int(v)) for u in range(len(deg)) for v in adj[u, :deg[u]]}
    assert len(edges) == int(deg.sum())                   # no duplicates
    for u, v in edges:
        assert (v, u) in edges or deg[v] == adj.shape[1]
    for v in range(len(deg)):
        old = before[1][v]
        np.testing.assert_array_equal(adj[v, :old], before[0][v, :old])
        added = adj[v, old:deg[v]]
        d = D.point_to_points(vecs[v], vecs[added])
        assert (np.diff(d) >= 0).all()


def test_reachable_matches_dfs():
    """The sparse-matrix BFS marks exactly the vertices a plain DFS from
    the entry reaches, on a graph with unreachable islands."""
    rng = np.random.default_rng(3)
    n, r = 300, 4
    adj = rng.integers(0, n, (n, r)).astype(np.int32)
    deg = rng.integers(0, r + 1, n).astype(np.int32)
    deg[:40] = 0                                          # sinks
    g = G.Graph(adj=adj, deg=deg, entry=100)
    seen = np.zeros(n, bool)
    stack, seen[g.entry] = [g.entry], True
    while stack:
        u = stack.pop()
        for v in adj[u, :deg[u]]:
            if not seen[v]:
                seen[v] = True
                stack.append(int(v))
    np.testing.assert_array_equal(G._reachable(g), seen)
    assert not seen.all()
    G._ensure_reachable(rng.standard_normal((n, 8)).astype(np.float32), g)
    assert G._reachable(g).all()


@pytest.mark.parametrize("n,k,levels", [(3000, 5, 0), (3000, 5, 4),
                                        (20000, 65, 16), (257 * 128 + 5,
                                                          11, 8)])
def test_smallest_k_matches_top_k(n, k, levels):
    """The two-stage top-k returns exactly ``lax.top_k``'s ids, ties
    (``levels`` > 0 quantizes the values into a few levels) included."""
    import jax
    import jax.numpy as jnp
    d = np.random.default_rng(n + k).random((9, n)).astype(np.float32)
    if levels:
        d = np.floor(d * levels) / levels
    want = jax.lax.top_k(-jnp.asarray(d), k)[1]
    np.testing.assert_array_equal(D.smallest_k(jnp.asarray(d), k), want)


def test_knn_graph_drops_self(vecs):
    """Each kNN row is the k+1 exact neighbors without the vertex itself
    (or without the last one, where a duplicate pushed self out)."""
    x = vecs.copy()
    x[11] = x[12] = x[10]                                 # duplicates
    ids = D.brute_force_knn(x, x, 9)
    knn = D.knn_graph(x, 8)
    for i in range(x.shape[0]):
        np.testing.assert_array_equal(knn[i], ids[i][ids[i] != i][:8])
