"""Block-layout invariants + shuffling properties (§4.1)."""
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need hypothesis; rest of the suite runs without")
from hypothesis import given, settings, strategies as st

from repro.core import layout as L
from repro.core.graph import Graph
from repro.core.params import GraphParams


def random_graph(n: int, deg: int, seed: int = 0) -> Graph:
    rng = np.random.default_rng(seed)
    adj = np.full((n, deg), -1, np.int32)
    degs = rng.integers(1, deg + 1, size=n).astype(np.int32)
    for u in range(n):
        nbrs = rng.choice(n - 1, size=degs[u], replace=False)
        nbrs[nbrs >= u] += 1                  # no self loops
        adj[u, : degs[u]] = nbrs
    return Graph(adj=adj, deg=degs, entry=0)


@settings(deadline=None, max_examples=20)
@given(n=st.integers(10, 200), eps=st.integers(2, 9),
       deg=st.integers(2, 8), seed=st.integers(0, 10_000))
def test_layout_bijection_property(n, eps, deg, seed):
    """Every shuffle scheme yields a bijection V -> (block, slot)."""
    g = random_graph(n, deg, seed)
    for scheme in ("none", "bnp", "bnf"):
        lay = L.make_layout(g, eps, scheme, bnf_iters=2)
        lay.validate()
        orr = L.overlap_ratio(g, lay)
        assert 0.0 <= orr <= 1.0


@settings(deadline=None, max_examples=10)
@given(n=st.integers(20, 120), eps=st.integers(2, 6),
       seed=st.integers(0, 1000))
def test_bnf_improves_over_sequential(n, eps, seed):
    g = random_graph(n, 6, seed)
    base = L.overlap_ratio(g, L.layout_sequential(g, eps))
    bnf = L.overlap_ratio(g, L.layout_bnf(g, eps, iters=4)[0])
    assert bnf >= base - 1e-9


def test_bns_monotone_lemma42():
    """Lemma 4.2: OR(G) non-decreasing over BNS iterations."""
    g = random_graph(60, 5, seed=3)
    _, history = L.layout_bns(g, eps=4, iters=3, tau=-1.0)
    for a, b in zip(history, history[1:]):
        assert b >= a - 1e-9


def test_bnp_neighbors_padded():
    """BNP puts the first vertex's neighbors in its block (Example 4)."""
    g = random_graph(50, 3, seed=1)
    lay = L.layout_bnp(g, eps=4)
    b0 = set(lay.blocks[lay.block_of[0]].tolist())
    nbrs = set(g.adj[0, : g.deg[0]].tolist())
    assert 0 in b0
    assert len(b0 & nbrs) >= min(len(nbrs), 3)


def test_shuffling_beats_baseline_on_real_graph(small_segment):
    """Paper Fig. 9: BNF locality >> ID-contiguous baseline on a real
    vector graph; the built segment's stored OR must match recompute."""
    seg = small_segment
    g = seg.graph
    eps = seg.view.layout.verts_per_block
    seq_or = L.overlap_ratio(g, L.layout_sequential(g, eps))
    assert seg.overlap_ratio > seq_or + 0.05
    assert seg.overlap_ratio == pytest.approx(
        L.overlap_ratio(g, seg.view.layout), abs=1e-5)


def test_kmeans_packer_worse_than_bnf(small_segment, small_data):
    """§7: naive k-means packing loses to graph-aware shuffling."""
    x, _ = small_data
    seg = small_segment
    eps = seg.view.layout.verts_per_block
    km = L.overlap_ratio(seg.graph, L.layout_kmeans(x, seg.graph, eps))
    assert seg.overlap_ratio > km


def test_gp3_gain_order_variant(small_segment):
    g = small_segment.graph
    eps = small_segment.view.layout.verts_per_block
    lay = L.make_layout(g, eps, "gp3", bnf_iters=2)
    lay.validate()


def test_from_block_of_matches_fill_loop():
    """Slots fill in ascending vertex id within each block — the
    assignment loop the vectorised form replaces."""
    rng = np.random.default_rng(5)
    rho, eps = 40, 5
    block_of = rng.permutation(np.repeat(np.arange(rho), eps))[:187]
    lay = L._from_block_of(block_of.astype(np.int32), rho, eps)
    blocks = np.full((rho, eps), -1, np.int32)
    fill = np.zeros(rho, int)
    for u, b in enumerate(block_of):
        blocks[b, fill[b]] = u
        assert lay.slot_of[u] == fill[b]
        fill[b] += 1
    np.testing.assert_array_equal(lay.blocks, blocks)


def test_block_counts_order_matches_bincount():
    """Each vertex's candidate blocks come most-neighbors first, ties to
    the lower block id — the order of a stable argsort of -bincount."""
    g = random_graph(150, 8, seed=2)
    e = g.edges().astype(np.int64)
    e = e[np.argsort(e[:, 0], kind="stable")]
    blk = np.random.default_rng(1).integers(0, 30, e.shape[0])
    u, b, cnt = L._block_counts(e[:, 0], blk, 30)
    for v in range(g.num_vertices):
        row = blk[e[:, 0] == v]
        c = np.bincount(row, minlength=30)
        want = np.argsort(-c, kind="stable")[: np.count_nonzero(c)]
        np.testing.assert_array_equal(b[u == v], want)
        np.testing.assert_array_equal(cnt[u == v], c[want])
