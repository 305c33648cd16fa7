"""Per-kernel shape/dtype sweeps vs the ref.py oracles (interpreted on
the CPU, the mode the ops wrappers pick there)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (block_rank, fused_round, pairwise_l2,
                           pq_adc_batch, tier0_rank)
from repro.kernels import ref


@pytest.mark.parametrize("q,n,d", [(8, 64, 16), (37, 203, 64),
                                   (128, 512, 128), (1, 9, 8)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_l2_tile_sweep(q, n, d, dtype, metric):
    rng = np.random.default_rng(q * n)
    qa = jnp.asarray(rng.standard_normal((q, d)), dtype)
    xa = jnp.asarray(rng.standard_normal((n, d)), dtype)
    got = pairwise_l2(qa, xa, metric=metric)
    want = ref.pairwise_l2_ref(qa, xa, metric=metric)
    tol = 1e-3 if dtype == np.float32 else 5e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * d)


@pytest.mark.parametrize("n,m,k,b", [(64, 4, 16, 1), (133, 8, 256, 5),
                                     (256, 16, 256, 3), (17, 2, 64, 2)])
def test_pq_adc_sweep(n, m, k, b):
    rng = np.random.default_rng(n * m)
    codes = jnp.asarray(rng.integers(0, k, (n, m)), jnp.uint8)
    luts = jnp.asarray(rng.standard_normal((b, m, k)), jnp.float32)
    got = pq_adc_batch(codes, luts)
    want = ref.pq_adc_ref(luts, codes)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("q,eps,d,top", [(19, 8, 32, 3), (64, 16, 128, 5),
                                         (5, 4, 16, 4), (128, 12, 64, 1)])
@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_block_rank_sweep(q, eps, d, top, metric):
    rng = np.random.default_rng(q * eps)
    qs = jnp.asarray(rng.standard_normal((q, d)), jnp.float32)
    tiles = jnp.asarray(rng.standard_normal((q, eps, d)), jnp.float32)
    dd, idx = block_rank(qs, tiles, top, metric=metric)
    dr, idxr = ref.block_rank_ref(qs, tiles, top, metric=metric)
    np.testing.assert_allclose(dd, dr, rtol=1e-3, atol=1e-3)
    # indices must agree where distances are distinct
    got_d = np.take_along_axis(np.asarray(dd), np.asarray(idx), axis=1)
    want_d = np.take_along_axis(np.asarray(dr), np.asarray(idxr), axis=1)
    np.testing.assert_allclose(got_d, want_d, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("q,rho,eps,d,f,hot_n",
                         [(16, 32, 4, 16, 1, 8), (37, 64, 8, 32, 2, 0),
                          (8, 16, 6, 24, 3, 16), (128, 96, 5, 64, 2, 40)])
@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_tier0_fetch_rank_sweep(q, rho, eps, d, f, hot_n, metric):
    """Fused probe+gather+rank vs the jnp oracle, including hot_n=0
    (sentinel pack, map all cold) and hot_n=rho (all hot)."""
    rng = np.random.default_rng(q * rho)
    qs = jnp.asarray(rng.standard_normal((q, d)), jnp.float32)
    cold = jnp.asarray(rng.standard_normal((rho, eps, d)), jnp.float32)
    slot_of = np.full(rho, -1, np.int32)
    if hot_n > 0:
        hot_ids = rng.permutation(rho)[:hot_n]
        slot_of[hot_ids] = np.arange(hot_n, dtype=np.int32)
        hot = cold[jnp.asarray(hot_ids)]
    else:
        hot = jnp.zeros((1, eps, d), jnp.float32)
    blocks = jnp.asarray(rng.integers(0, rho, (q, f)), jnp.int32)
    got_d, got_h = tier0_rank(qs, blocks, jnp.asarray(slot_of), hot,
                              cold, metric=metric)
    want_d, want_h = ref.tier0_fetch_rank_ref(
        qs, blocks, jnp.asarray(slot_of), hot, cold, metric=metric)
    np.testing.assert_array_equal(np.asarray(got_h), np.asarray(want_h))
    np.testing.assert_allclose(got_d, want_d, rtol=1e-4, atol=1e-4)
    # hot slots hold copies of the cold blocks -> distances must equal
    # an all-cold rank of the same blocks exactly
    all_cold, _ = ref.tier0_fetch_rank_ref(
        qs, blocks, jnp.asarray(np.full(rho, -1, np.int32)),
        jnp.zeros((1, eps, d), jnp.float32), cold, metric=metric)
    np.testing.assert_allclose(want_d, all_cold, rtol=0, atol=0)


def test_tier0_fetch_rank_matches_dists_form():
    """The kernel's distance form is the device search's `_dists` (f32
    sum of squared differences). A standalone kernel call and the eager
    jnp form may sum in a different order, so they agree to f32
    rounding, not bit for bit."""
    from repro.core.device_search import _dists
    rng = np.random.default_rng(3)
    qs = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    cold = jnp.asarray(rng.standard_normal((10, 4, 16)), jnp.float32)
    blocks = jnp.asarray(rng.integers(0, 10, (8, 2)), jnp.int32)
    got_d, _ = tier0_rank(qs, blocks,
                          jnp.asarray(np.full(10, -1, np.int32)),
                          jnp.zeros((1, 4, 16), jnp.float32), cold)
    want = _dists(qs, cold[blocks].reshape(8, 8, 16), "l2")
    np.testing.assert_allclose(np.asarray(got_d), np.asarray(want),
                               rtol=1e-6)


def _fused_round_case(q, rho, eps, d, f, hot_n, lam=5, seed=None,
                      idle_rows=0):
    rng = np.random.default_rng(q * rho if seed is None else seed)
    n = rho * eps
    qs = jnp.asarray(rng.standard_normal((q, d)), jnp.float32)
    cold = jnp.asarray(rng.standard_normal((rho, eps, d)), jnp.float32)
    vid = jnp.asarray(rng.permutation(n).reshape(rho, eps), jnp.int32)
    nbrs = jnp.asarray(rng.integers(-1, n, (rho, eps, lam)), jnp.int32)
    block_of = np.zeros(n, np.int32)
    block_of[np.asarray(vid).reshape(-1)] = np.repeat(
        np.arange(rho, dtype=np.int32), eps)
    slot_of = np.full(rho, -1, np.int32)
    if hot_n > 0:
        hot_ids = rng.permutation(rho)[:hot_n]
        slot_of[hot_ids] = np.arange(hot_n, dtype=np.int32)
        hot_v = cold[jnp.asarray(hot_ids)]
        hot_i = vid[jnp.asarray(hot_ids)]
        hot_n_arr = nbrs[jnp.asarray(hot_ids)]
    else:
        hot_v = jnp.zeros((1, eps, d), jnp.float32)
        hot_i = jnp.full((1, eps), -1, jnp.int32)
        hot_n_arr = jnp.full((1, eps, lam), -1, jnp.int32)
    u = rng.integers(0, n, (q, f)).astype(np.int32)
    u[rng.random((q, f)) < 0.2] = -1           # converged/empty slots
    if idle_rows:
        u[-idle_rows:] = -1                    # fully-converged queries
    u = jnp.asarray(u)
    args = (qs, u, jnp.asarray(block_of), jnp.asarray(slot_of),
            hot_v, hot_i, hot_n_arr, cold, vid, nbrs)
    return args


@pytest.mark.parametrize("q,rho,eps,d,f,hot_n",
                         [(16, 32, 4, 16, 1, 8), (37, 64, 8, 32, 2, 0),
                          (8, 16, 6, 24, 3, 16), (128, 96, 5, 64, 2, 40)])
def test_fused_round_matches_ref(q, rho, eps, d, f, hot_n):
    """The fused per-round kernel (cross-query-deduped gather) matches
    the straight-gather oracle: dedup only changes which gather
    produced a tile, never its payload — block metadata and the hit
    mask are exact; distances match to float tolerance here (this
    standalone comparison pits a jit-fused graph against the eager
    oracle, like the other kernel sweeps — inside the search jit the
    two fetch_impls are bit-identical, asserted in test_device_search);
    the expansion order walks the same non-decreasing key sequence.
    Duplicate requests and converged (-1) slots included."""
    args = _fused_round_case(q, rho, eps, d, f, hot_n)
    n_expand = f * 2
    dd, vid, nbrs, hit, order = fused_round(*args, n_expand)
    dd_r, vid_r, nbrs_r, hit_r, order_r = ref.fused_round_ref(
        *args, n_expand)
    np.testing.assert_array_equal(np.asarray(vid), np.asarray(vid_r))
    np.testing.assert_array_equal(np.asarray(nbrs), np.asarray(nbrs_r))
    np.testing.assert_array_equal(np.asarray(hit), np.asarray(hit_r))
    np.testing.assert_allclose(np.asarray(dd), np.asarray(dd_r),
                               rtol=1e-4, atol=1e-4)
    # reconstruct the masked selection key (the ref formula) and check
    # both orders rank it identically up to float-tolerance ties
    u = np.asarray(args[1])
    f_valid = np.repeat(u >= 0, eps, axis=1)
    dd_m = np.where((np.asarray(vid_r) >= 0) & f_valid,
                    np.asarray(dd_r), np.inf)
    is_t = ((np.asarray(vid_r)[:, :, None] == u[:, None, :]).any(-1)
            & (np.asarray(vid_r) >= 0))
    sel = np.where(is_t, -np.inf, dd_m)
    got_keys = np.take_along_axis(sel, np.asarray(order), axis=1)
    want_keys = np.take_along_axis(sel, np.asarray(order_r), axis=1)
    np.testing.assert_allclose(got_keys, want_keys, rtol=1e-4,
                               atol=1e-4)


def test_fused_round_idle_tile_emits_masked_sentinels():
    """A query tile whose rows are all converged takes the kernel's
    skip path: hit stays 0 and vid is the -1 sentinel, so the search
    loop (which gates every consumer on u >= 0) folds in nothing."""
    args = _fused_round_case(16, 32, 4, 16, 2, 8, idle_rows=16)
    dd, vid, nbrs, hit, order = fused_round(*args, 4)
    assert (np.asarray(hit) == 0).all()
    assert (np.asarray(vid) == -1).all()
    assert (np.asarray(dd) == 0).all()
    # live rows in the same call are unaffected: re-run with the idle
    # rows live and check the live half is unchanged
    args2 = _fused_round_case(16, 32, 4, 16, 2, 8, idle_rows=8)
    dd2, vid2, *_ = fused_round(*args2, 4)
    want = ref.fused_round_ref(*args2, 4)
    np.testing.assert_allclose(np.asarray(dd2[:8]),
                               np.asarray(want[0][:8]), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(vid2[:8]),
                                  np.asarray(want[1][:8]))


def test_block_rank_matches_search_semantics():
    """The kernel's top-m selection equals the block-pruning selection of
    the host search (ascending distance, ties by slot order)."""
    rng = np.random.default_rng(0)
    qs = jnp.asarray(rng.standard_normal((16, 24)), jnp.float32)
    tiles = jnp.asarray(rng.standard_normal((16, 6, 24)), jnp.float32)
    dd, idx = block_rank(qs, tiles, 6)
    order = np.argsort(np.asarray(dd), axis=1)
    np.testing.assert_array_equal(np.asarray(idx), order)
