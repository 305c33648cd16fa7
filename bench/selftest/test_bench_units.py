"""The yardstick's parts on their own: the work counts against hand
counts, the peaks table, the reference against a NumPy brute force,
the comparison, the copied generators against the program's, and the
trace reduction."""
import numpy as np
import pytest

import data as bench_data
import peaks
import reference
import trace_reduce
import work


# ------------------------------------------------------------------ work

def test_n_expand_hand_count():
    # F=2 targets, each with ceil(5 * 0.3) = 2 residents: 2 * 3
    assert work.n_expand(2, 6, 0.3) == 6
    assert work.n_expand(1, 16, 0.0) == 1


def test_gather_blocks_bytes_hand_count():
    # 10 copies of a 6 x 128 f32 tile (3,072 B), read and written
    assert work.gather_blocks_bytes(10, 6, 128, 4) == 10 * 3072 * 2


def test_rank_tiles_work_hand_count():
    ops, byt = work.rank_tiles_work(q=8, fetch_width=2, eps=6, dim=128,
                                    expand=6, itemsize=4)
    fe = 12
    assert ops == 3 * 8 * fe * 128 + 4 * 8 * fe * 6
    reads = 8 * 128 * 4 + 8 * 2 * 4 + 8 * fe * 128 * 4 + 8 * fe * 4
    writes = 8 * fe * 4 + 8 * 6 * 4
    assert byt == reads + writes


# ----------------------------------------------------------------- peaks

def test_peaks_v5e_row():
    p = peaks.peaks_for("TPU v5 lite")
    assert p.bf16_flops == 197e12
    assert p.hbm_bytes_per_s == 819e9
    assert p.hbm_bytes == 16e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks_for("cpu")


# ------------------------------------------------------------- reference

def _numpy_knn(x, q, k):
    d = ((q[:, None, :].astype(np.float64) - x[None]) ** 2).sum(-1)
    return np.argsort(d, axis=1, kind="stable")[:, :k]


def test_reference_matches_numpy_with_ties():
    rng = np.random.default_rng(0)
    # small integers: every distance is exact in f32, and many tie
    x = rng.integers(-2, 3, size=(300, 8)).astype(np.float32)
    q = rng.integers(-2, 3, size=(40, 8)).astype(np.float32)
    ids, d = reference.exact_knn(x, q, 10, query_block=16, row_block=64)
    want = _numpy_knn(x, q, 10)
    np.testing.assert_array_equal(ids, want)
    np.testing.assert_array_equal(
        d, ((q[:, None, :] - x[want]) ** 2).sum(-1))


def test_reference_matches_numpy_on_clustered_data():
    x = bench_data.clustered_vectors(2000, 32, seed=3)
    q = bench_data.query_set(x, 50, seed=4)
    ids, _ = reference.exact_knn(x, q, 10, row_block=512)
    np.testing.assert_array_equal(ids, _numpy_knn(x, q, 10))


def test_compare_reads_exact_answers_as_exact():
    x = bench_data.clustered_vectors(1000, 16, seed=1)
    q = bench_data.query_set(x, 20, seed=2)
    ids, d = reference.exact_knn(x, q, 10)
    got = reference.compare(x, q, ids, d, ids)
    assert got["recall_at_10"] == 1.0
    assert got["bad_rows"] == 0
    assert got["dist_gap"] < 1e-5


@pytest.mark.parametrize("fault", ["id", "order", "range", "repeat"])
def test_compare_catches_a_perturbed_answer(fault):
    x = bench_data.clustered_vectors(1000, 16, seed=1)
    q = bench_data.query_set(x, 20, seed=2)
    ref, d = reference.exact_knn(x, q, 10)
    ids, d = ref.copy(), d.copy()
    if fault == "id":
        ids[3, 4] = (ids[3, 4] + 1) % len(x)
        assert reference.compare(x, q, ids, d, ref)["dist_gap"] > 1e-3
        return
    if fault == "order":
        d[3, [1, 2]] = d[3, [2, 1]] + np.array([1.0, 0.0])
    elif fault == "range":
        ids[3, 4] = len(x)
    else:
        ids[3, 4] = ids[3, 5]
    assert reference.compare(x, q, ids, d, ref)["bad_rows"] == 1


def test_bf16_control_has_a_wide_distance_gap():
    x = bench_data.clustered_vectors(3000, 128, seed=5)
    q = bench_data.query_set(x, 64, seed=6)
    ref, _ = reference.exact_knn(x, q, 10)
    ids, d = reference.exact_knn(x, q, 10, precision="bf16")
    assert reference.compare(x, q, ids, d, ref)["dist_gap"] > 1e-3


# ------------------------------------------------------------------ data

@pytest.mark.parametrize("n,dim,seed", [(500, 32, 0), (3000, 128, 7),
                                        (1000, 96, 2 ** 31 + 3)])
def test_generators_equal_the_programs(n, dim, seed):
    from repro.data import vectors
    x = bench_data.clustered_vectors(n, dim, seed=seed)
    np.testing.assert_array_equal(x, vectors.clustered_vectors(
        n, dim, seed=seed))
    np.testing.assert_array_equal(
        bench_data.query_set(x, 64, seed=seed + 1),
        vectors.query_set(x, 64, seed=seed + 1))


def test_every_seed_serves_the_same_batches():
    cfg = {"n": 600, "dim": 16,
           "data": {"seed": 3, "clusters": 4, "background": 0.05}}
    traffic = {"batch": 8, "query_pool": 32, "jitter": 0.1}
    x, a = bench_data.cell_inputs(cfg, traffic, 1)
    _, b = bench_data.cell_inputs(cfg, traffic, 2 ** 31 + 9)
    assert not np.array_equal(a, b)
    # the same batches, each with the same rows, in another order
    key = lambda p: sorted(
        tuple(sorted(map(tuple, r))) for r in p.reshape(-1, 8, 16))
    assert key(a) == key(b)
    np.testing.assert_array_equal(
        a, bench_data.cell_inputs(cfg, traffic, 1)[1])
    with pytest.raises(ValueError):
        bench_data.cell_inputs(cfg, dict(traffic, query_pool=30), 1)


# ----------------------------------------------------------------- trace

def test_union_merges_overlaps():
    assert trace_reduce._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [(0, 3), (5, 8)]


def test_self_times_subtract_nested_ops():
    evs = [("while", 0, 100), ("fusion", 10, 30), ("sort", 40, 90),
           ("inner", 50, 60), ("copy", 120, 130)]
    self_ns, count = trace_reduce._self_times(evs, (0, 200))
    assert self_ns == {"while": 30, "fusion": 20, "sort": 40,
                       "inner": 10, "copy": 10}
    assert count["while"] == 1
    # clipped to a window that cuts the loop in half
    self_ns, _ = trace_reduce._self_times(evs, (0, 50))
    assert self_ns["fusion"] == 20 and self_ns["sort"] == 10
    assert "copy" not in self_ns


def test_idle_gaps_named_by_host_phase():
    dev = trace_reduce.DeviceTrace(
        name="/device:TPU:0", busy_ns=60, self_ns={}, count={},
        intervals=[(10, 40), (70, 100)])
    s = trace_reduce.TraceSummary(
        window=(0, 120), devices=[dev],
        host_spans=[("bench.window", 0, 120), ("bench.search", 5, 45),
                    ("bench.admit", 45, 70), ("other", 100, 120)])
    gaps = dict(trace_reduce.idle_gaps(s))
    assert gaps == {"bench.search": 10e-9, "bench.admit": 30e-9,
                    "bench.window": 20e-9}
