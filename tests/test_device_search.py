"""Device-side batched search vs host oracle + ground truth.

The jit-compiling searches (full ``device_anns``/``device_range_search``
traces) are marked ``slow``; the fast lane (`make test-fast` / CI's
device lane) keeps the pure-helper tests and the kernel suite.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import device_search as DS
from repro.core import distances as D
from repro.core.params import DeviceSearchParams
from repro.core.search import anns, recall_at_k

P48 = DeviceSearchParams(k=10, candidates=48, max_hops=256)


@pytest.fixture(scope="module")
def device_seg(small_segment):
    return DS.from_segment(small_segment)


@pytest.mark.slow
def test_device_anns_recall(device_seg, small_data):
    x, q = small_data
    r = DS.device_anns(device_seg, jnp.asarray(q), P48)
    truth = D.brute_force_knn(x, q, 10)
    assert recall_at_k(np.asarray(r.ids), truth) >= 0.8
    assert (np.asarray(r.io) > 0).all()
    # no tier-0 budget -> every touch is a cold DMA
    assert (np.asarray(r.tier0_hits) == 0).all()
    # distances must be the true distances of the returned ids
    for qi in range(4):
        valid = np.asarray(r.ids[qi]) >= 0
        dd = D.point_to_points(q[qi], x[np.asarray(r.ids[qi])[valid]])
        np.testing.assert_allclose(np.asarray(r.dists[qi])[valid], dd,
                                   rtol=1e-3, atol=1e-2)


@pytest.mark.slow
def test_device_io_comparable_to_host(device_seg, small_segment,
                                      small_data):
    x, q = small_data
    r = DS.device_anns(device_seg, jnp.asarray(q), P48)
    _, _, host_stats = anns(small_segment.view, q, 10,
                            small_segment.params.search)
    host_io = np.mean([s.block_reads for s in host_stats])
    assert np.asarray(r.io).mean() <= host_io * 1.5


# ------------------------------------------------------------ tier 0

@pytest.mark.slow
@pytest.mark.parametrize("fetch_width", [1, 2, 4])
def test_tier0_bit_identity_across_budgets(small_segment, small_data,
                                           fetch_width):
    """ISSUE 3 acceptance: tier-0-cached device_anns returns identical
    (ids, dists) to the uncached path for every fetch width and budget
    — including budget 0 and budget >= all blocks — while block touches
    (io + tier0_hits) stay constant and only migrate between tiers."""
    _, q = small_data
    p = dataclasses.replace(P48, max_hops=64, fetch_width=fetch_width)
    base = None
    prev_io = None
    for frac in (0.0, 0.1, 0.5, 1.0):
        ds = DS.from_segment(small_segment, tier0_frac=frac)
        r = DS.device_anns(ds, jnp.asarray(q), p)
        if base is None:
            base = r
        np.testing.assert_array_equal(np.asarray(base.ids),
                                      np.asarray(r.ids))
        np.testing.assert_array_equal(np.asarray(base.dists),
                                      np.asarray(r.dists))
        np.testing.assert_array_equal(np.asarray(base.hops),
                                      np.asarray(r.hops))
        np.testing.assert_array_equal(
            np.asarray(base.io) + np.asarray(base.tier0_hits),
            np.asarray(r.io) + np.asarray(r.tier0_hits))
        io_m = float(np.asarray(r.io).mean())
        if prev_io is not None:
            assert io_m <= prev_io + 1e-9      # monotone DMA reduction
        prev_io = io_m
    # budget >= all blocks: every touch is a tier-0 hit, zero DMAs
    assert prev_io == 0.0


@pytest.mark.slow
def test_tier0_fused_matches_jnp_fetch(small_segment, small_data):
    """The fused Pallas probe+gather+rank stage and the pure-jnp
    reference fetch stage are interchangeable."""
    _, q = small_data
    ds = DS.from_segment(small_segment, tier0_frac=0.2)
    p = dataclasses.replace(P48, max_hops=64)
    rf = DS.device_anns(ds, jnp.asarray(q), p)
    rj = DS.device_anns(ds, jnp.asarray(q),
                        dataclasses.replace(p, fetch_impl="jnp"))
    np.testing.assert_array_equal(np.asarray(rf.ids), np.asarray(rj.ids))
    np.testing.assert_array_equal(np.asarray(rf.dists),
                                  np.asarray(rj.dists))
    np.testing.assert_array_equal(np.asarray(rf.io), np.asarray(rj.io))
    np.testing.assert_array_equal(np.asarray(rf.tier0_hits),
                                  np.asarray(rj.tier0_hits))


def test_tier0_pack_is_nested_and_charged(small_segment):
    """Budget selection is prefix-nested (hotset ranking + id-order
    fill) and tier0_bytes reports the packed charge."""
    ds_small = DS.from_segment(small_segment, tier0_blocks=8)
    ds_big = DS.from_segment(small_segment, tier0_blocks=32)
    hot_small = set(np.flatnonzero(
        np.asarray(ds_small.hot_slot_of) >= 0).tolist())
    hot_big = set(np.flatnonzero(
        np.asarray(ds_big.hot_slot_of) >= 0).tolist())
    assert len(hot_small) == 8 and len(hot_big) == 32
    assert hot_small < hot_big
    assert DS.tier0_bytes(ds_big) > DS.tier0_bytes(ds_small) > 0
    # the pack holds exact copies of the packed blocks
    b = next(iter(hot_small))
    s = int(np.asarray(ds_small.hot_slot_of)[b])
    np.testing.assert_array_equal(np.asarray(ds_small.hot_vecs[s]),
                                  np.asarray(ds_small.vecs[b]))
    np.testing.assert_array_equal(np.asarray(ds_small.hot_vid[s]),
                                  np.asarray(ds_small.vid[b]))
    ds_off = DS.from_segment(small_segment, tier0_blocks=0)
    assert DS.tier0_bytes(ds_off) == 0
    assert (np.asarray(ds_off.hot_slot_of) == -1).all()


# ------------------------------------------- divergence-aware batching

@pytest.mark.slow
def test_batched_matches_singletons_with_duplicates(device_seg,
                                                    small_data):
    """ISSUE 4 acceptance (deterministic twin of the hypothesis
    property test): the deduped, compacted batched search is
    bit-identical to a loop of singleton-batch searches, under a query
    permutation and with duplicate queries in the batch."""
    _, q = small_data
    p = dataclasses.replace(P48, max_hops=64, fetch_width=2,
                            compact_frac=0.5)
    perm = [5, 0, 3, 0, 7, 5, 1, 2]          # dups + shuffled order
    qb = q[perm]
    r = DS.device_anns(device_seg, jnp.asarray(qb), p)
    p1 = dataclasses.replace(p, compact_frac=0.0)
    for row, qi in enumerate(perm):
        r1 = DS.device_anns(device_seg, jnp.asarray(q[qi: qi + 1]), p1)
        np.testing.assert_array_equal(np.asarray(r1.ids[0]),
                                      np.asarray(r.ids[row]))
        np.testing.assert_array_equal(np.asarray(r1.dists[0]),
                                      np.asarray(r.dists[row]))
    # a duplicated query's cold traffic fully joins its twin's gathers
    saved = np.asarray(r.dedup_saved)
    io = np.asarray(r.io)
    assert saved[3] == io[3] and io[3] > 0    # row 3 duplicates row 1
    assert saved[5] == io[5] and io[5] > 0    # row 5 duplicates row 0
    assert saved.sum() > 0 and (saved <= io).all()


@pytest.mark.slow
def test_compaction_is_result_invariant(device_seg, small_data):
    """Active-query compaction (any threshold) never changes results or
    per-query io/tier0/hops — it only repacks rows mid-loop (and with
    it the dedup tile grouping, so only dedup_saved may move)."""
    _, q = small_data
    base = None
    for cf in (0.0, 0.25, 1.0):
        r = DS.device_anns(
            device_seg, jnp.asarray(q),
            dataclasses.replace(P48, max_hops=64,
                                compact_frac=cf))
        if base is None:
            base = r
            continue
        for f in ("ids", "dists", "io", "hops", "tier0_hits"):
            np.testing.assert_array_equal(
                np.asarray(getattr(base, f)),
                np.asarray(getattr(r, f)), err_msg=f"compact={cf} {f}")
        assert int(r.rounds) == int(base.rounds)


def test_compaction_gathers_are_cond_gated(device_seg, small_data):
    """ROADMAP (a) regression (ISSUE 5): compaction must cost nothing
    on rounds that do not compact. The permuted ``queries``/``lut``
    rows are carried in the loop state and every permutation gather
    sits behind a ``lax.cond``, so the while-loop body's *top-level*
    gather count is identical with compaction on or off — a
    no-compaction trace issues zero extra gathers per round. (Before
    the fix the compact body re-gathered queries/lut plus all eleven
    state arrays unconditionally: ~13 extra top-level gathers.)"""
    import jax

    _, q = small_data

    def while_body_gathers(p):
        closed = jax.make_jaxpr(
            lambda qq: DS.device_anns(device_seg, qq, p))(jnp.asarray(q))
        counts = []

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "while":
                    body = eqn.params["body_jaxpr"].jaxpr
                    # top level only: gathers inside cond branches are
                    # exactly the ones a non-compacting round skips
                    counts.append(sum(1 for e in body.eqns
                                      if e.primitive.name == "gather"))
                    walk(body)
                elif eqn.primitive.name in ("jit", "pjit", "scan"):
                    walk(eqn.params["jaxpr"].jaxpr)
        walk(closed.jaxpr)
        return counts

    p = dataclasses.replace(P48, max_hops=64)
    off = while_body_gathers(p)
    on = while_body_gathers(dataclasses.replace(p, compact_frac=0.5))
    assert len(off) == len(on) == 1      # one batched block-search loop
    assert on[0] == off[0], (
        f"compaction added {on[0] - off[0]} unconditional gathers per "
        f"round — the permutation must stay cond-gated")


# ------------------------------------------------------- named stages

STAGED = DeviceSearchParams(k=10, candidates=48, max_hops=256,
                            fetch_width=2, compact_frac=0.25,
                            speculate=True, trace_rounds=True)
_HLO_OP = re.compile(r'^\s*(?:ROOT )?%\S+ = .*? ([a-z][\w-]*)\(.*'
                     r'op_name="([^"]*)"')


@pytest.fixture(scope="module", params=["device_anns",
                                        "device_range_search"])
def staged_hlo(request, small_segment, small_data):
    """(entry name, [(opcode, op_name path)] of every instruction) of
    the compiled search, with every optional loop stage on."""
    ds = DS.from_segment(small_segment, tier0_frac=0.1)
    q = jnp.asarray(small_data[1][:8])
    if request.param == "device_anns":
        lowered = DS.device_anns.lower(ds, q, STAGED)
    else:
        lowered = DS.device_range_search.lower(ds, q, radius=1.0,
                                               k_cap=64, p=STAGED,
                                               rounds=2)
    ops = [m.groups() for m in map(_HLO_OP.match,
                                   lowered.compile().as_text().splitlines())
           if m]
    return request.param, ops


@pytest.mark.slow
def test_compiled_search_carries_every_stage_name(staged_hlo):
    """Every stage of ``DS.STAGES`` names ops of the compiled search in
    their ``op_name`` metadata, which the profiler's trace carries; no
    op is under two stages."""
    _, ops = staged_hlo
    paths = [path.split("/") for _, path in ops]
    assert set(DS.STAGES) <= {c for p in paths for c in p}
    assert all(sum(c in DS.STAGES for c in p) <= 1 for p in paths)


@pytest.mark.slow
def test_loop_body_ops_fall_under_exactly_one_round_stage(staged_hlo):
    """Every gather, sort, scatter and custom call of the block-search
    loop's body is under exactly one ``round_*`` stage, so the stages'
    device time adds up to the round's."""
    entry, ops = staged_hlo
    rounds = [s for s in DS.STAGES if s.startswith("round_")]
    body = [(op, path.split("/")) for op, path in ops
            if op in ("gather", "sort", "scatter", "custom-call")
            and path.startswith(f"jit({entry})/while/body/")]
    assert {"gather", "sort", "scatter"} <= {op for op, _ in body}
    for op, path in body:
        assert sum(c in rounds for c in path) == 1, (op, "/".join(path))


@pytest.mark.slow
def test_dedup_counters_consistent(device_seg, small_data):
    """dedup_saved counts a subset of cold touches (io keeps its seed
    semantics: every cold touch), and duplicate queries drive it up."""
    _, q = small_data
    p = dataclasses.replace(P48, max_hops=64)
    r = DS.device_anns(device_seg, jnp.asarray(q), p)
    io, sv = np.asarray(r.io), np.asarray(r.dedup_saved)
    assert (sv >= 0).all() and (sv <= io).all()
    assert (np.asarray(r.hops) <= int(r.rounds)).all()
    qd = np.repeat(q[:4], 3, axis=0)          # heavy duplication
    rd = DS.device_anns(device_seg, jnp.asarray(qd), p)
    assert (np.asarray(rd.dedup_saved).mean()
            > sv.mean()), "duplicate-heavy batch must dedup more"


def test_cross_tile_dedup_on_duplicate_heavy_batch(device_seg,
                                                   small_data):
    """ISSUE 8 satellite (deterministic twin of the tiled hypothesis
    property): with ``round_tile_cap=8`` a 16-row batch runs as two
    round tiles, and rows 8..15 duplicating rows 0..7 sit in the
    OTHER tile — their cold traffic joins batch-wide, and every one of
    those joins is accounted in the cross-tile split."""
    _, q = small_data
    p = dataclasses.replace(P48, max_hops=64, fetch_width=2,
                            compact_frac=0.0, round_tile_cap=8)
    perm = list(range(8)) + list(range(8))    # tile 1 duplicates tile 0
    r = DS.device_anns(device_seg, jnp.asarray(q[perm]), p)
    io, sv, cx = (np.asarray(r.io), np.asarray(r.dedup_saved),
                  np.asarray(r.dedup_cross))
    assert (0 <= cx).all() and (cx <= sv).all() and (sv <= io).all()
    # a duplicate row's every request was already issued by its twin in
    # tile 0, so ALL its gathers join; the joins a tile-scope dedup
    # could not have seen (earliest requester in the other tile) land
    # in the cross-tile split — strictly positive for every dup row
    assert io[8:].sum() > 0
    np.testing.assert_array_equal(sv[8:], io[8:])
    assert (cx[8:] > 0).all()
    # tile-0 rows are the earliest requesters of every block they touch:
    # any join they make is with another tile-0 row (intra-tile only)
    assert (cx[:8] == 0).all()
    # results are invariant to the tiling itself
    r0 = DS.device_anns(device_seg, jnp.asarray(q[perm]),
                        dataclasses.replace(p, round_tile_cap=0))
    np.testing.assert_array_equal(np.asarray(r.ids), np.asarray(r0.ids))
    np.testing.assert_array_equal(np.asarray(r.dists),
                                  np.asarray(r0.dists))
    np.testing.assert_array_equal(io, np.asarray(r0.io))
    # single-tile run sees the same joins, just none of them cross-tile
    np.testing.assert_array_equal(sv, np.asarray(r0.dedup_saved))
    assert int(np.asarray(r0.dedup_cross).sum()) == 0


def test_pipeline_dma_knob_is_payload_invariant(device_seg, small_data):
    """ISSUE 8: ``pipeline_dma`` schedules the cold gather's DMAs — it
    must never change results or any per-query counter (the kernel-
    level payload identity of the double-buffered gather is pinned in
    test_kernels; this guards the end-to-end wiring)."""
    _, q = small_data
    p = dataclasses.replace(P48, max_hops=64, fetch_width=2)
    qb = jnp.asarray(q[:8])
    r_on = DS.device_anns(device_seg, qb,
                          dataclasses.replace(p, pipeline_dma=True))
    r_off = DS.device_anns(device_seg, qb,
                           dataclasses.replace(p, pipeline_dma=False))
    for f in ("ids", "dists", "io", "tier0_hits", "hops",
              "dedup_saved", "dedup_cross"):
        np.testing.assert_array_equal(
            np.asarray(getattr(r_on, f)), np.asarray(getattr(r_off, f)),
            err_msg=f"pipeline_dma changed {f}")
    assert int(r_on.rounds) == int(r_off.rounds)


@pytest.mark.slow
def test_speculation_is_result_and_counter_invariant(device_seg,
                                                     small_data):
    """ISSUE 9 acceptance (deterministic twin of the hypothesis
    property): the cross-round speculative pipeline never changes
    results or any non-speculative counter — a mis-speculated block is
    re-gathered by the authoritative path, never trusted — across
    batch sizes, round tilings and fetch widths. Its own counters obey
    hits <= paying gathers, are zero with the knob off, and are
    invariant to the round tiling (prediction runs on whole-batch
    state, unlike the dedup intra/cross split)."""
    _, q = small_data
    base_p = dataclasses.replace(P48, max_hops=64)
    last_spec = None
    for b, cap, fw in ((4, 0, 1), (8, 0, 2), (16, 0, 2), (16, 8, 2)):
        p = dataclasses.replace(base_p, round_tile_cap=cap,
                                fetch_width=fw)
        qb = jnp.asarray(q[:b])
        r0 = DS.device_anns(device_seg, qb, p)
        r1 = DS.device_anns(device_seg, qb,
                            dataclasses.replace(p, speculate=True))
        for f in ("ids", "dists", "io", "tier0_hits", "hops",
                  "dedup_saved", "dedup_cross"):
            np.testing.assert_array_equal(
                np.asarray(getattr(r0, f)),
                np.asarray(getattr(r1, f)),
                err_msg=f"speculate changed {f} (b={b}, cap={cap}, "
                        f"fw={fw})")
        assert int(r0.rounds) == int(r1.rounds)
        assert (np.asarray(r0.spec_hits) == 0).all()
        assert (np.asarray(r0.spec_wasted) == 0).all()
        io, sv = np.asarray(r1.io), np.asarray(r1.dedup_saved)
        sh, sw = np.asarray(r1.spec_hits), np.asarray(r1.spec_wasted)
        assert (sh >= 0).all() and (sw >= 0).all()
        # a hit is a paying gather pre-issued early — never more of
        # them than the batch actually paid for
        assert (sh <= io - sv).all()
        if (b, fw) == (16, 2):
            # tiling must not move the speculation counters (cap 0 and
            # cap 8 run in consecutive iterations here)
            if last_spec is not None:
                np.testing.assert_array_equal(last_spec[0], sh)
                np.testing.assert_array_equal(last_spec[1], sw)
            last_spec = (sh, sw)
    assert sh.sum() > 0, "this workload should speculate successfully"


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                       # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @pytest.mark.slow
    @given(rows=st.lists(st.integers(0, 23), min_size=8, max_size=8),
           cap=st.sampled_from([0, 4]),
           fw=st.sampled_from([1, 2]))
    @settings(max_examples=6, deadline=None)
    def test_speculation_invariance_property(rows, cap, fw, device_seg,
                                             small_data):
        """ANY batch composition x tiling x fetch width: speculation
        on/off ``(ids, dists)`` and every shared counter (including
        the zeroed spec columns of the off run) are bit-identical."""
        _, q = small_data
        p = dataclasses.replace(P48, max_hops=64, round_tile_cap=cap,
                                fetch_width=fw)
        qb = jnp.asarray(q[np.asarray(rows)])
        r0 = DS.device_anns(device_seg, qb, p)
        r1 = DS.device_anns(device_seg, qb,
                            dataclasses.replace(p, speculate=True))
        for f in ("ids", "dists", "io", "tier0_hits", "hops",
                  "dedup_saved", "dedup_cross"):
            np.testing.assert_array_equal(
                np.asarray(getattr(r0, f)), np.asarray(getattr(r1, f)))
        assert int(r0.rounds) == int(r1.rounds)
        assert not np.asarray(r0.spec_hits).any()
        assert not np.asarray(r0.spec_wasted).any()
        sh = np.asarray(r1.spec_hits)
        assert (sh <= np.asarray(r1.io)
                - np.asarray(r1.dedup_saved)).all()


def test_tier0_repack_from_observed_frequencies(small_segment):
    """ISSUE 4 satellite (dynamic tier-0 admission): a drifted observed
    frequency profile re-ranks the pack — the observed-hot blocks enter
    at a budget that would otherwise exclude them — while search
    results stay bit-identical (exact copies either way)."""
    rho = small_segment.view.store.num_blocks
    ds_static = DS.from_segment(small_segment, tier0_blocks=4)
    static_hot = set(np.flatnonzero(
        np.asarray(ds_static.hot_slot_of) >= 0).tolist())
    drifted = [b for b in range(rho) if b not in static_hot][:4]
    observed = {b: 100 + i for i, b in enumerate(drifted)}
    ds_dyn = DS.from_segment(small_segment, tier0_blocks=4,
                             observed=observed)
    dyn_hot = set(np.flatnonzero(
        np.asarray(ds_dyn.hot_slot_of) >= 0).tolist())
    assert dyn_hot == set(drifted), \
        "observed-hot blocks must displace the build-time pack"
    # higher observed count -> earlier slot (frequency-desc ranking)
    slots = np.asarray(ds_dyn.hot_slot_of)[drifted]
    assert (np.argsort(-np.asarray(
        [observed[b] for b in drifted])) == np.argsort(slots)).all()
    # the pack still holds exact copies
    b = drifted[0]
    s = int(np.asarray(ds_dyn.hot_slot_of)[b])
    np.testing.assert_array_equal(np.asarray(ds_dyn.hot_vecs[s]),
                                  np.asarray(ds_dyn.vecs[b]))


@pytest.mark.slow
def test_tier0_repack_results_bit_identical(small_segment, small_data):
    _, q = small_data
    p = dataclasses.replace(P48, max_hops=64)
    r0 = DS.device_anns(DS.from_segment(small_segment, tier0_blocks=8),
                        jnp.asarray(q[:8]), p)
    rho = small_segment.view.store.num_blocks
    r1 = DS.device_anns(
        DS.from_segment(small_segment, tier0_blocks=8,
                        observed={b: rho - b for b in range(rho)}),
        jnp.asarray(q[:8]), p)
    np.testing.assert_array_equal(np.asarray(r0.ids), np.asarray(r1.ids))
    np.testing.assert_array_equal(np.asarray(r0.dists),
                                  np.asarray(r1.dists))
    np.testing.assert_array_equal(
        np.asarray(r0.io) + np.asarray(r0.tier0_hits),
        np.asarray(r1.io) + np.asarray(r1.tier0_hits))


# -------------------------------------------------------- range search

@pytest.mark.slow
def test_device_range_search(device_seg, small_data):
    x, q = small_data
    d_gt = D.pairwise(q, x)
    radius = float(np.quantile(d_gt, 0.002))
    r = DS.device_range_search(
        device_seg, jnp.asarray(q), radius=radius, k_cap=64,
        p=DeviceSearchParams(k=10, candidates=32, max_hops=256))
    gt = D.brute_force_range(x, q, radius)
    hits = 0
    total = 0
    for qi in range(q.shape[0]):
        got = set(np.asarray(r.ids[qi])[np.asarray(
            r.in_range[qi])].tolist())
        want = set(gt[qi].tolist())
        if want:
            hits += len(got & want)
            total += len(want)
    assert total == 0 or hits / total >= 0.6


@pytest.mark.slow
def test_device_range_search_io_flat_across_rounds(device_seg,
                                                   small_data):
    """ISSUE 3 satellite regression: RS rounds thread the visited/
    result state, so a later round must NOT re-read (and re-count in
    ``io``) the blocks earlier rounds already fetched.

    Before the fix every round re-ran ``device_anns`` from scratch, so
    round r's DMA count matched a fresh search at that round's beam.
    After the fix each round only pays for *newly expanded* blocks: its
    DMA increment must stay well under the from-scratch cost, and the
    3-round total well under the pre-fix sum of scratch runs."""
    x, q = small_data
    d_gt = D.pairwise(q, x)
    radius = float(np.quantile(d_gt, 0.002))
    p = DeviceSearchParams(k=10, candidates=32, max_hops=256)
    io = {}
    for rounds in (1, 2, 3):
        r = DS.device_range_search(device_seg, jnp.asarray(q),
                                   radius=radius, k_cap=128, p=p,
                                   rounds=rounds)
        io[rounds] = float(np.asarray(r.io).mean())
    # the pre-fix behavior: a fresh search per round at the doubled beam
    scratch = {}
    for c in (32, 64, 128):
        rs = DS.device_anns(
            device_seg, jnp.asarray(q),
            DeviceSearchParams(k=c, candidates=c, max_hops=256))
        scratch[c] = float(np.asarray(rs.io).mean())
    assert io[1] == scratch[32]            # round 1 is a plain search
    # each resumed round fetches far fewer blocks than a scratch run at
    # the same beam (it skips everything already expanded)
    assert io[2] - io[1] <= 0.75 * scratch[64]
    assert io[3] - io[2] <= 0.75 * scratch[128]
    # and the total stays well under the pre-fix sum
    pre_fix_total = scratch[32] + scratch[64] + scratch[128]
    assert io[3] <= 0.65 * pre_fix_total, (
        f"RS DMAs must stay near-flat across rounds (threaded total "
        f"{io[3]:.1f} vs pre-fix {pre_fix_total:.1f})")


# ------------------------------------------------------------- helpers

def test_visited_bitmask_helpers():
    mask = jnp.zeros((2, 4), jnp.uint32)
    ids = jnp.asarray([5, 97])
    mask = DS._bit_set(mask, ids, jnp.asarray([True, True]))
    got = DS._bit_get(mask, jnp.asarray([[5, 6, 97], [97, 5, 0]]))
    np.testing.assert_array_equal(
        np.asarray(got), [[True, False, False], [True, False, False]])


def test_merge_top_dedup():
    keys = jnp.asarray([[1.0, 3.0, jnp.inf]])
    ids = jnp.asarray([[7, 9, -1]], jnp.int32)
    nk = jnp.asarray([[0.5, 1.0, 2.0]])
    ni = jnp.asarray([[9, 7, 11]], jnp.int32)
    k, i = DS._merge_top(keys, ids, nk, ni, 4)
    # 9 appears twice (3.0 and 0.5): keep 0.5; 7 twice (1.0 both)
    assert i[0, 0] == 9 and float(k[0, 0]) == 0.5
    assert 11 in np.asarray(i[0]).tolist()
    vals = np.asarray(i[0]).tolist()
    assert len([v for v in vals if v == 9]) == 1


def _merge_top_gather(keys, ids, new_keys, new_ids, size, extra=None,
                      new_extra=None):
    """The merge as an index sort followed by gathers: a lexsort by
    (id, key), then a stable argsort by key, each applied to the ids,
    keys and payload with ``take_along_axis``. ``_merge_top`` must give
    the same bits."""
    k = jnp.concatenate([keys, new_keys], axis=1)
    i = jnp.concatenate([ids, new_ids], axis=1)
    order = jnp.lexsort((k, i))
    k = jnp.take_along_axis(k, order, axis=1)
    i = jnp.take_along_axis(i, order, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros((i.shape[0], 1), bool), i[:, 1:] == i[:, :-1]], axis=1)
    dup |= i < 0
    k = jnp.where(dup, jnp.inf, k)
    i = jnp.where(dup, -1, i)
    order2 = jnp.argsort(k, axis=1)[:, :size]
    out = (jnp.take_along_axis(k, order2, axis=1),
           jnp.take_along_axis(i, order2, axis=1))
    if extra is None:
        return out
    e = jnp.concatenate([extra, new_extra], axis=1)
    e = jnp.where(dup, 0, jnp.take_along_axis(e, order, axis=1))
    return out + (jnp.take_along_axis(e, order2, axis=1),)


def _merge_top_np(keys, ids, new_keys, new_ids, size, extra=None,
                  new_extra=None):
    """NumPy reference of the same formulation."""
    k = np.concatenate([keys, new_keys], axis=1)
    i = np.concatenate([ids, new_ids], axis=1)
    order = np.lexsort((k, i), axis=1)
    k = np.take_along_axis(k, order, axis=1)
    i = np.take_along_axis(i, order, axis=1)
    dup = np.zeros(i.shape, bool)
    dup[:, 1:] = i[:, 1:] == i[:, :-1]
    dup |= i < 0
    k = np.where(dup, np.inf, k).astype(np.float32)
    i = np.where(dup, -1, i).astype(np.int32)
    order2 = np.argsort(k, axis=1, kind="stable")[:, :size]
    out = (np.take_along_axis(k, order2, axis=1),
           np.take_along_axis(i, order2, axis=1))
    if extra is None:
        return out
    e = np.concatenate([extra, new_extra], axis=1)
    e = np.where(dup, 0, np.take_along_axis(e, order, axis=1))
    return out + (np.take_along_axis(e, order2, axis=1).astype(np.int32),)


def _merge_inputs(rng, q, a, b, id_range, key_levels, invalid):
    """Random merge operands: keys drawn from ``key_levels`` values (few
    levels = heavy ties), ids from [0, id_range) (small range = many
    duplicates, often with different keys), a share ``invalid`` of
    slots id -1 / key inf, and a few live ids with an inf key."""
    def side(w):
        key = rng.integers(0, key_levels, (q, w)).astype(np.float32) / 4
        ids = rng.integers(0, id_range, (q, w)).astype(np.int32)
        bad = rng.random((q, w)) < invalid
        ids[bad] = -1
        key[bad | (rng.random((q, w)) < 0.05)] = np.inf
        flag = rng.integers(0, 2, (q, w)).astype(np.int32)
        return key, ids, flag
    (k0, i0, e0), (k1, i1, e1) = side(a), side(b)
    return k0, i0, k1, i1, e0, e1


@pytest.mark.parametrize("with_extra", [False, True])
@pytest.mark.parametrize("case", [
    # name: (Q, A, B, size, id_range, key_levels, invalid share)
    ("key_ties", (8, 40, 12, 24, 10_000, 3, 0.1)),
    ("dup_ids", (8, 40, 12, 24, 16, 1_000, 0.1)),
    ("invalid_slots", (8, 40, 12, 24, 64, 20, 0.6)),
    ("size_is_width", (8, 40, 12, 52, 32, 5, 0.2)),
    ("candidate_shape", (4, 1024, 186, 1024, 1500, 50, 0.3)),
], ids=lambda c: c[0] if isinstance(c, tuple) else None)
def test_merge_top_bit_identical_to_gather_formulation(case, with_extra):
    """``_merge_top`` (sorts that carry their payloads) returns the same
    bits as a lexsort + stable argsort + ``take_along_axis`` merge."""
    _, (q, a, b, size, id_range, key_levels, invalid) = case
    rng = np.random.default_rng(a * 31 + b + size + id_range)
    for _ in range(5):
        k0, i0, k1, i1, e0, e1 = _merge_inputs(
            rng, q, a, b, id_range, key_levels, invalid)
        extra = (e0, e1) if with_extra else (None, None)
        want = _merge_top_np(k0, i0, k1, i1, size, *extra)
        got = DS._merge_top(*map(jnp.asarray, (k0, i0, k1, i1)), size,
                            *(None if x is None else jnp.asarray(x)
                              for x in extra))
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert g.shape == (q, size) and g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g), w)


@pytest.mark.parametrize("shape", [
    # (Q, A, B, size, extra): the cell's candidate and result merges,
    # and the navigation beam's merge with its visited payload
    (256, 1024, 186, 1024, False),
    (256, 34, 12, 34, False),
    (256, 8, 8, 8, True),
])
def test_merge_top_lowers_without_gather(shape):
    """The merge is two payload-carrying sorts: its StableHLO has sorts
    and no gather, where the index-sort formulation has gathers."""
    q, a, b, size, with_extra = shape
    f32 = lambda w: jax.ShapeDtypeStruct((q, w), jnp.float32)
    i32 = lambda w: jax.ShapeDtypeStruct((q, w), jnp.int32)
    args = (f32(a), i32(a), f32(b), i32(b), size)
    args += (i32(a), i32(b)) if with_extra else ()
    new = jax.jit(DS._merge_top, static_argnums=4).lower(*args).as_text()
    old = jax.jit(_merge_top_gather, static_argnums=4).lower(*args).as_text()
    assert "gather" not in new
    assert new.count("stablehlo.sort") == 2
    assert "gather" in old


@pytest.mark.slow
def test_device_anns_identical_with_gather_merge(device_seg, small_data,
                                                 monkeypatch):
    """The whole search gives the same ids, dists and counters with the
    payload-carrying merge as with the index-sort + gather merge."""
    _, q = small_data
    p = dataclasses.replace(P48, max_hops=64)
    search = lambda: jax.jit(DS.device_anns.__wrapped__,
                             static_argnames=("p", "metric"))(
        device_seg, jnp.asarray(q), p)
    new = search()
    monkeypatch.setattr(DS, "_merge_top", _merge_top_gather)
    old = search()
    for field in ("ids", "dists", "io", "hops", "tier0_hits", "rounds"):
        np.testing.assert_array_equal(np.asarray(getattr(new, field)),
                                      np.asarray(getattr(old, field)),
                                      err_msg=field)


def test_device_search_params_validation():
    with pytest.raises(ValueError):
        DeviceSearchParams(k=0)
    with pytest.raises(ValueError):
        DeviceSearchParams(k=10, candidates=4)
    with pytest.raises(ValueError):
        DeviceSearchParams(fetch_impl="mosaic")
    with pytest.raises(ValueError):
        DeviceSearchParams(tier0_frac=1.5)


@pytest.mark.slow
def test_fetch_width_cuts_round_trips(device_seg, small_data):
    """§Perf cell 3: F blocks per round trip -> ~F-fold fewer trips at
    comparable recall and block reads."""
    x, q = small_data
    truth = D.brute_force_knn(x, q, 10)
    res = {}
    for fw in (1, 2):
        r = DS.device_anns(
            device_seg, jnp.asarray(q),
            dataclasses.replace(P48, fetch_width=fw))
        res[fw] = (recall_at_k(np.asarray(r.ids), truth),
                   float(np.asarray(r.io).mean()),
                   float(np.asarray(r.hops).mean()))
    assert res[2][0] >= res[1][0] - 0.05          # recall preserved
    assert res[2][2] <= 0.62 * res[1][2]          # trips ~halve
    assert res[2][1] <= 1.5 * res[1][1]           # bandwidth bounded
