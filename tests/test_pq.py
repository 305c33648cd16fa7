"""Product-quantization properties (§5.1 PQ routing)."""
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need hypothesis; rest of the suite runs without")
from hypothesis import given, settings, strategies as st

from repro.core import distances as D
from repro.core.params import PQParams
from repro.pq import (adc_distance, adc_lut, adc_lut_batch, encode_pq,
                      reconstruct, train_pq)


def test_pq_roundtrip_error_small():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((4000, 32)).astype(np.float32)
    cb = train_pq(x, PQParams(num_subspaces=8, train_iters=8))
    codes = encode_pq(x, cb)
    rec = reconstruct(codes, cb)
    rel = np.linalg.norm(x - rec, axis=1) / np.linalg.norm(x, axis=1)
    assert rel.mean() < 0.6            # 4 dims/subspace @ 256 centroids


def test_adc_matches_reconstructed_distance():
    """ADC(q, code) == ||q - reconstruct(code)||^2 exactly (L2)."""
    rng = np.random.default_rng(1)
    x = rng.standard_normal((1000, 16)).astype(np.float32)
    cb = train_pq(x, PQParams(num_subspaces=4, train_iters=6))
    codes = encode_pq(x, cb)
    q = rng.standard_normal(16).astype(np.float32)
    lut = adc_lut(q, cb)
    adc = adc_distance(lut, codes[:50])
    exact = D.point_to_points(q, reconstruct(codes[:50], cb))
    np.testing.assert_allclose(adc, exact, rtol=2e-4, atol=1e-4)


def test_adc_ranking_correlates_with_exact():
    rng = np.random.default_rng(2)
    x = rng.standard_normal((2000, 32)).astype(np.float32)
    cb = train_pq(x, PQParams(num_subspaces=8, train_iters=8))
    codes = encode_pq(x, cb)
    q = rng.standard_normal(32).astype(np.float32)
    adc = adc_distance(adc_lut(q, cb), codes)
    exact = D.point_to_points(q, x)
    # top-50 by ADC should capture most of exact top-10
    top_adc = set(np.argsort(adc)[:50].tolist())
    top_exact = set(np.argsort(exact)[:10].tolist())
    assert len(top_adc & top_exact) >= 7


@settings(deadline=None, max_examples=10)
@given(m=st.sampled_from([2, 4, 8]), metric=st.sampled_from(["l2", "ip"]))
def test_lut_batch_consistency(m, metric):
    rng = np.random.default_rng(3)
    x = rng.standard_normal((600, 16)).astype(np.float32)
    cb = train_pq(x, PQParams(num_subspaces=m, train_iters=4), metric)
    q = rng.standard_normal((5, 16)).astype(np.float32)
    batch = adc_lut_batch(q, cb)
    for i in range(5):
        np.testing.assert_allclose(batch[i], adc_lut(q[i], cb),
                                   rtol=1e-5, atol=1e-5)


def _dot_precisions(jaxpr):
    """The ``precision`` of every dot_general in ``jaxpr``, sub-jaxprs
    (scan, loops, nested jits) included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn.params["precision"])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out.extend(_dot_precisions(inner))
    return out


@pytest.mark.parametrize("site", ["pq_lloyd", "pq_encode", "pairwise_jit",
                                  "prune_rows"])
def test_build_matmuls_run_at_full_f32_precision(site):
    """Every matmul of the build that runs on the device asks for full
    f32 products. An accelerator's default precision rounds f32 inputs
    to bf16: |x|^2 + |c|^2 - 2 x.c then picks other nearest centroids
    (or neighbours), and the chip builds a coarser segment than the CPU
    from the same seed."""
    import jax
    import jax.numpy as jnp
    from repro.core import graph as G
    from repro.pq import pq as PQ
    f32 = jnp.float32
    fn, args = {
        "pq_lloyd": (lambda x, c: PQ._lloyd(x, c, 2),
                     (jnp.zeros((64, 8), f32), jnp.zeros((16, 8), f32))),
        "pq_encode": (PQ._encode, (jnp.zeros((64, 4, 8), f32),
                                   jnp.zeros((4, 16, 8), f32))),
        "pairwise_jit": (D.pairwise_jit, (jnp.zeros((8, 16), f32),
                                          jnp.zeros((32, 16), f32))),
        "prune_rows": (lambda u, c: G._prune_rows(u, c, 1.0, 4, "l2"),
                       (jnp.zeros((8, 16), f32),
                        jnp.zeros((8, 12, 16), f32))),
    }[site]
    precs = _dot_precisions(jax.make_jaxpr(fn)(*args).jaxpr)
    highest = jax.lax.Precision.HIGHEST
    assert precs, f"{site} has no matmul to check"
    assert all(p is not None and all(x == highest for x in p)
               for p in precs), precs
