"""Compile the served search for a described TPU v5e, without a chip.

The TPU compiler is installed with JAX, and it compiles for a chip that
is described rather than attached. What it refuses here (a kernel that
does not lower with Mosaic, a block that does not fit VMEM, a program
that does not fit HBM) it would refuse on the chip. Nothing runs, so
these tests say nothing about results or times.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU
library, and the test runner's workers all import every test file.
"""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs.starling_segment import SEGMENT_BIGANN_F32
from repro.core import device_search as DS
from repro.kernels import ops
from repro.serving.coordinator import SERVE_DEVICE_SEARCH

# the chip smoke's segment at n = 2^20: D=128 f32, Λ=31, 4 KB blocks
N, DIM, Q = 1 << 20, 128, 256
LAM = SEGMENT_BIGANN_F32.graph.max_degree
EPS = SEGMENT_BIGANN_F32.layout.verts_per_block(DIM, LAM)
RHO = -(-N // EPS)
HOT = int(SEGMENT_BIGANN_F32.cache.tier0_frac * RHO)
PQ_M = SEGMENT_BIGANN_F32.pq.num_subspaces
NAV_N = int(SEGMENT_BIGANN_F32.nav.sample_ratio * N)
NAV_DEG = SEGMENT_BIGANN_F32.nav.max_degree
V5E_HBM = 16 * 10 ** 9


@pytest.fixture(scope="module")
def topo():
    # only a missing TPU compiler skips; any other failure to describe
    # the chip (a version mismatch, a library that will not load) fails
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU compiler (libtpu) is installed")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _on(sharding):
    """shape, dtype -> a ShapeDtypeStruct placed by ``sharding``."""
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


def _segment_specs(sharding) -> DS.DeviceSegment:
    """The shapes ``from_segment`` packs for the smoke's segment."""
    s = _on(sharding)
    return DS.DeviceSegment(
        vecs=s((RHO, EPS, DIM), jnp.float32),
        vid=s((RHO, EPS), jnp.int32),
        deg=s((RHO, EPS), jnp.int32),
        nbrs=s((RHO, EPS, LAM), jnp.int32),
        block_of=s((N,), jnp.int32),
        pq_codes=s((N, PQ_M), jnp.uint8),
        pq_cent=s((PQ_M, 256, DIM // PQ_M), jnp.float32),
        nav_vecs=s((NAV_N, DIM), jnp.float32),
        nav_adj=s((NAV_N, NAV_DEG), jnp.int32),
        nav_ids=s((NAV_N,), jnp.int32),
        nav_entry=s((), jnp.int32),
        hot_vecs=s((HOT, EPS, DIM), jnp.float32),
        hot_vid=s((HOT, EPS), jnp.int32),
        hot_nbrs=s((HOT, EPS, LAM), jnp.int32),
        hot_slot_of=s((RHO,), jnp.int32))


@pytest.mark.parametrize("pipeline", [True, False])
def test_fused_round_lowers_with_mosaic(one_chip, pipeline):
    """The round kernel compiles (not interpreted) at the smoke's
    shapes: block gather from the HBM store by scalar-prefetched ids,
    then the rank pass, under both DMA schedules."""
    seg = _segment_specs(one_chip)
    s = _on(one_chip)
    f = SERVE_DEVICE_SEARCH.fetch_width
    n_expand = 6

    def step(q, u, ds):
        return ops.fused_round(q, u, ds.block_of, ds.hot_slot_of,
                               ds.hot_vecs, ds.hot_vid, ds.hot_nbrs,
                               ds.vecs, ds.vid, ds.nbrs, n_expand,
                               interpret=False, pipeline_dma=pipeline)

    compiled = jax.jit(step).lower(
        s((Q, DIM), jnp.float32), s((Q, f), jnp.int32), seg).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", ["pairwise_l2", "pq_adc_batch",
                                    "block_rank"])
def test_side_kernels_lower_with_mosaic(one_chip, kernel):
    """The kernels off the served path (the benchmarks' distance tile,
    PQ-ADC scan and block rank) compile at the smoke's widths too."""
    s = _on(one_chip)
    call, args = {
        "pairwise_l2": (lambda q, x: ops.pairwise_l2(q, x,
                                                     interpret=False),
                        (s((Q, DIM), jnp.float32),
                         s((1 << 16, DIM), jnp.float32))),
        "pq_adc_batch": (lambda c, lut: ops.pq_adc_batch(
                             c, lut, interpret=False),
                         (s((N, PQ_M), jnp.uint8),
                          s((Q, PQ_M, 256), jnp.float32))),
        "block_rank": (lambda q, t: ops.block_rank(q, t, 3,
                                                   interpret=False),
                       (s((Q, DIM), jnp.float32),
                        s((Q, EPS, DIM), jnp.float32))),
    }[kernel]
    compiled = jax.jit(call).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _served_step(one_chip, params):
    seg = _segment_specs(one_chip)
    q = _on(one_chip)((Q, DIM), jnp.float32)
    return DS.device_anns.lower(seg, q, p=params).compile()


@pytest.mark.parametrize("gamma", [SERVE_DEVICE_SEARCH.candidates, 1024,
                                   2048])
def test_served_step_compiles_with_the_kernel(one_chip, gamma):
    """The whole served step (``device_anns`` with the serving knobs, at
    the default Γ, the chip smoke's Γ=1024 and a wider one) compiles
    for one v5e at n = 2^20, runs the Pallas kernels compiled
    (``tpu_custom_call``), and fits the chip's HBM."""
    compiled = _served_step(one_chip, dataclasses.replace(
        SERVE_DEVICE_SEARCH, candidates=gamma,
        max_hops=max(SERVE_DEVICE_SEARCH.max_hops, 4 * gamma)))
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM


def test_jnp_fetch_step_has_no_kernel(one_chip):
    """The reference fetch stage compiles to plain XLA: the
    ``tpu_custom_call`` check above tells the two apart."""
    compiled = _served_step(
        one_chip, dataclasses.replace(SERVE_DEVICE_SEARCH,
                                      fetch_impl="jnp"))
    assert "tpu_custom_call" not in compiled.as_text()


def test_knn_block_compiles_without_a_full_width_top_k(one_chip):
    """One block of the exact kNN (the NSG seed and the ground truth) at
    n = 2^20 fits the chip, and no top-k runs over a row of n: the
    two-stage top-k keeps each one to a few thousand columns."""
    from repro.core import distances as D
    s = _on(one_chip)
    rows = D._rows(4096, N)
    lowered = D._knn_block.lower(s((rows, DIM), jnp.float32),
                                 s((N, DIM), jnp.float32), k=65,
                                 metric="l2")
    top_k = [line for line in lowered.as_text().splitlines()
             if "top_k" in line]
    assert top_k and not any(f"x{N}x" in line for line in top_k)
    mem = lowered.compile().memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM
