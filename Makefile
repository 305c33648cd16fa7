# Tier-1 verification and common dev entry points.

PY ?= python

.PHONY: test test-fast test-device test-e2e test-obs test-mesh \
	test-hybrid bench bench-io bench-device bench-batch bench-obs \
	bench-mesh bench-hybrid dev-deps

test:
	PYTHONPATH=src $(PY) -m pytest -x -q

# fast lane: skips the build-heavy tests marked @pytest.mark.slow
# (full-size segment builds, jit compiles); the full suite still runs
# via `make test` and the scheduled CI lane
test-fast:
	PYTHONPATH=src $(PY) -m pytest -x -q -m "not slow"

# interpret-mode device lane: the Pallas kernels + the non-compiling
# device-search helpers (the CI device lane runs exactly this)
test-device:
	PYTHONPATH=src $(PY) -m pytest -x -q -m "not slow" \
		tests/test_kernels.py tests/test_device_search.py

# the end-to-end conformance suite (ISSUE 5): one segment, every search
# path (host, device fused/jnp, served/batched) against the brute-force
# oracle, cross-path bit-identity, golden IOStats totals. Runs the
# Pallas kernels in interpret mode (the CPU default); includes the
# build-heavy slow cases — its own CI lane
test-e2e:
	PYTHONPATH=src $(PY) -m pytest -x -q tests/test_e2e_conformance.py

# the hybrid hot/cold tier (ISSUE 10): the hotset-bugfix regressions,
# hot-tier/delta-segment units, seed-override bit-identity and the
# scheduler layout-swap invalidation, plus the hybrid slice of the e2e
# conformance suite (recall + strict cold-I/O cut, tombstone masking,
# compaction bit-identity)
test-hybrid:
	PYTHONPATH=src $(PY) -m pytest -x -q tests/test_hybrid.py \
		tests/test_e2e_conformance.py -k "hybrid or delta"

# the hybrid budget sweep: memory-vs-disk modeled latency split at
# fixed recall, with the strict cold-I/O-cut acceptance asserted
# in-sweep; the fresh BENCH_hybrid_hot_tier.json is gated against the
# committed baseline
bench-hybrid:
	BENCH_SMOKE=1 PYTHONPATH=src $(PY) -m benchmarks.run \
		--only hybrid_hot_tier_sweep
	PYTHONPATH=src $(PY) -m benchmarks.check_regression \
		--artifact hybrid_hot_tier

bench:
	PYTHONPATH=src $(PY) -m benchmarks.run

bench-io:
	PYTHONPATH=src $(PY) -m benchmarks.run --only io_cache_hit_rate_sweep
	PYTHONPATH=src $(PY) -m benchmarks.run --only io_prefetch_width_sweep
	PYTHONPATH=src $(PY) -m benchmarks.run --only io_queue_depth_sweep
	PYTHONPATH=src $(PY) -m benchmarks.run --only io_tier2_budget_sweep

# the device sweeps: tier-0 VMEM budget (modeled DMA cut at matched
# recall), fetch width, RS round restarts, kernel micro, roofline render
bench-device:
	PYTHONPATH=src $(PY) -m benchmarks.run --only device_vs_host
	PYTHONPATH=src $(PY) -m benchmarks.run --only device_tier0_budget_sweep
	PYTHONPATH=src $(PY) -m benchmarks.run --only starling_fetch_width
	PYTHONPATH=src $(PY) -m benchmarks.run --only device_range_search_rounds
	PYTHONPATH=src $(PY) -m benchmarks.run --only kernel_micro
	PYTHONPATH=src $(PY) -m benchmarks.run --only roofline_tables

# smoke lane for the divergence-aware batched path (ISSUE 4), the
# adaptive repack control loop (ISSUE 5) and the cross-round
# speculative pipeline (ISSUE 9): tiny sweeps with the bit-identity /
# strict-DMA-cut / strict-latency-win assertions on (BENCH_SMOKE
# shrinks them; all skip gracefully with no jax backend). The fresh
# BENCH_*.json artifacts are then gated against the committed
# baselines (benchmarks/check_regression.py ARTIFACT_GATES,
# direction-aware: >10% regression of any gated metric fails the lane)
bench-batch:
	BENCH_SMOKE=1 PYTHONPATH=src $(PY) -m benchmarks.run \
		--only device_batch_dedup_sweep
	BENCH_SMOKE=1 PYTHONPATH=src $(PY) -m benchmarks.run \
		--only device_drift_repack_sweep
	BENCH_SMOKE=1 PYTHONPATH=src $(PY) -m benchmarks.run \
		--only device_speculate_sweep
	PYTHONPATH=src $(PY) -m benchmarks.check_regression

# the observability plane (repro.obs): trace/metrics/roundlog/
# calibration unit + property tests, then the trace smoke and the
# cost-calibration harness (BENCH_* perf artifacts + CALIB_*.json
# presets land in results/)
test-obs:
	PYTHONPATH=src $(PY) -m pytest -x -q -m "not slow" \
		tests/test_obs.py tests/test_trace_roundlog.py

bench-obs:
	BENCH_SMOKE=1 PYTHONPATH=src $(PY) -m benchmarks.run \
		--only obs_trace_smoke
	BENCH_SMOKE=1 PYTHONPATH=src $(PY) -m benchmarks.run \
		--only cost_calibration

# the mesh-serving plane (ISSUE 7): shard_map fan-out router over a
# forced 8-device host mesh — XLA_FLAGS must be set before jax
# initializes, hence the dedicated lane. Asserts routed-vs-single-
# target bit-identity, per-rank IOStats fold exactness, and the
# rebalance fire/quiet behaviour; skips (rather than fails) on worlds
# smaller than 8 devices
test-mesh:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		PYTHONPATH=src $(PY) -m pytest -x -q tests/test_router.py

# modeled-vs-served per-rank step time on the same forced mesh
# (results/BENCH_mesh_router.json, uploaded by the CI mesh lane), then
# the slowest-rank step-time gate against the committed baseline
bench-mesh:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		BENCH_SMOKE=1 PYTHONPATH=src $(PY) -m benchmarks.run \
		--only mesh_router_bench
	PYTHONPATH=src $(PY) -m benchmarks.check_regression \
		--artifact mesh_router

dev-deps:
	$(PY) -m pip install -r requirements-dev.txt
