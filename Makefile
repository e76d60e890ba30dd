# One-step entry points for the repo's standard workflows.

PY ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH),)

.PHONY: test test-fast conformance check bench bench-smoke ci obs \
	obs-artifacts worker-fleet artifact-check serve-trees serve-gateway

# tier-1 verify (see ROADMAP.md)
test:
	$(PY) -m pytest -x -q

# tier-1 minus the long end-to-end drivers (the `slow` marker) — what the
# CI tier-1 job runs; `make check` still runs everything
test-fast:
	$(PY) -m pytest -q -m "not slow"

# the observability suite alone: histograms, tracer, span integrity
# through the gateway, exposition renderers
obs:
	$(PY) -m pytest -q tests/test_obs.py

# short fully-traced gateway run -> sample trace JSONL + metrics snapshot
# (Prometheus text + JSON) under benchmarks/artifacts/, uploaded by CI
obs-artifacts:
	mkdir -p benchmarks/artifacts
	$(PY) -m repro.launch.serve --trees --gateway --rows 2000 \
		--gw-requests 80 --gw-rate 1000 \
		--gw-trace-out benchmarks/artifacts/trace_sample.jsonl \
		--gw-metrics-out benchmarks/artifacts/metrics_snapshot.prom

# cross-(backend, layout, variant, plan) bit-identity suite: reference /
# pallas (gather + leaf_major linear scan) / native_c / native_c_table
# (block_rows 1/4/8) / native_c_bitvector (interleave widths K=1/4/8)
# x padded / ragged / leaf_major / bitvector x {single,
# tree_parallel(2,3,8), row_parallel(2,4)}.  XLA is forced to 8 host
# devices so the tree-parallel shard_map path runs for real (the same
# configuration CI uses) — without the flag those cases fall back to the
# threaded per-shard-backend path, which must be bit-identical anyway.
conformance:
	XLA_FLAGS="--xla_force_host_platform_device_count=8 $$XLA_FLAGS" \
		$(PY) -m pytest -q tests/test_backends.py tests/test_plans.py

# the full gate: tier-1 tests, then the conformance suite standalone
check: test conformance

bench:
	$(PY) benchmarks/run.py

# tiny-forest bench pass: proves every backend and every execution plan
# executes (plan_scaling runs the shard_map tree-parallel path on 8 forced
# host devices) and produces the benchmarks/artifacts/bench_results.json
# artifact CI uploads
bench-smoke:
	REPRO_BENCH_TINY=1 REPRO_BENCH_DEVICES=8 \
		REPRO_BENCH_SNAPSHOT=BENCH_10.json \
		$(PY) benchmarks/run.py backend_matrix backend_bitvector \
		memory_footprint plan_scaling remote_scaleout coldstart_swap

# the remote-worker fabric suite: spawns loopback worker processes, runs
# the cross-process conformance + kill/re-dispatch tests, and (via
# REPRO_WORKER_SPAN_DIR) collects worker-side span JSONL under
# benchmarks/artifacts/ for the CI artifact upload
worker-fleet:
	mkdir -p benchmarks/artifacts
	REPRO_WORKER_SPAN_DIR=benchmarks/artifacts \
		$(PY) -m pytest -q tests/test_remote.py tests/test_spec.py

# ITRF artifact gate: the pytest artifact suite (round-trip bit-identity,
# mmap safety, registry retention), then the converter selftest, which
# trains a forest, converts it, and reloads the .itrf in a FRESH process
# via mmap asserting bit-identical reference partials.  Leaves
# benchmarks/artifacts/model.itrf for the CI artifact upload.
artifact-check:
	mkdir -p benchmarks/artifacts
	$(PY) -m pytest -q tests/test_artifact.py
	$(PY) -m repro.trees.convert --selftest benchmarks/artifacts/model.itrf
	$(PY) -m repro.trees.convert --inspect benchmarks/artifacts/model.itrf

# exactly what .github/workflows/ci.yml runs, as one local target
ci: test-fast conformance bench-smoke worker-fleet artifact-check

serve-trees:
	$(PY) -m repro.launch.serve --trees

serve-gateway:
	$(PY) -m repro.launch.serve --trees --gateway
