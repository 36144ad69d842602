# Repo verification entry points. `make verify` is what CI runs: the tier-1
# suite (must collect with zero errors — hypothesis is optional) plus the
# COO-vs-ELL backend equivalence tests that pin the production sweep path.

PY ?= python
export PYTHONPATH := src:$(PYTHONPATH)

.PHONY: verify test fast bench-kernels bench-backends serve-smoke \
    engine-smoke sweep-smoke runtime-smoke decomp-smoke trace-smoke \
    control-smoke partition-smoke obs-watchdog-smoke bench-collect \
    bench-regress

# tier-1 command; testpaths covers tests/ including the backend-equivalence
# suite (tests/test_backends.py) that pins the production ELL sweep path
verify:
	$(PY) -m pytest -x -q

test:
	$(PY) -m pytest -q

fast:
	$(PY) -m pytest -q -m fast

bench-kernels:
	PYTHONPATH=src:. $(PY) -c "from benchmarks import kernels_bench; \
	    [print(r.csv()) for r in kernels_bench.run()]"

bench-backends:
	PYTHONPATH=src:. $(PY) -c "from benchmarks import fig_backends; \
	    [print(r.csv()) for r in fig_backends.run()]"

# continuous-serving smoke: exercises the MatchServer pipeline (queue →
# shared sweeps → query-bank match → telemetry) on a tiny churn stream
serve-smoke:
	PYTHONPATH=src:. $(PY) benchmarks/serving_bench.py --smoke

# engine multi-device smoke: query-axis sharded-vs-vmap equality AND the
# graph-axis (2-D mesh) bitwise-equivalence suite under 4 forced host
# devices, then the 1/2/4-device bank-16 sweep (each device count in its
# own forced-platform subprocess) — what the CI multi-device job runs
engine-smoke:
	XLA_FLAGS=--xla_force_host_platform_device_count=4 \
	    $(PY) -m pytest tests/test_engine_sharding.py \
	    tests/test_graph_sharding.py -q
	PYTHONPATH=src:. $(PY) benchmarks/engine_bench.py --smoke --query-only

# graph-axis n_max-scaling sweep in smoke mode: 1/2/4 forced devices ×
# storm-forced serving with the vertices sharded over ("g",)
sweep-smoke:
	PYTHONPATH=src:. $(PY) benchmarks/engine_bench.py --smoke --graph-only

# async serving-runtime smoke: the determinism/drain/no-deadlock suite
# (timeout-bounded — a runtime deadlock must fail CI, not hang it), then
# the threaded runtime end-to-end on a flash-crowd scenario via the CLI
runtime-smoke:
	timeout 1500 $(PY) -m pytest tests/test_runtime.py -q
	PYTHONPATH=src timeout 300 $(PY) -m repro.launch.serve \
	    --arch igpm-pem --smoke --async --scenario flash_crowd \
	    --rate 3000 --ticks 12 --bank 4

# shared sub-pattern decomposition: the refcounted-DAG suite (bitwise
# node-table ≡ per-row equivalence on both backends, churn refcount
# oracle, dedup-vs-unshared store equality, checkpoint round-trip), then
# the same decomposed-bank equivalence under the 4-device shard_map path
decomp-smoke:
	$(PY) -m pytest tests/test_decompose.py -q
	XLA_FLAGS=--xla_force_host_platform_device_count=4 \
	    $(PY) -m pytest tests/test_engine_sharding.py -q

# observability smoke (DESIGN.md §8): the obs test suite (zero-cost
# disabled pinned bitwise + by compiled-trace count, flight recorder,
# exporters, cross-thread runtime trace), then the end-to-end gate — a
# traced serving run must stay within 5% of untraced, its JSONL must
# pass the trace_event span schema, and the traced flash-crowd runtime
# run must yield a Perfetto-loadable cross-thread artifact
trace-smoke:
	timeout 600 $(PY) -m pytest tests/test_obs.py -q
	PYTHONPATH=src:. timeout 600 $(PY) benchmarks/trace_smoke.py

# closed-loop RL serving controller smoke (DESIGN.md §9): the control +
# upgraded-DQN suites (closed-loop determinism, off-mode bitwise pin,
# ack accounting, checkpoint round-trip), the learned-vs-static
# closed-loop comparison rows (the win gate binds at full scale only),
# then a train-then-freeze closed-loop run end-to-end from the CLI
control-smoke:
	timeout 900 $(PY) -m pytest tests/test_control.py tests/test_dqn.py -q
	PYTHONPATH=src:. timeout 600 $(PY) benchmarks/serving_bench.py \
	    --smoke --control-only
	PYTHONPATH=src timeout 300 $(PY) -m repro.launch.serve \
	    --arch igpm-pem --smoke --async --scenario flash_crowd --rate 2000 \
	    --ticks 10 --closed-loop --control frozen --control-episodes 1

# edge-partitioned storage + multi-executor scale-out (DESIGN.md §10):
# partitioned-vs-replicated bitwise pins (sweeps, router semantics, loud
# overflow, served stores, cross-device-count checkpoint) and the
# 2-executor runtime drain, all under 4 forced host devices
partition-smoke:
	XLA_FLAGS=--xla_force_host_platform_device_count=4 \
	    timeout 1800 $(PY) -m pytest tests/test_graph_sharding.py -q \
	    -k "partition or executor or capacity"

# health watchdog end-to-end (DESIGN.md §11): the freshness + watchdog
# suites (per-query staleness oracle, burn windows, alias groups, ops
# endpoints, regression sentinel), then stall injection against the live
# threaded runtime — the injected executor stall must flip /health to
# 503 "stalled" and trigger a flight-recorder dump within one monitor
# period, with /metrics and /freshness staying up through the incident
obs-watchdog-smoke:
	timeout 600 $(PY) -m pytest tests/test_freshness.py \
	    tests/test_health.py -q -m "not slow"
	PYTHONPATH=src:. timeout 300 $(PY) benchmarks/watchdog_smoke.py

# merge benchmarks/out/*.json into the top-level BENCH_SUMMARY.json
bench-collect:
	PYTHONPATH=src:. $(PY) benchmarks/collect.py

# perf-regression sentinel: fresh rows vs benchmarks/baseline/. CI runs
# the smoke serving bench and gates only the freshness/* rows — they are
# VirtualClock + service-model runs, bit-deterministic across machines,
# so a tight tolerance is safe on shared runners.
bench-regress:
	PYTHONPATH=src:. timeout 900 $(PY) benchmarks/serving_bench.py --smoke
	PYTHONPATH=src:. $(PY) benchmarks/collect.py --out /tmp/fresh_summary.json
	PYTHONPATH=src:. $(PY) benchmarks/regress.py \
	    --fresh /tmp/fresh_summary.json \
	    --suites serving_bench_smoke --rows freshness/ --rel-tol 0.1
