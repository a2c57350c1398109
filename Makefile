.PHONY: test check-collect lint pilint promlint native clean cover chaos warmcheck plancheck containercheck soakcheck ingestcheck batchcheck obscheck meshcheck explaincheck eventcheck autopilotcheck hedgecheck profcheck plannercheck

# tests/ includes the fault-marked chaos suite (tests/test_faults.py),
# so `make test` exercises it too; `make chaos` is the focused runner.
test: check-collect lint pilint promlint warmcheck plancheck containercheck ingestcheck batchcheck obscheck meshcheck explaincheck eventcheck autopilotcheck hedgecheck profcheck plannercheck soakcheck
	python -m pytest tests/ -x -q

# Adaptive-planner smoke (PR 20): the full PQL surface (boolean
# chains, TopN, BSI Range/Sum, time-quantum views) must be bit-exact
# planner on vs off; ?explain=true must show the reordered operand
# order, the tier rationale, and >= 1 workload whose tier choice
# diverges from the static chain; a short-circuited branch must show
# zero container-block fetches for the killed siblings (?profile=true
# counters). /metrics stays promlint-clean both ways with the
# pilosa_plan_* families live.
plannercheck:
	JAX_PLATFORMS=cpu python tools/plannercheck.py

# Continuous-profiler smoke (PR 19): a live server sampling at 97 Hz
# under driven load must show >= 3 subsystems in /debug/profile,
# flamegraph-folded output that parses, a device-trace arm that
# answers 200/409/501 and nothing else, analytic flops/bytes on the
# /debug/kernels cells (XLA cost_analysis capture) and a
# promlint-clean exposition.
profcheck:
	JAX_PLATFORMS=cpu python tools/profcheck.py

# Tail-tolerant read gate (ISSUE 18): a real subprocess 2-node
# replica_n=2 cluster with executor.slice.delay armed on one replica
# must hold read p99 within 2x the healthy-cluster p99 under the
# routed+hedged posture, prove the hedge race rescues slow primary
# legs on the legacy arm, keep extra backend legs under 15% (the
# load-proportional budget), serve zero stale reads (bit-exact
# against acked writes incl. mid-fault freshness probes), recover
# after the fault clears, and keep /metrics promlint-clean with the
# pilosa_hedge_* families live.
hedgecheck:
	JAX_PLATFORMS=cpu python tools/hedgecheck.py

# Heat-driven autopilot smoke (PR 17): on a real-socket 2-node cluster
# with injected heat skew pinned to a degraded peer, the controller
# must produce a placement plan whose dry-run preview mutates nothing,
# apply it through the real rebalancer in causal order against the
# merged rebalance timeline (reason="autopilot"), rate-limit the next
# action (autopilot.cooldown journaled), abort a wedged apply cleanly
# on the mid-flight kill switch (token released, placement never left
# mid-transition), and keep /metrics promlint-clean with the
# pilosa_autopilot_* families.
autopilotcheck:
	JAX_PLATFORMS=cpu python tools/autopilotcheck.py

# Flight-recorder smoke (PR 16): a real-socket 2-node cluster must
# journal a breaker cycle into one causally-ordered cluster-merged
# timeline, feed per-peer replica vitals from the live fan-out, fire
# the slow-replica watchdog under an injected executor.slice.delay
# (degraded then recovered), and keep /metrics promlint-clean with
# the new families.
eventcheck:
	JAX_PLATFORMS=cpu python tools/eventcheck.py

# Query-inspector smoke (PR 15): ?explain=true must report the
# correct tier + decline-reason chain on all five serving paths
# (mesh, mesh-declined→HTTP, batched dense, serial compressed,
# coalesced lane), ?explain=only must plan without mutating, and the
# cost model must calibrate to median |error| <= 2x on warm engine
# Counts.
explaincheck:
	JAX_PLATFORMS=cpu python tools/explaincheck.py

# Collective data plane smoke (PR 14): an 8-device CPU-emulated mesh
# peer group must serve Count/TopN/Sum as single collective programs
# bit-exact vs the HTTP fan-out, and a live resize mid-query-load
# must produce zero failed ops — fallback to HTTP during TRANSITION,
# collective path resumed after commit.
meshcheck:
	JAX_PLATFORMS=cpu python tools/meshcheck.py

# Workload-observatory smoke (PR 13): a live server must show kernel
# cost cells with compile/steady separation, populated heatmap top-K,
# live SLO surfaces and a promlint-clean exposition.
obscheck:
	JAX_PLATFORMS=cpu python tools/obscheck.py

# Micro-batching smoke (PR 12): a concurrent mixed-format workload on
# a compressed index must form nonzero fused groups (container-lane
# tier), stay bit-exact vs the serial kernels, densify nothing, and a
# saturated QoS gate must shed with 503 + Retry-After then recover.
batchcheck:
	JAX_PLATFORMS=cpu python tools/batchcheck.py

# Bulk-ingest smoke (PR 11): the streaming ingest route must be
# >= 10x the legacy import path, bit-exact (incl. time-quantum
# views), land containers compressed with zero conversion churn, and
# shed with 503 + Retry-After when the QoS gate saturates.
ingestcheck:
	JAX_PLATFORMS=cpu python tools/ingestcheck.py

# Elastic-topology soak, short mode (PR 10): a real subprocess cluster
# resized 2→3→2 under sustained mixed traffic with HARD pass/fail —
# zero errors beyond drain sheds, bit-exact convergence at every
# generation, warm replay recovering post-commit. Long/kill variants:
# python benchmarks/soak_cluster.py --duration 300 --kill ...
soakcheck:
	JAX_PLATFORMS=cpu python benchmarks/soak_cluster.py --short

# Project-invariant static analysis (tools/pilint/): lock-order,
# guarded-state, deadline-clock, hot-path purity, swallow — plus the
# tools/lint.py findings folded in, so one command reports everything.
# Suppressions: `# pilint: disable=CODE`; accepted legacy findings
# live in tools/pilint/baseline.txt (--write-baseline regenerates).
pilint:
	python -m tools.pilint

# Compressed-container smoke (PR 7): the full PQL surface must be
# bit-exact with container-formats on vs off, across block shapes,
# residency states, and a mid-serve array->dense conversion.
containercheck:
	JAX_PLATFORMS=cpu python tools/containercheck.py

# Cluster warm-path smoke (PR 5): a real 2-node cluster must show a
# nonzero epoch-validated replay hit rate and zero stale reads.
warmcheck:
	JAX_PLATFORMS=cpu python tools/warmcheck.py

# Slice-plan cache smoke (PR 6): warm engine-path queries must show a
# >90% plan hit rate, and a write must invalidate bit-exactly.
plancheck:
	JAX_PLATFORMS=cpu python tools/plancheck.py

# Exposition-format lint against a LIVE in-process server's /metrics
# and /cluster/metrics (dependency-free promtool stand-in).
promlint:
	JAX_PLATFORMS=cpu python tools/promlint.py --selftest

# Deterministic fault-injection / graceful-drain suite only
# (pytest marker `faults`; see tests/test_faults.py). Runs with the
# lock instrumentation armed (pilosa_tpu/lockcheck.py): every chaos
# run doubles as a race-and-deadlock hunt — an observed lock-order
# cycle or a lock held across a fan-out RPC fails the process.
chaos:
	PILOSA_LOCKCHECK=1 python -m pytest tests/ -q -m faults

# Fails on ANY collection error (ImportError in a test module, etc.) —
# the tier-1 command's --continue-on-collection-errors silently masks
# whole files otherwise, as the py3.10 tomllib break demonstrated.
check-collect:
	python -m pytest tests/ --collect-only -q >/dev/null

# pyflakes when installed; tools/lint.py falls back to a built-in AST
# unused/duplicate-import checker so environments without the package
# still lint instead of silently skipping.
lint:
	python tools/lint.py pilosa_tpu tests

# Strict build of the native host runtime (one compiler line, in
# pilosa_tpu/native/__init__.py): fails loudly where the lazy loader
# would log and serve from pure Python.
native:
	python -c "from pilosa_tpu import native; native.build()"

cover:
	python -m pytest tests/ -q --tb=no -p no:cacheprovider

clean:
	rm -f pilosa_tpu/native/libpilosa_native.so*
	rm -rf .jax_cache chiprun_out
	find . -name __pycache__ -type d -exec rm -rf {} +
