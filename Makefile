# Test targets, the analog of the reference's Makefile (whose targets
# wrap pytest under mpirun; here the multi-process harness is the 8-device
# CPU-simulated mesh — see tests/conftest.py and SURVEY.md §4).

PYTEST      = python -m pytest
MESH_ENV    = JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8

.PHONY: test test_fast test_ops test_win_ops test_optimizers test_parallel \
        test_launcher test_models chaos dryrun native scaling \
        metrics-smoke flight-smoke soak-smoke obs-smoke \
        tune-smoke serve-smoke slo-smoke perf-gate lint bfcheck check \
        tsan asan

# Test files replayed under the sanitizers: the chaos suite (reconnect /
# dedup / fencing churn) plus the striped-transport + hosted-window stress
# tests — the paths that hammer the native layer's concurrency.
SANITIZE_TESTS = tests/test_chaos.py tests/test_hosted_windows.py

test:            ## full suite (~15 min on the single-core CI box)
	$(PYTEST) tests/ -q

test_fast:       ## the pre-commit gate: quick subset (skips @slow)
	$(PYTEST) tests/ -q -m "not slow"

# per-area targets mirroring the reference's test_torch_ops / test_torch_win_ops / ...
test_ops:
	$(PYTEST) tests/test_ops.py tests/test_basics.py tests/test_topology.py -q

test_win_ops:
	$(PYTEST) tests/test_win_ops.py -q

test_optimizers:
	$(PYTEST) tests/test_optimizers.py tests/test_optimization.py -q

test_parallel:
	$(PYTEST) tests/test_parallel.py tests/test_transformer_cp.py \
	    tests/test_tensor_parallel.py tests/test_pipeline_parallel.py \
	    tests/test_expert_parallel.py tests/test_flash.py -q

test_launcher:
	$(PYTEST) tests/test_launcher.py tests/test_heartbeat.py -q

test_models:
	$(PYTEST) tests/test_models.py tests/test_torch_interop.py -q

metrics-smoke:   ## telemetry-plane acceptance: 2-rank in-process job with a
                 ## non-empty KV scrape + health snapshot + prometheus lint,
                 ## bfrun --status from a separate process, and the < 100 ns
                 ## counter-increment microbench
	JAX_PLATFORMS=cpu python scripts/metrics_smoke.py

flight-smoke:    ## flight-recorder acceptance: < 1500 ns ring-record
                 ## microbench, step-time attribution over a real hosted
                 ## job, parseable dumps, and bfrun --dump retrieving a
                 ## merged clock-synced trace from a separate process
	JAX_PLATFORMS=cpu python scripts/flight_smoke.py

obs-smoke:       ## live-telemetry-plane acceptance: < 2 µs/record ring
                 ## sampling microbench, a 2-rank job streaming non-empty
                 ## bf.ts.* deltas (consensus gauge + per-edge
                 ## estimators), bfrun --top one-shot render from a
                 ## separate process naming a SIGKILLed publisher SILENT,
                 ## ts_export JSON-lines + OpenMetrics lint, and
                 ## step_attribution --live without a dump
	JAX_PLATFORMS=cpu python scripts/obs_smoke.py

tune-smoke:      ## self-tuning-controller acceptance: 4-rank in-process
                 ## job with armed delay_edges asymmetry — zero decisions
                 ## while healthy, slow-edge codec escalation, straggler
                 ## demotion within 4 ticks with numpy-oracle parity of
                 ## the healed tables, exact demote->promote round-trip,
                 ## and the bf.tune.* trail rendered by bfrun --top
	JAX_PLATFORMS=cpu python scripts/tune_smoke.py

serve-smoke:     ## serving-plane acceptance: 2-rank trainer publishing
                 ## every comm step + one read-only serve client — hot-swap
                 ## on fence bumps while training continues, batched
                 ## replies matching a numpy oracle on the swapped-in
                 ## snapshot, queue_full shedding with every admitted
                 ## future still resolving, and bfrun --serve/--status
                 ## attaching from a separate process (docs/serving.md)
	JAX_PLATFORMS=cpu python scripts/serve_smoke.py

slo-smoke:       ## request-path tracing + SLO-engine acceptance
                 ## (docs/slo.md): < 2 µs per-request trace record gate,
                 ## a publisher child + traced serve client where a
                 ## fault-injected pull delay fires the staleness
                 ## burn-rate alert (bfrun --top shows the SLO section,
                 ## --status --strict exits 2 on budget exhaustion) and
                 ## recovery clears it; the client+publisher flight
                 ## rings merge into ONE chrome trace with a cross-
                 ## process stripe flow pair and the snapshot lineage
                 ## resolving to its exact train step
	JAX_PLATFORMS=cpu python scripts/slo_smoke.py

soak-smoke:      ## durable sharded-control-plane churn soak, quick mode
                 ## (<= 4 min): WAL-replicated shard server processes,
                 ## ~64 raw clients with incarnation churn, one injected
                 ## SIGKILL — asserts ZERO lost deposit mass, exactly-once
                 ## counters continuous across the failover, health
                 ## convergence, bounded server RSS; then a second pass
                 ## with --rejoin (kill + in-place restart with snapshot
                 ## catch-up, ring converges back); then the quorum
                 ## (R=3) passes: --kill-pairs SIGKILLs a shard AND its
                 ## ring successor simultaneously (still zero loss), and
                 ## --partition arms the deterministic 2|2 network cut
                 ## (typed QuorumLostError during the window, exact
                 ## ledgers after heal). No JAX anywhere; full mode:
                 ## scripts/cp_soak.py --clients 5000 --churn --rejoin
	python scripts/cp_soak.py --quick
	python scripts/cp_soak.py --quick --rejoin
	python scripts/cp_soak.py --quick --kill-pairs
	python scripts/cp_soak.py --quick --partition

perf-gate:       ## perf regression gate: quick win_microbench +
                 ## opt_matrix_bench medians vs the committed
                 ## PERF_BASELINE.json (red beyond the band; seeded
                 ## slowdown self-check: BLUEFOG_PERF_GATE_DELAY_MS=50
                 ## must turn this target RED)
	JAX_PLATFORMS=cpu python scripts/perf_gate.py --quick

lint:            ## ruff (curated rule set, pyproject.toml) when installed;
                 ## otherwise bfcheck's stdlib-only fallback linter
	@if command -v ruff >/dev/null 2>&1; then \
	    ruff check bluefog_tpu scripts tests; \
	else \
	    echo "ruff not installed; using bfcheck's fallback linter"; \
	    python scripts/bfcheck --lint; \
	fi

bfcheck:         ## project-invariant static analysis (wire protocol, knob
                 ## registry, lock/thread discipline — docs/static_analysis.md)
	python scripts/bfcheck

check: lint bfcheck  ## the full static gate (make check = lint + bfcheck)

tsan:            ## ThreadSanitizer build of csrc + chaos/striped-stress replay
                 ## (zero reports required; csrc findings are bugs, never
                 ## suppressed — csrc/tsan.supp covers third-party libs only)
	SANITIZE=thread bash csrc/build.sh
	env BLUEFOG_NATIVE_SO=$(abspath csrc/build/libbf_runtime.tsan.so) \
	    LD_PRELOAD=$$(gcc -print-file-name=libtsan.so) \
	    TSAN_OPTIONS="exitcode=66 halt_on_error=0 suppressions=$(abspath csrc/tsan.supp)" \
	    JAX_PLATFORMS=cpu $(PYTEST) $(SANITIZE_TESTS) -q -m "not slow"

asan:            ## AddressSanitizer build of csrc + the same replay.
                 ## detect_leaks=0: CPython intentionally leaks at exit.
                 ## libstdc++ rides LD_PRELOAD next to libasan because the
                 ## python binary doesn't link it — without it ASan's init
                 ## can't resolve the real __cxa_throw and CHECK-aborts on
                 ## jaxlib/MLIR's first C++ exception.
	SANITIZE=address bash csrc/build.sh
	env BLUEFOG_NATIVE_SO=$(abspath csrc/build/libbf_runtime.asan.so) \
	    LD_PRELOAD="$$(gcc -print-file-name=libasan.so) $$(gcc -print-file-name=libstdc++.so)" \
	    ASAN_OPTIONS="detect_leaks=0 exitcode=66" \
	    JAX_PLATFORMS=cpu $(PYTEST) $(SANITIZE_TESTS) -q -m "not slow"

chaos: check metrics-smoke flight-smoke obs-smoke tune-smoke serve-smoke slo-smoke soak-smoke perf-gate  ## tier-1 chaos subset, fault injection replayed at TWO
                 ## seed offsets (BLUEFOG_CHAOS_SEED shifts every armed drop
                 ## point, so reconnect/dedup/fencing — and the telemetry
                 ## counters asserted against them — face different drop sites)
	JAX_PLATFORMS=cpu BLUEFOG_CHAOS_SEED=3 $(PYTEST) tests/test_chaos.py -q -m "not slow"
	JAX_PLATFORMS=cpu BLUEFOG_CHAOS_SEED=11 $(PYTEST) tests/test_chaos.py -q -m "not slow"

dryrun:          ## multi-chip sharding validation on the simulated mesh
	$(MESH_ENV) python -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('dryrun ok')"

native:          ## build the native runtime extension
	bash csrc/build.sh

scaling:         ## regenerate SCALING.md (compile-time scaling evidence)
	JAX_PLATFORMS=cpu python -m bluefog_tpu.scaling
