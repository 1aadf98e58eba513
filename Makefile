# Build orchestration (reference: Makefile building the CUDA .so; here the
# native piece is the C++ data-loader/id-generator shared library).

SHELL := /bin/bash

.PHONY: all native test test-fast chip-smoke clean pkg \
        verify lint plan-audit audit-step hlo-audit schedule-audit \
        concurrency-audit \
        check-obs check-obs-report check-resilience \
        check-reshard check-recovery check-streaming check-serving \
        check-online check-obsplane check-phase-profile check-isolation \
        check-tracing obs-report phase-profile

all: native

native:
	$(MAKE) -C cc

test:
	python -m pytest tests/ -q

# quick tier for tight dev loops: skips @pytest.mark.slow (long compiles,
# RSS-bounded streaming, 2-process cluster); CI runs the full `test`
test-fast:
	python -m pytest tests/ -q -m "not slow"

# the driver's tier-1 gate (ROADMAP.md "Tier-1 verify", verbatim semantics)
# plus the static gates (detlint rules, the SPMD step auditor), the
# observability gate, and the preemption-recovery drill — all on the CPU;
# the chip is checked by `python chip_smoke.py` through the chip tool
verify: lint plan-audit audit-step hlo-audit schedule-audit \
        concurrency-audit \
        check-obs check-obs-report check-phase-profile check-resilience \
        check-reshard check-recovery check-streaming check-serving \
        check-online check-obsplane check-isolation check-tracing
	set -o pipefail; rm -f /tmp/_t1.log; \
	timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
	  -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
	  -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; \
	rc=$${PIPESTATUS[0]}; \
	echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); \
	exit $$rc

# unified AST lint framework: env-registry, bare-except,
# host-fetch, named-scope-exchange, module-scope-jax (tools/detlint/)
lint:
	python -m tools.detlint

# plan-time capacity auditor: prices every reference plan (incl. the real
# Criteo-1TB vocab vector at world=16) backend-free — per-rank HBM, a2a
# payload/step, scatter-cliff slabs — enforces the PlanContracts, checks
# the byte model against analysis/memory.py, and self-drills two seeded
# violations (over-HBM, past-cliff); analysis/plan_audit.py
plan-audit:
	env JAX_PLATFORMS=cpu python tools/plan_audit.py --strict

# SPMD invariant auditor: traces the hybrid step abstractly on an
# 8-virtual-device CPU mesh and enforces the communication contract
# (2 fwd + 1 bwd all-to-all, no all_gather, no f64, donations intact)
audit-step:
	env JAX_PLATFORMS=cpu python tools/audit_step.py --strict

# optimized-HLO pass-budget auditor: compiles the hybrid step abstractly
# on the 8-virtual-device CPU mesh and enforces the per-phase pass budgets
# (dedup phase empty under SparseSGD, <=2 gathers per lookup group, no
# float convert round-trips; analysis/hlo_census.py)
hlo-audit:
	env JAX_PLATFORMS=cpu python tools/hlo_audit.py --strict

# schedule-graph auditor: compiles the hybrid step abstractly (incl. the
# streaming and Criteo-1TB cases), builds the dependency DAG of the
# optimized HLO, prices the critical path on the v5e cost model, and
# enforces the serialized-a2a baseline contracts + the StepSchedule
# overlap declaration check; self-drills a fake overlap-declaring
# schedule (analysis/schedule_audit.py)
schedule-audit:
	env JAX_PLATFORMS=cpu python tools/schedule_audit.py --strict

# concurrency auditor: jax-free AST lock-discipline analysis over the
# serving plane (shared attributes mutated from >=2 threads of control
# without a dominating lock, lock-acquisition-order cycles, blocking
# calls under a held lock, ConcurrencyContract drift) PLUS the
# explicit-state interleaving model checker proving the seqlock
# torn-read-detection and supervisor rid-monotonicity invariants over
# the full bounded interleaving space while refuting three seeded
# mutants (CRC check removed, stamps swapped, heartbeat deadline
# off-by-one); self-drills seeded Half-1 findings too
# (analysis/concurrency_audit.py)
concurrency-audit:
	env JAX_PLATFORMS=cpu python tools/concurrency_audit.py --strict

# measured phase-time observatory: run timed steps under
# jax.profiler.trace on the 8-virtual-device CPU mesh, attribute every
# trace event to its obs.scope phase, cross-check the measured
# serialized/overlapped classification against the schedule auditor's
# model, and render the calibration drift table (measured/modeled cost
# ratio per phase; analysis/phase_profile.py + tools/phase_profile.py)
phase-profile:
	env JAX_PLATFORMS=cpu python tools/phase_profile.py --strict

# the make verify smoke of the above: dense case only, 2 profiled steps
check-phase-profile:
	env JAX_PLATFORMS=cpu python tools/phase_profile.py --smoke --strict

# observability gate: obs.py imports cleanly under JAX_PLATFORMS=cpu and
# a DETPU_OBS=1 run of examples/dlrm/main.py at toy size with
# --metrics_out writes a parseable step-metrics sidecar
check-obs:
	python tools/check_obs.py

# observatory render gate: synthetic metrics JSONL + telemetry summary
# through the full fusion/render path (no jax, sub-second)
check-obs-report:
	python tools/obs_report.py --selftest

# the embedding telemetry observatory (acceptance run): 8-virtual-device
# CPU mesh, Zipfian inputs with planted heavy hitters + engineered rank
# skew; fails unless the top-k recovers the plants, the skew shows in the
# per-rank ratios, and the telemetry is jit-carried (0 steady-state
# recompiles, no host callbacks in the audited jaxpr)
obs-report:
	env JAX_PLATFORMS=cpu python tools/obs_report.py

# preemption drill: SIGTERM a child resilient driver mid-run, resume it,
# and require the final state to match an uninterrupted run bit for bit
check-resilience:
	python tools/check_resilience.py

# elastic-topology drill: preempt an 8-virtual-device run, auto-resume it
# on 4 devices (in-place checkpoint re-shard, degradation logged), and
# require determinism + logical-state equality vs the uninterrupted run
check-reshard:
	python tools/check_reshard.py

# NaN-storm chaos drill: a child run with DETPU_FAULT=nan@<step> must roll
# back to a ring checkpoint, quarantine the poisoned batch (naming the
# unhealthy table via the per-table sentinels), finish clean, and match
# the stream-minus-poison run's final checkpoint bit for bit
check-recovery:
	python tools/check_recovery.py

# streaming-vocab drill: oovflood a child streaming run (novel ids land
# in the shared buckets, admissions fire), preempt + resume it, and
# require 0 steady-state recompiles plus a final checkpoint (slot-map
# aux included) CRC-identical to the uninterrupted run
check-streaming:
	python tools/check_streaming.py

# serving overload drill: a world-8 child serves a Zipfian request
# stream under DETPU_FAULT=slow:serve_step+burst@ (every flush slow, a
# 16x QPS spike at second 2); requires bounded p99, clean typed
# shedding with degrade/recover events, post-burst recovery, a
# bitwise-unchanged read-only streaming state, and 0 steady-state
# recompiles across the padded-batch ladder (parallel/serving.py)
check-serving:
	python tools/check_serving.py

# online learning drill: concurrent train-and-serve in one child under
# DETPU_FAULT=oovflood@+burst@ (never-seen training ids + an 8x serve
# spike); requires admissions, typed sheds only, post-burst recovery,
# monotone snapshot versions, freshness p95 within the SLO, bounded p99,
# 0 steady-state recompiles, and a training trajectory CRC-identical to
# the same stream without serving (parallel/online.py)
check-online:
	python tools/check_online.py

# process-isolation drill: a real spawned world-8 serving worker is
# SIGKILLed mid-burst (DETPU_FAULT=die@<rid> in the WORKER env only);
# the supervisor must contain the crash (typed Unavailable, zero lost
# futures), restart within the backoff budget, dump a CRC-intact
# blackbox, resume full service at 0 steady-state recompiles, and keep
# training CRC-identical to the serving-free run; tools/check_isolation.py
check-isolation:
	python tools/check_isolation.py

# cross-process tracing drill: world-8 supervised worker under
# die@<rid> + burst; one retained trace must CROSS the restart
# (worker_restarted / served_after_restart marks), every retained
# trace's stage spans must sum to latency_ms within 1e-6 ms (including
# the five-stage partitions pickled over the supervisor boundary), the
# federated /metrics scrape must serve the worker's families next to
# the supervisor's, at 0 steady-state recompiles; tools/check_tracing.py
check-tracing:
	python tools/check_tracing.py

# observability-plane drill: a world-8 child serves under burst chaos
# while its Prometheus endpoint is scraped MID-LOAD over real HTTP; the
# per-stage latency sketches must sum to the end-to-end served latency
# within 5% (the p99-attribution instrument) with 0 steady-state
# recompiles, and a second nan@-injected training child must leave a
# CRC-intact <dir>.blackbox.json post-mortem naming the unhealthy
# table(s) (utils/mplane.py)
check-obsplane:
	python tools/check_obsplane.py

# the quickest proof that the system still starts on the chip: run it on
# a machine that has one (one process per chip). Here, without a chip, it
# exits 2; `python chip_smoke.py --dry-cpu` debugs the command on the CPU.
chip-smoke:
	python chip_smoke.py

pkg:
	python -m build --wheel 2>/dev/null || pip wheel --no-deps -w dist .

clean:
	$(MAKE) -C cc clean
	rm -rf build dist *.egg-info
