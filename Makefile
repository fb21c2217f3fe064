# Reference: Makefile `test` target (Makefile:7-9 — two pytest passes
# under PJRT_USE_TORCH_ALLOCATOR).  Here: one suite on an emulated
# 8-device CPU mesh; kernels run in interpret mode.

PYTHON ?= python
PYTEST ?= $(PYTHON) -m pytest

.PHONY: test test-all test-inproc bench chaos chaos-multihost chaos-elastic chaos-sdc chaos-replace serve-smoke serve-chaos router-chaos handoff-smoke ckpt-smoke obs-smoke supervisor-smoke fleet-smoke store-chaos lint dryrun chip-smoke

# Per-file subprocess isolation: XLA:CPU's in-process multi-device runtime
# can SIGABRT nondeterministically mid-suite (scripts/run_tests.py docstring);
# fresh interpreters per file + retry-on-signal keep the evidence intact.
test:
	$(PYTHON) scripts/run_tests.py -m "not slow"

test-all:
	$(PYTHON) scripts/run_tests.py

# direct in-process run (fastest when the runtime race doesn't bite)
test-inproc:
	$(PYTEST) tests/ -q

bench:
	python bench.py

# tier-1-adjacent regression gate: drive the REAL bench.py model path
# (accelerate + trainer.step + metrics) for a few steps on CPU — fast
# enough for every PR, catches hot-loop wiring breakage that unit tests
# with tiny ad-hoc models can miss.  Second leg: the same path with
# int8 quantized matmuls (xla impl on CPU) so the quant plumbing is
# gated per-PR too (docs/performance.md "Quantized matmuls")
bench-smoke:
	JAX_PLATFORMS=cpu python bench.py --fast --platform cpu --iters 2
	JAX_PLATFORMS=cpu python bench.py --fast --platform cpu --iters 2 \
		--quant int8 --no-decode

# serving gate (docs/serving.md): drive the continuous-batching engine
# on a mixed-length staggered workload on CPU, PLUS the shared-prefix
# leg (N requests over K system prompts through a prefix-cache +
# batched-prefill + priority engine, one request streamed, no-prefix
# control); reports tokens/s + TTFT and per-token latency percentiles
# + prefix_hit_rate / prefill_tokens_saved, and FAILS unless greedy
# outputs on EVERY leg are token-identical to batch-synchronous
# generate() AND the prefix cache actually fired (hit rate > 0,
# tokens saved > 0)
serve-smoke:
	JAX_PLATFORMS=cpu python bench.py --serve --fast --platform cpu

# train->serve handoff gate (docs/serving.md "Live weight handoff"):
# fit -> in-memory handoff -> serve -> fit -> handoff again on an
# emulated 8-device fsdp/tp mesh; FAILS unless the served tokens are
# identical to serving checkpoint-round-trip weights AND the second
# handoff is a pure transfer-cache hit (no recompile)
handoff-smoke:
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python bench.py --handoff --fast --platform cpu

# tiered-checkpointing gate (docs/resilience.md "Tiered
# checkpointing"): the same fit loop with blocking orbax saves vs
# tiered in-gap snapshots on 8 emulated CPU devices; FAILS unless the
# save-step stall (save_blocked_ms per save, dispatch_depth 2) drops
# >= 10x AND resume from every tier (host RAM, local disk, mirror) is
# bitwise identical to the blocking path
ckpt-smoke:
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python bench.py --checkpoint --fast --platform cpu

# telemetry gate (docs/observability.md): obs off-vs-on per-step
# overhead under a budget at dispatch_depth=2, /metrics Prometheus-
# parseable with non-zero step + serve series, /healthz flips to
# degraded under an injected watchdog stall and recovers, trainer +
# tiered-checkpoint + serving spans export as ONE valid Chrome-trace
# JSON, and an injected flip_bits SDC abort writes a flight-recorder
# bundle naming the flagged step
obs-smoke:
	JAX_PLATFORMS=cpu python bench.py --obs --fast --platform cpu

# supervisor gate (docs/resilience.md "Supervisor"): the full
# fault-tolerance loop with ZERO human intervention — (1) 2-process
# dp=2 chaos SDC flip on host 1 -> both workers abort SDCError ->
# supervisor restarts EXCLUDING host 1 -> shrunken dp=1 pod resumes
# from the newest valid tier and matches an uninterrupted reference
# trajectory, restart/exclusion counters scraped from the daemon's
# /metrics; (2) injected hang -> HangError -> restart full pod ->
# resumed completion; (3) induced crash loop through the `supervise`
# CLI -> bounded backoff, budget exhaustion, terminal give-up with a
# final flight bundle naming the reason
supervisor-smoke:
	JAX_PLATFORMS=cpu python scripts/supervisor_smoke.py

# fleet-observability gate (docs/observability.md "Fleet view"): a
# 2-process supervised run with an injected SDC flip must yield ONE
# aggregated scrape from the daemon's obs port — Prometheus-parseable
# with per-host labels, BOTH hosts' merged step_time_ms histogram, a
# goodput breakdown whose buckets sum to wall clock within 5%, and
# restart downtime attributed to the sdc-exclude policy rule — plus a
# serve request whose trace id appears on every span of its lifecycle
# in the exported Chrome-trace timeline
fleet-smoke:
	JAX_PLATFORMS=cpu python scripts/fleet_smoke.py

# serve-side fault-tolerance gate (docs/serving.md "Serving under the
# supervisor"): (1) a supervised serve worker is SIGKILLed mid-decode
# -> crash-backoff restart -> the request journal replays -> FAILS
# unless 100% of submitted requests end completed (greedy outputs
# token-identical to an uninterrupted reference) or explicitly
# shed/unserved, zero silent losses, with the restart downtime
# attributed to a down: bucket in the supervisor goodput ledger;
# (2) a 2-worker serve fleet with a sustained injected slowdown on
# host 1 -> fleet_straggler drift verdict -> the opt-in
# straggler-eviction rule excludes host 1 (elastic shrink) and
# attributes the downtime to down:straggler-evict
serve-chaos:
	JAX_PLATFORMS=cpu python scripts/serve_chaos_smoke.py

# routing-tier fault-tolerance gate (docs/serving.md "Router tier"):
# (A) SIGKILL a serve replica mid-decode behind the router -> the
# circuit breaker opens on consecutive probe failures, the journal-
# named remainder fails over to the survivor under the original rids
# (greedy tokens identical to a single-engine reference), and the
# router's breaker/failover/goodput series surface on the daemon's
# aggregated /metrics + /fleet; (B) SIGKILL the ROUTER mid-wave ->
# restart replays the assignment journal and reconciles against the
# workers' journals — 100% accounting, no duplicate completions;
# (C) a same-template wave pins the warm replica (prefix_hit_rate)
# vs a routing-off control that spreads it cold
router-chaos:
	JAX_PLATFORMS=cpu python scripts/router_chaos_smoke.py

# host-replacement gate (docs/resilience.md "Host replacement &
# grow-back"): (1) a 2-process dp=2 worker SIGKILLs itself (no flight
# bundle — the hardware-loss signature) -> crash-replace -> the hot-
# spare pool refills the slot -> the pod relaunches at FULL width and
# the post-rejoin loss trajectory is bitwise identical to an
# uninterrupted dp=2 reference; (2) provisioning is armed to fail ->
# replace-fallback-shrink (dp=1) -> a preemption boundary later the
# daemon's grow-back re-provisions the excluded slot, readmits it, and
# the run finishes back at world=2 — with the provisioning windows
# attributed to down:provisioning in a goodput ledger that still sums
# to wall clock, and the fleet-history CLI replaying the timeline
chaos-replace:
	JAX_PLATFORMS=cpu python scripts/chaos_replace_smoke.py

# fault-injection suite (docs/resilience.md) under 3 seeds: CHAOS_SEED
# shifts where the NaN losses / preemptions / I/O faults / injected
# hangs land, so three different fault schedules exercise the same
# guarantees.  test_watchdog.py rides along: deterministic fake-clock
# coverage of the hang-detection path the chaos runs trip for real.
chaos:
	for s in 0 1 2; do \
		echo "== chaos seed $$s =="; \
		CHAOS_SEED=$$s JAX_PLATFORMS=cpu $(PYTEST) tests/test_resilience.py \
			tests/test_watchdog.py tests/test_elastic.py \
			tests/test_sdc.py tests/test_perf.py \
			tests/test_serving.py tests/test_prefix_cache.py \
			tests/test_quant.py \
			tests/test_handoff.py tests/test_tiered.py \
			tests/test_obs.py tests/test_tracing_timeline.py \
			tests/test_supervisor.py tests/test_fleet.py \
			tests/test_serve_resilience.py \
			tests/test_router.py \
			-m "not slow" \
			-q || exit 1; \
	done
	$(MAKE) supervisor-smoke
	$(MAKE) fleet-smoke
	$(MAKE) serve-chaos
	$(MAKE) router-chaos
	$(MAKE) chaos-replace
	$(MAKE) data-chaos
	$(MAKE) store-chaos

# streaming-data-plane gate (docs/data.md): the full store/stream
# suite under 3 ChaosStore fault schedules — transient errors, 429
# throttles, torn reads, checksum corruption, dead sources.  Proves
# kill -9 mid-stream + restart yields bitwise-identical remaining
# batches under injected store faults, quarantine-at-encounter equals
# a pre-excluded run, a dead source sheds to survivors, and injected
# stalls land in the data_wait goodput bucket — never as HangError.
data-chaos:
	for s in 0 1 2; do \
		echo "== data chaos seed $$s =="; \
		CHAOS_SEED=$$s JAX_PLATFORMS=cpu $(PYTEST) \
			tests/test_datastream.py -m "not slow" -q || exit 1; \
	done

# unified object-store-plane gate (docs/resilience.md "Object-store
# tier-2"): the shared PUT/GET client + two-phase commit under 3
# write-side ChaosObjectStore fault schedules — transient 5xx, partial
# (torn-object) uploads, acknowledged-but-lost writes, lost commit
# markers, stale listings, dead destinations.  Proves kill -9
# mid-trickle under write faults restarts to a bitwise newest-tier
# restore, torn uploads stay invisible to restore_latest_valid, a
# breaker-open mirror degrades to tier-1-only, and a journal archive
# upload killed after rotation loses no record (union replay 100%).
# Runs the slow subprocess kill fixtures too — they ARE the gate.
store-chaos:
	for s in 0 1 2; do \
		echo "== store chaos seed $$s =="; \
		CHAOS_SEED=$$s JAX_PLATFORMS=cpu $(PYTEST) \
			tests/test_store.py -q || exit 1; \
	done

# multi-host robustness proof: 2-process jax.distributed fixtures
# (cross-host resume consensus with divergent quarantine, preemption
# sync, coordination primitives) — subprocess-based, so run separately
# from the in-process suites
chaos-multihost:
	JAX_PLATFORMS=cpu $(PYTEST) tests/ -m multihost -q

# elastic-resume proof: corrupt-batch quarantine + topology-change
# chaos scenarios under 3 seeds (fast, in-process), then the
# subprocess DP=2 <-> DP=1 save/restore fixtures
chaos-elastic:
	for s in 0 1 2; do \
		echo "== chaos-elastic seed $$s =="; \
		CHAOS_SEED=$$s JAX_PLATFORMS=cpu $(PYTEST) tests/test_elastic.py \
			-m "not slow" -q || exit 1; \
	done
	JAX_PLATFORMS=cpu $(PYTEST) tests/test_elastic.py -m "elastic and slow" -q

# SDC-defense proof: bit-flip chaos (cross-replica localization,
# recompute spot checks, deterministic replay) under 3 seeds, then the
# 2-process DP=2 fixture where a flip on host 1 is localized to host 1
chaos-sdc:
	for s in 0 1 2; do \
		echo "== chaos-sdc seed $$s =="; \
		CHAOS_SEED=$$s JAX_PLATFORMS=cpu $(PYTEST) tests/test_sdc.py \
			-m "not slow" -q || exit 1; \
	done
	JAX_PLATFORMS=cpu $(PYTEST) tests/test_sdc.py -m "sdc and slow" -q

dryrun:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

lint:
	python -m compileall -q torchacc_tpu benchmarks bench.py chip_smoke.py __graft_entry__.py

# the quickest proof that train and serve still start on the chip
# (one TPU v5e; `python chip_smoke.py --chips 4` is the sharded-training
# phase on four).  Exits non-zero without a TPU.
chip-smoke:
	python chip_smoke.py
