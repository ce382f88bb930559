# test-t1 uses `set -o pipefail`/PIPESTATUS, which POSIX sh lacks
SHELL := /bin/bash

.PHONY: smoke test test-t1 lint lint-robust lint-selfcheck native bench bench-aug bench-dispatch bench-serve bench-overload bench-router bench-serve-hotpath bench-compile bench-pipeline bench-fleet-search bench-control trace status clean reproduce chaos gameday gameday-smoke

# telemetry journal dir for the trace/status targets (override:
#   make trace TELEMETRY=/shared/run TRACE_OUT=overlap.json)
TELEMETRY ?= telemetry
TRACE_OUT ?= trace.json

# the quickest proof the system still starts on the chip (run it
# through the chip tool; without a TPU it exits non-zero and says why).
# `python chip_smoke.py --rehearse` rehearses the plumbing on the CPU.
smoke:
	python chip_smoke.py

test:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q

# faalint: single-parse multi-pass static analysis
# (docs/STATIC_ANALYSIS.md) — the migrated robustness rules R1-R9 plus
# the concurrency (C1-C3), dispatch-hazard (D1-D3) and determinism
# (T1-T3) passes, with suppression/baseline hygiene (S1/S2).
# Pure-host; prints its measured wall time (must stay well under ~10s
# so the tier-1 preamble never eats test budget).
lint:
	python -m tools.faalint

# historical alias (the legacy entry point delegates to faalint)
lint-robust: lint

# regression-corpus gate: every pre-fix snippet of the historical bugs
# is flagged by the intended pass, every post-fix shape stays clean
lint-selfcheck:
	python -m tools.faalint --selfcheck

# the tier-1 verify command, verbatim from ROADMAP.md (the plain `test`
# target differs: it includes slow-marked tests and stops on collection
# errors) — this is the gate the driver actually runs, with the
# static-analysis gate as a preamble
test-t1: lint
	set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 1200 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); exit $$rc

# composed-fault chaos smoke (docs/RESILIENCE.md "Hostile shared
# filesystem"): FAA_FAULT (a SIGKILLed actor) layered with FAA_FSFAULT
# (publish->claim lag + seeded transient read errors) over a bounded
# 3-process fleet drill — completes degraded-but-correct, prints a
# telemetry-stamped CHAOS line with the reclaim/epoch evidence
chaos:
	JAX_PLATFORMS=cpu python -m pytest tests/test_fsfault.py::test_chaos_composed_fault_smoke -q -s -m slow -p no:cacheprovider

# trace-driven game days (docs/GAMEDAYS.md): the full deterministic
# scenario suite over a real serving plane, verdicts journaled and the
# suite JSON (with provenance stamps) written next to the docs table.
# `gameday-smoke` runs the same topologies/predicates under scaled load.
gameday:
	JAX_PLATFORMS=cpu python -m fast_autoaugment_tpu.launch.gameday_cli --suite --out docs/gameday.json

gameday-smoke:
	JAX_PLATFORMS=cpu python -m fast_autoaugment_tpu.launch.gameday_cli --suite --smoke

# real-data fire-drill: fetch CIFAR-10 with
# md5 verification, train WRN-40-2 + fa_reduced_cifar10 at the headline
# config, evaluate any reference .pth under ./ckpts via the manifest —
# skips gracefully when offline (this build environment is zero-egress)
reproduce:
	python tools/reproduce.py --dataroot ./data --ckpt-dir ./ckpts

native:
	$(MAKE) -C native

bench:
	python bench.py

# augmentation-dispatch bench: per-op table + exact vs grouped
# aug_images_per_sec at several G, with compile-time metrics.  Honors
# FAA_BENCH_REQUIRE_QUIET=1 (refuses on a contended host, exit 3).
bench-aug:
	python tools/bench_aug.py

# step-dispatch/device-cache bench: train_steps_per_sec at
# --steps-per-dispatch N in {1,8,32} with the device cache vs the
# host-fed N=1 loop, per-(N, cache) compile seconds in the JSON line.
# Honors FAA_BENCH_REQUIRE_QUIET=1 (refuses on a contended host).
bench-dispatch:
	python bench.py --dispatch-only

# AOT policy-serving bench: p50/p99 latency + imgs/s at fixed offered
# QPS through the batch-coalescing PolicyServer, with contention,
# watchdog and compile_cache stamps; re-verifies served outputs match
# direct apply_policy bitwise (docs/BENCHMARKS.md "Compile cost & cache")
bench-serve:
	python tools/bench_serve.py

# overload drill: offered QPS swept past calibrated capacity with
# shedding on (bounded queue + deadlines + adaptive LIFO) vs off —
# goodput, shed rate, deadline-miss rate and p99-of-admitted per arm
# (docs/RESILIENCE.md "Serving under overload")
bench-overload:
	python tools/bench_serve.py --overload

# serving-plane bench: a real router over N serve_cli replicas (two
# policies resident via tenancy), routed vs direct arms as PAIRED
# ALTERNATING rounds with per-arm medians (the 1-core A/B discipline),
# affinity hit rate + router topology stamped in the JSON line
# (docs/SERVING.md "Measuring the plane")
bench-router:
	python tools/bench_router.py

# serving data-plane hotpath bench: legacy (npz + fresh connections,
# default replica) vs zerocopy (raw wire format + keep-alive pool,
# --donate --double-buffer replica) as paired alternating rounds —
# per-request HOST overhead from the replica's own
# faa_serve_stage_seconds deltas, plus the 4-way bitwise gate (both
# wire formats x both data planes serve identical bytes)
# (docs/BENCHMARKS.md "Serving data plane")
bench-serve-hotpath:
	python tools/bench_serve_hotpath.py --out BENCH_r09_serve_hotpath.json

# cold/warm compile-tax bench: the same train-step workload in two
# fresh processes sharing the persistent compile cache
# (JAX_COMPILATION_CACHE_DIR, else the fixed in-checkout path) — the
# warm process must report cache hits and a first step in seconds
bench-compile:
	python tools/bench_compile.py

# serial-vs-async phase-2 scheduling bench: the same seeded search
# through the historical scheduler (dispatch trace armed) and the
# --async-pipeline actor/learner service — dispatch-gap p50/p99,
# device busy fraction, phase-2 wall + host ask/tell latency headroom
# in one JSON line (docs/BENCHMARKS.md "Search pipelining").  Honors
# FAA_BENCH_REQUIRE_QUIET=1 (refuses on a contended host, exit 3).
bench-pipeline:
	python tools/bench_pipeline.py

# multi-host fleet-search bench: the same seeded search single-host vs
# a real 1-learner + N-actor process fleet over a shared
# --fleet-transport dir — round publish->claim / return->apply
# latencies, learner cost/round vs the ask(K) budget, per-host
# busy-frac and journal-proven concurrent phase-1/phase-2 lanes on
# distinct host ids, byte-identity of the artifacts
# (docs/BENCHMARKS.md "Search pipelining", multi-host section).
# Honors FAA_BENCH_REQUIRE_QUIET=1 (refuses on a contended host).
bench-fleet-search:
	python tools/bench_fleet_search.py

# control-plane bench: a real 3-replica --traffic-stats fleet with a
# drill-mode control_cli — injected drift (FAA_FAULT drift@...) ->
# detect -> canary -> promote mid-traffic vs a steady arm, as paired
# alternating rounds with medians; reports shift->detect and
# detect->promote latency, rollover goodput and the zero-drop verdict
# (docs/CONTROL.md "Measuring the loop")
bench-control:
	python tools/bench_control.py

# render a --telemetry journal dir as a Chrome trace (open the output
# in chrome://tracing or ui.perfetto.dev): per-thread dispatch spans,
# phase-1/phase-2 overlap lanes, shed/breaker/watchdog markers
# (docs/OBSERVABILITY.md "Timelines")
trace:
	python tools/trace_export.py --telemetry $(TELEMETRY) --out $(TRACE_OUT)

# one fleet table from telemetry journals + fleet heartbeats under a
# shared dir: per-host busy-frac, dispatch-gap p50/p99, incident
# counts, reclaimed units (docs/OBSERVABILITY.md "Fleet status")
status:
	python tools/faa_status.py --dir $(TELEMETRY)

clean:
	$(MAKE) -C native clean
