# test-t1 uses `set -o pipefail`/PIPESTATUS, which POSIX sh lacks
SHELL := /bin/bash

.PHONY: smoke test test-t1 lint lint-robust lint-selfcheck native benchmark trace status clean reproduce chaos gameday gameday-smoke

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

# the tier-1 gate as the driver runs it (the plain `test` target
# differs: it includes slow-marked tests, runs on one worker and stops
# on collection errors), with the static-analysis gate as a preamble
test-t1: lint
	set -o pipefail; rm -rf /tmp/_t1.log /tmp/_t1.xml; timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist loadfile --junitxml=/tmp/_t1.xml -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); exit $$rc

# composed-fault chaos smoke (docs/RESILIENCE.md "Hostile shared
# filesystem"): FAA_FAULT (a SIGKILLed actor) layered with FAA_FSFAULT
# (publish->claim lag + seeded transient read errors) over a bounded
# 3-process fleet drill — completes degraded-but-correct, prints a
# CHAOS line with the reclaim/epoch evidence
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

# one run of one cell of the on-chip benchmark (BENCHMARK.json; PERF.md
# says what the numbers mean).  Needs a TPU: exit 3 and no result line
# without one.
#   ARGS="--workload wrn40x2_train --seed 1 --seconds 10 --trace 0"
benchmark:
	python3 benchmarks/run.py $(ARGS)

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
