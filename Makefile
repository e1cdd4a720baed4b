# Build/test entry points (the reference drives everything through
# Makefile targets — Makefile-test.mk:108-143; this is the standalone
# equivalent).

PY ?= python
PYTEST_FLAGS ?= -q

.PHONY: all native test test-fast test-device bench multichip-dryrun \
  replay-smoke obs-smoke tas-smoke perf-smoke apply-smoke ha-smoke \
  chaos-smoke federation-smoke overload-smoke sim-smoke \
  readplane-smoke smoke \
  bench-gate lint lint-sanitize clean

all: native

# Native runtime pieces (indexed pending-queue heap; ctypes-loaded).
native:
	$(MAKE) -C native

test: native
	$(PY) -m pytest tests/ $(PYTEST_FLAGS)

# Skip the slow device-parity suites (CI smoke tier).
test-fast: native
	$(PY) -m pytest tests/ $(PYTEST_FLAGS) \
	  --ignore=tests/test_multichip_parity.py \
	  --ignore=tests/test_drain_parity.py \
	  --ignore=tests/test_preempt_churn.py

# Only the device kernels / parity suites (run after kernel changes).
# Together with test-fast this covers the whole tests/ tree: everything
# test-fast --ignores is enumerated here.
test-device: native
	$(PY) -m pytest tests/test_quota_parity.py tests/test_assign_parity.py \
	  tests/test_commit_grouped.py tests/test_preempt_device.py \
	  tests/test_classical_preempt_device.py tests/test_fair_device.py \
	  tests/test_tas_device.py tests/test_drain_parity.py \
	  tests/test_preempt_churn.py \
	  tests/test_multichip_parity.py $(PYTEST_FLAGS)

# The perf suite (BASELINE.json configs 2-5); FAST=1 for a smoke run.
# Needs a TPU and fails without one; `JAX_PLATFORMS=cpu make bench`
# rehearses on the CPU and stamps every row `cpu`. The quickest proof
# that the main path runs on the chip is `python3 chip_smoke.py`.
bench:
	$(PY) bench.py

bench-fast:
	KUEUE_TPU_BENCH_FAST=1 $(PY) bench.py

# Static analysis: the graftlint AST rules (D1/J1/U1/O1/R1) over the
# package plus the in-process emitter/validator self-check (V1/V2).
# One entry point, one exit code, one JSON report (--json FILE).
lint:
	JAX_PLATFORMS=cpu $(PY) -m tools.graftlint kueue_tpu/ --self-check

# Runtime sanitizer (dynamic D1 + F1): sim triples replayed across
# PYTHONHASHSEED values must keep identical decision digests, and an
# instrumented federation run must never fire an effect (handoff,
# revoke, SSE publish) while the route journal has unsynced appends.
# --self-test also arms both planted regressions (shuffle, fsync-drop)
# in subprocesses and requires each to FAIL with the violation named.
lint-sanitize: lint
	JAX_PLATFORMS=cpu $(PY) -m tools.graftlint.sanitize --self-test

# Flight-recorder determinism smoke: record a 50-workload scenario,
# replay it twice, diff the decision-stream checksums (replay/).
# lint runs first: replaying a tree that violates D1 proves nothing.
replay-smoke: lint
	JAX_PLATFORMS=cpu $(PY) tools/replay_smoke.py

# Batched-TAS smoke: drain one TAS world with the batched planner on
# and off (subprocess per arm), assert the batched arm ran device
# cycles AND that admissions + topology assignments are byte-identical
# across the toggle, then run the TAS equivalence suite. lint first:
# the planner lives in a D1 determinism zone.
tas-smoke: lint
	JAX_PLATFORMS=cpu $(PY) tools/tas_smoke.py
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_tas_batched.py \
	  tests/test_tas_device.py $(PYTEST_FLAGS)

# Observability smoke: tracer + serving endpoint, 50-workload admit,
# /metrics scrape validated by tools/promcheck, Perfetto export
# validated by tools/trace_schema, /debug/trace + explain (obs/).
# lint runs first: O1 violations invalidate digest-neutrality claims.
obs-smoke: lint
	JAX_PLATFORMS=cpu $(PY) tools/obs_smoke.py

# Perf-telemetry smoke: two engines over the same short mixed world,
# bare vs fully instrumented (tracer + perf recorder + SLO engine);
# asserts digest identity, >=4 apply sub-phase histograms, promcheck /
# trace_schema cleanliness and a loose overhead tripwire (obs/perf.py,
# obs/slo.py). lint first: the capture paths live in O1/D1 zones.
perf-smoke: lint
	JAX_PLATFORMS=cpu $(PY) tools/perf_smoke.py

# Columnar-apply smoke: one churn world drained through both
# KUEUE_TPU_COLUMNAR arms to byte-identical digests and final state,
# then two lethal subprocess stages (SIGKILL at the Nth bulk admission,
# torn journal tail) whose journal rebuilds must converge to the
# uninterrupted control — zero lost/duplicate admissions
# (controllers/colapply.py, oracle/engine_bridge.py, replay/faults.py).
# lint first: colapply sits in a U1/D1 zone.
apply-smoke: lint
	JAX_PLATFORMS=cpu $(PY) tools/apply_smoke.py

# HA failover smoke: leader + follower replicas over one journal;
# the leader is SIGKILLed mid-admission (and, in a second arm, with a
# torn journal tail); the follower must steal the fenced lease, replay-
# verify the last ha_digest checkpoint, promote at epoch 2, and drain
# to a byte-identical admitted-state digest — zero lost or duplicate
# admissions (kueue_tpu/ha). lint first: the ha/ zone pins (J1, R1
# kind registration) are part of the contract.
ha-smoke: lint
	JAX_PLATFORMS=cpu $(PY) tools/ha_smoke.py

# Seeded chaos sweep: 8 seeds expanded into deterministic multi-stage
# fault plans (SIGKILL at cycle/admission/maintenance boundaries, torn
# journal tails, torn checkpoints, ENOSPC, clock skew, oracle crash
# storms); every seed must recover to zero lost/duplicate admissions
# with the checkpoint+suffix rebuild byte-identical to a genesis
# replay, and the storm arm must demote + re-promote the oracle
# breaker (store/checkpoint.py, replay/faults.py, oracle/supervisor.py).
# lint first: the checkpoint and supervisor zone pins are part of the
# recovery contract.
chaos-smoke: lint
	JAX_PLATFORMS=cpu $(PY) tools/chaos_smoke.py

# Multi-cell federation chaos sweep: 8 seeds, each a deterministic
# fault chain over three real HA cells behind the dispatcher tier —
# whole-cell SIGKILL mid-admission, dispatcher crash between route-
# intent fsync and handoff, bounded network partition, zombie rejoin
# under the fence epoch. Every seed must end with per-cell live
# digests identical to cold journal rebuilds and the union of
# per-cell admitted sets equal to the submitted set, pairwise
# disjoint (kueue_tpu/federation, replay/faults.py). lint first: the
# federation zone pin and R1 kind registration are part of the
# contract.
federation-smoke: lint
	JAX_PLATFORMS=cpu $(PY) tools/federation_smoke.py

# Overload survival, end to end through the real HTTP front door: a
# deterministic open-loop storm (kueue_tpu/loadgen) at 5x the shed
# rate while a fault plan wedges a cycle (hang -> watchdog sampler
# catches it with stacks) and collapses free disk (disk-pressure-ramp
# -> journal read-only, submits 503, budget re-arms). Excess load must
# shed 429 with clamped Retry-After, the ladder must walk back to rung
# 0, and a cold journal rebuild must show exactly the accepted set
# admitted — zero lost/duplicate (tools/overload_smoke.py). lint
# first: the watchdog/diskguard/loadgen zone pins are part of the
# contract.
overload-smoke: lint
	JAX_PLATFORMS=cpu $(PY) tools/overload_smoke.py

# World-simulator smoke: 8 fuzzed world-seed triples through the full
# invariant oracle (host-vs-device differential + metamorphic
# catalog), a multi-day compressed fault-storm arm that must re-run
# digest-identically, and a planted lost-arrival regression that must
# auto-shrink to a minimal reproducer exiting 3 under `kueuectl sim
# run --repro` (tools/sim_smoke.py). lint first: the sim/loadgen/
# watchdog/ladder C1 clock-discipline pins are part of the contract.
sim-smoke: lint
	JAX_PLATFORMS=cpu $(PY) tools/sim_smoke.py

# Read-plane smoke: leader + two stateless read replicas over one
# journal; reads routed exclusively to the replicas (the leader's
# /metrics must prove zero read queries served), every answer stamped
# with its staleness envelope, leader SIGKILLed mid-storm while the
# replicas keep answering within bound and watch streams stay live
# (tools/readplane_smoke.py). lint first: the readplane/ J1 zone pin
# is part of the contract.
readplane-smoke: lint
	JAX_PLATFORMS=cpu $(PY) tools/readplane_smoke.py

# Bench regression sentinel: noise-aware per-scenario gate over the
# accumulated BENCH_r*/MULTICHIP_r* trajectory (tools/bench_sentinel.py).
# Fails (exit 1) when the latest round regressed past its scenario's
# fitted threshold, pointing at the apply sub-phase histogram.
bench-gate:
	$(PY) tools/bench_sentinel.py --dir .

# The full CI smoke chain: every subsystem smoke, ending on the bench
# regression gate so a perf regression fails the same entry point as a
# correctness one.
smoke: lint-sanitize replay-smoke tas-smoke obs-smoke perf-smoke \
  apply-smoke ha-smoke chaos-smoke federation-smoke overload-smoke \
  sim-smoke readplane-smoke bench-gate

# Validate the multi-chip sharding compiles + executes on a virtual mesh.
multichip-dryrun:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	  $(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

# Regenerate the README/ARCHITECTURE perf blocks from the latest
# BENCH_r*.json; -check greppably fails when docs drift from the
# shipped artifact.
docs-perf:
	$(PY) tools/docs_perf.py

docs-perf-check:
	$(PY) tools/docs_perf.py --check

clean:
	$(MAKE) -C native clean
