# Build/test orchestration. Reference parity: the reference Makefile's
# test / citest / lint / generate_tests / pyspec / detect_generator_incomplete
# surface (Makefile:90-199), adapted to this repo's layout (no venv juggling:
# the environment is pre-baked; no markdown build step at test time: the spec
# compiler execs markdown on import).

PYTHON ?= python
TEST_VECTOR_DIR ?= ../consensus-spec-tests/tests
GENERATORS = bls ssz_generic ssz_static shuffling operations epoch_processing \
             sanity genesis finality rewards fork_choice forks transition \
             merkle random custody_sharding scenarios

.PHONY: test testall citest testfast chaos sched msm firehose scenarios proofs forkchoice frontdoor slo lint lint-fast pyspec generate_tests \
        clean_vectors detect_generator_incomplete bench bench_quick \
        graft_check chip_smoke native replay random_codegen coverage \
        deposit_contract_json

# Default developer loop: full suite (minimal preset, BLS stubbed where the
# suite chooses; JAX pinned to the virtual 8-device CPU mesh by tests/conftest.py).
test:
	$(PYTHON) -m pytest tests/ -x -q -m "not slow"

# Everything, including the multi-minute compile-bound crypto tests the
# default lane defers (reference Makefile:98-100 keeps a fast-minimal
# default too; nothing is deleted — this lane runs it all).
testall:
	$(PYTHON) -m pytest tests/ -q

# CI profile: no -x, junit output, ALL tests.
citest:
	$(PYTHON) -m pytest tests/ -q --junitxml=test-results/junit.xml

# Quick sanity loop: skip every device-pairing test.
testfast:
	$(PYTHON) -m pytest tests/ -x -q -k "not pairing"

# Fault-tolerance lane: the robustness unit suite plus the seeded chaos
# convergence runs (faults at every device-boundary seam must leave the
# state root bit-identical to the fault-free oracle — see README "Fault
# tolerance"). Deterministic schedules only; the long randomized soak is
# marked `slow` and runs in testall/citest. Hard wall-clock bound so a
# retry/backoff regression hangs the lane loudly instead of silently.
# The run writes the canonical obs snapshot (every fault/retry/breaker
# counter the chaos schedules ticked) to test-results/ and validates it —
# CI uploads it as the chaos lane's observability artifact.
chaos:
	mkdir -p test-results
	OBS_SNAPSHOT=test-results/obs_chaos.json OBS_SNAPSHOT_LANE=chaos \
	OBS_FLIGHT_DIR=test-results \
	timeout -k 10 600 $(PYTHON) -m pytest \
	    tests/test_chaos_epoch.py tests/test_robustness.py -q -m "not slow"
	$(PYTHON) tools/obs_dump.py check test-results/obs_chaos.json

# Unified verification scheduler lane: admission/collapse/backpressure
# mechanics, device-vs-host lane agreement, and the compile-cache pin
# (one XLA compile per (class, bucket)) — see README "Verification
# scheduler". Writes + validates the lane's obs snapshot like chaos does;
# the scheduler's own counters/gauges/histograms are the artifact.
sched:
	mkdir -p test-results
	OBS_SNAPSHOT=test-results/obs_sched.json OBS_SNAPSHOT_LANE=sched \
	OBS_FLIGHT_DIR=test-results \
	timeout -k 10 600 $(PYTHON) -m pytest \
	    tests/test_sched.py -q -m "not slow"
	$(PYTHON) tools/obs_dump.py check test-results/obs_sched.json

# Pippenger MSM lane: the bucket-MSM kernel's cost pins (eval_shape loop
# counts, point-op budget), host-oracle equivalence on edge batches, the
# sched "msm" work class (compile-per-bucket pin, chaos corrupt faults,
# 2G2T self-check), and the cold-lane committee aggregation regression —
# see README "Pippenger MSM". Obs snapshot validated like the sibling
# lanes; the msm-class sched_* and bls_pubkey_*_device series are the
# artifact.
msm:
	mkdir -p test-results
	OBS_SNAPSHOT=test-results/obs_msm.json OBS_SNAPSHOT_LANE=msm \
	OBS_FLIGHT_DIR=test-results \
	timeout -k 10 600 $(PYTHON) -m pytest \
	    tests/test_msm.py -q -m "not slow"
	$(PYTHON) tools/obs_dump.py check test-results/obs_msm.json

# Attestation firehose lane: the streaming gossip->aggregate->flush
# service (ingest dedup, committee collapse, double-buffered flush,
# backpressure) plus the gossip driver's partial-drain seam it consumes —
# see README "Attestation firehose". Obs snapshot validated like the
# chaos/sched lanes; the firehose_* series are the artifact.
firehose:
	mkdir -p test-results
	OBS_SNAPSHOT=test-results/obs_firehose.json OBS_SNAPSHOT_LANE=firehose \
	OBS_FLIGHT_DIR=test-results \
	timeout -k 10 600 $(PYTHON) -m pytest \
	    tests/test_firehose.py tests/test_gossip_driver.py -q -m "not slow"
	$(PYTHON) tools/obs_dump.py check test-results/obs_firehose.json

# Scenario-engine lane: seeded long-horizon histories (reorg storms, fork
# ladders, equivocation waves, droughts) replayed through the oracle /
# chaos-engine / firehose lanes with bit-identical checkpoint assertions,
# plus the emit->replay->diff bidirectional conformance loop — see README
# "Scenario engine". The ≥2,000-slot soak is @slow (testall/citest only);
# this lane stays bounded for the inner loop. Obs snapshot validated like
# the chaos/sched/firehose lanes; the scenario_* series are the artifact.
scenarios:
	mkdir -p test-results
	OBS_SNAPSHOT=test-results/obs_scenarios.json OBS_SNAPSHOT_LANE=scenarios \
	OBS_FLIGHT_DIR=test-results \
	timeout -k 10 600 $(PYTHON) -m pytest \
	    tests/test_scenarios.py -q -m "not slow"
	$(PYTHON) tools/obs_dump.py check test-results/obs_scenarios.json

# Light-client read lane: device-batched Merkle multiproofs (ops +
# engine + the sched "multiproof" kind) pinned against the ssz host
# oracle, plus the dirty-column proof cache and its service — see README
# "Read path". Obs snapshot validated like the chaos/sched/firehose
# lanes; the proof_* series are the artifact.
proofs:
	mkdir -p test-results
	OBS_SNAPSHOT=test-results/obs_proofs.json OBS_SNAPSHOT_LANE=proofs \
	OBS_FLIGHT_DIR=test-results \
	timeout -k 10 600 $(PYTHON) -m pytest \
	    tests/test_proofs.py -q -m "not slow"
	$(PYTHON) tools/obs_dump.py check test-results/obs_proofs.json

# Fork-choice head lane: the device-resident LMD-GHOST tracker (ops +
# engine + the sched "forkchoice" kind + forkchoice/ service) pinned
# bit-identical against the spec's get_head across the three scenario
# lanes, chaos and breaker-open hard-down included — see README "Fork
# choice". Obs snapshot validated like the sibling lanes; the
# forkchoice_* series are the artifact.
forkchoice:
	mkdir -p test-results
	OBS_SNAPSHOT=test-results/obs_forkchoice.json OBS_SNAPSHOT_LANE=forkchoice \
	OBS_FLIGHT_DIR=test-results \
	timeout -k 10 600 $(PYTHON) -m pytest \
	    tests/test_forkchoice.py -q -m "not slow"
	$(PYTHON) tools/obs_dump.py check test-results/obs_forkchoice.json

# Front-door admission lane: the unified admission plane over the four
# service lanes (frontdoor/ + the scheduler's EDF seal-policy seam) —
# per-tenant quotas, the shed ladder, deadline sealing, and the three
# seeded traffic profiles replayed bit-identically under chaos — see
# README "Front door". Obs snapshot validated like the sibling lanes;
# the frontdoor_* series are the artifact.
frontdoor:
	mkdir -p test-results
	OBS_SNAPSHOT=test-results/obs_frontdoor.json OBS_SNAPSHOT_LANE=frontdoor \
	OBS_FLIGHT_DIR=test-results \
	timeout -k 10 600 $(PYTHON) -m pytest \
	    tests/test_frontdoor.py -q -m "not slow"
	$(PYTHON) tools/obs_dump.py check test-results/obs_frontdoor.json

# Declarative SLO gate (slo.json at the repo root): the bench trajectory
# and obs-snapshot invariants as machine-checked objectives — see README
# "Observability" and the SLO table in BASELINE.md. Evaluates the shipped
# BENCH_OBS.json plus whatever lane snapshots the sibling targets left in
# test-results/, against BENCH_LOCAL.json history; rc != 0 names the
# violated SLO. bench.py embeds the same verdict in every record it
# persists; this target is the standalone/CI entry point.
slo:
	$(PYTHON) tools/slo_check.py --bench BENCH_LOCAL.json \
	    BENCH_OBS.json $(wildcard test-results/obs_*.json)

# Compile-check every module and spec document (the exec-based analog of the
# reference's `make pyspec` build of eth2spec modules). With ARTIFACTS=1 the
# flattened per-(fork x preset) sources are ALSO written to build/specs/ and
# the emission is proven deterministic: each file is rendered twice and the
# two renders must be byte-identical (CI runs this same check).
pyspec:
	$(PYTHON) -m compileall -q consensus_specs_tpu generators tests bench.py __graft_entry__.py
	$(PYTHON) -c "from consensus_specs_tpu.compiler import get_spec; \
	    [get_spec(f, p) for f in ('phase0','altair','bellatrix') for p in ('minimal','mainnet')]; \
	    print('all fork x preset spec modules compile')"
ifeq ($(ARTIFACTS),1)
	$(PYTHON) -c "\
	from consensus_specs_tpu.compiler.spec_compiler import emit_spec_artifact, render_spec_source; \
	pairs = [(f, p) for f in ('phase0','altair','bellatrix') for p in ('minimal','mainnet')]; \
	paths = [emit_spec_artifact(f, p) for f, p in pairs]; \
	stale = [str(pth) for (f, p), pth in zip(pairs, paths) \
	         if pth.read_text() != render_spec_source(f, p)]; \
	assert not stale, f'non-deterministic emission: {stale}'; \
	print('spec artifacts (x2, byte-identical):'); \
	[print(' ', pth) for pth in paths]"
endif

# Static gate: compile-check + AST lint (unused imports, import shadowing,
# mutable defaults, tuple asserts, bare excepts) + tpulint (JAX hot-path
# invariants: jit purity, dtype pinning, donation aliasing, import layering,
# scatter bans, lock discipline, guarded fields, thread escapes — see
# BASELINE.md). The reference's flake8+mypy role (linter.ini) — those tools
# are not in this image. --max-seconds 30 is the runtime ratchet: the
# interprocedural fixpoints must stay a sub-minute gate as the tree grows
# (per-rule cost is visible via `tpulint --json` timings_s).
lint: pyspec
	$(PYTHON) tools/lint.py
	$(PYTHON) tools/typegate.py
	$(PYTHON) tools/tpulint.py consensus_specs_tpu --baseline tpulint_baseline.json --max-seconds 30
	$(PYTHON) tools/tpulint.py --self-test

# Inner-loop lint: full interprocedural analysis (the call graph needs every
# module), but only findings on files changed since $(SINCE) are reported —
# seconds of signal on the file you are editing, no baseline noise from the
# rest of the tree. `make lint-fast SINCE=origin/main` before pushing.
SINCE ?= HEAD
lint-fast:
	$(PYTHON) tools/tpulint.py consensus_specs_tpu --since $(SINCE)

# Regenerate the checked-in randomized test module (reference:
# tests/generators/random/generate.py workflow).
random_codegen:
	$(PYTHON) generators/random/generate.py

# Run every vector generator into TEST_VECTOR_DIR (reference: make generate_tests).
generate_tests: $(addprefix gen_,$(GENERATORS))

# Generation is a pure-host lane (it never loads the TPU): pin the
# CPU backend and verify through the batched XLA pairing kernels — the
# reference generates with milagro instead of py_ecc for the same reason.
gen_%:
	CONSENSUS_TPU_GEN_BLS=jax JAX_PLATFORMS=cpu \
	$(PYTHON) generators/$*/main.py -o $(TEST_VECTOR_DIR)

clean_vectors:
	rm -rf $(TEST_VECTOR_DIR)

# Crash forensics: list INCOMPLETE sentinels left by a crashed generator run
# (reference Makefile:195-199).
detect_generator_incomplete:
	@find $(TEST_VECTOR_DIR) -name INCOMPLETE 2>/dev/null || true

# Replay a vector tree (ours or an external consensus-spec-tests corpus)
# against the compiled specs; non-zero exit on any mismatch.
replay:
	$(PYTHON) -m consensus_specs_tpu.conformance $(TEST_VECTOR_DIR)

# Native components (ctypes-loaded C++).
native:
	$(MAKE) -C consensus_specs_tpu/native

bench:
	$(PYTHON) bench.py

# Fast TPU re-capture (VERDICT r3 item 5): small batches + fewer repeats,
# reusing the persistent XLA compile cache — appends a BENCH_LOCAL.json
# entry at the current sha. Target <5 min warm.
bench_quick:
	BENCH_BLS_N=512 BENCH_E2E_RESIDENT_EPOCHS=6 BENCH_KZG_BLOBS=32 \
	BENCH_ATT_VALIDATORS=32768 BENCH_SR_VALIDATORS=262144 \
	BENCH_E2E_VALIDATORS=1048576 BENCH_PROOF_VALIDATORS=1048576 \
	BENCH_PROOF_QUERIES=2048 $(PYTHON) bench.py

# The main path once on one TPU chip (exits non-zero without one);
# `--rehearse` runs the same phases on the CPU at small sizes.
chip_smoke:
	$(PYTHON) chip_smoke.py

# Regenerate the checked-in deposit contract artifact from the in-repo
# assembler (consensus_specs_tpu/evm/deposit_contract_asm.py). The JSON is a
# conformance anchor: tests/test_deposit_contract_evm.py fails if it drifts.
deposit_contract_json:
	$(PYTHON) -m consensus_specs_tpu.evm.build
	$(PYTHON) -m consensus_specs_tpu.evm.build --check

# What the driver compile-checks: single-chip entry + 8-device CPU-mesh dry
# run, on the CPU mesh that tests/conftest.py provisions.
graft_check:
	$(PYTHON) -c "\
	from consensus_specs_tpu.utils.backend import force_cpu; force_cpu(8); \
	import __graft_entry__ as g; fn, args = g.entry(); fn(*args); \
	g.dryrun_multichip(8); print('graft entry ok')"

# Line coverage over consensus_specs_tpu via stdlib sys.monitoring
# (tools/coverage.py — the environment has no pytest-cov; reference
# gates with --cov, Makefile:100). COVERAGE_MIN gates the build.
COVERAGE_MIN ?= 85
coverage:
	$(PYTHON) tools/coverage.py --min $(COVERAGE_MIN) -- -m pytest tests/ -q -m "not slow"
