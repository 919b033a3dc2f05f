# Repo quality/test targets (reference analogue: the reference Makefile's
# quality/style/test tiers).

.PHONY: quality style lint lint-sarif divergence flight-check perf-check numerics-check pipe-check fleet-check kernel-check tune-selfcheck telemetry-selfcheck trace-selfcheck ft-selfcheck aot-selfcheck test test-slow test-all test-cli check-imports dryrun api-docs cache-pack cache-seed

# Persistent XLA compile cache (tests/conftest.py points every run and its
# subprocess children here). cache-pack snapshots a warm cache into a
# shareable artifact; cache-seed restores it into an EMPTY dir only — a
# half-written or corrupt cache segfaults XLA:CPU mid-suite, so a non-empty
# dir is left alone (wipe with `rm -rf $(JAX_CACHE_DIR)` if a run dies with
# a faulthandler dump, then re-seed). CI: store the artifact, `make
# cache-seed test`. See docs/usage_guides/testing.md for measured times.
JAX_CACHE_DIR ?= $(or $(JAX_COMPILATION_CACHE_DIR),.cache/jax)
JAX_CACHE_ARTIFACT ?= .cache/jax_compile_cache.tar.gz

cache-pack:
	@mkdir -p $(dir $(JAX_CACHE_ARTIFACT))
	@tar -C $(JAX_CACHE_DIR) -czf $(JAX_CACHE_ARTIFACT) .
	@du -h $(JAX_CACHE_ARTIFACT)

cache-seed:
	@if [ -f $(JAX_CACHE_ARTIFACT) ] && [ -z "$$(ls -A $(JAX_CACHE_DIR) 2>/dev/null)" ]; then \
		mkdir -p $(JAX_CACHE_DIR) && tar -C $(JAX_CACHE_DIR) -xzf $(JAX_CACHE_ARTIFACT) && \
		echo "seeded $(JAX_CACHE_DIR) from $(JAX_CACHE_ARTIFACT)"; \
	else echo "cache-seed: nothing to do (no artifact, or cache already warm)"; fi

# lint if ruff is installed (its exit code propagates); the zero-dep
# AST/import gates always run
quality: lint
	@if command -v ruff >/dev/null 2>&1; then ruff check accelerate_tpu tests examples; else echo "ruff not installed; skipping lint"; fi
	python scripts/check_repo.py

# TPU correctness linter: self-lint the tree (exit nonzero on any
# error-severity finding) + prove every rule fires on its seeded-defect
# fixture. Runs on the CPU backend — safe on machines with no TPU.
# The flight-check gate rides along non-strict: TPU3xx warnings print but
# don't fail the build (yet).
lint:
	env JAX_PLATFORMS=cpu python -m accelerate_tpu.commands.cli lint accelerate_tpu --selfcheck
	$(MAKE) --no-print-directory divergence
	$(MAKE) --no-print-directory perf-check
	$(MAKE) --no-print-directory numerics-check
	$(MAKE) --no-print-directory tune-selfcheck
	$(MAKE) --no-print-directory pipe-check
	$(MAKE) --no-print-directory fleet-check
	$(MAKE) --no-print-directory kernel-check
	-$(MAKE) --no-print-directory flight-check
	-$(MAKE) --no-print-directory telemetry-selfcheck
	-$(MAKE) --no-print-directory trace-selfcheck
	-$(MAKE) --no-print-directory ft-selfcheck
	-$(MAKE) --no-print-directory aot-selfcheck

# Multi-host divergence analyzer (TPU4xx): prove TPU401-405 fire on their
# seeded deadlock fixtures (and the clean fixture stays quiet), then
# self-analyze the tree. This gate is STRICT for the TPU401-403 errors —
# a collective not every rank reaches is a guaranteed all-host hang —
# while the TPU404/405 warnings report but pass. Pure AST, no jax needed.
divergence:
	env JAX_PLATFORMS=cpu python -m accelerate_tpu.commands.cli divergence accelerate_tpu --selfcheck

# Merged SARIF 2.1.0 artifact for GitHub code scanning: the AST,
# divergence, numerics, pipe, fleet, and kernel tiers each contribute one
# runs[] entry (six runs; scripts/merge_sarif.py's test pins the count).
# Findings don't fail this target (make lint is the gate); the artifact
# is for PR annotation.
lint-sarif:
	@mkdir -p .cache
	-env JAX_PLATFORMS=cpu python -m accelerate_tpu.commands.cli lint accelerate_tpu --format sarif > .cache/lint.sarif
	-env JAX_PLATFORMS=cpu python -m accelerate_tpu.commands.cli divergence accelerate_tpu --format sarif > .cache/divergence.sarif
	-env JAX_PLATFORMS=cpu python -m accelerate_tpu.commands.cli numerics-check accelerate_tpu --format sarif > .cache/numerics.sarif
	-env JAX_PLATFORMS=cpu python -m accelerate_tpu.commands.cli pipe-check \
		examples/by_feature/pipe_check.py::train_step --mesh pipe=4,data=2 --format sarif > .cache/pipe.sarif
	-env JAX_PLATFORMS=cpu python -m accelerate_tpu.commands.cli fleet-check \
		accelerate_tpu/serving_fleet.py accelerate_tpu/scheduling.py accelerate_tpu/ft \
		accelerate_tpu/telemetry/httpd.py accelerate_tpu/telemetry/flightrec.py \
		accelerate_tpu/telemetry/trace.py accelerate_tpu/serving_proc.py \
		accelerate_tpu/serving_transport.py --format sarif > .cache/fleet.sarif
	-env JAX_PLATFORMS=cpu python -m accelerate_tpu.commands.cli kernel-check \
		examples/by_feature/kernel_check.py::decode_step --format sarif > .cache/kernel.sarif
	python scripts/merge_sarif.py .cache/lint.sarif .cache/divergence.sarif .cache/numerics.sarif .cache/pipe.sarif .cache/fleet.sarif .cache/kernel.sarif -o lint-merged.sarif

# Static perf tier: prove TPU501-505 fire on their seeded defects, each
# clean twin stays silent, and the roofline math matches the hand-computed
# reference exactly — then roofline the example step over a fake 8-device
# CPU mesh. The dogfood pass is non-strict for warnings (TPU501/503-505
# print but pass) while TPU502 (redundant collective) is error-severity
# and gates strictly: re-reducing an already-uniform value has no
# legitimate use.
perf-check:
	env JAX_PLATFORMS=cpu python -m accelerate_tpu.commands.cli perf-check --selfcheck \
		examples/by_feature/flight_check.py::train_step --mesh data=8

# Numerics tier: prove TPU601-606 fire on their seeded defects, each
# clean twin stays silent, and the interval arithmetic matches the
# hand-computed reference exactly — then interpret the example's
# mixed-precision step over a fake 8-device CPU mesh AND run the AST
# key-reuse tier over the whole tree. The gate is STRICT for TPU602
# (provable fp16/fp8 overflow has no legitimate use) via its error
# severity; TPU601/603-606 warnings report but pass.
numerics-check:
	env JAX_PLATFORMS=cpu python -m accelerate_tpu.commands.cli numerics-check --selfcheck \
		examples/by_feature/numerics_check.py::train_step --mesh data=8
	env JAX_PLATFORMS=cpu python -m accelerate_tpu.commands.cli numerics-check accelerate_tpu
	env JAX_PLATFORMS=cpu python -m accelerate_tpu.commands.cli numerics-check examples

# Config tier (autotuner): prove TPU701-705 fire on their seeded
# misconfigurations (TPU701 end to end through a real single-candidate
# tune whose static peak HBM cannot fit a tiny budget) and every clean
# twin stays silent — then dogfood a real tune over the example train
# workload. The gate is STRICT for TPU701 (an infeasible declared config
# cannot run) via its error severity; TPU702-705 warnings report but
# pass.
tune-selfcheck:
	env JAX_PLATFORMS=cpu python -m accelerate_tpu.commands.cli tune --selfcheck \
		examples/by_feature/tune.py::train_workload --mesh data=8 \
		--meshes "data=8;data=4,tensor=2" --compressions none,int8 --generation cpu

# Pipeline tier (pipemodel): prove TPU801-805 fire on their seeded
# schedule defects, every clean twin stays silent, and the bubble /
# roofline arithmetic matches the hand-computed reference exactly — then
# analyze the example's real pipeline_apply step on a fake 8-device CPU
# mesh (pipe=4 x data=2). The gate is STRICT for TPU804 (a collective
# over the pipe axis inside the tick body deadlocks or serializes the
# MPMD schedule) via its error severity; TPU801-803/805 warnings report
# but pass.
pipe-check:
	env JAX_PLATFORMS=cpu python -m accelerate_tpu.commands.cli pipe-check --selfcheck \
		examples/by_feature/pipe_check.py::train_step --mesh pipe=4,data=2

# Fleet tier (hostsim + fleet_rules): prove TPU901-905 fire on their
# seeded defects (ABBA deadlock, unlocked cross-thread attribute,
# sleep-under-lock, protocol-invariant breaks, unjoined worker) and
# every clean twin stays silent — then dogfood the host-concurrency lint
# over the real fleet surface AND model-check the replica health state
# machine extracted from serving_fleet.py against the PR-15 invariants
# (plus the process supervisor's worker lifecycle from serving_proc.py:
# respawn cap, restart-storm breaker, shed-on-zero-routable).
# The gate is STRICT for TPU901 (a reachable ABBA deadlock) and TPU904
# (a protocol invariant violation or an unpinned failure path) via their
# error severity; TPU902/903/905 warnings report but pass. Pure stdlib —
# the fastest gate in the chain.
fleet-check:
	env JAX_PLATFORMS=cpu python -m accelerate_tpu.commands.cli fleet-check --selfcheck \
		accelerate_tpu/serving_fleet.py accelerate_tpu/scheduling.py accelerate_tpu/ft \
		accelerate_tpu/telemetry/httpd.py accelerate_tpu/telemetry/flightrec.py \
		accelerate_tpu/telemetry/trace.py accelerate_tpu/serving_proc.py \
		accelerate_tpu/serving_transport.py

# Kernel tier (kernelmodel + kernel_rules): prove TPU1001-1006 fire on
# their seeded defects (VMEM overflow, ragged tile, index-map gap, alias
# hazard, unregistered call, drifted contract), every clean twin (the
# shipped reference kernels) stays silent, and the kernel cost math
# matches the hand-computed reference exactly — then trace the example
# decode step AND run the AST registration gate over every tree path
# that issues a pallas_call (ops/ registration is the tracked follow-up;
# the gate scopes to kernels/ + examples until those contracts land).
# The gate is STRICT for TPU1001/1003/1005 (an unlowerable block, a
# garbage output region, an invisible kernel cost) via their error
# severity; TPU1002/1004/1006 warnings report but pass.
kernel-check:
	env JAX_PLATFORMS=cpu python -m accelerate_tpu.commands.cli kernel-check --selfcheck \
		examples/by_feature/kernel_check.py::decode_step --mesh data=8
	env JAX_PLATFORMS=cpu python -m accelerate_tpu.commands.cli kernel-check \
		accelerate_tpu/kernels examples

# SPMD flight-check: prove TPU301/302/303 fire on their seeded defects,
# then report the example step (peak HBM + collective traffic) on a fake
# 8-device CPU mesh.
flight-check:
	env JAX_PLATFORMS=cpu python -m accelerate_tpu.commands.cli flight-check --selfcheck \
		examples/by_feature/flight_check.py::train_step --mesh data=8 --donate 0

# Runtime telemetry: 5-step CPU loop -> JSONL -> parse -> summarize; proves
# the event-log schema, the step split, the recompile watchdog, and the
# summarize CLI agree end to end.
telemetry-selfcheck:
	env JAX_PLATFORMS=cpu python -m accelerate_tpu.commands.cli telemetry selfcheck

# Request tracing: seeded drift fixture (handoff moved fewer bytes than
# priced -> exactly ONE latched trace_drift) + clean twin (zero) through
# the full Tracer -> EventLog -> reconstruction -> chrome-export ->
# flight-recorder pipeline. Pure stdlib, no jax.
trace-selfcheck:
	env JAX_PLATFORMS=cpu python -m accelerate_tpu.commands.cli trace selfcheck

# Fault tolerance: seeded good/uncommitted/corrupt/recoverable checkpoint
# fixtures -> prove manifest verify (crc32 + sizes), discovery walk-back,
# tmp GC/recovery, and protected pruning classify every one correctly;
# plus a mesh-mismatch (topology v2) fixture -> prove `checkpoints
# describe` classifies identical/elastic/unknown and prices the reshard.
ft-selfcheck:
	env JAX_PLATFORMS=cpu python -m accelerate_tpu.commands.cli checkpoints verify --selfcheck

# Compile cache (aot/): cold compile -> serialized executable store ->
# second cache deserializes with ZERO XLA compiles -> a poisoned entry is
# rejected cleanly and healed. Proves the AOT warm-start loop on CPU.
aot-selfcheck:
	env JAX_PLATFORMS=cpu python -m accelerate_tpu.commands.cli compile-cache --selfcheck

style:
	@if command -v ruff >/dev/null 2>&1; then ruff check --fix accelerate_tpu tests examples && ruff format accelerate_tpu tests examples; else echo "ruff not installed; style target is a no-op here"; fi

test: cache-seed  # fast tier (addopts excludes -m slow)
	python -m pytest tests/ -q

test-slow:  # subprocess/integration tier
	python -m pytest tests/ -q -m slow --override-ini addopts=""

test-all:
	python -m pytest tests/ -q -m "" --override-ini addopts=""

test-cli:
	python -m pytest tests/test_cli.py -q

api-docs:
	python scripts/gen_api_docs.py

dryrun:
	python __graft_entry__.py 8
