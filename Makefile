# Build/packaging (reference parity: Makefile `make build` / `make check`).

PY ?= python

.PHONY: all native test check bench bench-regress audit asan \
	metrics-smoke mesh-smoke chaos-smoke body-smoke \
	staging-smoke timeline-smoke chip-smoke \
	clean analyze analyze-abi analyze-lint analyze-tidy analyze-tsan \
	fuzz prove ringcheck surface

all: native

native:
	$(MAKE) -C pingoo_tpu/native

test: native
	$(PY) -m pytest tests/ -x -q

check:
	$(PY) -m compileall -q pingoo_tpu
	$(PY) -c "import pingoo_tpu.config, pingoo_tpu.compiler, pingoo_tpu.engine"
	$(MAKE) analyze
	$(MAKE) mesh-smoke
	$(MAKE) chaos-smoke
	$(MAKE) body-smoke
	$(MAKE) staging-smoke
	$(MAKE) timeline-smoke

# Static analysis suite (docs/STATIC_ANALYSIS.md) — offline-safe; each
# pass skips with a warning when its toolchain is missing, and each is
# individually invocable. `analyze` also re-runs the metrics-schema
# audit so one target gates every machine-checked invariant:
#   analyze-abi   C++ header vs numpy dtypes vs committed golden layout
#   analyze-lint  JAX hot-path AST linter (host syncs, recompile
#                 hazards, hot-function allocation)
#   analyze-tidy  clang-tidy bugprone/concurrency vs tracked baseline
#   analyze-tsan  extended ring_stress under -fsanitize=thread
#   fuzz          differential HTTP-parsing fuzzer across all three
#                 parse paths (docs/FUZZING.md)
#   prove         lowering-soundness prover + compile surface +
#                 ring-protocol model checker (ISSUE 18; skips with a
#                 warning when jax is unavailable)
analyze: analyze-abi analyze-lint analyze-tidy analyze-tsan fuzz prove
	$(PY) tools/check_metrics_schema.py

analyze-abi:
	$(PY) -m tools.analyze abi

analyze-lint:
	$(PY) -m tools.analyze lint

analyze-tidy:
	$(PY) -m tools.analyze tidy

analyze-tsan:
	$(PY) -m tools.analyze tsan

# Machine-checked lowering soundness (ISSUE 18, docs/STATIC_ANALYSIS.md
# "Prove"): discharge every obligation on the seed 500-rule plan + the
# body plan, refresh COMPILE_SURFACE.json, model-check the ring
# protocol, and run the five mutation self-tests. Offline-safe.
prove:
	env JAX_PLATFORMS=cpu $(PY) -m tools.analyze prove

ringcheck:
	$(PY) -m tools.analyze ringcheck

surface:
	$(PY) -m tools.analyze surface

# Differential parsing fuzzer (ISSUE 11, docs/FUZZING.md): 5k seeded
# framing/encoding mutants through the native listener, the python
# listener's parse oracle, and interpreter field extraction; any
# non-documented divergence of RequestTuple fields or verdict bits
# fails. Deterministic, offline-safe (no native toolchain -> 2-path).
fuzz: native
	env JAX_PLATFORMS=cpu $(PY) -m tools.analyze fuzz

bench: native
	$(PY) bench.py

# Served-path proof on the accelerator (ISSUE 21): config 2 through
# `python -m pingoo_tpu --native-plane`, every status against the
# interpreter. Exits non-zero where JAX finds no accelerator.
chip-smoke:
	$(PY) chip_smoke.py

# Bench trajectory gate (ISSUE 5 satellite): `bench.py --history`
# appends each run to BENCH_history.jsonl; this compares the latest run
# against the previous comparable one (same backend) and fails on a
# >BENCH_REGRESS_THRESHOLD (default 10%) regression of any tracked
# metric.
bench-regress:
	$(PY) tools/bench_regress.py

# Dependency audit — the reference ships .github/workflows/audit.yml
# (cargo audit + cargo deny); the equivalent here is pip-audit over the
# Python environment plus the EXACT native runtime libraries the data
# plane links (the image has no dev packages to query, so surface the
# versioned sonames for CVE review). pip-audit needs network; when it
# is unavailable the target still emits the frozen dependency list for
# an offline scanner.
audit:
	@$(PY) -m pip_audit 2>/dev/null || \
		{ echo "pip-audit unavailable/offline; frozen deps for offline review:"; \
		  $(PY) -m pip freeze; }
	@echo "-- native plane runtime libraries --"
	@ldconfig -p | grep -E 'libssl|libcrypto|libnghttp2' || true
	@if [ -x pingoo_tpu/native/httpd ]; then \
		ldd pingoo_tpu/native/httpd | grep -E 'ssl|crypto|nghttp2'; fi
	@echo "-- metrics schema parity --"
	$(PY) tools/check_metrics_schema.py

# Mesh-serving smoke (ISSUE 6, docs/SCHEDULER.md): serve live requests
# through PINGOO_MESH=2x2x2 on 8 fake host devices, prove verdict
# bit-identity vs single-device + scheduler/deadline metrics export.
# Offline-safe: skips with a warning when jax is unavailable.
mesh-smoke:
	$(PY) tools/mesh_smoke.py

# Sidecar supervision chaos smoke (ISSUE 10, docs/RESILIENCE.md):
# SIGKILL the ring sidecar mid-batch and prove crash-reattach
# reconciliation (zero lost / double-posted tickets, bounded p99,
# bit-exact verdicts), heartbeat-freeze detection, and ladder demotion
# under injected device faults. Offline-safe: skips with a warning
# when jax or the native toolchain is unavailable.
chaos-smoke:
	$(PY) tools/chaos_smoke.py

# Compact-staging smoke (ISSUE 15, docs/EXECUTOR.md "Compact
# staging"): prove PINGOO_STAGING=compact is bit-identical to the
# full-mode oracle on BOTH planes, with the ParityAuditor clean over
# the compact path and a nonzero staged-bytes saving on a long-URL
# stream. Offline-safe: skips when jax is unavailable; the sidecar
# half skips without the native toolchain.
staging-smoke:
	$(PY) tools/staging_smoke.py

# Perf-ledger + timeline smoke (ISSUE 17, docs/OBSERVABILITY.md): prove
# the compile ledger records the warm-up compiles (JSONL agreeing with
# the counters), sampled batch spans nest and export as Chrome-trace
# JSON with the cross-plane ring-wait join, the durable cost ledger
# round-trips EWMAs and discards stale fingerprints, and the record
# path costs <2% of a batch. Offline-safe: skips when jax is
# unavailable; the sidecar half skips without the native toolchain.
timeline-smoke:
	$(PY) tools/timeline_smoke.py

# Streaming body-inspection smoke (ISSUE 13, docs/BODY_STREAMING.md):
# prove stream==contiguous==oracle scanner parity with seams inside
# every match literal, the window-gap degrade lane, and the native
# httpd under PINGOO_BODY_INSPECT=on blocking torn-literal bodies
# (gate off = bit-exact status quo). Offline-safe: skips with a
# warning when jax is unavailable; the native half skips without g++.
body-smoke:
	$(PY) tools/body_smoke.py

# Live observability smoke: boot the native plane + ring sidecar + a
# Python listener, scrape both /__pingoo/metrics endpoints in both
# formats, and validate them against the documented inventory
# (docs/OBSERVABILITY.md / pingoo_tpu/obs/schema.py).
metrics-smoke: native
	env JAX_PLATFORMS=cpu $(PY) tools/metrics_smoke.py

# ASAN/UBSAN build of the native data plane (httpd_asan).
asan:
	$(MAKE) -C pingoo_tpu/native asan

clean:
	$(MAKE) -C pingoo_tpu/native clean
	find . -name __pycache__ -type d -exec rm -rf {} +
