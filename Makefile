# Build targets (reference: Makefile — here the compute path is XLA-compiled
# at runtime; native builds cover the C++ host components).

NATIVE_DIR := distributed_llama_multiusers_tpu/native
NATIVE_SO := $(NATIVE_DIR)/libdllama_native.so

.PHONY: all native test verify lint lockgraph protocol jitcheck leakcheck kernelcheck hooks sanitize dryrun chaos fleet tracecheck check clean

all: native

native: $(NATIVE_SO)

$(NATIVE_SO): $(NATIVE_DIR)/quant_codec.cpp
	python -c "from distributed_llama_multiusers_tpu.native import ensure_built; import sys; sys.exit(0 if ensure_built(quiet=False) else 1)"

test: native
	python -m pytest tests/ -x -q

# Canonical tier-1 gate: the driver's command (`commands` in /root/TESTS_LAST_RUN.json).
# Depends on native like `test` does: without the .so the native codec
# tests skip and the gate would report success with less coverage.
verify: SHELL := /bin/bash
verify: native
	set -o pipefail; log=$$(mktemp /tmp/_t1.XXXXXX.log); xml=$$(mktemp /tmp/_t1.XXXXXX.xml); \
	timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 \
	  python -m pytest tests/ -q -m 'not slow' \
	  --continue-on-collection-errors -p no:cacheprovider \
	  -p xdist -n 6 --dist loadfile --junitxml=$$xml -p no:randomly 2>&1 | tee $$log; \
	rc=$${PIPESTATUS[0]}; \
	echo DOTS_PASSED=$$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' $$xml | head -n 1 | awk '{n=$$1-$$2-$$3-$$4; print (n<0 ? 0 : n)}'); \
	echo WORKERS_DOWN=$$(grep -acE '\[gw[0-9]+\] node down' $$log); \
	rm -f $$log $$xml; exit $$rc

# Static project-invariant gate (docs/LINT.md): cross-file lock-order
# graph, blocking-under-lock, guarded-attr atomicity, pod-broadcast
# pairing, lock discipline on declared guarded state, host-sync
# transfers in the decode path, clock hygiene, condvar/thread hygiene,
# sharding-axis names. Companion to `verify` — run BOTH before shipping
# runtime/serving changes; lint is pure stdlib (no jax, no native
# build), so it's the cheap first gate. tests/test_dlint.py runs the
# same analysis inside tier-1, so `verify` fails on findings too; this
# target is the fast direct entry point. Under GitHub Actions the
# findings render as ::error workflow annotations on the PR diff.
LINT_FORMAT := $(if $(filter true,$(GITHUB_ACTIONS)),--format github,)
lint:
	python -m distributed_llama_multiusers_tpu.analysis $(LINT_FORMAT)

# One-command serving-path parity gate on the 8-virtual-device CPU mesh:
# scheduler decode / chunked prefill / speculative verify / multi-step /
# prefix cache / pipelined+fused churn (0 flushes) all stream-identical
# to the mesh-free engine, plus sharded + pipeline-parallel train steps.
# Prints one JSON line and writes nothing. Run it before shipping
# mesh/collective/serving-dispatch changes — it is the CPU stand-in for a real pod.
dryrun:
	python scripts/dryrun_multichip.py

# Chaos gate (docs/SERVING.md "Failure containment & chaos testing" +
# "Crash recovery & stream resumption"): the deterministic
# fault-injection suite — engine faults contained mid-churn with
# unaffected streams byte-identical, breaker closed→open→half-open→
# closed over /health+/stats, watchdog firing on a blackholed consume,
# fault-plan determinism, control-packet integrity, the HTTP
# bounded-wait 503 — plus the crash-durability suite: kill the
# scheduler mid-stream, recover from the journal, and every resumed
# stream is byte-identical (zero lost / zero duplicated tokens).
# Mock-engine based: runs in seconds, no accelerator. Run it before
# shipping scheduler/serving/control-plane changes; the same tests ride
# tier-1 via `verify`.
chaos:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_failures.py tests/test_journal.py -q

# Fleet gate (docs/SERVING.md "Fleet serving"): the multi-replica router
# suite — routing under load (least-loaded wins, breaker-open replicas
# excluded), prefix-affinity determinism with the consistent-hash 1/N
# movement bound, typed shed handling, and THE pin: a live SSE stream
# migrated off a dying replica is byte-identical with zero lost and zero
# duplicated tokens vs the uninterrupted run. Mock-engine based: runs in
# seconds, no accelerator. Run it before shipping fleet/, server/http.py
# admin-endpoint, or recovery changes; the same tests ride tier-1 via
# `verify`.
fleet:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_fleet.py -q

# Tracing gate (docs/OBSERVABILITY.md "Distributed tracing", ISSUE 20):
# the fleet-trace suite — wire-format mint/parse/accept, span-ring
# cursors and per-track drop accounting, clock-offset-corrected
# cross-replica merge, phase attribution end to end, and THE pin: a
# mid-stream migration yields ONE merged Perfetto timeline with every
# span carrying the client's trace id, the migration.gap slice bridging
# the splice, and summary.phases.migration_gap_ms reconciling with the
# router histogram. Mock-engine based: runs in seconds, no accelerator.
# Run it before shipping telemetry/, fleet/router.py, or summary-schema
# changes; the same tests ride tier-1 via `verify`.
tracecheck:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_tracectx.py -q

# The pre-ship bundle: the cheap static gate first, then the full
# tier-1 suite, then the tracing gate explicitly (it already rides
# `verify`; running it last gives a focused tail signal when the suite
# output is long). One command for "is this shippable".
check: lint verify tracecheck

# Reviewer aid for new lock/broadcast code (ROADMAP items 2-4): the
# statically computed lock-order DAG, DOT on stdout (waived edges
# dashed). Pipe into `dot -Tsvg` or read directly — new edges are what
# to eyeball in review.
lockgraph:
	python -m distributed_llama_multiusers_tpu.analysis --graph

# Reviewer aid for packet-layout changes (ROADMAP item 5 adds new ops +
# a shipped-KV-page replay surface): the wire-protocol op table
# extracted from parallel/multihost.py — op value, encoder, replay-arm
# line, fixed header widths — plus the diff vs the pinned
# analysis/protocol.lock. `make lint` FAILS when the layout changed at
# the same PROTOCOL_VERSION (docs/LINT.md "protocol-manifest"); after a
# legitimate bump, re-pin with
# `python -m distributed_llama_multiusers_tpu.analysis --update-protocol-manifest`.
protocol:
	python -m distributed_llama_multiusers_tpu.analysis --protocol-table

# Compile-stability gate (docs/LINT.md "The runtime recompile witness",
# ISSUE 15): prints the extracted device-program surface of
# runtime/engine.py — every compiled step family with its donation
# spec, dispatchers, and warmup coverage (the reviewer aid for new step
# families) — then runs the witness suite with DLLAMA_JITCHECK=1: a
# real serving churn must compile NOTHING after warmup, and a
# deliberately unwarmed family must make the witness FIRE. (The suite
# drives both strict and counter-only modes itself via jitcheck.force;
# its slow subprocess fixture exercises the DLLAMA_JITCHECK=1 env path
# end to end.) Run it before shipping engine/warmup/dispatch changes;
# the static checks (jit-stability / donation-discipline /
# warmup-coverage) ride `make lint`, and the serving pin rides tier-1
# via `verify`.
jitcheck:
	python -m distributed_llama_multiusers_tpu.analysis --jit-table
	env JAX_PLATFORMS=cpu python -m pytest tests/test_jitcheck.py -q

# Resource-lifecycle gate (docs/LINT.md "resource-balance" /
# "device-affinity" + "The runtime leak witness", ISSUE 17): prints the
# extracted lifecycle surface — every declared resource kind with its
# acquire/release vocabulary and transitive releaser closure, the
# device-affine methods, the batching-loop roots (the reviewer aid for
# new acquire/release pairs; `--graph resources` draws the same surface
# as DOT) — then runs the witness suite: a clean scheduler stop must
# hold NOTHING, and a deliberately leaked registry entry must make
# DLLAMA_LEAKCHECK=1 RAISE at the drain point. (The suite drives both
# strict and counter-only modes itself via leakcheck.force; its slow
# subprocess fixture reruns the serving+prefix suites under
# DLLAMA_LEAKCHECK=1 end to end.) Run it before shipping scheduler/
# pool/registry lifecycle changes; the static checks ride `make lint`.
leakcheck:
	python -m distributed_llama_multiusers_tpu.analysis --resource-table
	env JAX_PLATFORMS=cpu python -m pytest tests/test_leakcheck.py -q

# Kernel-numerics gate (PERF.md "Promotion to shipping", ISSUE 18): the
# interpret-mode parity pins for the shipping dequant path, standalone on
# jax CPU — no TPU needed. Two layers: the kernel-lab oracle check (every
# variant vs numpy dequant, single-chunk plane) and the pytest pins —
# the i8blockdot (d_in, d_out, m) parity grid, consumers of one input
# against their standalone calls per mode, the BLOCKDOT_MAX_M routing
# boundary, and the block geometry and the modes `--dequant` offers. Run it
# before shipping ops/pallas_q40.py changes; the same
# pytest pins ride tier-1 via `verify` (the >=256-token decode-stream
# token-identity pin is slow-marked — run it explicitly when touching
# kernel numerics: pytest tests/test_pallas_q40.py -m slow).
kernelcheck:
	env JAX_PLATFORMS=cpu python scripts/kernel_lab3.py --check
	env JAX_PLATFORMS=cpu python -m pytest tests/test_pallas_q40.py tests/test_q40_geometry.py -q -m 'not slow'

# Install the git pre-commit hook running the diff-proportional lint
# (`dlint --changed`, docs/LINT.md) so findings surface at commit time
# instead of in tier-1. Idempotent; refuses to clobber a foreign hook.
hooks:
	sh scripts/install_hooks.sh

# ASan+UBSan gate for the native codec (the reference's sanitizer-CI
# analogue, SURVEY.md §5.2): rebuilds the .so instrumented and reruns the
# native test suite against it. detect_leaks=0: CPython itself "leaks".
# The hard load assert matters: tests/test_native.py SKIPS when the library
# won't load, so without it a broken sanitized build would pass green.
# Path comes from the module (single source of truth, like the build line).
NATIVE_SAN_SO = $$(python -c "from distributed_llama_multiusers_tpu.native import _SO_SAN_PATH; print(_SO_SAN_PATH)")
sanitize:
	python -c "from distributed_llama_multiusers_tpu.native import ensure_built; import sys; sys.exit(0 if ensure_built(quiet=False, sanitize=True) else 1)"
	ASAN_OPTIONS=detect_leaks=0:detect_odr_violation=0 \
	LD_PRELOAD=$$(gcc -print-file-name=libasan.so) \
	DLLAMA_NATIVE_SO=$(NATIVE_SAN_SO) \
	sh -c 'python -c "from distributed_llama_multiusers_tpu.native import load; assert load() is not None, \"sanitized .so failed to load\"" && python -m pytest tests/test_native.py -q'

clean:
	rm -f $(NATIVE_SO) $(NATIVE_SAN_SO)
