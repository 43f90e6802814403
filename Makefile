# Dev targets (reference: Makefile:80-290 — manifests/generate/protogen/
# test tiers/installation-manifests).

PY ?= python

.PHONY: test test-int lint lint-fast metrics-lint trace-lint manifests api-docs protogen nbwatch spm bench bench-train bench-smoke bench-compare gateway-smoke fleet-smoke journey-smoke autoscale-smoke rollout-smoke gateway-bench adapter-bench disagg-bench overlap-bench spec-bench prefix-bench batchgen-bench chip-smoke chip-smoke-rehearse image install-manifests

test:
	$(PY) -m pytest tests/ -x -q

# Whole-repo static analysis (hack/sublint.py + substratus_tpu/analysis/):
# shard (PartitionSpec axes vs the parallel/mesh.py registry), hostsync
# (host-device syncs reachable from the engine decode loop / trainer
# step), concurrency (cross-thread writes, thread lifecycle, blocking in
# async), broad-except, lockorder (interprocedural lock cycles /
# blocking-while-locked), lifecycle (alloc-free, pin-unpin,
# shutdown-before-close), protodrift (wire-format producer/consumer key
# agreement + endianness) — plus the wrapped metrics/trace runtime
# lints. Exits nonzero on any unsuppressed finding; suppressions require
# reasons (docs/development.md#static-analysis-sublint). Diffs against
# the committed sublint.sarif baseline (stable fingerprints: only NEW
# findings fail; the suppression count ratchets against it) and then
# regenerates it as the CI artifact.
lint:
	$(PY) hack/sublint.py --baseline sublint.sarif --sarif sublint.sarif

# AST families only — no runtime deps, no subprocesses; fast enough for
# a pre-commit hook and runs on a box with nothing but python installed.
lint-fast:
	$(PY) hack/sublint.py --checks \
	  shard,hostsync,concurrency,broad-except,lockorder,lifecycle,protodrift

# Aliases into the unified driver: one check family each. `make
# trace-lint FILES=path.jsonl` still lints a real span export directly.
metrics-lint:
	$(PY) hack/sublint.py --checks metrics

trace-lint:
ifdef FILES
	$(PY) hack/trace_lint.py $(FILES)
else
	$(PY) hack/sublint.py --checks trace
endif

# Controller integration tier only (fake apiserver; reference
# `make test-integration`).
test-int:
	$(PY) -m pytest tests/test_controllers.py tests/test_sci.py -q

manifests:
	$(PY) -m substratus_tpu.api.crdgen > config/crd/substratus-crds.yaml

api-docs:
	$(PY) -m substratus_tpu.api.docgen > docs/api.md

protogen:
	protoc --python_out=substratus_tpu/sci --proto_path=substratus_tpu/sci \
	  substratus_tpu/sci/sci.proto

nbwatch:
	g++ -O2 -Wall -o native/nbwatch native/nbwatch.cc

# C++ SentencePiece encoder for the serving hot path (ctypes-loaded;
# pure-Python fallback when absent).
spm:
	g++ -O2 -Wall -shared -fPIC -o native/libspm_tokenizer.so native/spm_tokenizer.cc

bench:
	$(PY) bench.py

# The second BASELINE primary metric: 7B LoRA finetune step-time.
bench-train:
	$(PY) tools/bench_train.py

# CPU-scaled runs of both bench scripts plus the 2-process lockstep gang
# bench, each piped through the schema validator — proves every script
# emits one valid JSON line (platform "cpu": a shape check, not a speed).
bench-smoke:
	JAX_PLATFORMS=cpu $(PY) bench.py --config tiny --batch 4 --cache-len 128 \
	  --steps 8 --quantize int8 | $(PY) hack/bench_compare.py --validate -
	JAX_PLATFORMS=cpu $(PY) tools/bench_train.py --smoke \
	  | $(PY) hack/bench_compare.py --validate -
	JAX_PLATFORMS=cpu $(PY) tools/engine_bench.py --smoke --gang 2 \
	  --transport tcp --long-admission 8200 \
	  | $(PY) hack/bench_compare.py --validate -

# Gateway chaos smoke: 2 in-process CPU replicas behind the routing
# gateway, scripted kill mid-stream / hedge / recover-after-backoff
# (tools/gateway_smoke.py; the pytest chaos test drives the same
# harness). JSON verdict on stdout, nonzero exit on any stage failing.
gateway-smoke:
	JAX_PLATFORMS=cpu $(PY) tools/gateway_smoke.py

# Fleet telemetry smoke (ISSUE 11 acceptance): 2 in-process replicas
# behind the gateway — /debug/fleetz must show BOTH replicas with
# non-empty ring-buffer series + EWMA signals, a consistent fleet
# rollup, merged SLO percentiles from the /loadz poll path, and the
# substratus_fleet_* families on /metrics (tools/fleet_smoke.py).
fleet-smoke:
	JAX_PLATFORMS=cpu $(PY) tools/fleet_smoke.py

# Request-journey smoke (ISSUE 17 acceptance): gateway + 1 prefill + 1
# decode worker in-process, ONE chat request through the gateway — the
# response's x-trace-id must resolve on /debug/journeyz to a single
# stitched journey whose waterfall shows all four hops (gateway edge,
# prefill, KV handoff, decode) and `sub trace <id>` must render it
# (tools/journey_smoke.py). JSON verdict on stdout.
journey-smoke:
	JAX_PLATFORMS=cpu $(PY) tools/journey_smoke.py

# Closed-loop autoscaling smoke (ISSUE 12 acceptance): one in-process
# replica behind the gateway, the real decision core closing the loop
# — a load ramp scales the fleet up, sustained idleness drains one
# replica back out, and EVERY stream issued across both transitions
# must end [DONE] with no error event (tools/autoscale_smoke.py; the
# pytest chaos suite drives the same FleetSupervisor and adds the
# kill-one-replica self-healing leg).
autoscale-smoke:
	JAX_PLATFORMS=cpu $(PY) tools/autoscale_smoke.py

# Zero-downtime rollout smoke (ISSUE 20 acceptance): two in-process
# replicas behind the gateway, the real RolloutCoordinator rolling the
# fleet to "seed:1" and back to "seed:0" over /swapz + /loadz while
# SSE streams pump continuously — both replicas must converge on each
# rollout's weights_version and EVERY stream issued across both
# rollouts must end [DONE] with no error event
# (tools/rollout_smoke.py, controller/rollout.py).
rollout-smoke:
	JAX_PLATFORMS=cpu $(PY) tools/rollout_smoke.py

# Routed-2-replica vs direct throughput/TTFT capture (ISSUE 5
# acceptance: routed aggregate tok/s >= 1.7x single replica on the
# smoke shape). Spawns replica server subprocesses; heavier than
# gateway-smoke, so not part of the CI tests workflow.
gateway-bench:
	JAX_PLATFORMS=cpu $(PY) tools/engine_bench.py --smoke --gateway 2 \
	  --max-tokens 32 | $(PY) hack/bench_compare.py --validate -

# Multi-tenant adapter packing capture (ISSUE 6 acceptance): a mixed
# 4-adapter engine vs a base-only engine on the same shape with the
# simulated device step — packed aggregate tok/s must stay within 15%
# of base (tests/test_adapters.py asserts the ratio; this target
# validates the capture schema).
adapter-bench:
	JAX_PLATFORMS=cpu $(PY) tools/engine_bench.py --smoke --adapters 4 \
	  | $(PY) hack/bench_compare.py --validate -

# Disaggregated prefill/decode capture (ISSUE 7 acceptance): a
# 1-prefill + 1-decode pair over the real TCP KV handoff vs 2
# monolithic engines on the same shape under a prompt-burst workload
# with the simulated device step — burst-window p99 inter-token
# latency must drop >=30% with aggregate tok/s within 10%
# (docs/serving.md "Disaggregated prefill/decode").
disagg-bench:
	JAX_PLATFORMS=cpu $(PY) tools/engine_bench.py --smoke --disagg \
	  | $(PY) hack/bench_compare.py --validate -

# Overlapped decode scheduler capture (ISSUE 10 acceptance): one-step-
# ahead dispatch with on-device token feedback vs the synchronous
# scheduler on the same shape, simulated device step + real per-token
# detokenize host work in the emit path — steady-state inter-token
# mean must hold <= 1.15x the device floor with aggregate tok/s within
# 5% or better, greedy outputs token-exact (tests/test_overlap.py
# asserts; docs/performance.md "Overlapped scheduling"). The capture
# also embeds hard gates bench_compare --validate evaluates (ISSUE 11):
# bubble ratio <= 0.15, bubble attribution coverage >= 0.9, tok/s vs
# sync >= 0.95 — a host-path regression fails here WITH a cause
# (docs/performance.md "Pipeline-bubble attribution").
overlap-bench:
	JAX_PLATFORMS=cpu $(PY) tools/engine_bench.py --smoke --overlap \
	  | $(PY) hack/bench_compare.py --validate -

# Speculation x overlap composition capture (ISSUE 14 acceptance):
# plain / spec-only / overlap-only / spec+overlap engines on the same
# repetitive-prompt shape, simulated device step + the overlap leg's
# per-token host work — the composed engine's aggregate tok/s must
# beat BOTH single-lever legs (the pipelined spec rounds amortize the
# floor across accepted drafts while the one-step-ahead dispatch hides
# the proposal scan + emit work), greedy outputs token-exact across
# all four engines, and pipeline_flushes_total{reason="spec"} must not
# move (docs/performance.md "Speculative decoding";
# tests/test_spec_overlap.py asserts the same invariants in-process).
spec-bench:
	JAX_PLATFORMS=cpu $(PY) tools/engine_bench.py --smoke --spec-overlap \
	  | $(PY) hack/bench_compare.py --validate -

# Shared-prefix KV reuse capture (ROADMAP item 1 evidence): repeated
# system-prompt workload, prefix registry on vs off — TTFT and
# aggregate tok/s.
prefix-bench:
	JAX_PLATFORMS=cpu $(PY) tools/engine_bench.py --smoke --prefix-reuse \
	  | $(PY) hack/bench_compare.py --validate -

# Batch-generation actor gang capture (ISSUE 9 acceptance): a 2-actor
# gang draining one shared prompt manifest through the continuous-
# refill driver vs one identical actor, simulated device step — gang
# aggregate tok/s must reach >=1.8x single AND steady-state decode
# slot occupancy >=0.9 (tests/test_batchgen.py asserts both; this
# target validates the capture schema — docs/batch-generation.md).
batchgen-bench:
	JAX_PLATFORMS=cpu $(PY) tools/engine_bench.py --smoke --batchgen 2 \
	  | $(PY) hack/bench_compare.py --validate -

# Bench JSON schema + >10% regression gate (hack/bench_compare.py):
# self-tests that a synthetic 20% regression fails and that any
# BENCH_*.json history beside it loads.
bench-compare:
	$(PY) hack/bench_compare.py --self-test

# Does the system still start on the chip? Serve and finetune
# TinyLlama-1.1B through the normal entry points and run every Pallas
# kernel on the attached TPU (one chip; fails where JAX finds none).
# `chip-smoke-rehearse` is the same control flow on the CPU at tiny size.
chip-smoke:
	$(PY) chip_smoke.py

chip-smoke-rehearse:
	$(PY) chip_smoke.py --rehearse

image:
	docker build -t ghcr.io/substratus-tpu/runtime:latest .

# Single-file install manifest (reference `make installation-manifests`).
# Explicit --- separators: bare concatenation merges adjacent YAML docs.
install-manifests: manifests
	{ cat config/crd/substratus-crds.yaml; echo '---'; \
	  cat config/manager/manager.yaml; echo '---'; \
	  cat config/sci/deployment.yaml; } > install/substratus-tpu.yaml
