# Dev targets (reference: Makefile:80-290 — manifests/generate/protogen/
# test tiers/installation-manifests).

PY ?= python

.PHONY: test test-int lint lint-fast metrics-lint trace-lint manifests api-docs protogen nbwatch spm gateway-smoke fleet-smoke journey-smoke autoscale-smoke rollout-smoke chip-smoke chip-smoke-rehearse image install-manifests

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
