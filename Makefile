# kepler-tpu build/test/deploy targets (analog of the reference Makefile).

SHELL := /bin/bash
PYTHON ?= python
IMG ?= kepler-tpu
TAG ?= latest
CLUSTER_NAME ?= kepler-tpu-dev

VERSION := $(shell $(PYTHON) -c "from kepler_tpu.version import __version__; print(__version__)" 2>/dev/null || echo unknown)
GIT_COMMIT := $(shell git rev-parse --short HEAD 2>/dev/null || echo unknown)
GIT_BRANCH := $(shell git rev-parse --abbrev-ref HEAD 2>/dev/null || echo unknown)

.PHONY: all
all: test

# -- test ---------------------------------------------------------------------
# Tests run on a virtual 8-device CPU mesh (tests/conftest.py) so multi-chip
# sharding is exercised without TPU hardware — the analog of the reference's
# `go test -race` everywhere (Makefile:131).
.PHONY: test
test:
	$(PYTHON) -m pytest tests/ -q

.PHONY: test-verbose
test-verbose:
	$(PYTHON) -m pytest tests/ -v

.PHONY: chaos
chaos: ## fault-injection resilience subset (chaos marker) + randomized kepchaos sweep (25 schedules, shrinks on red) + diurnal scale soak
	$(PYTHON) -m pytest tests/ -q -m chaos
	$(PYTHON) -m kepler_tpu.chaos --seed 1 --schedules 25
	$(PYTHON) -m benchmarks.soak --agents 40 --seconds 36 --interval 3 \
		--workloads 20 --diurnal

.PHONY: chaos-long
chaos-long: ## extended kepchaos sweep: 100 randomized schedules from seed 1
	$(PYTHON) -m kepler_tpu.chaos --seed 1 --schedules 100

.PHONY: verify
verify: lint chaos multihost ## the lint surface plus the chaos subset and the multi-host dryrun — the PR gate's sibling path

.PHONY: chip-smoke
chip-smoke: ## ON A TPU HOST: the aggregator's window path end to end, checked against NumPy; fails without a chip (tier-1 covers its logic on a CPU child)
	$(PYTHON) chip_smoke.py

.PHONY: bench
bench: ## north-star benchmark; one process, fails when JAX finds no accelerator unless JAX_PLATFORMS=cpu is set on purpose
	$(PYTHON) bench.py

.PHONY: bench-scenarios
bench-scenarios: ## five BASELINE.json scenarios + temporal-fleet; budget GATE (exits nonzero on regression)
	$(PYTHON) benchmarks/scenarios.py

.PHONY: dryrun
dryrun: ## compile-check driver entry points on a virtual 8-device mesh (CPU-pinned child; the parent stays off jax)
	$(PYTHON) __graft_entry__.py

.PHONY: multichip
multichip: ## node-sharded fleet window dryrun on 8 simulated devices (bit-equal vs single-device)
	$(PYTHON) -c "from __graft_entry__ import dryrun_fleet_sharded; dryrun_fleet_sharded(8)"

.PHONY: multihost
multihost: ## multi-host fleet window dryrun: virtual 2-host leg (bit-equal, capacity, host-death) + real 2-process leg (skips without the Gloo CPU backend)
	$(PYTHON) -c "from __graft_entry__ import dryrun_fleet_multihost; dryrun_fleet_multihost(2)"

.PHONY: introspect
introspect: ## smoke the introspection plane: /debug/window + /debug/fleet on a local aggregator
	$(PYTHON) hack/introspect_smoke.py

.PHONY: blackbox
blackbox: ## 2-replica kill+rejoin; assert the merged black-box timeline names the succession and is bit-deterministic
	$(PYTHON) hack/blackbox_smoke.py

# -- native -------------------------------------------------------------------
.PHONY: native
native: ## build the C++ batched procfs/sysfs scanner (ctypes, no pybind11)
	$(PYTHON) -c "from kepler_tpu.native import ensure_built; print(ensure_built(force=True))"

.PHONY: native-tsan
native-tsan: ## ThreadSanitizer pass over the native scanner (the -race analog)
	g++ -O1 -g -fsanitize=thread -std=c++17 -pthread -Wall -Wextra \
		kepler_tpu/native/src/scan.cpp \
		kepler_tpu/native/src/scan_tsan_test.cpp \
		-o /tmp/kepler_scan_tsan
	/tmp/kepler_scan_tsan

.PHONY: native-asan
native-asan: ## AddressSanitizer pass over the native scanner/renderer
	g++ -O1 -g -fsanitize=address -std=c++17 -pthread -Wall -Wextra \
		kepler_tpu/native/src/scan.cpp \
		kepler_tpu/native/src/scan_tsan_test.cpp \
		-o /tmp/kepler_scan_asan
	/tmp/kepler_scan_asan

# -- lint ---------------------------------------------------------------------
# keplint (stdlib-only, always runs) + ruff + mypy (committed configs in
# pyproject.toml; both skip with a notice when not installed so the lint
# surface degrades predictably instead of failing on toolchain absence).
# See docs/developer/static-analysis.md.
.PHONY: lint
lint:
	$(PYTHON) -m compileall -q kepler_tpu tests hack benchmarks
	$(PYTHON) -m kepler_tpu.analysis --device-tier --protocol-tier kepler_tpu hack benchmarks
	$(PYTHON) hack/gen_lint_docs.py --check
	$(PYTHON) hack/gen_fault_docs.py --check
	$(PYTHON) hack/gen_journal_docs.py --check
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check kepler_tpu tests hack; \
	else \
		echo "ruff not installed; skipping ruff"; \
	fi
	@if $(PYTHON) -c "import mypy" >/dev/null 2>&1; then \
		$(PYTHON) -m mypy kepler_tpu; \
	else \
		echo "mypy not installed; skipping typing ratchet"; \
	fi

.PHONY: keplint
keplint: ## project-native AST invariant checks only (host tiers; no device traces)
	$(PYTHON) -m kepler_tpu.analysis kepler_tpu hack benchmarks

.PHONY: kepljax
kepljax: ## device tier alone: trace registered programs, run KTL120-123
	$(PYTHON) -m kepler_tpu.analysis --device-tier --only=KTL120,KTL121,KTL122,KTL123 kepler_tpu

.PHONY: kepljax-snapshots
kepljax-snapshots: ## regenerate the KTL123 golden program fingerprints (.kepljax.json)
	$(PYTHON) -m kepler_tpu.analysis --update-snapshots

.PHONY: protocheck
protocheck: ## kepmc protocol tier alone: exhaustively explore the registered protocol models, run KTL130-132
	$(PYTHON) -m kepler_tpu.analysis --protocol-tier --only=KTL130,KTL131,KTL132 kepler_tpu

.PHONY: keplint-sarif
keplint-sarif: ## keplint + device/protocol-tier findings as SARIF 2.1.0 (CI annotation feed; stdout is pipeable JSON)
	@$(PYTHON) -m kepler_tpu.analysis --device-tier --protocol-tier --format=sarif kepler_tpu hack benchmarks

.PHONY: keplint-baseline
keplint-baseline: ## refreeze the keplint baseline (after fixing findings)
	$(PYTHON) -m kepler_tpu.analysis --write-baseline

.PHONY: gen-lint-docs
gen-lint-docs: ## regenerate docs/developer/static-analysis.md from the registry
	$(PYTHON) hack/gen_lint_docs.py

.PHONY: gen-fault-docs
gen-fault-docs: ## regenerate the resilience.md fault-site table from fault.SITE_CATALOG
	$(PYTHON) hack/gen_fault_docs.py

.PHONY: gen-journal-docs
gen-journal-docs: ## regenerate the observability.md journal-kind table from journal.KIND_CATALOG
	$(PYTHON) hack/gen_journal_docs.py

# -- docs ---------------------------------------------------------------------
.PHONY: gen-metric-docs
gen-metric-docs: ## regenerate docs/user/metrics.md from the live collectors
	$(PYTHON) hack/gen_metric_docs.py

.PHONY: gen-config-docs
gen-config-docs: ## regenerate docs/user/configuration.md from the Config schema
	$(PYTHON) hack/gen_config_docs.py

.PHONY: check-metric-docs
check-metric-docs:
	$(PYTHON) hack/gen_metric_docs.py --check
	$(PYTHON) hack/gen_config_docs.py --check
	$(PYTHON) hack/gen_lint_docs.py --check
	$(PYTHON) hack/gen_fault_docs.py --check
	$(PYTHON) hack/gen_journal_docs.py --check

# -- run ----------------------------------------------------------------------
.PHONY: run
run: ## run the node agent against the real host (needs RAPL access)
	$(PYTHON) -m kepler_tpu.cmd.main

.PHONY: run-fake
run-fake: ## run with the fake meter + stdout exporter (no hardware needed)
	$(PYTHON) -m kepler_tpu.cmd.main \
		--config.file=compose/dev/kepler/etc/kepler/config.yaml \
		--exporter.stdout --no-kube.enable --aggregator.endpoint=

.PHONY: run-aggregator
run-aggregator: ## run the TPU fleet aggregator
	$(PYTHON) -m kepler_tpu.cmd.aggregator --aggregator.enable

# -- image / deploy -----------------------------------------------------------
.PHONY: image
image:
	docker build -t $(IMG):$(TAG) .

.PHONY: compose-up
compose-up: ## dev stack: agent + aggregator + prometheus + grafana
	cd compose/dev && docker compose up --build -d

.PHONY: compose-down
compose-down:
	cd compose/dev && docker compose down -v

.PHONY: monitoring-up
monitoring-up: ## standalone prometheus+grafana overlay (compose/monitoring)
	cd compose/monitoring && docker compose up -d

.PHONY: monitoring-down
monitoring-down:
	cd compose/monitoring && docker compose down -v

.PHONY: cluster-e2e
cluster-e2e: ## scrape assertions against the deployed kind cluster
	hack/cluster.sh e2e

.PHONY: cluster-up
cluster-up: ## kind dev cluster (hack/cluster.sh)
	CLUSTER_NAME=$(CLUSTER_NAME) hack/cluster.sh up

.PHONY: cluster-down
cluster-down:
	CLUSTER_NAME=$(CLUSTER_NAME) hack/cluster.sh down

.PHONY: deploy
deploy: ## build image, load into kind, apply manifests
	CLUSTER_NAME=$(CLUSTER_NAME) IMG=$(IMG) TAG=$(TAG) hack/cluster.sh deploy

.PHONY: undeploy
undeploy:
	kubectl delete -k manifests/k8s || true

.PHONY: version
version:
	@echo "version=$(VERSION) commit=$(GIT_COMMIT) branch=$(GIT_BRANCH)"

.PHONY: help
help:
	@grep -E '^[a-zA-Z_-]+:.*?## .*$$' $(MAKEFILE_LIST) | \
		awk 'BEGIN {FS = ":.*?## "}; {printf "  \033[36m%-18s\033[0m %s\n", $$1, $$2}'
