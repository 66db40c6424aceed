# Build/test/conformance pipeline — target parity with the reference
# Makefile (build, codegen-drift, lint, unit/integration tests, kind
# cluster, CRS download + ConfigMap generation, ftw pipeline, helm sync).

PYTHON ?= python
KIND_CLUSTER_NAME ?= coraza-tpu
CORERULESET_VERSION ?= v4.23.0
CORERULESET_URL ?= https://github.com/coreruleset/coreruleset/archive/refs/tags/$(CORERULESET_VERSION).tar.gz
BUILD_DIR ?= build
IMG ?= ghcr.io/coraza-tpu/coraza-kubernetes-operator-tpu:latest

.PHONY: all
all: test

# -- build --------------------------------------------------------------------

.PHONY: build
build:  ## Byte-compile the package (no native build step required).
	$(PYTHON) -m compileall -q coraza_kubernetes_operator_tpu

.PHONY: docker.build
docker.build:
	docker build -t $(IMG) .

# -- tests --------------------------------------------------------------------

.PHONY: test test.unit
test test.unit:  ## Fast tier: unit + kernel + controller tests on the virtual CPU mesh.
	$(PYTHON) -m pytest tests/ -x -q

.PHONY: test.slow
test.slow:  ## Nightly tier: full mesh-shape matrix and large-shape kernel cases.
	$(PYTHON) -m pytest tests/ -x -q -m slow

.PHONY: test.all
test.all:  ## Both tiers in one run.
	$(PYTHON) -m pytest tests/ -x -q -m ""

.PHONY: test.integration
test.integration:  ## In-process integration scenarios (cache+sidecar+controllers).
	$(PYTHON) -m pytest tests/test_engine_e2e.py tests/test_sidecar.py tests/test_ftw.py -q

.PHONY: ftw.crs-lite
ftw.crs-lite:  ## Conformance: crs-lite corpus (CRS v4-structured) in-process.
	$(PYTHON) -c "from coraza_kubernetes_operator_tpu.ftw.corpus import load_ruleset_text; \
	from coraza_kubernetes_operator_tpu.ftw.runner import run_corpus; import json, sys; \
	r = run_corpus('ftw/tests-crs-lite', load_ruleset_text()); \
	print(json.dumps(r.summary())); sys.exit(0 if r.ok else 1)"

.PHONY: pipeline.smoke
pipeline.smoke:  ## Host/device overlap gate: pipelined >= 1.2x sync, verdicts identical.
	$(PYTHON) hack/pipeline_smoke.py

.PHONY: ingest.smoke
ingest.smoke:  ## Async frontend gate: async >= 2x threaded req/s, verdicts identical.
	$(PYTHON) hack/ingest_smoke.py

.PHONY: ingest.fuzz
ingest.fuzz:  ## Seeded protocol fuzz: identical error taxonomy on both frontends, zero leaks.
	$(PYTHON) hack/ingest_fuzz.py

.PHONY: native.parity
native.parity:  ## Native tiered-pipeline gate: fuzz + ftw corpora, bit-identical tensors and verdicts vs the Python fallback.
	$(MAKE) native
	$(PYTHON) hack/native_parity_smoke.py

.PHONY: sched.smoke
sched.smoke:  ## Adaptive scheduler gate: adaptive p99 <= best static delay, verdicts identical.
	$(PYTHON) hack/sched_smoke.py

.PHONY: cache.smoke
cache.smoke:  ## Verdict cache gate: cache-on >= 2x uncached req/s on Zipfian traffic, verdicts identical.
	$(PYTHON) hack/verdict_cache_smoke.py

.PHONY: chaos.smoke
chaos.smoke:  ## Sidecar under the fault matrix: stall, divergence, device storm, outage, ingress storm, crash-restart, device loss, poison storm.
	$(PYTHON) hack/chaos_smoke.py

.PHONY: restart.smoke
restart.smoke:  ## Crash-safe warm restart across a real process boundary: SIGKILL, restore under cache outage, bit-identical verdicts.
	$(PYTHON) hack/restart_smoke.py

.PHONY: compile.smoke
compile.smoke:  ## Cold-compile ceiling gate: crs-lite wall + minimized-state + signature caps.
	$(PYTHON) hack/compile_time_smoke.py

.PHONY: trace.smoke
trace.smoke:  ## Flight-recorder gate: sampling off vs on within 5% req/s, complete span chains per serving path.
	$(PYTHON) hack/trace_smoke.py

.PHONY: extproc.smoke
extproc.smoke:  ## Envoy e2e gate: ftw corpus through a real Envoy -> ext_proc, verdicts bit-identical to the HTTP frontend. Loud skip when no Envoy binary.
	$(PYTHON) hack/extproc_smoke.py

.PHONY: automata.smoke
automata.smoke:  ## Two-level automata gate: ftw+crs-lite replay on vs off, byte-identical verdicts, dfa-hot + prefiltered tiers exercised in the flat bins alone, the bins' Pallas interpret parity on CPU.
	$(PYTHON) hack/automata_smoke.py

.PHONY: metrics.lint
metrics.lint:  ## Metric catalog drift: every registered cko_*/waf_* metric documented, no dead doc entries.
	$(PYTHON) hack/metrics_lint.py

.PHONY: presubmit
presubmit:  ## Gate before any end-of-round snapshot: warm-cache freshness FIRST (pytest writes entries and would mask staleness), then the fast tier.
	$(PYTHON) hack/check_cache_fresh.py tests/.jax_cache --hint 'run make test over the FINAL code and commit tests/.jax_cache'
	$(PYTHON) -m pytest tests/ -x -q

.PHONY: lint
lint:
	$(PYTHON) -m compileall -q coraza_kubernetes_operator_tpu tests ftw hack tools
	@if $(PYTHON) -m ruff --version >/dev/null 2>&1; then \
		$(PYTHON) -m ruff check coraza_kubernetes_operator_tpu tests ftw hack tools; \
	else echo "ruff not installed; syntax check only (CI runs the full ruff gate)"; fi

.PHONY: typecheck
typecheck:  ## mypy gate over seclang/compiler/engine/analysis (config: pyproject.toml).
	@if $(PYTHON) -m mypy --version >/dev/null 2>&1; then \
		$(PYTHON) -m mypy; \
	else echo "mypy not installed (pip install 'mypy==1.11.*'); CI runs the typecheck gate"; fi

# The static-analysis gate (docs/ANALYSIS.md): rulelint over the bundled
# corpora (zero error-severity findings required) + jaxlint over our own
# package (any finding fails) + nativelint over the ctypes/C++ boundary.
# Same entrypoint the `analysis` CI job runs.
.PHONY: analyze
analyze:  ## Ruleset static analysis + JAX hot-path self-lint + native ABI lint.
	$(PYTHON) -m coraza_kubernetes_operator_tpu.cmd.analyze \
		ftw/rules ftw/rules/crs-lite --jaxlint --native

.PHONY: analyze.json
analyze.json:  ## Same gate, machine-readable (CI uploads this as an artifact).
	@$(PYTHON) -m coraza_kubernetes_operator_tpu.cmd.analyze \
		ftw/rules ftw/rules/crs-lite --jaxlint --native --json

# -- conformance (ftw) --------------------------------------------------------

.PHONY: ftw
ftw:  ## Replay the bundled go-ftw corpus in-process, honoring ftw/ftw.yml.
	$(PYTHON) ftw/run.py

.PHONY: ftw.coreruleset
ftw.coreruleset: coreruleset.download  ## CRS -> ConfigMaps + RuleSet manifests.
	$(PYTHON) hack/generate_coreruleset_configmaps.py \
		--crs-dir $(BUILD_DIR)/coreruleset --out-dir $(BUILD_DIR)/crs-manifests \
		--include-test-rule --ignore-pmFromFile

.PHONY: coreruleset.download
coreruleset.download:
	mkdir -p $(BUILD_DIR)
	test -d $(BUILD_DIR)/coreruleset || ( \
		curl -sSL $(CORERULESET_URL) -o $(BUILD_DIR)/crs.tar.gz && \
		mkdir -p $(BUILD_DIR)/coreruleset && \
		tar -xzf $(BUILD_DIR)/crs.tar.gz -C $(BUILD_DIR)/coreruleset --strip-components=1 )

# -- cluster ------------------------------------------------------------------

.PHONY: cluster.kind
cluster.kind:  ## kind + Gateway API CRDs + operator (hack/kind_cluster.py).
	$(PYTHON) hack/kind_cluster.py setup --name $(KIND_CLUSTER_NAME)

.PHONY: cluster.kind.delete
cluster.kind.delete:
	$(PYTHON) hack/kind_cluster.py delete --name $(KIND_CLUSTER_NAME)

.PHONY: deploy
deploy:  ## Apply CRDs + RBAC + manager via kustomize.
	kubectl apply --server-side -k config/default

.PHONY: undeploy
undeploy:
	kubectl delete -k config/default --ignore-not-found

# -- helm ---------------------------------------------------------------------

.PHONY: helm.sync-crds
helm.sync-crds:  ## Copy generated CRDs into the chart (reference Makefile:263-265).
	cp config/crd/bases/*.yaml charts/coraza-kubernetes-operator-tpu/crds/

.PHONY: helm.lint
helm.lint:
	helm lint charts/coraza-kubernetes-operator-tpu

# -- native -------------------------------------------------------------------

.PHONY: native
native:  ## Build the C++ host runtime (request tensorizer).
	$(MAKE) -C native

.PHONY: native.sanitize
native.sanitize:  ## ASan/UBSan gate: parity corpus + seeded blob-bounds fuzz under sanitizers, bit-identical digests vs the regular build.
	$(MAKE) -C native all asan
	$(PYTHON) hack/native_sanitize_smoke.py

.PHONY: help
help:
	@grep -E '^[a-zA-Z_.-]+:.*##' $(MAKEFILE_LIST) | sed 's/:.*##/\t/'
