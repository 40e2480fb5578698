# Dev workflow (reference analog: Makefile targets test-integration etc.)

# CPU test env: 8 virtual devices.
TEST_ENV = JAX_PLATFORMS=cpu \
	XLA_FLAGS="--xla_force_host_platform_device_count=8"

# Default gate = the fast path: everything except @pytest.mark.slow
# (redundant-coverage heavyweights — full-parity sweeps, checkpoint
# roundtrips, multi-process rendezvous). The slow set runs in test-all
# (nightly CI + before releases). Rationale: the full suite costs >20 min
# serially on a small box, and a slow gate is where skipped-gate
# temptation breeds (round 3 shipped red for exactly this reason).
.PHONY: test
test:
	$(TEST_ENV) python -m pytest tests/ -x -q -m "not slow"

.PHONY: test-all
test-all:
	$(TEST_ENV) python -m pytest tests/ -x -q

# Back-compat alias.
.PHONY: test-fast
test-fast: test

.PHONY: bench
bench:
	python bench.py

# Static program & concurrency audit (docs/static-analysis.md): AST lint
# for the recurring concurrency/precision defect classes + abstract
# jaxpr contracts over the registered hot programs. Strict = also fail
# on stale baseline suppressions, any XLA backend compile during the
# audit (it must be pure abstract tracing), and a >30 s wall time.
.PHONY: check
check:
	$(TEST_ENV) python -m runbooks_tpu.cli.main check --strict --budget-s 30

# Regenerate CRD manifests (reference analog: `make manifests`).
.PHONY: manifests
manifests:
	python -m runbooks_tpu.api.crds config/crd

# Regenerate protobuf message classes (reference analog: `make protogen`).
.PHONY: protogen
protogen:
	cd runbooks_tpu/sci && protoc --python_out=. sci.proto

.PHONY: nbwatch
nbwatch:
	$(MAKE) -C native/nbwatch

# In-process system test (reference analog: `make test-system-kind`).
.PHONY: test-system
test-system:
	$(TEST_ENV) python test/system.py

# Real-kind smoke (reference analog: test/system.sh against an actual
# cluster): builds + loads images, installs the operator, applies the
# opt-125m example, curls a served completion. Skips where docker/kind
# are unavailable; see the kind-smoke CI job.
.PHONY: test-system-kind
test-system-kind:
	bash test/system_kind.sh

# --- Dev loop (reference analog: skaffold.{gcp,kind}.yaml + the Makefile
# dev-run hybrid mode: controller runs LOCALLY against the cluster in the
# current kubeconfig context, so reconciler changes need no image build).

.PHONY: skaffold-local skaffold-gcp
skaffold-local:
	skaffold dev -f skaffold.local.yaml
skaffold-gcp:
	skaffold dev -f skaffold.gcp.yaml

.PHONY: dev-run-local
dev-run-local: export CLOUD=local
dev-run-local: export SCI_ADDRESS=localhost:10080
dev-run-local: export CLUSTER_NAME=local
dev-run-local: export ARTIFACT_BUCKET_URL=file:///tmp/runbooks-tpu-bucket
dev-run-local: export REGISTRY_URL=localhost:5000
dev-run-local:
	kubectl scale -n runbooks-tpu deploy/controller-manager --replicas 0 || true
	python -m runbooks_tpu.controller.main

.PHONY: dev-run-gcp
dev-run-gcp: export CLOUD=gcp
dev-run-gcp: export PROJECT_ID=$(shell gcloud config get-value project)
dev-run-gcp: export CLUSTER_NAME=runbooks-tpu
dev-run-gcp: export PRINCIPAL=runbooks-tpu@$(PROJECT_ID).iam.gserviceaccount.com
dev-run-gcp: export SCI_ADDRESS=localhost:10080
dev-run-gcp:
	kubectl scale -n runbooks-tpu deploy/controller-manager --replicas 0 || true
	# One shell: tunnel + controller, tunnel torn down when the controller
	# exits; wait for the tunnel to listen before starting.
	bash -c 'kubectl port-forward -n runbooks-tpu svc/sci 10080:10080 & \
	  pf=$$!; trap "kill $$pf 2>/dev/null" EXIT; \
	  for i in $$(seq 20); do \
	    (exec 3<>/dev/tcp/127.0.0.1/10080) 2>/dev/null && break; sleep 0.5; \
	  done; \
	  python -m runbooks_tpu.controller.main'
