"""Model reconciler: builds the modeller Job (train/import), TPU-aware.

Reference behavior mirrored (reference: internal/controller/
model_controller.go): gate on image built (:54-57), params ConfigMap,
status.artifacts.url (:77), modeller SA (:83-90), base-model/dataset
readiness gates with conditions (:92-172), modeller Job with artifact RW +
dataset RO /content/data + base model RO /content/model mounts (:286-395),
backoff policy that retries only cheap import jobs (:294-303). TPU-first
additions: resources.tpu -> google.com/tpu + topology selectors, and
multi-host pod-slice fan-out with jax.distributed env (SURVEY.md §7 M4 —
the reference is single-pod only).
"""

from __future__ import annotations

from runbooks_tpu.api import conditions as cond
from runbooks_tpu.api.types import Model
from runbooks_tpu.cloud.base import BucketMount
from runbooks_tpu.cloud.resources import (
    apply_cpu_resources,
    apply_tpu_resources,
    fan_out_job,
    parse_tpu,
)
from runbooks_tpu.controller.common import (
    SA_MODELLER,
    job_status,
    mount_params,
    reconcile_params_configmap,
    reconcile_service_account,
    resolve_env,
    resolve_preemption_restarts,
    validate_params,
)
from runbooks_tpu.controller.manager import Ctx, Result
from runbooks_tpu.k8s import objects as ko


RESTARTS_ANNOTATION = "runbooks-tpu.dev/slice-restarts"

# Trainer metrics exposition port (fleet scraper target; see
# controller/fleet.py and train/trainer.py main()).
METRICS_PORT = 8080


class ModelReconciler:
    kind = "Model"

    def reconcile(self, ctx: Ctx, raw: dict) -> Result:
        model = Model(raw)

        # Image gate: either preset or produced by the build reconciler.
        if not model.image:
            return Result(requeue_after=1.0)

        err = validate_params(model.params)
        if err is not None:
            # Invalid spec.params (e.g. quantize: int3, source: hf, or an
            # accumulateSteps that is not a power of two / does not divide
            # batch_size): a visible condition beats a crash-looping
            # loader/trainer Job. Terminal until the spec changes — no
            # requeue.
            model.set_condition(cond.COMPLETE, False,
                                cond.REASON_INVALID_PARAMS, err)
            model.commit_status(ctx.client)
            return Result()

        reconcile_params_configmap(ctx.client, model)

        if model.artifacts_url != ctx.cloud.object_artifact_url(model):
            model.set_artifacts_url(ctx.cloud.object_artifact_url(model))
            model.commit_status(ctx.client)

        reconcile_service_account(ctx.client, ctx.cloud, ctx.sci,
                                  SA_MODELLER, model.namespace)

        # Live training telemetry (step/loss/goodput) from the fleet
        # scraper — `rbt get`/`kubectl get` show progress, not just
        # readiness. Status-only; written when the aggregate changed.
        from runbooks_tpu.controller.fleet import FLEET

        telemetry = FLEET.model_summary(model.namespace, model.name)
        if telemetry is not None \
                and model.status.get("telemetry") != telemetry:
            model.status["telemetry"] = telemetry
            model.commit_status(ctx.client)

        # Dependency gates.
        from runbooks_tpu.controller.common import gate_dependency

        base = dataset = None
        if model.base_model_ref:
            base, ok = gate_dependency(
                ctx, model, "Model", model.base_model_ref,
                cond.REASON_BASEMODEL_NOT_FOUND,
                cond.REASON_BASEMODEL_NOT_READY)
            if not ok:
                return Result(requeue_after=2.0)
        if model.dataset_ref:
            dataset, ok = gate_dependency(
                ctx, model, "Dataset", model.dataset_ref,
                cond.REASON_DATASET_NOT_FOUND, cond.REASON_DATASET_NOT_READY)
            if not ok:
                return Result(requeue_after=2.0)

        job_name = f"{model.name}-modeller"
        num_slices = int((model.tpu or {}).get("slices", 1))
        job_names = ([f"{job_name}-slice-{i}" for i in range(num_slices)]
                     if num_slices > 1 else [job_name])
        existing_jobs = [ctx.client.get("batch/v1", "Job", model.namespace, n)
                         for n in job_names]
        if any(j is None for j in existing_jobs):
            for obj in self._modeller_objects(ctx, model, base, dataset,
                                              job_name, num_slices):
                kind = obj["kind"]
                av = obj["apiVersion"]
                if ctx.client.get(av, kind, model.namespace,
                                  ko.name(obj)) is None:
                    ko.set_owner(obj, model.obj)
                    ctx.client.create(obj)
            model.set_condition(cond.COMPLETE, False, cond.REASON_JOB_RUNNING)
            model.commit_status(ctx.client)
            return Result(requeue_after=2.0)

        statuses = [job_status(j) for j in existing_jobs]
        complete = all(c for c, _ in statuses)
        failed = any(f for _, f in statuses)
        if failed:
            # Slice-restart-with-resume (SURVEY §7 hard part #1): a TPU
            # slice Job fails whole once its in-place budget is spent (the
            # podFailurePolicy fails application errors immediately and
            # preemption-shaped exits after backoffLimit retries). Instead
            # of treating that as terminal like the reference does,
            # recreate the Job — the trainer resumes step-exactly from the
            # last intact orbax checkpoint in the artifact bucket — up to
            # resources.tpu.maxRestarts (default 3) attempts.
            if any(ko.deep_get(j, "metadata", "deletionTimestamp")
                   for j in existing_jobs if j is not None):
                # Restart already in flight: Job deletion is asynchronous
                # (finalizers, pod GC). Don't count another attempt while
                # the old Job is still terminating.
                return Result(requeue_after=1.0)
            limit = int((model.tpu or {}).get("maxRestarts", 3)) \
                if model.tpu else 0
            restarts = int(ko.annotations(model.obj).get(
                RESTARTS_ANNOTATION, "0"))
            if restarts < limit:
                from runbooks_tpu.controller.metrics import REGISTRY
                from runbooks_tpu.obs.trace import instant

                # Observability: slice restarts are the single biggest
                # goodput sink at pod scale — count them per Model so a
                # preemption-thrashing fleet shows up on /metrics, and
                # mark the trace so the restart window is attributable.
                REGISTRY.inc("controller_slice_restarts_total",
                             model=model.name,
                             help_text="Train-Job slice recreations "
                                       "(restart-with-resume).")
                instant("slice_restart", model=model.name,
                        attempt=restarts + 1, limit=limit)
                for j, name in zip(existing_jobs, job_names):
                    if j is not None:
                        ctx.client.delete("batch/v1", "Job",
                                          model.namespace, name)
                # Dedicated field manager: owns only the restart counter.
                ctx.client.apply({
                    "apiVersion": model.obj["apiVersion"], "kind": "Model",
                    "metadata": {"name": model.name,
                                 "namespace": model.namespace,
                                 "annotations": {
                                     RESTARTS_ANNOTATION: str(restarts + 1),
                                 }}}, "model-controller-restart")
                # Re-read before the status write: the apply above bumped
                # the resourceVersion, and a stale PUT /status 409s on a
                # real apiserver.
                fresh = ctx.client.get(model.obj["apiVersion"], "Model",
                                       model.namespace, model.name)
                model = Model(fresh if fresh is not None else model.obj)
                model.set_condition(
                    cond.COMPLETE, False, cond.REASON_JOB_RESTARTED,
                    f"slice restart {restarts + 1}/{limit}; resuming from "
                    "last checkpoint")
                model.commit_status(ctx.client)
                return Result(requeue_after=1.0)
            model.set_condition(cond.COMPLETE, False, cond.REASON_JOB_FAILED,
                                f"job {job_name} failed")
            model.set_ready(False)
            model.commit_status(ctx.client)
            return Result()
        if not complete:
            return Result(requeue_after=2.0)

        changed = model.set_condition(cond.COMPLETE, True,
                                      cond.REASON_JOB_COMPLETE)
        if not model.ready:
            model.set_ready(True)
            changed = True
        if changed:
            model.commit_status(ctx.client)
        if RESTARTS_ANNOTATION in ko.annotations(model.obj):
            # Success clears the restart budget: a future retrain starts
            # with a full maxRestarts, not the leftovers of this run.
            ctx.client.apply({
                "apiVersion": model.obj["apiVersion"], "kind": "Model",
                "metadata": {"name": model.name,
                             "namespace": model.namespace,
                             "annotations": {RESTARTS_ANNOTATION: None}},
            }, "model-controller-restart")
        return Result()

    # ------------------------------------------------------------------

    def _modeller_objects(self, ctx: Ctx, model: Model, base, dataset,
                          job_name: str, num_slices: int = 1):
        """All objects to create for the workload: one Job (plus headless
        Service when multi-host), times num_slices for DCN multislice."""
        job = self._modeller_job(ctx, model, base, dataset, job_name)
        tpu = parse_tpu(model.tpu) if model.tpu else None
        if num_slices > 1:
            if tpu is None:
                raise ValueError("tpu.slices requires a tpu block")
            from runbooks_tpu.cloud.resources import multislice_jobs

            return multislice_jobs(job, tpu, num_slices)
        if tpu is not None:
            svc = fan_out_job(job, tpu)
            if svc is not None:
                return [job, svc]
        return [job]

    def _modeller_job(self, ctx: Ctx, model: Model, base, dataset,
                      job_name: str):
        tpu = parse_tpu(model.tpu) if model.tpu else None
        container = {
            "name": "model",
            "image": model.image,
            "env": resolve_env(model.env),
            # Trainer /metrics exposition for the fleet scraper
            # (controller/fleet.py): the named port is how the scraper
            # resolves the URL, RBT_METRICS_PORT turns the endpoint on in
            # train/trainer.py main().
            "ports": [{"name": "metrics",
                       "containerPort": METRICS_PORT}],
        }
        container["env"].append({"name": "RBT_METRICS_PORT",
                                 "value": str(METRICS_PORT)})
        if "JAX_COMPILATION_CACHE_DIR" not in model.env:
            # The compile cache goes on the durable artifacts mount, so a
            # restarted Job (preemption, slice restart) skips the XLA
            # recompile. Placed here, from outside: the workload derives no
            # cache path of its own (utils/jax_cache.py).
            container["env"].append({
                "name": "JAX_COMPILATION_CACHE_DIR",
                "value": "/content/artifacts/jax_cache"})
        if model.command:
            container["command"] = list(model.command)
        pod_spec = {
            "serviceAccountName": SA_MODELLER,
            "restartPolicy": "Never",
            "securityContext": {"fsGroup": 3003},
            "containers": [container],
        }
        pod_meta = {"labels": {"model": model.name, "role": "run"}}

        ctx.cloud.mount_bucket(pod_meta, pod_spec, model,
                               BucketMount("artifacts", "artifacts",
                                           read_only=False))
        if dataset is not None:
            ctx.cloud.mount_bucket(pod_meta, pod_spec, dataset,
                                   BucketMount("artifacts", "data"))
        if base is not None:
            ctx.cloud.mount_bucket(pod_meta, pod_spec, base,
                                   BucketMount("artifacts", "model"))
        mount_params(pod_spec, "model", model)
        apply_cpu_resources(pod_spec, "model", model.resources)
        if tpu is not None:
            apply_tpu_resources(pod_spec, "model", tpu,
                                spot=model.spec.get("resources", {})
                                .get("spot", False))

        single_host_tpu = tpu is not None and not tpu.multi_host
        job = {
            "apiVersion": "batch/v1",
            "kind": "Job",
            "metadata": {"name": job_name, "namespace": model.namespace,
                         "labels": {"model": model.name, "role": "run"}},
            "spec": {
                # Expensive accelerator jobs do not blind-retry application
                # errors; cheap CPU import jobs get a few attempts
                # (reference :294-303). Single-host TPU jobs absorb
                # preemption-shaped failures IN PLACE (policy below);
                # multi-host slices fail whole on any pod failure — a lost
                # host crashes the peers' jax.distributed processes with
                # generic exit codes, so per-pod exit-code policy cannot
                # tell preemption from error there. Their restart-on-
                # preemption is the reconciler's slice-recreate path
                # (bounded by resources.tpu.maxRestarts), and resume is
                # step-exact either way (docs/fault-tolerance.md).
                "backoffLimit": (
                    resolve_preemption_restarts(model.params)
                    if single_host_tpu else 0 if tpu is not None else 3),
                "template": {"metadata": pod_meta, "spec": pod_spec},
            },
        }
        if single_host_tpu:
            # Restart-on-preemption, fail-on-error (docs/fault-tolerance
            # .md): a preempted node (DisruptionTarget) restarts free of
            # charge; the trainer's clean preemption exit (EXIT_PREEMPTED,
            # after its emergency checkpoint — it resumes step-exactly
            # from the artifact bucket) and a handler-less SIGTERM kill
            # (143) consume the backoffLimit budget above; any other
            # non-zero exit is an application error and fails the Job
            # immediately instead of blind-retrying an expensive slice.
            from runbooks_tpu.utils.contract import (
                EXIT_PREEMPTED,
                EXIT_SIGTERM_DEFAULT,
            )

            job["spec"]["podFailurePolicy"] = {"rules": [
                {"action": "Ignore",
                 "onPodConditions": [{"type": "DisruptionTarget",
                                      "status": "True"}]},
                {"action": "Count",
                 "onExitCodes": {"containerName": "model", "operator": "In",
                                 "values": [EXIT_PREEMPTED,
                                            EXIT_SIGTERM_DEFAULT]}},
                {"action": "FailJob",
                 "onExitCodes": {"containerName": "model",
                                 "operator": "NotIn",
                                 "values": [EXIT_PREEMPTED,
                                            EXIT_SIGTERM_DEFAULT]}},
            ]}
        ko.set_owner(job, model.obj)
        return job
