"""Shared reconciler helpers: job lifecycle, env resolution, SA plumbing,
params ConfigMaps.

Reference analogs: internal/controller/utils.go (reconcileJob/jobResult/
isPodReady/resolveEnv), params_reconciler.go, service_accounts_controller.go.
"""

from __future__ import annotations

import json
import re
from typing import Dict, List, Optional, Tuple

from runbooks_tpu.api import conditions as cond
from runbooks_tpu.api.serve_params import OptionError, ServeOptions
from runbooks_tpu.api.types import Resource
from runbooks_tpu.k8s import objects as ko
from runbooks_tpu.utils.contract import params_to_env

FIELD_MANAGER = "runbooks-tpu-controller"

# Well-known workload ServiceAccounts (reference:
# service_accounts_controller.go:16-22).
SA_CONTAINER_BUILDER = "container-builder"
SA_MODELLER = "modeller"
SA_MODEL_SERVER = "model-server"
SA_NOTEBOOK = "notebook"
SA_DATA_LOADER = "data-loader"

_SECRET_RE = re.compile(
    r"^\s*\$\{\{\s*secrets\.([A-Za-z0-9-_.]+)\.([A-Za-z0-9-_.]+)\s*\}\}\s*$")


def resolve_env(env: Dict[str, str]) -> List[dict]:
    """NAME: value map -> container env list; values of the form
    ``${{ secrets.<name>.<key> }}`` become secretKeyRef (reference:
    internal/controller/utils.go:67-93)."""
    out = []
    for name, value in sorted(env.items()):
        m = _SECRET_RE.match(str(value))
        if m:
            out.append({"name": name, "valueFrom": {"secretKeyRef": {
                "name": m.group(1), "key": m.group(2)}}})
        else:
            out.append({"name": name, "value": str(value)})
    return out


def params_env(params: dict) -> List[dict]:
    return [{"name": k, "value": v}
            for k, v in sorted(params_to_env(params).items())]


# spec.params is free-form (it flows verbatim into the params.json
# ConfigMap + PARAM_* env — mount_params), but a typo'd `quantize: int3`
# would otherwise surface only as a crash-looping container behind a
# never-ready Deployment; validating at reconcile time turns it into a
# visible condition. What the server reads is declared, with its floors and
# rules, by ServeOptions (api/serve_params.py) and validated by building
# the record; what the loader and the trainer read is tabled here.
# `quantize` mirrors the reference's Server contract (reference:
# examples/llama2-70b/server.yaml `quantize: int4`), consumed by
# serve/api.load_model and models/loader.py.

# Gradient accumulation (train/step.py make_train_step): microbatch count
# per optimizer step. Power-of-two enum — a typo'd value would otherwise
# surface only as a crash-looping trainer Job at ValueError time; accepted
# under every spelling TrainJobConfig.from_params honors (snake_case
# params.json convention, the reference's camelCase spec style, and the
# PARAM_* env round-trip's lowercase).
_ACCUM_KEYS = ("accumulate_steps", "accumulateSteps", "accumulatesteps")
_ACCUM_ENUM = ("1", "2", "4", "8", "16", "32", "64")

# Overlapped collective-matmul tensor parallelism (train AND serve specs;
# docs/tensor-parallel-performance.md). Same spelling set as accumulate:
# snake_case params.json, the reference's camelCase spec style, and the
# PARAM_* env round-trip's lowercase.
_CM_KEYS = ("collective_matmul", "collectiveMatmul", "collectivematmul")
_CM_ENUM = ("off", "ring", "auto")

ENUM_PARAMS = {
    "quantize": ("none", "int8", "int4"),
    "source": ("huggingface", "dir", "random"),
    **{k: _ACCUM_ENUM for k in _ACCUM_KEYS},
    **{k: _CM_ENUM for k in _CM_KEYS},
}

# Preemption-tolerant trainer restarts (docs/fault-tolerance.md): how many
# preemption-shaped pod failures (trainer EXIT_PREEMPTED after an emergency
# checkpoint, or SIGTERM's default 143) the train Job absorbs in-place
# (backoffLimit) before the Job fails. Same spelling set as the other
# validated trainer knobs.
_RESTART_KEYS = ("preemption_restarts", "preemptionRestarts",
                 "preemptionrestarts")
DEFAULT_PREEMPTION_RESTARTS = 2

# Integer-valued params the trainer int()-coerces at startup: key ->
# minimum allowed value. A non-integer or out-of-range value would
# crash-loop the Job at TrainJobConfig.from_params instead of surfacing a
# condition.
_MAX_BAD_STEPS_KEYS = ("max_bad_steps", "maxBadSteps", "maxbadsteps")

# Mesh geometry axes (parallel/mesh.py MESH_AXES — keep in sync; not
# imported so the controller stays jax-free). A spec selects sharded
# serving/training with mesh_<axis> integer params; -1 means "fill with
# the remaining devices" on at most ONE axis.
_MESH_AXES = ("data", "stage", "expert", "fsdp", "sequence", "tensor")

INT_PARAMS = {
    "loss_chunk": 0,
    "prefetch_depth": 0,
    "batch_size": 1,
    "seq_len": 1,
    "steps": 1,
    "mesh_stage": 1,
    # Consecutive non-finite steps the trainer tolerates before aborting.
    **{k: 1 for k in _MAX_BAD_STEPS_KEYS},
    **{k: 0 for k in _RESTART_KEYS},
}

# Float-valued params the workloads float()-coerce at startup: key ->
# minimum allowed value (same crash-loop-vs-condition rationale as
# INT_PARAMS; docs/fault-tolerance.md).
FLOAT_PARAMS = {
    "maintenance_poll_s": 0.0,    # trainer: 0 disables polling
}


# Server.spec.slo objectives (docs/observability.md): each is a positive
# number; the Server reconciler evaluates them every reconcile against the
# fleet scraper's per-replica telemetry and flips the SLOViolated
# condition. Validated like the params knobs — a typo'd objective name
# would otherwise silently never trip.
SLO_OBJECTIVES = ("ttftP99Ms", "queueWaitP90Ms", "errorRatePct")


def validate_slo(slo) -> Optional[str]:
    """First validation error in a Server spec.slo block, or None."""
    if slo is None:
        return None
    if not isinstance(slo, dict):
        return "spec.slo: must be a mapping of objective -> target"
    for key, val in slo.items():
        if key not in SLO_OBJECTIVES:
            return (f"spec.slo.{key}: unknown objective (expected one of "
                    f"{'|'.join(SLO_OBJECTIVES)})")
        try:
            num = float(val)
        except (TypeError, ValueError):
            return f"spec.slo.{key}: {val!r} is not a number"
        if num <= 0:
            return f"spec.slo.{key}: {val} must be > 0"
    return None


# Server.spec.gateway (serve/gateway.py, docs/serving-dataplane.md): the
# prefix-aware routing data plane the reconciler deploys in front of the
# replicas. Validated like spec.slo — a typo'd knob must surface as a
# condition, not a crash-looping gateway Deployment.
GATEWAY_KEYS = {
    "enabled": None,                 # truthy flag
    "replicas": ("int", 1),
    "policy": ("enum", ("prefix", "random")),
    "blockChars": ("int", 8),
    "sessionAffinity": None,         # truthy flag
}

# Server.spec.autoscale (controller/autoscale.py): replica autoscaling
# knobs. minReplicas/maxReplicas bound the range; the rest tune the
# sustain/cooldown behavior.
AUTOSCALE_KEYS = {
    "minReplicas": ("int", 1),
    "maxReplicas": ("int", 1),
    "queueWaitP90Ms": ("float", 0.0, False),   # > 0
    "scaleOutSustainS": ("float", 0.0, True),  # >= 0
    "scaleInSustainS": ("float", 0.0, True),
    "cooldownS": ("float", 0.0, True),
    "scaleInOccupancy": ("float", 0.0, False),
}


def _validate_block(block, prefix: str, keys: dict) -> Optional[str]:
    if block is None:
        return None
    if not isinstance(block, dict):
        return f"{prefix}: must be a mapping"
    for key, val in block.items():
        rule = keys.get(key, "unknown")
        if rule == "unknown":
            return (f"{prefix}.{key}: unknown field (expected one of "
                    f"{'|'.join(sorted(keys))})")
        if rule is None:
            continue
        if rule[0] == "enum":
            if str(val) not in rule[1]:
                return (f"{prefix}.{key}: {val!r} is not one of "
                        f"{'|'.join(rule[1])}")
            continue
        kind, lo = rule[0], rule[1]
        inclusive = rule[2] if len(rule) > 2 else True
        try:
            num = int(val) if kind == "int" else float(val)
        except (TypeError, ValueError):
            return (f"{prefix}.{key}: {val!r} is not "
                    f"{'an integer' if kind == 'int' else 'a number'}")
        if (num < lo) if inclusive else (num <= lo):
            op = ">=" if inclusive else ">"
            return f"{prefix}.{key}: {val} must be {op} {lo}"
    return None


def validate_gateway(gateway) -> Optional[str]:
    """First validation error in a Server spec.gateway block, or None."""
    return _validate_block(gateway, "spec.gateway", GATEWAY_KEYS)


def validate_autoscale(autoscale) -> Optional[str]:
    """First validation error in a Server spec.autoscale block, or
    None. maxReplicas is required (an unbounded autoscaler is a billing
    incident) and must not be below minReplicas."""
    err = _validate_block(autoscale, "spec.autoscale", AUTOSCALE_KEYS)
    if err is not None or autoscale is None:
        return err
    if autoscale.get("maxReplicas") is None:
        return "spec.autoscale.maxReplicas: required"
    mn = int(autoscale.get("minReplicas", 1))
    mx = int(autoscale["maxReplicas"])
    if mx < mn:
        return (f"spec.autoscale.maxReplicas: {mx} must be >= "
                f"minReplicas {mn}")
    return None


def resolve_preemption_restarts(params: dict,
                                default: int = DEFAULT_PREEMPTION_RESTARTS,
                                ) -> int:
    """The preemption-restart budget from a validated spec.params dict."""
    for key in _RESTART_KEYS:
        if params.get(key) is not None:
            return int(params[key])
    return default

# Keep in sync with TrainJobConfig.batch_size: the divisibility check must
# hold against the default the trainer will actually use when the spec
# leaves batch_size out.
DEFAULT_TRAIN_BATCH_SIZE = 8


def validate_params(params: dict) -> Optional[str]:
    """First validation error in a spec.params dict, or None when clean."""
    for key, allowed in ENUM_PARAMS.items():
        val = params.get(key)
        if val is not None and str(val) not in allowed:
            return (f"spec.params.{key}: {val!r} is not one of "
                    f"{'|'.join(allowed)}")
    for key, lo in INT_PARAMS.items():
        val = params.get(key)
        if val is None:
            continue
        try:
            if int(val) < lo:
                return f"spec.params.{key}: {val} must be >= {lo}"
        except (TypeError, ValueError):
            return f"spec.params.{key}: {val!r} is not an integer"
    for key, flo in FLOAT_PARAMS.items():
        val = params.get(key)
        if val is None:
            continue
        try:
            if float(val) < flo:
                return f"spec.params.{key}: {val} must be >= {flo}"
        except (TypeError, ValueError):
            return f"spec.params.{key}: {val!r} is not a number"
    try:
        ServeOptions.from_params(params)
    except OptionError as err:
        return str(err)
    # Mesh geometry (parallel/mesh.py): mesh_<axis> params select a
    # sharded engine. An unknown axis name is a typo the workload would
    # silently ignore (serving a single chip while the spec says eight);
    # more than one -1 fill axis is ambiguous and MeshConfig would
    # crash-loop the replica on it.
    fill_axes = []
    for key in sorted(k for k in params if k.startswith("mesh_")):
        axis = key[len("mesh_"):]
        if axis not in _MESH_AXES:
            return (f"spec.params.{key}: unknown mesh axis (expected "
                    f"mesh_<axis> with axis one of "
                    f"{'|'.join(_MESH_AXES)})")
        try:
            size = int(params[key])
        except (TypeError, ValueError):
            return f"spec.params.{key}: {params[key]!r} is not an integer"
        if size == -1:
            fill_axes.append(key)
        elif size < 1:
            return (f"spec.params.{key}: {size} must be >= 1 (or -1 to "
                    "fill with the remaining devices)")
    if len(fill_axes) > 1:
        return ("spec.params: at most one mesh axis may be -1 (fill), "
                f"got {', '.join(fill_axes)}")
    accum = next((params[k] for k in _ACCUM_KEYS
                  if params.get(k) is not None), None)
    if accum is not None:
        batch = params.get("batch_size", DEFAULT_TRAIN_BATCH_SIZE)
        if int(batch) % int(accum):
            return (f"spec.params.accumulate_steps: {accum} does not "
                    f"divide batch_size {batch}")
        # make_train_step rejects accumulation under the 1f1b pipeline
        # schedule (it already microbatches); catch it at reconcile time
        # rather than crash-looping the Job.
        stages = int(params.get("mesh_stage", 1))
        schedule = str((params.get("model_overrides") or {})
                       .get("pipeline_schedule", "1f1b"))
        if int(accum) > 1 and stages > 1 and schedule == "1f1b":
            return ("spec.params.accumulate_steps: not supported with the "
                    "1f1b pipeline schedule (mesh_stage > 1); set "
                    "model_overrides.pipeline_microbatches instead")
    return None


# ---------------------------------------------------------------------------
# Jobs
# ---------------------------------------------------------------------------

def job_status(job: Optional[dict]) -> Tuple[bool, bool]:
    """(complete, failed) from Job conditions."""
    if not job:
        return False, False
    for c in ko.deep_get(job, "status", "conditions", default=[]) or []:
        if c.get("type") == "Complete" and c.get("status") == "True":
            return True, False
        if c.get("type") == "Failed" and c.get("status") == "True":
            return False, True
    return False, False


def reconcile_job(client, job: dict) -> Tuple[bool, bool]:
    """Create-if-absent then report (complete, failed) (reference:
    utils.go:23-35)."""
    ns, name = ko.namespace(job), ko.name(job)
    existing = client.get("batch/v1", "Job", ns, name)
    if existing is None:
        client.create(job)
        return False, False
    return job_status(existing)


def is_pod_ready(pod: Optional[dict]) -> bool:
    if not pod:
        return False
    for c in ko.deep_get(pod, "status", "conditions", default=[]) or []:
        if c.get("type") == "Ready" and c.get("status") == "True":
            return True
    return False


# ---------------------------------------------------------------------------
# Params ConfigMap (reference: params_reconciler.go)
# ---------------------------------------------------------------------------

def params_configmap_name(obj: Resource) -> str:
    return f"{obj.name}-{obj.kind.lower()}-params"


def reconcile_params_configmap(client, obj: Resource) -> None:
    cm = {
        "apiVersion": "v1",
        "kind": "ConfigMap",
        "metadata": {"name": params_configmap_name(obj),
                     "namespace": obj.namespace},
        "data": {"params.json": json.dumps(obj.params, sort_keys=True)},
    }
    ko.set_owner(cm, obj.obj)
    client.apply(cm, FIELD_MANAGER)


def mount_params(pod_spec: dict, container_name: str, obj: Resource) -> None:
    """Mount params.json at /content/params.json via subPath + inject the
    PARAM_* env (the reference documents the env half in its contract but
    only implements the file mount — here both are real; reference:
    params_reconciler.go:78-104, docs/container-contract.md)."""
    vols = pod_spec.setdefault("volumes", [])
    if not any(v.get("name") == "params" for v in vols):
        vols.append({"name": "params", "configMap": {
            "name": params_configmap_name(obj)}})
    for container in pod_spec.get("containers", []):
        if container.get("name") != container_name:
            continue
        container.setdefault("volumeMounts", []).append({
            "name": "params",
            "mountPath": "/content/params.json",
            "subPath": "params.json",
        })
        container.setdefault("env", []).extend(params_env(obj.params))


# ---------------------------------------------------------------------------
# ServiceAccounts (reference: service_accounts_controller.go)
# ---------------------------------------------------------------------------

def reconcile_service_account(client, cloud, sci, name: str,
                              namespace: str) -> None:
    sa = client.get("v1", "ServiceAccount", namespace, name)
    if sa is None:
        sa = {"apiVersion": "v1", "kind": "ServiceAccount",
              "metadata": {"name": name, "namespace": namespace}}
    principal, bound = cloud.get_principal(sa)
    cloud.associate_principal(sa)
    client.apply(sa, FIELD_MANAGER)
    if principal and not bound:
        sci.bind_identity(principal=principal, ksa=name, namespace=namespace)


# ---------------------------------------------------------------------------
# Dependency gates
# ---------------------------------------------------------------------------

def gate_dependency(ctx, obj: Resource, dep_kind: str, dep_name: str,
                    not_found_reason: str, not_ready_reason: str,
                    gate_condition: str = cond.COMPLETE,
                    ) -> Tuple[Optional[Resource], bool]:
    """Fetch a dependency and set gate_condition=False when it is missing or
    not ready (Servers gate via Serving, Jobs/Notebooks via Complete).
    Returns (dep, ok)."""
    from runbooks_tpu.api.types import API_VERSION, KIND_TO_CLASS

    raw = ctx.client.get(API_VERSION, dep_kind, obj.namespace, dep_name)
    if raw is None:
        obj.set_condition(gate_condition, False, not_found_reason,
                          f"{dep_kind} {dep_name!r} not found")
        obj.commit_status(ctx.client)
        return None, False
    dep = KIND_TO_CLASS[dep_kind](raw)
    if not dep.ready:
        obj.set_condition(gate_condition, False, not_ready_reason,
                          f"{dep_kind} {dep_name!r} not ready")
        obj.commit_status(ctx.client)
        return dep, False
    return dep, True
