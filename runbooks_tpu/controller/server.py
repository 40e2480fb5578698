"""Server reconciler: Service + Deployment for a ready Model.

Reference behavior mirrored (reference: internal/controller/
server_controller.go): model readiness gate with conditions (:210-246),
model-server SA (:251-258), Service port 80 -> "http-serve" 8080 (:307-335),
Deployment with readiness probe GET / on 8080 and the model mounted RO at
/content/model (:114-205), Serving condition from ReadyReplicas (:280-296).
TPU-first: resources.tpu schedules the server pods onto TPU slices
(single-host topologies; inference fan-out across hosts arrives with the
multi-host serving engine).
"""

from __future__ import annotations

import json
import math
import threading
import time
import urllib.request
from typing import Dict, List, Optional, Tuple

from runbooks_tpu.api import conditions as cond
from runbooks_tpu.api.types import Server
from runbooks_tpu.cloud.base import BucketMount
from runbooks_tpu.cloud.resources import (
    apply_cpu_resources,
    apply_tpu_resources,
    parse_tpu,
)
from runbooks_tpu.controller.common import (
    FIELD_MANAGER,
    SA_MODEL_SERVER,
    gate_dependency,
    mount_params,
    reconcile_params_configmap,
    reconcile_service_account,
    resolve_env,
    validate_autoscale,
    validate_gateway,
    validate_params,
    validate_slo,
)
from runbooks_tpu.controller.manager import Ctx, Result
from runbooks_tpu.k8s import objects as ko

SERVE_PORT = 8080
GATEWAY_PORT = 8080

# How often a Server with spec.slo re-reconciles so the condition tracks
# fresh scrapes even with no spec/dependency events. Autoscaling Servers
# share the cadence: sustain/cooldown windows need regular evaluation.
SLO_REQUEUE_S = 5.0

# Per-replica POST /debug/incident timeout. Short: the fan-out runs on
# a side thread, but a wedged replica should not pin that thread long.
INCIDENT_POST_TIMEOUT_S = 2.0


class _IncidentBook:
    """Async incident fan-out for SLOViolated onsets.

    The reconcile path does no network of its own (the scraper owns
    that); firing ``POST /debug/incident`` at every replica inline
    would block a reconcile for seconds on a wedged pod. So an onset
    fire()s a daemon thread that POSTs each replica and parks the
    results here; the NEXT reconcile (Servers with spec.slo requeue
    every SLO_REQUEUE_S) folds them into ``.status.lastIncident``.
    In-process state, like AUTOSCALE — a controller restart just
    re-fires on the next onset."""

    def __init__(self):
        self._lock = threading.Lock()
        self._results: Dict[Tuple[str, str], dict] = {}  # guarded-by: _lock
        self._threads: Dict[Tuple[str, str], threading.Thread] = {}  # guarded-by: _lock

    def reset(self) -> None:
        with self._lock:
            self._results.clear()
            self._threads.clear()

    def fire(self, key: Tuple[str, str], reason: str,
             targets: List[Tuple[str, str]]) -> None:
        """Start one capture sweep over [(replica, base_url)] unless one
        is already in flight for this Server."""
        with self._lock:
            running = self._threads.get(key)
            if running is not None and running.is_alive():
                return
            thread = threading.Thread(
                target=self._sweep, args=(key, reason, list(targets)),
                name=f"rbt-incident-{key[1]}", daemon=True)
            self._threads[key] = thread
        thread.start()

    def _sweep(self, key, reason, targets) -> None:
        bundles = []
        for replica, base in targets:
            entry = {"replica": replica}
            try:
                req = urllib.request.Request(
                    base + "/debug/incident",
                    data=json.dumps({"reason": reason}).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(
                        req, timeout=INCIDENT_POST_TIMEOUT_S) as resp:
                    body = json.loads(resp.read().decode("utf-8",
                                                         "replace"))
                if body.get("path"):
                    entry["path"] = body["path"]
                else:
                    entry["debounced"] = True
            except (OSError, ValueError):
                entry["error"] = "unreachable"
            bundles.append(entry)
        wall = time.time()
        with self._lock:
            self._results[key] = {
                "reason": reason,
                "time": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                      time.gmtime(wall)),
                "unixTime": round(wall, 3),
                "bundles": bundles,
            }

    def take(self, key: Tuple[str, str]) -> Optional[dict]:
        """Pop-on-read: once a reconcile folds the sweep into
        `.status.lastIncident` the status object is the durable record,
        and keeping the entry would (a) grow the book for every Server
        ever fired and (b) hand a deleted-and-recreated Server its
        predecessor's incident on the new object's first reconcile."""
        with self._lock:
            return self._results.pop(key, None)

    def wait(self, key: Tuple[str, str], timeout_s: float = 10.0) -> bool:
        """Block until the in-flight sweep for `key` finishes (tests)."""
        with self._lock:
            thread = self._threads.get(key)
        if thread is None:
            return True
        thread.join(timeout=timeout_s)
        return not thread.is_alive()


# Process-wide book (same pattern as autoscale.AUTOSCALE).
INCIDENTS = _IncidentBook()


def _validate_serve_mesh(server: Server) -> Optional[str]:
    """Serve-specific mesh-geometry checks (validate_params already vetted
    the per-axis values for every workload kind). A serving replica is ONE
    process: pipeline stages are a training-only axis, and a mesh must fit
    the chips of a single-host slice — both would otherwise crash-loop the
    Deployment at engine construction instead of surfacing a condition."""
    params = server.params
    sizes = {k: int(params[k]) for k in params if k.startswith("mesh_")}
    if sizes.get("mesh_stage", 1) > 1:
        return ("spec.params.mesh_stage: pipeline stages are a training "
                "axis; the serving engine is one process per replica "
                "(docs/tensor-parallel-performance.md)")
    if not server.tpu:
        return None
    try:
        slice_ = parse_tpu(server.tpu)
    except ValueError as exc:
        return f"spec.resources.tpu: {exc}"
    if not sizes:
        return None
    if slice_.multi_host:
        return (f"spec.resources.tpu: topology {slice_.topology} spans "
                f"{slice_.hosts} hosts, but a mesh-sharded serving "
                f"replica is one process; pick a single-host topology "
                f"(<= {slice_.chips_per_host} chips for {slice_.type})")
    if any(s == -1 for s in sizes.values()):
        return None  # the fill axis adapts to whatever the slice provides
    product = math.prod(sizes.values())
    if product != slice_.chips:
        return (f"spec.params: mesh axes multiply to {product} chips but "
                f"tpu topology {slice_.topology} provides {slice_.chips}; "
                "make the products match, or set one axis to -1 to fill")
    return None


class ServerReconciler:
    kind = "Server"

    def reconcile(self, ctx: Ctx, raw: dict) -> Result:
        server = Server(raw)
        err = validate_params(server.params) \
            or _validate_serve_mesh(server) \
            or validate_slo(server.spec.get("slo")) \
            or validate_gateway(server.spec.get("gateway")) \
            or validate_autoscale(server.spec.get("autoscale"))
        if err is not None:
            # Invalid spec.params (e.g. quantize: int3): surface a condition
            # instead of shipping a params.json the serve container will
            # crash-loop on. Terminal until the spec changes — no requeue.
            server.set_condition(cond.SERVING, False,
                                 cond.REASON_INVALID_PARAMS, err)
            server.commit_status(ctx.client)
            return Result()
        if server.spec.get("engineRef"):
            # Multi-tenant LoRA tenant (docs/multi-tenant-lora.md): this
            # Server maps onto another Server's pooled engine instead of
            # deploying its own — N fine-tunes cost ONE engine's HBM.
            # Runs before the image gate: a tenant deploys no container,
            # so it needs no image.
            return self._reconcile_shared_engine(ctx, server)
        if not server.image:
            return Result(requeue_after=1.0)
        reconcile_params_configmap(ctx.client, server)

        if not server.model_ref:
            server.set_condition(cond.SERVING, False,
                                 cond.REASON_MODEL_NOT_FOUND,
                                 "spec.model is required")
            server.commit_status(ctx.client)
            return Result()
        model, ok = gate_dependency(
            ctx, server, "Model", server.model_ref,
            cond.REASON_MODEL_NOT_FOUND, cond.REASON_MODEL_NOT_READY,
            gate_condition=cond.SERVING)
        if not ok:
            return Result(requeue_after=2.0)

        reconcile_service_account(ctx.client, ctx.cloud, ctx.sci,
                                  SA_MODEL_SERVER, server.namespace)

        svc = self._service(server)
        ko.set_owner(svc, server.obj)
        ctx.client.apply(svc, FIELD_MANAGER)

        # Fleet telemetry + SLOs (controller/fleet.py): the scrape loop
        # populates FLEET between reconciles; this pass only folds the
        # latest aggregate into .status.telemetry and the SLOViolated
        # condition — no network from the reconciler itself (the
        # SLO-onset incident fan-out POSTs from a side thread; see
        # _IncidentBook). Runs BEFORE the autoscale decision so the
        # decision sees this reconcile's verdict, not the last one's.
        changed = self._apply_telemetry_and_slo(ctx, server)

        autoscale_spec = server.spec.get("autoscale") or {}
        replicas = server.spec.get("replicas", 1)
        desired = replicas
        if autoscale_spec:
            desired, aschanged = self._autoscale(ctx, server,
                                                 autoscale_spec)
            changed |= aschanged

        dep = self._deployment(ctx, server, model, replicas=desired)
        ko.set_owner(dep, server.obj)
        ctx.client.apply(dep, FIELD_MANAGER)

        gateway_spec = server.spec.get("gateway") or {}
        gateway_enabled = bool(gateway_spec.get("enabled"))
        gw_ready = True
        if gateway_enabled:
            gw_svc = self._gateway_service(server)
            ko.set_owner(gw_svc, server.obj)
            ctx.client.apply(gw_svc, FIELD_MANAGER)
            gw_dep = self._gateway_deployment(server, gateway_spec)
            ko.set_owner(gw_dep, server.obj)
            ctx.client.apply(gw_dep, FIELD_MANAGER)
            gw_cur = ctx.client.get("apps/v1", "Deployment",
                                    server.namespace,
                                    f"{server.name}-gateway")
            gw_ready = (ko.deep_get(gw_cur, "status", "readyReplicas",
                                    default=0) or 0) >= 1
        elif ctx.client.get("apps/v1", "Deployment", server.namespace,
                            f"{server.name}-gateway") is not None:
            # spec.gateway.enabled flipped off: a stale gateway left
            # running would keep routing (with frozen config — it is no
            # longer re-applied) while the spec says it must not exist.
            ctx.client.delete("apps/v1", "Deployment", server.namespace,
                              f"{server.name}-gateway")
            ctx.client.delete("v1", "Service", server.namespace,
                              f"{server.name}-gateway")

        current = ctx.client.get("apps/v1", "Deployment", server.namespace,
                                 server.name)
        ready_replicas = ko.deep_get(current, "status", "readyReplicas",
                                     default=0) or 0
        # Serving gate. Without autoscaling: every requested replica must
        # be ready (unchanged semantics). With autoscaling the target
        # moves under the Deployment, so gating on spec.replicas (or the
        # instantaneous desired count mid-transition) would flip a
        # healthy Server to not-serving during every scale event; the
        # floor the autoscaler guarantees (minReplicas) is the real
        # availability contract. With the gateway enabled, the ONLY
        # ingress path is the gateway — a Server whose gateway Deployment
        # is down is not serving no matter how many replicas are ready.
        if autoscale_spec:
            needed = max(1, int(autoscale_spec.get("minReplicas", 1)))
        else:
            needed = max(1, replicas)
        replicas_ok = ready_replicas >= needed
        serving = replicas_ok and gw_ready
        if not replicas_ok:
            message = f"{ready_replicas}/{needed} replicas ready"
            if autoscale_spec:
                message += f" (autoscale target {desired})"
        elif not gw_ready:
            message = "replicas ready but gateway Deployment is not"
        else:
            message = f"{ready_replicas}/{desired} replicas ready"
            if gateway_enabled:
                message += ", gateway ready"
        changed |= server.set_condition(
            cond.SERVING, serving,
            cond.REASON_DEPLOYMENT_READY if serving
            else cond.REASON_DEPLOYMENT_NOT_READY, message)
        if server.ready != serving:
            server.set_ready(serving)
            changed = True
        if changed:
            server.commit_status(ctx.client)
        requeue = None if serving else 2.0
        if server.spec.get("slo") or autoscale_spec:
            requeue = (SLO_REQUEUE_S if requeue is None
                       else min(requeue, SLO_REQUEUE_S))
        return Result(requeue_after=requeue)

    # ------------------------------------------------------------------

    def _reconcile_shared_engine(self, ctx: Ctx, server: Server) -> Result:
        """Tenant Server with ``spec.engineRef``: instead of a Deployment
        per fine-tune (N tenants = N x base weights in HBM), the tenant
        maps onto ANOTHER Server's pooled engine (docs/multi-tenant-
        lora.md). What the tenant gets: spec validation (adapter
        required, host must exist / be serving / run an adapter pool), a
        params ConfigMap (the contract record of its adapter), and a
        Service ALIASING the host's replica pods — clients of the tenant
        hit the shared engine, passing the adapter per request. No
        Deployment is ever created for the tenant."""
        ref = str(server.spec.get("engineRef"))
        if not (server.params.get("adapter") or "").strip():
            server.set_condition(
                cond.SERVING, False, cond.REASON_INVALID_PARAMS,
                "spec.engineRef requires spec.params.adapter (the "
                "tenant's fine-tune to serve)")
            server.commit_status(ctx.client)
            return Result()
        reconcile_params_configmap(ctx.client, server)
        from runbooks_tpu.api.types import API_VERSION

        host = ctx.client.get(API_VERSION, "Server",
                              server.namespace, ref)
        if host is None:
            server.set_condition(
                cond.SERVING, False, cond.REASON_ENGINE_NOT_FOUND,
                f"shared engine Server {ref!r} not found")
            server.commit_status(ctx.client)
            return Result(requeue_after=2.0)
        from runbooks_tpu.api.serve_params import OptionError, ServeOptions

        try:
            pool = ServeOptions.from_params(ko.deep_get(
                host, "spec", "params", default={}) or {}).adapter_pool
        except OptionError:     # the host carries its own condition
            pool = 0
        if pool < 1:
            server.set_condition(
                cond.SERVING, False, cond.REASON_ENGINE_NO_POOL,
                f"shared engine Server {ref!r} has no adapter pool "
                "(spec.params.adapter_pool >= 1 required)")
            server.commit_status(ctx.client)
            return Result(requeue_after=2.0)
        # Tenant ingress: a Service selecting the HOST's replica pods.
        svc = self._service(server)
        svc["spec"]["selector"] = {"server": ref, "role": "run"}
        ko.set_owner(svc, server.obj)
        ctx.client.apply(svc, FIELD_MANAGER)
        host_ready = bool(ko.deep_get(host, "status", "ready",
                                      default=False))
        changed = server.set_condition(
            cond.SERVING, host_ready,
            cond.REASON_DEPLOYMENT_READY if host_ready
            else cond.REASON_ENGINE_NOT_READY,
            (f"served by shared engine servers/{ref} "
             f"(adapter {server.params.get('adapter')!r})") if host_ready
            else f"shared engine servers/{ref} is not serving yet")
        if server.ready != host_ready:
            server.set_ready(host_ready)
            changed = True
        if changed:
            server.commit_status(ctx.client)
        return Result(requeue_after=None if host_ready else 2.0)

    # ------------------------------------------------------------------

    def _autoscale(self, ctx: Ctx, server: Server,
                   spec: dict) -> tuple:
        """One autoscale evaluation (controller/autoscale.py). Returns
        (desired_replicas, status_changed)."""
        from runbooks_tpu.controller import autoscale as autoscale_mod
        from runbooks_tpu.controller.fleet import (
            DEFAULT_INTERVAL_S,
            FLEET,
        )
        from runbooks_tpu.controller.metrics import REGISTRY
        from runbooks_tpu.obs import history as obs_history

        key = ("Server", server.namespace, server.name)
        # Scale-in hygiene (the fleet scraper only prunes on its own
        # sweep cadence): drop samples for replica pods that no longer
        # exist or are terminating, so the p90 the decision reads is not
        # biased toward dead pods' last distributions — and mark their
        # history rings stale, so the windowed p90 below excludes them
        # too.
        live = []
        for pod in ctx.client.list("v1", "Pod", namespace=server.namespace,
                                   label_selector={"server": server.name,
                                                   "role": "run"}):
            if not ko.deep_get(pod, "metadata", "deletionTimestamp",
                               default=None):
                live.append(ko.name(pod))
        for rep in FLEET.retain(key, live):
            REGISTRY.drop_series(replica=rep)
            obs_history.HISTORY.mark_stale(replica=rep)

        import os

        try:
            interval = float(os.environ.get("FLEET_SCRAPE_SECONDS",
                                            str(DEFAULT_INTERVAL_S)))
        except ValueError:
            interval = DEFAULT_INTERVAL_S
        # Seed from the .status.autoscale mirror when present: AUTOSCALE
        # is in-process state, so after a controller restart a fresh
        # ScaleState seeding from spec.replicas would instantly discard
        # scaled-out capacity (replicas=1, desired was 4 -> Deployment
        # snapped back to 1 under load). The status mirror lives on the
        # CR and survives the restart; evaluate() clamps it to the
        # current min/max bounds.
        base = (server.status.get("autoscale") or {}).get(
            "desiredReplicas") or server.spec.get("replicas", 1)
        summary = FLEET.server_summary(server.namespace, server.name)
        # Windowed queue-wait p90 (obs/history.py): once the history
        # spans the scale-out sustain window, the decision reads the
        # REAL p90 of observations inside that window — a burst that
        # already drained cannot look "sustained" the way the instant
        # merged p90 (cumulative since replica start) can, and stale
        # (vanished/terminating) replicas' distributions are excluded
        # by construction. The sustain clock stays as the re-arm
        # mechanism; only the signal feeding it changes. Cold history
        # keeps the instant p90.
        if summary is not None:
            sustain_s = float(spec.get(
                "scaleOutSustainS",
                autoscale_mod.DEFAULT_SCALE_OUT_SUSTAIN_S))
            qw = obs_history.HISTORY.window_quantile(
                "serve_queue_wait_seconds", 0.90,
                max(sustain_s, 2.0 * interval),
                sel={"kind": "Server", "namespace": server.namespace,
                     "name": server.name})
            if qw is not None:
                summary = dict(summary,
                               queueWaitP90Ms=round(qw * 1000.0, 1))
        desired, action = autoscale_mod.evaluate(
            (server.namespace, server.name), spec,
            server.spec.get("slo") or {}, summary,
            ko.is_condition_true(server.obj, cond.SLO_VIOLATED),
            FLEET.scrape_age(key), 2.0 * interval, base)
        if action is not None:
            print(f"autoscale: servers/{server.name} -> {desired} "
                  f"({action['direction']}: {action['reason']})",
                  flush=True)
            REGISTRY.inc(
                "controller_autoscale_actions_total",
                server=server.name, namespace=server.namespace,
                direction=action["direction"],
                help_text="Autoscaler replica-count changes, by server "
                          "and direction.")
        mn = max(1, int(spec.get("minReplicas", 1)))
        status = autoscale_mod.status_block(
            (server.namespace, server.name), mn,
            int(spec.get("maxReplicas", mn)))
        changed = server.status.get("autoscale") != status
        if changed:
            server.status["autoscale"] = status
        return desired, changed

    # ------------------------------------------------------------------

    def _apply_telemetry_and_slo(self, ctx: Ctx, server: Server) -> bool:
        from runbooks_tpu.controller import burnrate
        from runbooks_tpu.controller.fleet import FLEET
        from runbooks_tpu.controller.metrics import REGISTRY
        from runbooks_tpu.obs import history as obs_history

        changed = False
        fleet_summary = FLEET.server_summary(server.namespace, server.name)
        slo = server.spec.get("slo") or {}
        sel = {"kind": "Server", "namespace": server.namespace,
               "name": server.name}

        # Burn-rate evaluation over the fleet history rings
        # (controller/burnrate.py): per-objective multi-window burn
        # rates + error-budget accounting. verdicts is empty without
        # spec.slo; a verdict is computable only once the history spans
        # a full window pair (or was restored from a snapshot).
        verdicts = []
        burn_fields = {}
        if slo:
            now = time.time()
            verdicts = burnrate.evaluate(slo, obs_history.HISTORY, sel,
                                         now=now)
            budgets = [v.budget_remaining_pct for v in verdicts
                       if v.budget_remaining_pct is not None]
            burns = [v.burn["5m"] for v in verdicts if "5m" in v.burn]
            if budgets:
                burn_fields["errorBudgetRemainingPct"] = round(
                    min(budgets), 1)
            if burns:
                burn_fields["burnRate"] = round(max(burns), 2)
                # The dash's burn panel reads this series from history
                # (the scraper can't — the gauge lives in the
                # controller's own registry, which never self-scrapes).
                obs_history.HISTORY.append_scalar(
                    "controller_slo_burn_rate",
                    {**sel, "window": "5m"}, now, max(burns))
            for v in verdicts:
                for window, burn in v.burn.items():
                    REGISTRY.set_gauge(
                        "controller_slo_burn_rate", round(burn, 3),
                        server=server.name, namespace=server.namespace,
                        objective=v.key, window=window,
                        help_text="Error-budget burn rate per SLO "
                                  "objective and trailing window (1 = "
                                  "exactly on budget).")
                if v.budget_remaining_pct is not None:
                    REGISTRY.set_gauge(
                        "controller_slo_error_budget_remaining_pct",
                        round(v.budget_remaining_pct, 1),
                        server=server.name, namespace=server.namespace,
                        objective=v.key,
                        help_text="Percent of the objective's error "
                                  "budget left over the trailing 6h "
                                  "window.")

        # No fleet summary yet (e.g. first reconcile after a restart,
        # before the first scrape sweep) but burn fields computable from
        # the restored rings: MERGE into the CR's published telemetry —
        # replacing it would blank replicasUp/latency cells until the
        # next sweep.
        if fleet_summary is not None:
            telemetry = dict(fleet_summary)
        elif burn_fields:
            telemetry = dict(server.status.get("telemetry") or {})
        else:
            telemetry = None
        if telemetry is not None:
            telemetry.update(burn_fields)
            if server.status.get("telemetry") != telemetry:
                server.status["telemetry"] = telemetry
                changed = True
        # Fold a finished incident fan-out (this onset's or an earlier
        # one's — the sweep runs on a side thread) into status so
        # `.status.lastIncident` points at the latest bundles.
        incident = INCIDENTS.take((server.namespace, server.name))
        if incident is not None \
                and server.status.get("lastIncident") != incident:
            server.status["lastIncident"] = incident
            changed = True

        if not slo:
            return changed
        was_violated = ko.is_condition_true(server.obj, cond.SLO_VIOLATED)
        if fleet_summary is not None and not fleet_summary.get("replicasUp"):
            # Every replica unreachable: HOLD the last verdict. A total
            # outage must not clear an active violation (the autoscaler/
            # alert signal would vanish at the worst moment) — and the
            # burn windows, fed by no fresh scrapes, would decay toward
            # zero and shed exactly then. The fleet_scrape_up/age gauges
            # carry the outage itself.
            return changed
        # Per-objective verdict: the burn-rate windows once computable,
        # the PR-6 instant-threshold check as the cold-history fallback
        # (a fresh controller must still alert while the rings warm).
        violations = []
        for v in verdicts:
            if v.computable:
                if v.fired:
                    violations.append((v.reason, v.detail))
            else:
                violations.extend(self._violations(
                    {v.key: slo[v.key]}, fleet_summary))
        any_burn = any(v.computable for v in verdicts)
        if fleet_summary is None and not any_burn:
            changed |= server.set_condition(
                cond.SLO_VIOLATED, False, cond.REASON_SLO_NO_DATA,
                "no replica telemetry scraped yet")
        elif violations:
            reason, detail = violations[0][0], "; ".join(
                v[1] for v in violations)
            changed |= server.set_condition(
                cond.SLO_VIOLATED, True, reason, detail)
            if not was_violated:
                # Counts violation ONSETS (condition False -> True), not
                # reconciles spent violated — the rate the autoscaler and
                # alerts want. A controller restart that restores the
                # history re-derives the same verdict against the same
                # persisted condition, so it neither re-counts nor
                # re-fires the capture below.
                REGISTRY.inc(
                    "controller_slo_violations_total",
                    server=server.name, objective=reason,
                    help_text="SLOViolated condition onsets, by server "
                              "and first violated objective.")
                # Capture the evidence WHILE the violation is live:
                # every replica snapshots its flight ring / memory /
                # program census into an incident bundle (debounced
                # replica-side). Fan-out runs on a daemon thread; the
                # next reconcile folds the bundle paths into status.
                self._fire_incident_capture(ctx, server,
                                            f"slo_{reason}")
        else:
            changed |= server.set_condition(
                cond.SLO_VIOLATED, False, cond.REASON_SLO_MET,
                "all objectives within target")
        REGISTRY.set_gauge(
            "fleet_slo_violated",
            int(bool(violations)) if any_burn or (
                fleet_summary is not None
                and fleet_summary.get("replicasUp")) else 0,
            kind="Server", namespace=server.namespace, name=server.name,
            help_text="1 while the Server's SLOViolated condition is "
                      "true.")
        return changed

    @staticmethod
    def _fire_incident_capture(ctx: Ctx, server: Server,
                               reason: str) -> None:
        """Start the per-replica POST /debug/incident sweep for one
        SLOViolated onset (run pods only — the gateway has no engine
        state worth bundling)."""
        from runbooks_tpu.controller.fleet import pod_base_url

        targets: List[Tuple[str, str]] = []
        for pod in ctx.client.list("v1", "Pod", namespace=server.namespace,
                                   label_selector={"server": server.name,
                                                   "role": "run"}):
            if ko.deep_get(pod, "metadata", "deletionTimestamp",
                           default=None):
                continue
            if ko.deep_get(pod, "status", "phase", default="") != "Running":
                continue
            base = pod_base_url(pod)
            if base:
                targets.append((ko.name(pod), base))
        if targets:
            INCIDENTS.fire((server.namespace, server.name), reason,
                           targets)

    @staticmethod
    def _violations(slo: dict, summary) -> list:
        """(reason, detail) per violated objective, hardest-violated
        first kept stable by declaration order. Cumulative error rate is
        used as-is (the counters reset with the replica); the histogram
        quantiles come from the merged cross-replica distributions."""
        if not summary:
            return []
        out = []
        checks = (
            ("ttftP99Ms", "ttftP99Ms", cond.REASON_SLO_TTFT),
            ("queueWaitP90Ms", "queueWaitP90Ms",
             cond.REASON_SLO_QUEUE_WAIT),
            ("errorRatePct", "errorRatePct", cond.REASON_SLO_ERROR_RATE),
        )
        for spec_key, summary_key, reason in checks:
            target = slo.get(spec_key)
            measured = summary.get(summary_key)
            if target is None or measured is None:
                continue
            if float(measured) > float(target):
                out.append((reason,
                            f"{spec_key} {measured} > target {target}"))
        return out

    # ------------------------------------------------------------------

    def _service(self, server: Server) -> dict:
        return {
            "apiVersion": "v1",
            "kind": "Service",
            "metadata": {"name": server.name, "namespace": server.namespace},
            "spec": {
                "selector": {"server": server.name, "role": "run"},
                "ports": [{"name": "http-serve", "port": 80,
                           "targetPort": SERVE_PORT, "protocol": "TCP"}],
            },
        }

    def _gateway_service(self, server: Server) -> dict:
        """Client-facing Service for the routing data plane: port 80 ->
        the gateway pods. The replica Service stays (the gateway and the
        fleet scraper address pods directly), but with spec.gateway
        enabled this is the ingress clients should use
        (docs/serving-dataplane.md)."""
        return {
            "apiVersion": "v1",
            "kind": "Service",
            "metadata": {"name": f"{server.name}-gateway",
                         "namespace": server.namespace},
            "spec": {
                "selector": {"server": server.name, "role": "gateway"},
                "ports": [{"name": "http-gateway", "port": 80,
                           "targetPort": GATEWAY_PORT, "protocol": "TCP"}],
            },
        }

    def _gateway_deployment(self, server: Server, gateway: dict) -> dict:
        """The gateway Deployment (serve/gateway.py): same image as the
        serve container, CPU-only, discovers replica pods via the k8s API
        (RBT_GATEWAY_SERVER/NAMESPACE). Stateless — scale it with
        spec.gateway.replicas for HA; the consistent-hash affinity ring
        is stable across gateway replicas (SHA-1 points, no shared
        state)."""
        container = {
            "name": "gateway",
            "image": server.image,
            "command": ["python", "-m", "runbooks_tpu.serve.gateway"],
            "env": resolve_env(server.env) + [
                {"name": "RBT_GATEWAY_SERVER", "value": server.name},
                {"name": "RBT_GATEWAY_NAMESPACE",
                 "value": server.namespace},
                {"name": "RBT_GATEWAY_POLICY",
                 "value": str(gateway.get("policy", "prefix"))},
                {"name": "RBT_GATEWAY_BLOCK_CHARS",
                 "value": str(gateway.get("blockChars", 64))},
                {"name": "RBT_GATEWAY_AFFINITY",
                 "value": "0" if gateway.get("sessionAffinity") is False
                 else "1"},
            ],
            "ports": [{"name": "http-gateway",
                       "containerPort": GATEWAY_PORT}],
            # Readiness = "can route somewhere": the gateway 503s its
            # probe while zero backends are healthy, so the Service only
            # sends traffic to gateways that can place it.
            "readinessProbe": {
                "httpGet": {"path": "/", "port": GATEWAY_PORT},
                "periodSeconds": 5,
                "initialDelaySeconds": 2,
            },
        }
        pod_spec = {
            "serviceAccountName": SA_MODEL_SERVER,
            "containers": [container],
        }
        mount_params(pod_spec, "gateway", server)
        return {
            "apiVersion": "apps/v1",
            "kind": "Deployment",
            "metadata": {"name": f"{server.name}-gateway",
                         "namespace": server.namespace},
            "spec": {
                "replicas": int(gateway.get("replicas", 1)),
                "selector": {"matchLabels": {"server": server.name,
                                             "role": "gateway"}},
                "template": {
                    "metadata": {"labels": {"server": server.name,
                                            "role": "gateway"}},
                    "spec": pod_spec,
                },
            },
        }

    def _deployment(self, ctx: Ctx, server: Server, model,
                    replicas: Optional[int] = None) -> dict:
        tpu = parse_tpu(server.tpu) if server.tpu else None
        container = {
            "name": "serve",
            "image": server.image,
            "env": resolve_env(server.env),
            "ports": [{"name": "http-serve",
                       "containerPort": SERVE_PORT}],
            "readinessProbe": {
                "httpGet": {"path": "/", "port": SERVE_PORT},
                "periodSeconds": 5,
                "initialDelaySeconds": 5,
            },
            "startupProbe": {
                "httpGet": {"path": "/", "port": SERVE_PORT},
                "failureThreshold": 60,
                "periodSeconds": 10,
            },
        }
        if server.command:
            container["command"] = list(server.command)
        pod_spec = {
            "serviceAccountName": SA_MODEL_SERVER,
            "containers": [container],
        }
        pod_meta = {"labels": {"server": server.name, "role": "run"}}
        ctx.cloud.mount_bucket(pod_meta, pod_spec, model,
                               BucketMount("artifacts", "model"))
        mount_params(pod_spec, "serve", server)
        apply_cpu_resources(pod_spec, "serve", server.resources)
        if tpu is not None:
            apply_tpu_resources(pod_spec, "serve", tpu)
        return {
            "apiVersion": "apps/v1",
            "kind": "Deployment",
            "metadata": {"name": server.name, "namespace": server.namespace},
            "spec": {
                "replicas": (int(replicas) if replicas is not None
                             else server.spec.get("replicas", 1)),
                "selector": {"matchLabels": {"server": server.name,
                                             "role": "run"}},
                "template": {"metadata": pod_meta, "spec": pod_spec},
            },
        }
