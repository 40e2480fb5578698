#!/usr/bin/env python3
"""System test: the full operator loop, twice — in-process and over HTTP.

Reference analog: test/system.sh, which creates a kind cluster, deploys the
operator, applies the opt-125m example, waits for ready, and curls a
completion. This image has no Docker/kind, so the same loop runs two ways:

1. **In-process**: reconcilers against the in-memory FakeCluster with a
   REAL gRPC SCI, REAL HTTP upload endpoint, and REAL serving engine +
   HTTP API (tiny random model).
2. **Over HTTP** (the closest achievable analog of system.sh's real
   apiserver): the SAME manager + reconcilers + leader election, but
   through the real stdlib ``K8sClient`` against ``FakeApiServer`` —
   every reconcile GET/POST/SSA-PATCH/status-PUT and every watch event
   crosses a real socket, and the simulated kubelet completes Jobs via
   status-subresource PUTs on a second HTTP client. Zero direct
   FakeCluster calls in this phase.

Run: python test/system.py   (CPU, ~2 min)
"""

import asyncio
import json
import os
import socket
import ssl
import sys
import threading
import time
import urllib.request

# This system test is a correctness gate, not a perf gate — it always runs
# on CPU, whatever backend the ambient environment names.
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# No phase may hang the gate: the reference's system.sh runs under CI
# timeouts; this is the in-process equivalent. Generous for slow CPU jit
# (full run is ~2 min here), fatal for a wedged backend init or watch.
DEADLINE_S = int(os.environ.get("RBT_SYSTEM_DEADLINE_S", "780"))


def _start_watchdog() -> None:
    def watchdog():
        time.sleep(DEADLINE_S)
        print(f"SYSTEM TEST DEADLINE EXCEEDED ({DEADLINE_S}s); aborting",
              flush=True)
        os._exit(2)

    threading.Thread(target=watchdog, daemon=True).start()


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def wait_for(pred, what, timeout=60):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            print(f"ok: {what}")
            return
        time.sleep(0.1)
    raise SystemExit(f"TIMEOUT: {what}")


def _retry_conflict(fn, tries: int = 20) -> None:
    """Real controllers re-read and retry on optimistic-concurrency 409s
    (the operator may touch the object between our GET and status PUT)."""
    from runbooks_tpu.k8s.fake import Conflict

    for _ in range(tries):
        try:
            return fn()
        except Conflict:
            time.sleep(0.05)
    return fn()


def kubelet_complete_job(client, namespace: str, name: str) -> None:
    """What the kubelet/job-controller would do, expressed through the
    same client API the operator uses (over HTTP in wire mode)."""
    def attempt():
        job = client.get("batch/v1", "Job", namespace, name)
        assert job is not None, f"no job {namespace}/{name}"
        job.setdefault("status", {})["conditions"] = [
            {"type": "Complete", "status": "True"}]
        job["status"]["succeeded"] = 1
        client.update_status(job)
    _retry_conflict(attempt)


def kubelet_deployment_ready(client, namespace: str, name: str) -> None:
    def attempt():
        dep = client.get("apps/v1", "Deployment", namespace, name)
        assert dep is not None, f"no deployment {namespace}/{name}"
        dep.setdefault("status", {})["readyReplicas"] = 1
        dep["status"]["replicas"] = 1
        client.update_status(dep)
    _retry_conflict(attempt)


def make_sci(workdir):
    """Real gRPC SCI server + real HTTP upload endpoint, shared by both
    phases."""
    from aiohttp import web

    from runbooks_tpu.sci.base import LocalSCI
    from runbooks_tpu.sci.grpc_service import GrpcSCI, serve
    from runbooks_tpu.sci.http_endpoint import create_app

    grpc_port, http_port = free_port(), free_port()
    sci_impl = LocalSCI(root=workdir,
                        endpoint=f"http://localhost:{http_port}")
    grpc_server = serve(sci_impl, port=grpc_port)

    def run_http():
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(create_app(sci_impl))
        loop.run_until_complete(runner.setup())
        loop.run_until_complete(
            web.TCPSite(runner, "localhost", http_port).start())
        loop.run_forever()

    threading.Thread(target=run_http, daemon=True).start()
    return GrpcSCI(f"localhost:{grpc_port}"), grpc_server


def control_plane_flow(client, label: str) -> None:
    """Apply the opt-125m example and drive it to ready through whatever
    client is given (FakeCluster in-process, K8sClient over HTTP)."""
    from runbooks_tpu.api.types import API_VERSION
    from runbooks_tpu.cli import main as cli

    cli.make_client = lambda args: client

    examples = os.path.join(os.path.dirname(__file__), "..", "examples",
                            "facebook-opt-125m")
    assert cli.main(["apply", "-f", examples]) == 0

    wait_for(lambda: client.get("batch/v1", "Job", "default",
                                "opt-125m-modeller"),
             f"[{label}] modeller job created")
    kubelet_complete_job(client, "default", "opt-125m-modeller")
    wait_for(lambda: (client.get(API_VERSION, "Model", "default",
                                 "opt-125m") or {})
             .get("status", {}).get("ready"), f"[{label}] model ready")

    wait_for(lambda: client.get("apps/v1", "Deployment", "default",
                                "opt-125m"),
             f"[{label}] server deployment created")
    kubelet_deployment_ready(client, "default", "opt-125m")
    wait_for(lambda: (client.get(API_VERSION, "Server", "default",
                                 "opt-125m") or {})
             .get("status", {}).get("ready"), f"[{label}] server Serving")


def make_ctx(client, sci, workdir):
    from runbooks_tpu.cloud.base import CommonConfig
    from runbooks_tpu.cloud.local import LocalCloud
    from runbooks_tpu.controller.manager import Ctx

    return Ctx(client=client,
               cloud=LocalCloud(CommonConfig(
                   cluster_name="system",
                   artifact_bucket_url=f"file://{workdir}/artifacts",
                   registry_url="registry.system:5000")),
               sci=sci)


def phase_inprocess(sci, workdir) -> None:
    from runbooks_tpu.controller.main import make_manager
    from runbooks_tpu.k8s.fake import FakeCluster

    client = FakeCluster()
    mgr = make_manager(make_ctx(client, sci, workdir))
    stop = threading.Event()
    threading.Thread(target=mgr.run, args=(stop,),
                     kwargs={"resync_seconds": 0.3}, daemon=True).start()
    control_plane_flow(client, "in-process")
    stop.set()


def phase_wire(sci, workdir) -> None:
    """The operator end-to-end over real sockets: K8sClient <-> HTTP
    apiserver, watch-driven manager, leader election on a Lease."""
    from runbooks_tpu.controller.leader import LeaderElector
    from runbooks_tpu.controller.main import (
        make_manager, run_with_leader_election)
    from runbooks_tpu.k8s.client import K8sClient, KubeConfig
    from runbooks_tpu.k8s.httpfake import FakeApiServer

    with FakeApiServer() as server:
        def http_client():
            cfg = KubeConfig(server.url, ssl.create_default_context(), {})
            return K8sClient(cfg)

        operator_client = http_client()
        kubelet_client = http_client()   # separate conn: the "kubelet"

        mgr = make_manager(make_ctx(operator_client, sci, workdir))
        elector = LeaderElector(operator_client, lease_duration_s=2.0,
                                renew_s=0.3, namespace="default")
        elector.run()
        stop = threading.Event()
        threading.Thread(target=run_with_leader_election,
                         args=(mgr, elector, stop),
                         kwargs={"poll_s": 0.1, "resync_seconds": 0.3},
                         daemon=True).start()
        wait_for(elector.is_leader.is_set, "[wire] leader elected",
                 timeout=15)

        control_plane_flow(kubelet_client, "wire")

        # Evidence this really crossed the wire: the apiserver saw the
        # client's watches, SSA applies, and status-subresource PUTs.
        methods = {(m, p.rsplit("/", 1)[-1]) for m, p, q, ct
                   in server.requests}
        watched = [q for m, p, q, ct in server.requests if "watch=true" in q]
        ssa = [ct for m, p, q, ct in server.requests
               if m == "PATCH" and ct == "application/apply-patch+yaml"]
        status_puts = [p for m, p, q, ct in server.requests
                       if m == "PUT" and p.endswith("/status")]
        assert watched, "no watch requests hit the wire"
        assert ssa, "no server-side-apply PATCHes hit the wire"
        assert status_puts, "no status-subresource PUTs hit the wire"
        print(f"ok: [wire] {len(server.requests)} HTTP requests "
              f"({len(watched)} watches, {len(ssa)} SSA patches, "
              f"{len(status_puts)} status PUTs)")
        stop.set()
        elector.stop()


def phase_serve() -> None:
    """Real serving engine answers a completion (the curl in system.sh)."""
    from aiohttp import web

    import jax

    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.models.transformer import init_params
    from runbooks_tpu.serve.api import create_server

    cfg = get_config("debug", dtype="float32")
    app = create_server(cfg, init_params(cfg, jax.random.key(0)),
                        max_slots=2, warmup=False)
    serve_port = free_port()

    def run_serve():
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(app)
        loop.run_until_complete(runner.setup())
        loop.run_until_complete(
            web.TCPSite(runner, "localhost", serve_port).start())
        loop.run_forever()

    threading.Thread(target=run_serve, daemon=True).start()
    wait_for(lambda: _http_ok(f"http://localhost:{serve_port}/"),
             "serve readiness probe")

    req = urllib.request.Request(
        f"http://localhost:{serve_port}/v1/completions",
        data=json.dumps({"prompt": "Hello", "max_tokens": 8,
                         "temperature": 0.0}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        body = json.load(resp)
    assert body["object"] == "text_completion", body
    assert body["usage"]["completion_tokens"] >= 1, body
    print("ok: /v1/completions answered", body["usage"])

    # Streamed completion over the same HTTP wire (SSE, stream: true).
    req = urllib.request.Request(
        f"http://localhost:{serve_port}/v1/completions",
        data=json.dumps({"prompt": "Hello", "max_tokens": 8,
                         "temperature": 0.0, "stream": True}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        assert resp.headers["Content-Type"].startswith("text/event-stream")
        raw = resp.read().decode()
    events = [ln[len("data: "):] for ln in raw.split("\n")
              if ln.startswith("data: ")]
    assert events[-1] == "[DONE]", events[-1:]
    streamed = "".join(
        json.loads(e)["choices"][0]["text"] for e in events[:-1])
    assert streamed == body["choices"][0]["text"], (
        streamed, body["choices"][0]["text"])
    print("ok: /v1/completions streamed", len(events) - 1, "chunks")

    # Shared-prefix registration: the same completion behind a registered
    # prefix must reuse the cached KV and produce identical text.
    sys_prompt = "You are a helpful assistant. " * 4
    req = urllib.request.Request(
        f"http://localhost:{serve_port}/v1/prefix",
        data=json.dumps({"prompt": sys_prompt}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=300) as resp:
        plen = json.load(resp)["cached_prefix_len"]
    assert plen >= 16, plen

    def completion(prompt):
        req = urllib.request.Request(
            f"http://localhost:{serve_port}/v1/completions",
            data=json.dumps({"prompt": prompt, "max_tokens": 6,
                             "temperature": 0.0}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            return json.load(resp)["choices"][0]["text"]

    text_prefixed = completion(sys_prompt + "Hello")
    with urllib.request.urlopen(
            f"http://localhost:{serve_port}/metrics", timeout=30) as resp:
        metrics = resp.read().decode()
    reused = [int(ln.split()[-1]) for ln in metrics.splitlines()
              if ln.startswith("serve_prefix_tokens_reused_total")]
    assert reused and reused[0] >= plen, metrics
    assert isinstance(text_prefixed, str)
    print(f"ok: /v1/prefix registered {plen} tokens and completions "
          f"reused {reused[0]}")


def main() -> int:
    import tempfile

    _start_watchdog()
    workdir = tempfile.mkdtemp(prefix="rbt-system-")
    sci, grpc_server = make_sci(workdir)

    phase_inprocess(sci, workdir)
    phase_wire(sci, workdir)
    phase_serve()

    grpc_server.stop(grace=0)
    print("SYSTEM TEST PASSED")
    return 0


def _http_ok(url: str) -> bool:
    try:
        with urllib.request.urlopen(url, timeout=2) as resp:
            return resp.status == 200
    except OSError:
        return False


if __name__ == "__main__":
    raise SystemExit(main())
