"""Flight recorder + distributed request tracing + incident snapshots
(ISSUE 12).

Covers: the always-on bounded span ring (recording with RBT_TRACE=0,
request-id indexing, boundedness under sustained traffic), tail
sampling (slow/deadline requests promoted to trace.jsonl, fast ones
not), Perfetto multi-pod metadata (process_name/thread_name events,
host-derived trace pid), gateway hop stitching end to end through the
real HTTP stack (minted X-Request-Id, forwarded traceparent, gateway
access log, `rbt trace` merging gateway + 2 replicas into one
clock-ordered timeline), and incident snapshots (fault-injected engine
crash and SLOViolated onset each produce exactly one parseable bundle,
debounce verified; trainer max_bad_steps abort; /debug/incident(s)
endpoints; `rbt incidents`).
"""

import asyncio
import dataclasses
import json
import os
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, HTTPServer

import pytest

from runbooks_tpu.obs import flight as obs_flight
from runbooks_tpu.obs import incident as obs_incident
from runbooks_tpu.obs import trace as obs_trace

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


@pytest.fixture(autouse=True)
def clean_obs_state(monkeypatch):
    """Flight ring + incident debounce book are process-global: every
    test starts from a clean slate and leaves one behind."""
    obs_flight.RING.clear()
    obs_incident.MANAGER.reset()
    monkeypatch.delenv("RBT_TRACE", raising=False)
    monkeypatch.delenv("RBT_TRACE_TAIL_MS", raising=False)
    monkeypatch.delenv("RBT_FLIGHT", raising=False)
    yield
    obs_trace.close()
    obs_trace.configure(None)
    obs_flight.RING.clear()
    obs_incident.MANAGER.reset()


def tiny_cfg():
    from runbooks_tpu.models.config import get_config

    return dataclasses.replace(
        get_config("llama2-7b"), vocab_size=128, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, max_seq_len=64, dtype="float32")


def tiny_params(cfg):
    import jax

    from runbooks_tpu.models.transformer import init_params

    return jax.jit(lambda r: init_params(cfg, r))(jax.random.key(0))


# ---------------------------------------------------------------------------
# Ring mechanics
# ---------------------------------------------------------------------------

def test_ring_bounded_and_indexed_by_request_id():
    ring = obs_flight.FlightRecorder(capacity=32)
    for i in range(100):
        ring.record({"name": "decode", "ph": "X", "ts": float(i),
                     "args": {"request_ids": [f"r-{i % 4}"]}})
    stats = ring.stats()
    assert stats["events"] == 32 and stats["capacity"] == 32
    assert stats["recorded"] == 100 and stats["dropped"] == 68
    # Request-id filter matches both the list form and the /i suffix.
    assert all("r-1" in e["args"]["request_ids"]
               for e in ring.snapshot(request_id="r-1"))
    ring.record({"name": "prefill", "ph": "X", "ts": 1e9,
                 "args": {"request_id": "r-9/0"}})
    assert len(ring.snapshot(request_id="r-9")) == 1


def test_spans_record_into_ring_without_rbt_trace(tmp_path):
    obs_trace.configure(str(tmp_path / "trace.jsonl"))
    with obs_trace.span("prefill", bucket=16, request_ids=["rid-a"]):
        pass
    obs_trace.instant("tick", request_id="rid-a")
    # Ring has both; the FILE has neither (RBT_TRACE off).
    events = obs_flight.RING.snapshot(request_id="rid-a")
    assert {e["name"] for e in events} == {"prefill", "tick"}
    assert not os.path.exists(tmp_path / "trace.jsonl")
    # RBT_FLIGHT=0 restores the zero-cost null path.
    os.environ["RBT_FLIGHT"] = "0"
    try:
        assert obs_trace.span("x") is obs_trace.span("y")
    finally:
        del os.environ["RBT_FLIGHT"]


def test_trace_file_carries_perfetto_metadata(tmp_path, monkeypatch):
    """Multi-pod merge fix: each file generation opens with
    process_name/thread_name metadata naming component@host + the real
    pid, and events carry the host-derived trace pid."""
    monkeypatch.setenv("RBT_TRACE", "1")
    path = str(tmp_path / "trace.jsonl")
    obs_trace.configure(path)
    obs_flight.set_component("serve")
    try:
        with obs_trace.span("phase", i=0):
            pass

        def other():
            with obs_trace.span("phase", i=1):
                pass

        t = threading.Thread(target=other)
        t.start()
        t.join()
    finally:
        obs_trace.close()
        obs_trace.configure(None)
        obs_flight.set_component("proc")
    events = []
    with open(path) as f:
        assert f.readline().strip() == "["
        for line in f:
            line = line.strip().rstrip(",")
            if line:
                events.append(json.loads(line))
    meta = [e for e in events if e["ph"] == "M"]
    procs = [e for e in meta if e["name"] == "process_name"]
    threads = [e for e in meta if e["name"] == "thread_name"]
    assert len(procs) == 1
    assert "serve@" in procs[0]["args"]["name"]
    assert f"pid={os.getpid()}" in procs[0]["args"]["name"]
    assert len(threads) == 2  # two distinct recording threads
    # Events carry the derived trace pid (stable, host-scoped), and the
    # metadata rows carry the same one — merged files can't collide.
    spans = [e for e in events if e["ph"] == "X"]
    assert {e["pid"] for e in spans} == {obs_trace.trace_pid()}
    assert procs[0]["pid"] == obs_trace.trace_pid()


# ---------------------------------------------------------------------------
# Engine: always-on timelines + tail sampling
# ---------------------------------------------------------------------------

def _engine(cfg, params, **kw):
    from runbooks_tpu.serve.engine import InferenceEngine

    return InferenceEngine(cfg, params, max_slots=2, seed=0, **kw)


def test_engine_timeline_reconstructible_from_ring(tmp_path):
    """RBT_TRACE stays OFF: the ring alone reconstructs one request's
    queue-wait -> prefill -> decode path, and stays bounded under
    sustained traffic."""
    from runbooks_tpu.serve.engine import Request

    obs_trace.configure(str(tmp_path / "trace.jsonl"))
    # Small ring so 8 waves genuinely wrap it; the LAST wave's full
    # timeline must still be reconstructible from what remains.
    obs_flight.RING.resize(32)
    try:
        cfg = tiny_cfg()
        engine = _engine(cfg, tiny_params(cfg))
        for wave in range(8):
            reqs = [Request(prompt_tokens=[1, 2, 3], max_tokens=4,
                            request_id=f"w{wave}-r{i}")
                    for i in range(2)]
            engine.generate(reqs)
        events = obs_flight.RING.snapshot(request_id="w7-r0")
        names = {e["name"] for e in events}
        assert {"queue_wait", "prefill", "decode"} <= names
        stats = obs_flight.RING.stats()
        assert stats["events"] <= stats["capacity"] == 32
        assert stats["dropped"] > 0  # sustained traffic really wrapped
        assert not os.path.exists(tmp_path / "trace.jsonl")
    finally:
        obs_flight.RING.resize(obs_flight.ring_capacity())


def test_tail_sampling_promotes_only_interesting_requests(
        tmp_path, monkeypatch):
    from runbooks_tpu.obs.metrics import REGISTRY
    from runbooks_tpu.serve.engine import Request

    path = str(tmp_path / "trace.jsonl")
    obs_trace.configure(path)
    cfg = tiny_cfg()
    engine = _engine(cfg, tiny_params(cfg))

    # Threshold far above any CPU request time: nothing promotes.
    monkeypatch.setenv("RBT_TRACE_TAIL_MS", "3600000")
    engine.generate([Request(prompt_tokens=[1, 2, 3], max_tokens=4,
                             request_id="fast-1")])
    assert not os.path.exists(path)

    # Threshold 0: every finish is "slow" -> promoted even with
    # RBT_TRACE=0, with the tail_sample marker naming the reason.
    monkeypatch.setenv("RBT_TRACE_TAIL_MS", "0")
    before = REGISTRY.counter_value("serve_tail_samples_total",
                                    reason="slow")
    engine.generate([Request(prompt_tokens=[1, 2, 3], max_tokens=4,
                             request_id="slow-1")])
    assert REGISTRY.counter_value("serve_tail_samples_total",
                                  reason="slow") == before + 1
    events = []
    with open(path) as f:
        assert f.readline().strip() == "["
        for line in f:
            line = line.strip().rstrip(",")
            if line:
                events.append(json.loads(line))
    promoted = [e for e in events
                if obs_flight._matches(e, "slow-1")]
    assert {"queue_wait", "prefill", "decode"} <= \
        {e["name"] for e in promoted}
    markers = [e for e in events if e["name"] == "tail_sample"]
    assert markers and markers[-1]["args"]["reason"] == "slow"
    # The fast request's timeline never reached the file.
    assert not any(obs_flight._matches(e, "fast-1") for e in events)

    # Deadline expiry promotes regardless of the latency threshold.
    monkeypatch.delenv("RBT_TRACE_TAIL_MS")
    before_dl = REGISTRY.counter_value("serve_tail_samples_total",
                                       reason="deadline")
    req = Request(prompt_tokens=[1, 2, 3], max_tokens=512,
                  deadline_s=0.001, request_id="late-1")
    engine.generate([req])
    assert req.finish_reason == "deadline"
    assert REGISTRY.counter_value("serve_tail_samples_total",
                                  reason="deadline") == before_dl + 1


# ---------------------------------------------------------------------------
# Incident snapshots: engine crash, trainer abort, HTTP surface
# ---------------------------------------------------------------------------

def _bundles(root):
    inc_dir = os.path.join(str(root), "artifacts", "incidents")
    if not os.path.isdir(inc_dir):
        return []
    return sorted(os.path.join(inc_dir, n) for n in os.listdir(inc_dir)
                  if n.endswith(".json"))


def test_engine_crash_captures_exactly_one_bundle(tmp_path, monkeypatch):
    """RBT_FAULT_INJECT=engine:K: the worker's crash handler dooms the
    in-flight futures, captures ONE incident bundle (debounce verified),
    error-promotes the doomed timelines, and the reset engine serves
    again."""
    from runbooks_tpu.serve.api import EngineWorker
    from runbooks_tpu.serve.engine import EngineStepFailed, Request

    monkeypatch.setenv("RBT_CONTENT_DIR", str(tmp_path))
    obs_trace.configure(str(tmp_path / "artifacts" / "trace.jsonl"))
    # Fault at step 1: step 0 completes (queue_wait/prefill/decode land
    # in the ring), then the second step blows up with the request
    # still in flight — the realistic mid-request crash.
    monkeypatch.setenv("RBT_FAULT_INJECT", "engine:1")
    cfg = tiny_cfg()
    engine = _engine(cfg, tiny_params(cfg))
    monkeypatch.delenv("RBT_FAULT_INJECT")
    worker = EngineWorker(engine)
    try:
        fut = worker.submit(Request(prompt_tokens=[1, 2, 3], max_tokens=32,
                                    request_id="doomed-1"))
        with pytest.raises(EngineStepFailed):
            fut.result(timeout=60)
        deadline = time.monotonic() + 10
        while not _bundles(tmp_path) and time.monotonic() < deadline:
            time.sleep(0.02)
        bundles = _bundles(tmp_path)
        assert len(bundles) == 1, bundles
        bundle = json.load(open(bundles[0]))
        assert bundle["reason"] == "engine_crash"
        assert "doomed-1" in bundle["extra"]["doomed_requests"]
        # The acceptance surface: flight ring + memory/program census +
        # metrics snapshot all present and parseable.
        assert bundle["flight"]["events"], "flight ring missing"
        assert "live_arrays" in bundle["memory"]
        assert any(p.get("component") == "serve"
                   for p in bundle["programs"])
        assert "serve_incidents_total" in bundle["metrics"]
        assert "unexpected" in bundle["compiles"]
        # Debounce: an immediate second capture for the same reason is
        # swallowed — a crash storm leaves one bundle per window.
        assert obs_incident.capture("engine_crash") is None
        assert len(_bundles(tmp_path)) == 1
        # Doomed request's timeline was error-promoted to trace.jsonl.
        trace_path = tmp_path / "artifacts" / "trace.jsonl"
        assert trace_path.exists()
        text = trace_path.read_text()
        assert "doomed-1" in text and "tail_sample" in text
        # The reset engine serves the next request normally.
        ok = worker.submit(Request(prompt_tokens=[1, 2, 3], max_tokens=4,
                                   request_id="after-1"))
        assert len(ok.result(timeout=60).output_tokens) == 4
    finally:
        worker.stop()


def test_trainer_max_bad_steps_abort_captures_incident(
        tmp_path, monkeypatch):
    from runbooks_tpu.parallel.mesh import MeshConfig
    from runbooks_tpu.train.optimizer import OptimizerConfig
    from runbooks_tpu.train.trainer import TrainJobConfig, run_training

    monkeypatch.setenv("RBT_FAULT_INJECT", "nonfinite:2+")
    job = TrainJobConfig(
        model="debug", model_overrides={"dtype": "float32"},
        mesh=MeshConfig(data=2, fsdp=2, tensor=2),
        optimizer=OptimizerConfig(learning_rate=1e-3, warmup_steps=0,
                                  total_steps=100, schedule="constant"),
        batch_size=4, seq_len=32, steps=10, checkpoint_every=100,
        log_every=1, max_bad_steps=2, artifacts_dir=str(tmp_path))
    with pytest.raises(RuntimeError, match="consecutive non-finite"):
        run_training(job)
    inc_dir = tmp_path / "incidents"
    bundles = sorted(inc_dir.glob("*.json"))
    assert len(bundles) == 1
    bundle = json.load(open(bundles[0]))
    assert bundle["reason"] == "train_max_bad_steps"
    assert bundle["extra"]["bad_streak"] == 2
    assert bundle["flight"]["events"], "trainer spans missing from ring"


def test_http_incident_endpoints_and_debounce(tmp_path, monkeypatch):
    """POST /debug/incident captures (once per debounce window); GET
    /debug/incidents lists and fetches."""
    from aiohttp.test_utils import TestClient, TestServer

    from runbooks_tpu.serve.api import create_server

    monkeypatch.setenv("RBT_CONTENT_DIR", str(tmp_path))
    cfg = tiny_cfg()
    app = create_server(cfg, tiny_params(cfg), max_slots=2, warmup=False)

    async def drive():
        async with TestClient(TestServer(app)) as client:
            r = await client.post("/v1/completions", json={
                "prompt": "hi", "max_tokens": 2})
            assert r.status == 200
            r = await client.post("/debug/incident",
                                  json={"reason": "manual-test"})
            body = await r.json()
            assert body["path"] and not body["debounced"]
            assert os.path.exists(body["path"])
            # Same reason inside the window: debounced, still 1 bundle.
            r = await client.post("/debug/incident",
                                  json={"reason": "manual-test"})
            body2 = await r.json()
            assert body2["debounced"] and body2["path"] is None
            assert len(_bundles(tmp_path)) == 1
            r = await client.get("/debug/incidents")
            listing = await r.json()
            assert len(listing["incidents"]) == 1
            name = listing["incidents"][0]["name"]
            assert listing["incidents"][0]["reason"] == "manual-test"
            r = await client.get(f"/debug/incidents?name={name}")
            bundle = await r.json()
            assert bundle["reason"] == "manual-test"
            assert bundle["flight"]["events"]
            r = await client.get("/debug/incidents?name=../../etc/passwd")
            assert r.status == 404
            # /debug/flight on the serve tier: request-indexed.
            r = await client.get("/debug/flight")
            flight_body = await r.json()
            assert flight_body["component"] == "serve"
            assert flight_body["stats"]["events"] > 0
            # /metrics carries the new families.
            r = await client.get("/metrics")
            text = await r.text()
            assert "flight_ring_events" in text
            assert 'serve_incidents_total{reason="manual-test"} 1' in text
            assert "serve_incident_age_seconds" in text

    asyncio.run(drive())


# ---------------------------------------------------------------------------
# Gateway hop stitching + `rbt trace` end to end (real HTTP stack)
# ---------------------------------------------------------------------------

class _AppHost:
    """Run aiohttp apps on a dedicated thread's event loop so the main
    thread can drive them with sync urllib (the CLI's transport)."""

    def __init__(self, apps):
        from aiohttp import web

        self._web = web
        self.urls = []
        self._loop = asyncio.new_event_loop()
        self._runners = []
        self._thread = threading.Thread(target=self._loop.run_forever,
                                        daemon=True)
        self._thread.start()
        for app in apps:
            fut = asyncio.run_coroutine_threadsafe(self._start(app),
                                                   self._loop)
            self.urls.append(fut.result(timeout=120))

    async def _start(self, app):
        runner = self._web.AppRunner(app)
        await runner.setup()
        site = self._web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        self._runners.append(runner)
        port = runner.addresses[0][1]
        return f"http://127.0.0.1:{port}"

    def stop(self):
        async def teardown():
            for runner in self._runners:
                await runner.cleanup()

        asyncio.run_coroutine_threadsafe(teardown(),
                                         self._loop).result(timeout=30)
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=10)


def _post_json(url, payload, headers=None):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    with urllib.request.urlopen(req, timeout=120) as resp:
        return resp.status, dict(resp.headers), \
            json.loads(resp.read().decode())


def test_rbt_trace_merges_gateway_and_replicas(tmp_path, monkeypatch,
                                               capsys):
    """Acceptance: one request id stitches gateway + 2 real replicas
    through the real HTTP stack, and `rbt trace` prints one merged,
    clock-ordered timeline."""
    from runbooks_tpu.cli import main as cli
    from runbooks_tpu.serve.api import create_server
    from runbooks_tpu.serve.gateway import create_gateway

    monkeypatch.setenv("RBT_CONTENT_DIR", str(tmp_path))
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    replicas = [create_server(cfg, params, max_slots=2, warmup=True)
                for _ in range(2)]
    host = _AppHost(replicas)
    try:
        gw = create_gateway(
            {f"r{i}": url for i, url in enumerate(host.urls)},
            scrape_interval_s=0)
        gw_host = _AppHost([gw])
        try:
            gw_url = gw_host.urls[0]
            # Client supplies NO id: the gateway mints one and forwards
            # it (plus a minted traceparent) upstream.
            status, headers, _body = _post_json(
                f"{gw_url}/v1/completions",
                {"prompt": "stitch me", "max_tokens": 3})
            assert status == 200
            rid = headers["X-Request-Id"]
            assert rid.startswith("req-")
            assert headers.get("traceparent")
            backend = headers["X-Gateway-Replica"]
            # Gateway access-log line carries the same id (grep parity
            # with the serve tier's access line).
            out = capsys.readouterr().out
            assert f"gateway: access /v1/completions rid={rid}" in out
            assert f"backend={backend}" in out

            # Gateway ring: route decision + proxy span under this id;
            # replica ring: the engine phases under the SAME id.
            with urllib.request.urlopen(
                    f"{gw_url}/debug/flight?request_id={rid}",
                    timeout=30) as resp:
                gw_flight = json.loads(resp.read().decode())
            assert gw_flight["component"] == "gateway"
            assert set(gw_flight["replicas"]) == {"r0", "r1"}
            gw_names = {e["name"] for e in gw_flight["events"]}
            assert {"route_decision", "proxy"} <= gw_names

            # An explicit client id is accepted verbatim (sanitized)
            # and rides to the replica's ring too.
            status, headers2, _ = _post_json(
                f"{gw_url}/v1/completions",
                {"prompt": "stitch me again", "max_tokens": 3},
                headers={"X-Request-Id": "trace-e2e-1"})
            assert headers2["X-Request-Id"] == "trace-e2e-1"
            capsys.readouterr()

            # `rbt trace` against the gateway: merged timeline across
            # the gateway + both replicas, clock-ordered, covering both
            # tiers' phases. (In this in-process test all three apps
            # share ONE ring/identity, so the POD labels all read
            # gateway@<host> and duplicates dedupe; distinct-pod
            # labeling is covered by test_merged_timeline_labels.)
            rc = cli.main(["trace", "trace-e2e-1", "--url", gw_url])
            assert rc == 0
            out = capsys.readouterr().out
            assert "across 3 pod(s)" in out
            for phase in ("route_decision", "proxy", "queue_wait",
                          "prefill", "decode"):
                assert phase in out, f"{phase} missing from timeline:\n{out}"
            # Clock-ordered: offsets are non-decreasing down the table.
            offsets = [float(line.split("ms", 1)[0].lstrip("+"))
                       for line in out.splitlines()
                       if line.startswith("+")]
            assert offsets == sorted(offsets)

            # `rbt incidents` end to end over the same transport.
            _post_json(f"{host.urls[0]}/debug/incident",
                       {"reason": "e2e"})
            rc = cli.main(["incidents", "--url", host.urls[0]])
            assert rc == 0
            out = capsys.readouterr().out
            assert "e2e" in out
        finally:
            gw_host.stop()
    finally:
        host.stop()


def test_merged_timeline_labels_and_dedupe():
    """Pure-function coverage of the cross-pod merge: distinct sources
    keep their component@host labels, events interleave by wall clock,
    and identical events fetched from two sources dedupe to the first."""
    from runbooks_tpu.cli.main import _format_timeline, _merged_timeline

    gw_event = {"name": "proxy", "ph": "X", "ts": 1000.0, "dur": 500.0,
                "pid": 1, "tid": 1,
                "args": {"request_id": "r", "backend": "r0"}}
    rep_event = {"name": "prefill", "ph": "X", "ts": 1200.0, "dur": 100.0,
                 "pid": 2, "tid": 1, "args": {"request_id": "r"}}
    merged = _merged_timeline([
        ("gateway@gw-0", {"events": [gw_event]}),
        ("serve@srv-1/r0", {"events": [rep_event, gw_event]}),
    ])
    assert [(label, e["name"]) for _, label, e in merged] == [
        ("gateway@gw-0", "proxy"), ("serve@srv-1/r0", "prefill")]
    rows = _format_timeline(merged)
    assert rows[0][0] == "+0.0ms" and rows[0][1] == "gateway@gw-0"
    assert rows[1][0] == "+0.2ms" and rows[1][1] == "serve@srv-1/r0"
    assert "backend=r0" in rows[0][4]


# ---------------------------------------------------------------------------
# Controller: SLOViolated onset fires per-replica captures
# ---------------------------------------------------------------------------

def test_slo_onset_fires_incident_capture(tmp_path, monkeypatch):
    """An SLOViolated onset POSTs /debug/incident to every running
    replica (side thread), the bundle lands once (replica-side
    debounce), and .status.lastIncident points at it."""
    from runbooks_tpu.api.types import API_VERSION, Model, Server
    from runbooks_tpu.cloud.base import CommonConfig
    from runbooks_tpu.cloud.local import LocalCloud
    from runbooks_tpu.controller import fleet as fl
    from runbooks_tpu.controller import server as server_mod
    from runbooks_tpu.controller.manager import Ctx, Manager
    from runbooks_tpu.controller.model import ModelReconciler
    from runbooks_tpu.controller.server import INCIDENTS, ServerReconciler
    from runbooks_tpu.k8s import objects as ko
    from runbooks_tpu.k8s.fake import FakeCluster
    from tests.test_gateway import load_sample

    monkeypatch.setenv("RBT_CONTENT_DIR", str(tmp_path))
    fl.FLEET.reset()
    INCIDENTS.reset()

    # Replica stub: the REAL capture behind the real HTTP verb the
    # controller uses (the full aiohttp endpoint is covered above).
    class Handler(BaseHTTPRequestHandler):
        def do_POST(self):
            assert self.path == "/debug/incident"
            n = int(self.headers.get("Content-Length") or 0)
            body = json.loads(self.rfile.read(n) or b"{}")
            path = obs_incident.capture(body.get("reason", "manual"))
            payload = json.dumps({"path": path,
                                  "debounced": path is None}).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(payload)))
            self.end_headers()
            self.wfile.write(payload)

        def log_message(self, *args):
            return

    httpd = HTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        client = FakeCluster()
        cloud = LocalCloud(CommonConfig(
            cluster_name="t", artifact_bucket_url=f"file://{tmp_path}/b",
            registry_url="r.local:5000"))
        from runbooks_tpu.sci.base import FakeSCI

        ctx = Ctx(client=client, cloud=cloud, sci=FakeSCI())
        mgr = Manager(ctx, [ModelReconciler(), ServerReconciler()])
        client.create(Model.new("m", spec={"image": "loader"}).obj)
        client.create(Server.new("srv", spec={
            "image": "img", "model": {"name": "m"},
            "slo": {"queueWaitP90Ms": 50}}).obj)
        client.create({
            "apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": "srv-0", "namespace": "default",
                         "labels": {"server": "srv", "role": "run"},
                         "annotations": {fl.METRICS_PORT_ANNOTATION:
                                         str(httpd.server_address[1])}},
            "spec": {}, "status": {"phase": "Running",
                                   "podIP": "127.0.0.1"}})
        mgr.reconcile_until_stable()
        client.mark_job_complete("default", "m-modeller")
        mgr.reconcile_until_stable()

        key = ("Server", "default", "srv")
        # Sustained 400 ms queue wait >> the 50 ms objective: onset.
        fl.FLEET.update(key, load_sample("srv-0", qw_s=0.4, active=4,
                                         queue=6))
        mgr.process_event("Server",
                          client.get(API_VERSION, "Server", "default",
                                     "srv"))
        srv = client.get(API_VERSION, "Server", "default", "srv")
        assert ko.is_condition_true(srv, "SLOViolated")
        assert INCIDENTS.wait(("default", "srv"), timeout_s=15)
        bundles = _bundles(tmp_path)
        assert len(bundles) == 1, bundles
        bundle = json.load(open(bundles[0]))
        assert bundle["reason"].startswith("slo_")
        assert "metrics" in bundle and "memory" in bundle
        # Next reconcile folds the sweep into status.lastIncident.
        mgr.process_event("Server",
                          client.get(API_VERSION, "Server", "default",
                                     "srv"))
        srv = client.get(API_VERSION, "Server", "default", "srv")
        last = ko.deep_get(srv, "status", "lastIncident")
        assert last["reason"].startswith("slo_")
        assert last["bundles"][0]["replica"] == "srv-0"
        assert last["bundles"][0]["path"] == bundles[0]

        # Clear, then re-violate inside the debounce window: the onset
        # fires again, the REPLICA debounces, still exactly one bundle.
        fl.FLEET.update(key, load_sample("srv-0", qw_s=0.0, active=0,
                                         queue=0))
        mgr.process_event("Server",
                          client.get(API_VERSION, "Server", "default",
                                     "srv"))
        assert not ko.is_condition_true(
            client.get(API_VERSION, "Server", "default", "srv"),
            "SLOViolated")
        fl.FLEET.update(key, load_sample("srv-0", qw_s=0.4, active=4,
                                         queue=6))
        mgr.process_event("Server",
                          client.get(API_VERSION, "Server", "default",
                                     "srv"))
        assert INCIDENTS.wait(("default", "srv"), timeout_s=15)
        assert len(_bundles(tmp_path)) == 1
        result = server_mod.INCIDENTS.take(("default", "srv"))
        assert result["bundles"][0].get("debounced") is True
    finally:
        httpd.shutdown()
        httpd.server_close()
        fl.FLEET.reset()
        INCIDENTS.reset()
