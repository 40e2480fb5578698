"""Fleet telemetry plane tests (ISSUE 6).

Covers: the controller scraper against a real (fake-replica) /metrics
endpoint including histogram re-exposition and a down replica;
SLOViolated condition transitions in both directions across reconciles;
request-id propagation end to end (header in -> engine spans -> header
out); trace.jsonl rotation; `rbt top`; the metrics-catalog drift check;
and the bench regression gate helper.
"""

import dataclasses
import json
import os
import re
import threading

import pytest

from runbooks_tpu.api import conditions as cond
from runbooks_tpu.api.types import API_VERSION, Model, Server
from runbooks_tpu.cloud.base import CommonConfig
from runbooks_tpu.cloud.local import LocalCloud
from runbooks_tpu.controller import fleet as fl
from runbooks_tpu.controller.common import validate_slo
from runbooks_tpu.controller.manager import Ctx, Manager
from runbooks_tpu.controller.model import ModelReconciler
from runbooks_tpu.controller.server import ServerReconciler
from runbooks_tpu.k8s import objects as ko
from runbooks_tpu.k8s.fake import FakeCluster
from runbooks_tpu.obs import metrics as obs_metrics
from runbooks_tpu.obs import trace as obs_trace
from runbooks_tpu.obs.metrics import CATALOG, Registry, serve_metrics
from runbooks_tpu.sci.base import FakeSCI


# ---------------------------------------------------------------------------
# Harness
# ---------------------------------------------------------------------------

@pytest.fixture()
def harness(tmp_path):
    client = FakeCluster()
    cloud = LocalCloud(CommonConfig(
        cluster_name="testcluster",
        artifact_bucket_url=f"file://{tmp_path}/bucket",
        registry_url="registry.local:5000"))
    ctx = Ctx(client=client, cloud=cloud, sci=FakeSCI())
    mgr = Manager(ctx, [ModelReconciler(), ServerReconciler()])
    return client, ctx, mgr


@pytest.fixture(autouse=True)
def clean_fleet_state():
    fl.FLEET.reset()
    yield
    fl.FLEET.reset()


def make_pod(client, name, labels, port, ip="127.0.0.1"):
    client.create({
        "apiVersion": "v1", "kind": "Pod",
        "metadata": {"name": name, "namespace": "default",
                     "labels": dict(labels, role="run"),
                     "annotations": {fl.METRICS_PORT_ANNOTATION: str(port)}},
        "spec": {"containers": [{"name": "c"}]},
        "status": {"phase": "Running", "podIP": ip},
    })


def replica_registry(ttft_values=(0.02, 0.05, 0.4), requests=10, failed=0,
                     tokens=500, slots=3, queue=1):
    reg = Registry()
    reg.set_counter("serve_requests_total", requests)
    reg.set_counter("serve_requests_failed_total", failed)
    reg.set_counter("serve_tokens_generated_total", tokens)
    reg.set_gauge("serve_active_slots", slots)
    reg.set_gauge("serve_queue_depth", queue)
    for v in ttft_values:
        reg.observe("serve_ttft_seconds", v)
        reg.observe("serve_queue_wait_seconds", v / 10)
    return reg


# ---------------------------------------------------------------------------
# Exposition parser (scrape side of obs/metrics.py)
# ---------------------------------------------------------------------------

def test_parse_exposition_round_trip():
    reg = replica_registry()
    reg.set_gauge("weird", 1, path='a"b\\c\nd')
    families = obs_metrics.parse_exposition(reg.render())
    assert families["serve_requests_total"].type == "counter"
    assert families["serve_requests_total"].total() == 10.0
    assert families["serve_active_slots"].value() == 3.0
    # Escaped label values round-trip exactly.
    assert families["weird"].value(path='a"b\\c\nd') == 1.0
    hist = families["serve_ttft_seconds"].merged_histogram()
    assert hist.count == 3
    assert hist.sum == pytest.approx(0.47)
    # The 0.4 observation sits in the 0.5 bucket; p99 lands inside it.
    assert 0.25 <= hist.quantile(0.99) <= 0.5


def test_set_histogram_mirrors_bucket_exactly():
    src = Registry()
    for v in (0.002, 0.03, 7.0):
        src.observe("lat_seconds", v)
    parsed = obs_metrics.parse_exposition(src.render())["lat_seconds"]
    hist = parsed.merged_histogram()
    dst = Registry()
    dst.set_histogram("lat_seconds", hist.bounds, hist.cumulative,
                      hist.count, hist.sum, replica="p0")
    out = obs_metrics.parse_exposition(dst.render())["lat_seconds"]
    mirrored = out.histograms[(("replica", "p0"),)]
    assert mirrored.cumulative == hist.cumulative
    assert mirrored.count == 3
    assert mirrored.sum == pytest.approx(hist.sum)


def test_registry_drop_series():
    reg = Registry()
    reg.set_gauge("g", 1, replica="a", kind="Server")
    reg.set_counter("c_total", 2, replica="a")
    reg.observe("h_seconds", 0.1, replica="a")
    reg.set_gauge("g", 1, replica="b", kind="Server")
    assert reg.drop_series(replica="a") == 3
    text = reg.render()
    assert 'replica="a"' not in text
    assert 'replica="b"' in text


# ---------------------------------------------------------------------------
# Controller scraper
# ---------------------------------------------------------------------------

def test_scraper_mirrors_replica_metrics_and_marks_down(harness):
    client, ctx, _ = harness
    client.create(Server.new("srv", spec={"image": "x"}).obj)
    reg_a = replica_registry(requests=10, tokens=500)
    reg_b = replica_registry(requests=4, tokens=100, slots=1, failed=2)
    httpd_a = serve_metrics(0, reg_a)
    httpd_b = serve_metrics(0, reg_b)
    make_pod(client, "srv-a", {"server": "srv"}, httpd_a.server_address[1])
    make_pod(client, "srv-b", {"server": "srv"}, httpd_b.server_address[1])

    registry, state = Registry(), fl.FleetState()
    scraper = fl.FleetScraper(ctx, state=state, registry=registry)
    try:
        assert scraper.scrape_once() == 2
        text = registry.render()
        # Per-replica mirrored series with {kind, name, replica} labels.
        for rep, val in (("srv-a", 10.0), ("srv-b", 4.0)):
            assert (f'serve_requests_total{{kind="Server",name="srv",'
                    f'namespace="default",replica="{rep}"}} {val}') in text
        # Histograms re-expose bucket-exactly (cumulative le series).
        assert re.search(
            r'serve_ttft_seconds_bucket\{[^}]*replica="srv-a"[^}]*\} \d',
            text)
        # Freshness/liveness gauges.
        assert 'fleet_scrape_up{kind="Server",name="srv",' \
               'namespace="default",replica="srv-a"} 1' in text
        assert "fleet_scrape_age_seconds" in text
        # Aggregated summary merges across replicas.
        summary = state.server_summary("default", "srv")
        assert summary["replicas"] == 2 and summary["replicasUp"] == 2
        assert summary["activeSlots"] == 4
        assert summary["requestsTotal"] == 14
        assert summary["errorRatePct"] == pytest.approx(2 / 14 * 100, 0.01)
        assert summary["ttftP99Ms"] > 0

        # Replica b dies: next sweep marks it down, keeps a up.
        httpd_b.shutdown()
        httpd_b.server_close()
        assert scraper.scrape_once() == 1
        text = registry.render()
        assert 'fleet_scrape_up{kind="Server",name="srv",' \
               'namespace="default",replica="srv-b"} 0' in text
        assert 'fleet_scrape_up{kind="Server",name="srv",' \
               'namespace="default",replica="srv-a"} 1' in text
        summary = state.server_summary("default", "srv")
        assert summary["replicasUp"] == 1
        assert summary["activeSlots"] == 3  # only the live replica counts

        # Pod deleted entirely: its mirrored series are dropped, not
        # frozen at their last values.
        client.delete("v1", "Pod", "default", "srv-b")
        scraper.scrape_once()
        assert 'replica="srv-b"' not in registry.render()
    finally:
        httpd_a.shutdown()
        httpd_a.server_close()


def test_scraper_tokens_per_sec_rate(harness):
    client, ctx, _ = harness
    client.create(Server.new("srv", spec={"image": "x"}).obj)
    reg = replica_registry(tokens=1000)
    httpd = serve_metrics(0, reg)
    make_pod(client, "srv-a", {"server": "srv"}, httpd.server_address[1])
    registry, state = Registry(), fl.FleetState()
    scraper = fl.FleetScraper(ctx, state=state, registry=registry)
    try:
        scraper.scrape_once()
        assert state.server_summary("default", "srv")["tokensPerSec"] == 0.0
        reg.set_counter("serve_tokens_generated_total", 2000)
        import time

        time.sleep(0.05)
        scraper.scrape_once()
        tps = state.server_summary("default", "srv")["tokensPerSec"]
        assert tps > 0, "second scrape should compute a token rate"
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_scraper_training_pod_summary(harness):
    client, ctx, _ = harness
    client.create(Model.new("m", spec={"image": "trainer"}).obj)
    reg = Registry()
    reg.set_gauge("train_step", 40)
    reg.set_gauge("train_loss", 2.125)
    reg.set_gauge("train_goodput_ratio", 0.95)
    httpd = serve_metrics(0, reg)
    make_pod(client, "m-modeller-0", {"model": "m"},
             httpd.server_address[1])
    registry, state = Registry(), fl.FleetState()
    scraper = fl.FleetScraper(ctx, state=state, registry=registry)
    try:
        assert scraper.scrape_once() == 1
        summary = state.model_summary("default", "m")
        assert summary == {"replicas": 1, "replicasUp": 1, "step": 40,
                           "loss": 2.125, "goodput": 0.95}
        assert 'train_step{kind="Model"' in registry.render()
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_modeller_job_exposes_metrics_port(harness):
    client, ctx, mgr = harness
    client.create(Model.new("m", spec={"image": "trainer"}).obj)
    mgr.reconcile_until_stable()
    job = client.get("batch/v1", "Job", "default", "m-modeller")
    container = job["spec"]["template"]["spec"]["containers"][0]
    assert {"name": "metrics", "containerPort": 8080} in container["ports"]
    env = {e["name"]: e.get("value") for e in container["env"]}
    assert env["RBT_METRICS_PORT"] == "8080"
    # The compile cache is placed from outside, on the durable mount.
    assert env["JAX_COMPILATION_CACHE_DIR"] == "/content/artifacts/jax_cache"


# ---------------------------------------------------------------------------
# SLO conditions + status telemetry
# ---------------------------------------------------------------------------

def ttft_sample(replica, ttft_s, n=10, extra=None):
    """A synthetic up-replica sample whose merged TTFT p99 ~= ttft_s."""
    fam = obs_metrics.ParsedFamily("serve_ttft_seconds", "histogram")
    hist = obs_metrics.ParsedHistogram()
    hist.bounds = [b for b in obs_metrics.DEFAULT_BUCKETS]
    import bisect

    idx = bisect.bisect_left(hist.bounds, ttft_s)
    cum = []
    acc = 0
    for i in range(len(hist.bounds)):
        if i == idx:
            acc = n
        cum.append(acc)
    hist.cumulative = cum
    hist.count = n
    hist.sum = ttft_s * n
    fam.histograms[()] = hist
    fams = {"serve_ttft_seconds": fam}
    slots = obs_metrics.ParsedFamily("serve_active_slots", "gauge")
    slots.samples[()] = 2.0
    fams["serve_active_slots"] = slots
    reqs = obs_metrics.ParsedFamily("serve_requests_total", "counter")
    reqs.samples[()] = float(n)
    fams["serve_requests_total"] = reqs
    if extra:
        fams.update(extra)
    return fl.ReplicaSample(replica, up=True, last_success=0.0,
                            families=fams)


def test_slo_violated_condition_transitions(harness):
    client, ctx, mgr = harness
    client.create(Model.new("m", spec={"image": "loader"}).obj)
    client.create(Server.new("srv", spec={
        "image": "img", "model": {"name": "m"},
        "slo": {"ttftP99Ms": 100}}).obj)
    mgr.reconcile_until_stable()
    client.mark_job_complete("default", "m-modeller")
    mgr.reconcile_until_stable()
    client.mark_deployment_ready("default", "srv")
    mgr.reconcile_until_stable()

    # No scrape data yet: condition present but False/NoTelemetry.
    srv = client.get(API_VERSION, "Server", "default", "srv")
    c = ko.get_condition(srv, cond.SLO_VIOLATED)
    assert c["status"] == "False" and c["reason"] == cond.REASON_SLO_NO_DATA

    from runbooks_tpu.controller.metrics import REGISTRY

    before = REGISTRY.counter_value(
        "controller_slo_violations_total", server="srv",
        objective=cond.REASON_SLO_TTFT)

    # Violating traffic lands in the fleet state -> ONE reconcile flips
    # the condition (acceptance: within one reconcile).
    fl.FLEET.update(("Server", "default", "srv"),
                    ttft_sample("srv-pod", 0.4))
    mgr.process_event("Server",
                      client.get(API_VERSION, "Server", "default", "srv"))
    srv = client.get(API_VERSION, "Server", "default", "srv")
    c = ko.get_condition(srv, cond.SLO_VIOLATED)
    assert c["status"] == "True"
    assert c["reason"] == cond.REASON_SLO_TTFT
    assert "ttftP99Ms" in c["message"] and "100" in c["message"]
    # Onset counted once.
    assert REGISTRY.counter_value(
        "controller_slo_violations_total", server="srv",
        objective=cond.REASON_SLO_TTFT) == before + 1
    # .status.telemetry carries the live load summary.
    telem = ko.deep_get(srv, "status", "telemetry")
    assert telem["activeSlots"] == 2
    assert telem["ttftP99Ms"] > 100

    # Load drops -> the condition sheds on the next reconcile.
    fl.FLEET.update(("Server", "default", "srv"),
                    ttft_sample("srv-pod", 0.01))
    mgr.process_event("Server",
                      client.get(API_VERSION, "Server", "default", "srv"))
    srv = client.get(API_VERSION, "Server", "default", "srv")
    c = ko.get_condition(srv, cond.SLO_VIOLATED)
    assert c["status"] == "False" and c["reason"] == cond.REASON_SLO_MET
    # No new onset counted.
    assert REGISTRY.counter_value(
        "controller_slo_violations_total", server="srv",
        objective=cond.REASON_SLO_TTFT) == before + 1


def test_slo_error_rate_objective(harness):
    client, ctx, mgr = harness
    client.create(Model.new("m", spec={"image": "loader"}).obj)
    client.create(Server.new("srv", spec={
        "image": "img", "model": {"name": "m"},
        "slo": {"errorRatePct": 5}}).obj)
    mgr.reconcile_until_stable()
    client.mark_job_complete("default", "m-modeller")
    failed = obs_metrics.ParsedFamily("serve_requests_failed_total",
                                      "counter")
    failed.samples[()] = 3.0
    fl.FLEET.update(
        ("Server", "default", "srv"),
        ttft_sample("p0", 0.01,
                    extra={"serve_requests_failed_total": failed}))
    mgr.reconcile_until_stable()
    srv = client.get(API_VERSION, "Server", "default", "srv")
    c = ko.get_condition(srv, cond.SLO_VIOLATED)
    assert c["status"] == "True"
    assert c["reason"] == cond.REASON_SLO_ERROR_RATE


def test_slo_holds_verdict_through_total_outage(harness):
    """Every replica down: the last SLO verdict HOLDS (an outage must
    not clear an active violation), and the dead replica's token-rate
    gauge resets so it never reads as still serving."""
    client, ctx, mgr = harness
    client.create(Model.new("m", spec={"image": "loader"}).obj)
    client.create(Server.new("srv", spec={
        "image": "img", "model": {"name": "m"},
        "slo": {"ttftP99Ms": 100}}).obj)
    mgr.reconcile_until_stable()
    client.mark_job_complete("default", "m-modeller")
    fl.FLEET.update(("Server", "default", "srv"),
                    ttft_sample("srv-pod", 0.4))
    mgr.reconcile_until_stable()
    srv = client.get(API_VERSION, "Server", "default", "srv")
    assert ko.is_condition_true(srv, cond.SLO_VIOLATED)

    # Replica goes down (pod still present): verdict unchanged.
    down = dataclasses.replace(
        fl.FLEET.get_sample(("Server", "default", "srv"), "srv-pod"),
        up=False)
    fl.FLEET.update(("Server", "default", "srv"), down)
    mgr.process_event("Server",
                      client.get(API_VERSION, "Server", "default", "srv"))
    srv = client.get(API_VERSION, "Server", "default", "srv")
    c = ko.get_condition(srv, cond.SLO_VIOLATED)
    assert c["status"] == "True" and c["reason"] == cond.REASON_SLO_TTFT


def test_down_replica_token_rate_resets(harness):
    client, ctx, _ = harness
    client.create(Server.new("srv", spec={"image": "x"}).obj)
    reg = replica_registry(tokens=1000)
    httpd = serve_metrics(0, reg)
    make_pod(client, "srv-a", {"server": "srv"}, httpd.server_address[1])
    registry, state = Registry(), fl.FleetState()
    scraper = fl.FleetScraper(ctx, state=state, registry=registry)
    try:
        scraper.scrape_once()
        reg.set_counter("serve_tokens_generated_total", 5000)
        import time

        time.sleep(0.05)
        scraper.scrape_once()
        fam = obs_metrics.parse_exposition(
            registry.render())["fleet_tokens_per_sec"]
        assert fam.total() > 0
    finally:
        httpd.shutdown()
        httpd.server_close()
    # Endpoint dead, pod still Running: the rate gauge must drop to 0.
    scraper.scrape_once()
    fam = obs_metrics.parse_exposition(
        registry.render())["fleet_tokens_per_sec"]
    assert fam.total() == 0.0


def test_scraper_survives_label_collisions(harness):
    """A scraped exposition already carrying kind/replica labels (a
    process sharing its registry with a controller) must mirror without
    a duplicate-kwarg crash — the scraped pod's identity wins."""
    client, ctx, _ = harness
    client.create(Server.new("srv", spec={"image": "x"}).obj)
    reg = Registry()
    reg.set_gauge("serve_active_slots", 7, kind="Server", name="other",
                  namespace="elsewhere", replica="other-pod")
    reg.observe("serve_ttft_seconds", 0.1, kind="Server",
                replica="other-pod")
    httpd = serve_metrics(0, reg)
    make_pod(client, "srv-a", {"server": "srv"}, httpd.server_address[1])
    registry, state = Registry(), fl.FleetState()
    scraper = fl.FleetScraper(ctx, state=state, registry=registry)
    try:
        assert scraper.scrape_once() == 1
    finally:
        httpd.shutdown()
        httpd.server_close()
    text = registry.render()
    assert 'serve_active_slots{kind="Server",name="srv",' \
           'namespace="default",replica="srv-a"} 7' in text


def test_rbt_top_separates_namespaces(capsys):
    """Same-named Servers in two namespaces must not blend each other's
    series in the top table."""
    from runbooks_tpu.cli import main as cli

    reg = Registry()
    for ns, slots in (("a", 1), ("b", 5)):
        lbl = dict(kind="Server", namespace=ns, name="chat",
                   replica=f"chat-{ns}")
        reg.set_gauge("fleet_scrape_up", 1, **lbl)
        reg.set_gauge("fleet_scrape_age_seconds", 0.0, **lbl)
        reg.set_gauge("serve_active_slots", slots, **lbl)
        reg.set_gauge("fleet_slo_violated", 1 if ns == "b" else 0,
                      kind="Server", namespace=ns, name="chat")
    httpd = serve_metrics(0, reg)
    try:
        assert cli.main(["top", "--once",
                         "--url",
                         f"http://127.0.0.1:{httpd.server_address[1]}"]) \
            == 0
    finally:
        httpd.shutdown()
        httpd.server_close()
    out = capsys.readouterr().out
    row_a = next(ln for ln in out.splitlines() if "chat-a" in ln)
    row_b = next(ln for ln in out.splitlines() if "chat-b" in ln)
    assert "slots=1" in row_a and "ok" in row_a
    assert "slots=5" in row_b and "VIOLATED" in row_b


def test_invalid_slo_surfaces_condition(harness):
    client, ctx, mgr = harness
    client.create(Server.new("bad", spec={
        "image": "img", "model": {"name": "m"},
        "slo": {"ttftP99": 100}}).obj)  # typo'd objective name
    mgr.reconcile_until_stable()
    srv = client.get(API_VERSION, "Server", "default", "bad")
    c = ko.get_condition(srv, cond.SERVING)
    assert c["status"] == "False"
    assert c["reason"] == cond.REASON_INVALID_PARAMS
    assert "ttftP99" in c["message"]

    assert validate_slo(None) is None
    assert validate_slo({"ttftP99Ms": 100}) is None
    assert "not a number" in validate_slo({"ttftP99Ms": "fast"})
    assert "> 0" in validate_slo({"queueWaitP90Ms": 0})
    assert "unknown objective" in validate_slo({"p99": 1})


def test_model_status_telemetry(harness):
    client, ctx, mgr = harness
    client.create(Model.new("m", spec={"image": "trainer"}).obj)
    step = obs_metrics.ParsedFamily("train_step", "gauge")
    step.samples[()] = 40.0
    loss = obs_metrics.ParsedFamily("train_loss", "gauge")
    loss.samples[()] = 2.5
    fl.FLEET.update(("Model", "default", "m"), fl.ReplicaSample(
        "m-0", up=True, last_success=0.0,
        families={"train_step": step, "train_loss": loss}))
    mgr.reconcile_until_stable()
    m = client.get(API_VERSION, "Model", "default", "m")
    telem = ko.deep_get(m, "status", "telemetry")
    assert telem["step"] == 40 and telem["loss"] == 2.5


# ---------------------------------------------------------------------------
# Request-scoped tracing end to end
# ---------------------------------------------------------------------------

def tiny_cfg():
    from runbooks_tpu.models.config import get_config

    return dataclasses.replace(
        get_config("llama2-7b"), vocab_size=128, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, max_seq_len=64, dtype="float32")


def test_request_id_propagation_end_to_end(tmp_path, monkeypatch, capsys):
    """Header in -> queue/prefill/decode spans -> header out, plus the
    generated-id, traceparent, and access-log paths."""
    import asyncio

    import jax
    from aiohttp.test_utils import TestClient, TestServer

    from runbooks_tpu.models.transformer import init_params
    from runbooks_tpu.serve.api import create_server

    monkeypatch.setenv("RBT_TRACE", "1")
    path = str(tmp_path / "trace.jsonl")
    obs_trace.configure(path)
    cfg = tiny_cfg()
    app = create_server(cfg, init_params(cfg, jax.random.key(0)),
                        max_slots=2, warmup=False)
    tp_in = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"

    async def drive():
        async with TestClient(TestServer(app)) as client:
            r = await client.post(
                "/v1/completions",
                json={"prompt": "hello", "max_tokens": 4},
                headers={"X-Request-Id": "my-req-1", "traceparent": tp_in})
            assert r.status == 200
            assert r.headers["X-Request-Id"] == "my-req-1"
            tp_out = r.headers["traceparent"]
            assert tp_out.startswith("00-" + "ab" * 16 + "-")
            assert tp_out != tp_in  # fresh parent-id for the hop
            # No header: an id is generated and still returned.
            r2 = await client.post("/v1/completions", json={
                "prompt": "hi", "max_tokens": 2})
            assert r2.headers["X-Request-Id"].startswith("req-")
            # SSE streaming carries the id on the stream response.
            r3 = await client.post(
                "/v1/completions",
                json={"prompt": "hey", "max_tokens": 2, "stream": True},
                headers={"X-Request-Id": "sse-req"})
            assert r3.headers["X-Request-Id"] == "sse-req"
            await r3.text()

    try:
        asyncio.run(drive())
    finally:
        obs_trace.close()
        obs_trace.configure(None)

    events = []
    with open(path) as f:
        assert f.readline().strip() == "["
        for line in f:
            line = line.strip().rstrip(",")
            if line:
                events.append(json.loads(line))
    by_phase = {}
    for e in events:
        args = e.get("args", {})
        rids = list(args.get("request_ids", []))
        if "request_id" in args:
            rids.append(args["request_id"])
        if "my-req-1" in rids:
            by_phase[e["name"]] = by_phase.get(e["name"], 0) + 1
    # The request's trace covers its queue wait, its prefill, and every
    # decode chunk it was active in (4 tokens = 1 prefill + 3 decodes).
    assert by_phase.get("queue_wait") == 1
    assert by_phase.get("prefill") == 1
    assert by_phase.get("decode", 0) >= 3
    # Access log lines carry the ids.
    out = capsys.readouterr().out
    assert "rid=my-req-1" in out and "rid=sse-req" in out


def test_request_scope_sanitizes_hostile_ids():
    from runbooks_tpu.serve.api import request_scope

    rid, tp = request_scope({"X-Request-Id": "ok-id\r\nInjected: 1"})
    assert "\r" not in rid and "\n" not in rid and " " not in rid
    assert rid.startswith("ok-id")
    rid, tp = request_scope({})
    assert rid.startswith("req-") and tp is None
    rid, tp = request_scope({"traceparent": "00-" + "0f" * 16 + "-"
                             + "11" * 8 + "-00"})
    assert rid == "0f" * 16
    assert tp is not None and tp.startswith("00-" + "0f" * 16)


# ---------------------------------------------------------------------------
# Trace rotation (satellite)
# ---------------------------------------------------------------------------

def test_trace_rotation_caps_size(tmp_path, monkeypatch):
    monkeypatch.setenv("RBT_TRACE", "1")
    monkeypatch.setenv("RBT_TRACE_MAX_MB", "0.0005")  # ~512 bytes
    path = str(tmp_path / "trace.jsonl")
    obs_trace.configure(path)
    try:
        for i in range(80):
            with obs_trace.span("phase", i=i):
                pass
    finally:
        obs_trace.close()
        obs_trace.configure(None)
    assert os.path.exists(path + ".1"), "rotation never happened"
    cap = int(0.0005 * 2**20)
    # Both generations stay line-parseable with their own '[' header and
    # within a write of the cap.
    for p in (path, path + ".1"):
        assert os.path.getsize(p) <= cap + 200
        with open(p) as f:
            assert f.readline().strip() == "["
            for line in f:
                line = line.strip().rstrip(",")
                if line:
                    json.loads(line)


# ---------------------------------------------------------------------------
# rbt top + rbt get telemetry column
# ---------------------------------------------------------------------------

def test_rbt_top_once_against_controller_metrics(capsys):
    from runbooks_tpu.cli import main as cli

    reg = Registry()
    lbl = dict(kind="Server", namespace="default", name="srv",
               replica="srv-1")
    reg.set_gauge("fleet_scrape_up", 1, **lbl)
    reg.set_gauge("fleet_scrape_age_seconds", 0.0, **lbl)
    reg.set_gauge("fleet_tokens_per_sec", 42.5, **lbl)
    reg.set_gauge("serve_active_slots", 3, **lbl)
    reg.set_gauge("serve_queue_depth", 1, **lbl)
    reg.set_histogram("serve_ttft_seconds", [0.05, 0.1, 0.25],
                      [0, 5, 10], 10, 1.5, **lbl)
    reg.set_gauge("fleet_slo_violated", 1, kind="Server",
                  namespace="default", name="srv")
    mlbl = dict(kind="Model", namespace="default", name="m", replica="m-0")
    reg.set_gauge("fleet_scrape_up", 0, **mlbl)
    reg.set_gauge("fleet_scrape_age_seconds", 33.0, **mlbl)
    reg.set_gauge("train_step", 40, **mlbl)
    reg.set_gauge("train_loss", 2.125, **mlbl)
    httpd = serve_metrics(0, reg)
    try:
        rc = cli.main(["top", "--once",
                       "--url",
                       f"http://127.0.0.1:{httpd.server_address[1]}"])
    finally:
        httpd.shutdown()
        httpd.server_close()
    assert rc == 0
    out = capsys.readouterr().out
    srv_line = next(ln for ln in out.splitlines()
                    if ln.startswith("servers/srv"))
    assert "srv-1" in srv_line and "yes" in srv_line
    assert "VIOLATED" in srv_line
    assert "slots=3" in srv_line and "queue=1" in srv_line
    assert "ttft99=" in srv_line and "tok/s=42.5" in srv_line
    m_line = next(ln for ln in out.splitlines()
                  if ln.startswith("models/m"))
    assert "NO" in m_line and "33s" in m_line
    assert "step=40" in m_line and "loss=2.125" in m_line


def test_rbt_top_once_from_crd_status(monkeypatch, capsys):
    from runbooks_tpu.cli import main as cli

    client = FakeCluster()
    srv = Server.new("srv", spec={"image": "x"})
    srv.obj["status"] = {
        "ready": True,
        "telemetry": {"activeSlots": 2, "queueWaitP90Ms": 12.0,
                      "ttftP99Ms": 88.0, "tokensPerSec": 120.5,
                      "replicas": 2, "replicasUp": 2},
        "conditions": [{"type": "SLOViolated", "status": "True",
                        "reason": "TTFTP99AboveTarget", "message": ""}],
    }
    client.create(srv.obj)
    monkeypatch.setattr(cli, "make_client", lambda args: client)
    assert cli.main(["top", "--once"]) == 0
    out = capsys.readouterr().out
    line = next(ln for ln in out.splitlines()
                if ln.startswith("servers/srv"))
    assert "VIOLATED" in line
    assert "slots=2" in line and "ttft99=88.0ms" in line
    assert "up=2/2" in line


def test_rbt_get_shows_telemetry(monkeypatch, capsys):
    from runbooks_tpu.cli import main as cli

    client = FakeCluster()
    m = Model.new("m1", spec={"image": "x"})
    m.obj["status"] = {"telemetry": {"step": 7, "loss": 3.25,
                                     "goodput": 0.9}}
    client.create(m.obj)
    monkeypatch.setattr(cli, "make_client", lambda args: client)
    assert cli.main(["get", ""]) == 0
    out = capsys.readouterr().out
    assert "TELEMETRY" in out
    assert "step=7" in out and "loss=3.25" in out


# ---------------------------------------------------------------------------
# Metrics-catalog drift check (satellite)
# ---------------------------------------------------------------------------

_METRIC_NAME_RE = re.compile(r"`([a-z][a-z0-9_]*_[a-z0-9_]+)`")


def _doc_catalog_names():
    doc = os.path.join(os.path.dirname(__file__), "..", "docs",
                       "observability.md")
    with open(doc) as f:
        text = f.read()
    section = text.split("### Metric catalog", 1)[1].split("###", 1)[0]
    names = set()
    for line in section.splitlines():
        if not line.startswith("|") or "---" in line:
            continue
        # Only the first (Metric) column holds family names; label/unit
        # columns use single-word tokens that don't match the pattern.
        first_cell = line.split("|")[1]
        names.update(_METRIC_NAME_RE.findall(first_cell))
    return names


def test_metric_catalog_doc_in_sync_with_code():
    doc_names = _doc_catalog_names()
    code_names = set(CATALOG)
    assert doc_names - code_names == set(), \
        f"docs/observability.md lists unknown metrics: {doc_names - code_names}"
    assert code_names - doc_names == set(), \
        f"metrics missing from docs/observability.md: {code_names - doc_names}"


def test_runtime_families_are_cataloged(harness):
    """Every family the runtime paths actually register must be in the
    catalog (and therefore, by the test above, in the docs)."""
    client, ctx, mgr = harness
    client.create(Server.new("srv", spec={"image": "x"}).obj)
    reg = replica_registry()
    httpd = serve_metrics(0, reg)
    registry, state = Registry(), fl.FleetState()
    scraper = fl.FleetScraper(ctx, state=state, registry=registry)
    make_pod(client, "srv-a", {"server": "srv"}, httpd.server_address[1])
    try:
        scraper.scrape_once()
    finally:
        httpd.shutdown()
        httpd.server_close()
    mgr.reconcile_until_stable()

    from runbooks_tpu.controller.metrics import REGISTRY as GLOBAL

    prefixes = ("controller_", "serve_", "train_", "fleet_", "process_")
    for text in (registry.render(), GLOBAL.render()):
        families = obs_metrics.parse_exposition(text)
        runtime = {n for n in families if n.startswith(prefixes)}
        assert runtime <= set(CATALOG), \
            f"uncataloged families registered at runtime: " \
            f"{runtime - set(CATALOG)}"
