"""Unified observability subsystem tests (runbooks_tpu.obs).

Covers the ISSUE-5 acceptance surface: histogram bucket/quantile math,
promtool-style exposition lint over both the controller and serve
endpoints (every line parses, # TYPE precedes samples, counters end in
_total, proper content type), spec label escaping, trace JSONL
well-formedness under concurrent spans, goodput accounting across a
fault-injected restart, and the serve latency histograms populated via
the engine smoke path.
"""

import dataclasses
import json
import math
import os
import re
import threading
import urllib.request

import jax
import pytest

from runbooks_tpu.models.config import get_config
from runbooks_tpu.models.transformer import init_params
from runbooks_tpu.obs import goodput as obs_goodput
from runbooks_tpu.obs import metrics as obs_metrics
from runbooks_tpu.obs import profile as obs_profile
from runbooks_tpu.obs import trace as obs_trace
from runbooks_tpu.obs.metrics import CONTENT_TYPE, Registry


def tiny_cfg():
    return dataclasses.replace(
        get_config("llama2-7b"), vocab_size=128, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, max_seq_len=64, dtype="float32",
    )


# ---------------------------------------------------------------------------
# Exposition lint (promtool-style): every line must parse, # TYPE must
# precede its family's samples, counters must end in _total.
# ---------------------------------------------------------------------------

_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_COMMENT_RE = re.compile(
    rf"^# (HELP ({_NAME}) .+|TYPE ({_NAME}) (counter|gauge|histogram))$")
_LABEL_RE = re.compile(rf'({_NAME})="((?:[^"\\\n]|\\\\|\\"|\\n)*)"')
_SAMPLE_RE = re.compile(rf"^({_NAME})(\{{(.*)\}})? (\S+)$")


def lint_exposition(text: str):
    """Parse a Prometheus text exposition; assert structural validity.
    Returns {family: type}."""
    assert text.endswith("\n"), "exposition must end with a newline"
    types = {}
    seen_samples = set()
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("#"):
            m = _COMMENT_RE.match(line)
            assert m, f"malformed comment line: {line!r}"
            if m.group(3):  # TYPE
                name = m.group(3)
                assert name not in types, f"duplicate # TYPE for {name}"
                assert name not in seen_samples, \
                    f"# TYPE after samples for {name}"
                types[name] = m.group(4)
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"malformed sample line: {line!r}"
        name, label_blob, value = m.group(1), m.group(3), m.group(4)
        float(value)  # must parse
        if label_blob:
            stripped = _LABEL_RE.sub("", label_blob).replace(",", "")
            assert stripped == "", \
                f"unparseable labels in {line!r}: leftover {stripped!r}"
        family = name
        if family not in types:
            for suffix in ("_bucket", "_sum", "_count"):
                base = name[: -len(suffix)] if name.endswith(suffix) else None
                if base and types.get(base) == "histogram":
                    family = base
                    break
        assert family in types, f"sample {name} has no preceding # TYPE"
        seen_samples.add(family)
        if types[family] == "counter":
            assert name.endswith("_total"), \
                f"counter {name} must end in _total"
        if types[family] == "histogram" and name.endswith("_bucket"):
            assert 'le="' in (label_blob or ""), \
                f"histogram bucket sample without le label: {line!r}"
    return types


# ---------------------------------------------------------------------------
# Metrics core
# ---------------------------------------------------------------------------

def test_histogram_buckets_sum_count_and_exposition():
    r = Registry()
    values = [0.0005, 0.003, 0.003, 0.04, 0.7, 20.0, 99.0]
    for v in values:
        r.observe("ttft_seconds", v, help_text="test hist")
    text = r.render()
    lint_exposition(text)
    # Cumulative bucket counts at selected bounds.
    assert 'ttft_seconds_bucket{le="0.001"} 1' in text
    assert 'ttft_seconds_bucket{le="0.005"} 3' in text
    assert 'ttft_seconds_bucket{le="0.05"} 4' in text
    assert 'ttft_seconds_bucket{le="1"} 5' in text
    assert 'ttft_seconds_bucket{le="30"} 6' in text
    # +Inf equals _count; 99.0 lives only there.
    assert 'ttft_seconds_bucket{le="+Inf"} 7' in text
    assert "ttft_seconds_count 7" in text
    assert f"ttft_seconds_sum {round(sum(values), 9)}" in text


def test_histogram_quantile_estimates():
    r = Registry()
    # 100 observations uniform in (0, 0.1]: the q-quantile should land
    # near q * 0.1 (bucket-interpolation error bounded by bucket width).
    for i in range(1, 101):
        r.observe("lat_seconds", i / 1000.0)
    for q in (0.5, 0.9, 0.99):
        est = r.quantile("lat_seconds", q)
        assert abs(est - q * 0.1) <= 0.026, (q, est)
    # Quantile of an empty/unknown series is NaN, not a crash.
    assert math.isnan(r.quantile("nope_seconds", 0.5))
    # Everything past the top bound clamps to the top finite bound.
    r2 = Registry()
    r2.observe("big_seconds", 1e6)
    assert r2.quantile("big_seconds", 0.99) == 30.0


def test_histogram_per_labelset_series():
    r = Registry()
    r.observe("disp_seconds", 0.002, bucket="16")
    r.observe("disp_seconds", 0.2, bucket="128")
    text = r.render()
    lint_exposition(text)
    assert 'disp_seconds_bucket{bucket="16",le="0.0025"} 1' in text
    assert 'disp_seconds_bucket{bucket="128",le="0.25"} 1' in text
    assert 'disp_seconds_count{bucket="16"} 1' in text


def test_label_escaping_per_spec():
    r = Registry()
    r.set_gauge("weird_gauge", 1, path='a"b\\c\nd')
    text = r.render()
    lint_exposition(text)
    # One line, with the three specials escaped exactly per the spec.
    assert 'weird_gauge{path="a\\"b\\\\c\\nd"} 1' in text
    assert "\nd" not in text.split("weird_gauge")[1].splitlines()[0]


def test_registry_type_lines_and_counter_naming():
    r = Registry()
    r.inc("controller_reconcile_total", kind="Model")
    r.set_gauge("queue_depth", 3, kind="Model")
    r.observe("reconcile_seconds", 0.01, kind="Model")
    types = lint_exposition(r.render())
    assert types["controller_reconcile_total"] == "counter"
    assert types["queue_depth"] == "gauge"
    assert types["reconcile_seconds"] == "histogram"
    assert types["process_uptime_seconds"] == "gauge"


def test_set_counter_mirrors_absolute_value():
    r = Registry()
    r.set_counter("serve_decode_steps_total", 41)
    r.set_counter("serve_decode_steps_total", 42)
    assert r.counter_value("serve_decode_steps_total") == 42.0
    assert "serve_decode_steps_total 42.0" in r.render()


def test_controller_metrics_reexport_and_http_content_type():
    """controller/metrics.py re-exports the obs registry, and its HTTP
    endpoint serves the spec content type (satellite: no bare
    text/plain)."""
    from runbooks_tpu.controller import metrics as controller_metrics

    assert controller_metrics.REGISTRY is obs_metrics.REGISTRY
    assert controller_metrics.Registry is Registry
    httpd = controller_metrics.serve_metrics(0)
    try:
        port = httpd.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5) as resp:
            assert resp.headers["Content-Type"] == CONTENT_TYPE
            body = resp.read().decode("utf-8")
        lint_exposition(body)
        assert "process_uptime_seconds" in body
    finally:
        httpd.shutdown()
        httpd.server_close()


# ---------------------------------------------------------------------------
# Trace spans
# ---------------------------------------------------------------------------

def read_trace_events(path):
    """Parse the trace file: a '[' header then one JSON event per line
    (trailing comma allowed — the Chrome JSON Array Format with the
    closing bracket omitted). Every line must parse."""
    events = []
    with open(path) as f:
        first = f.readline().strip()
        assert first == "[", "trace must open the JSON array"
        for line in f:
            line = line.strip().rstrip(",")
            if not line:
                continue
            events.append(json.loads(line))
    return events


def test_trace_spans_concurrent_writers(tmp_path, monkeypatch):
    monkeypatch.setenv("RBT_TRACE", "1")
    path = str(tmp_path / "trace.jsonl")
    obs_trace.configure(path)
    try:
        def worker(tid):
            for i in range(25):
                with obs_trace.span("phase", worker=tid, i=i):
                    pass
                obs_trace.instant("tick", worker=tid)

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        obs_trace.close()
        obs_trace.configure(None)
    events = read_trace_events(path)
    spans = [e for e in events if e["ph"] == "X"]
    instants = [e for e in events if e["ph"] == "i"]
    assert len(spans) == 100 and len(instants) == 100
    for e in events:
        assert isinstance(e["ts"], (int, float))
        assert {"name", "ph", "pid", "tid"} <= set(e)
    for e in spans:
        assert e["dur"] >= 0
    # All four writer identities present (no thread's events torn/lost).
    assert {e["args"]["worker"] for e in spans} == {0, 1, 2, 3}


def test_trace_disabled_is_noop(tmp_path, monkeypatch):
    monkeypatch.delenv("RBT_TRACE", raising=False)
    obs_trace.configure(str(tmp_path / "off.jsonl"))
    try:
        with obs_trace.span("x", a=1):
            pass
        obs_trace.instant("y")
    finally:
        obs_trace.configure(None)
    # RBT_TRACE off: nothing reaches the FILE (events still land in the
    # always-on flight ring, obs/flight.py).
    assert not os.path.exists(tmp_path / "off.jsonl")
    # With the flight recorder ALSO off, the span path hands back a
    # shared null context (no allocation at all).
    monkeypatch.setenv("RBT_FLIGHT", "0")
    assert obs_trace.span("a") is obs_trace.span("b")


# ---------------------------------------------------------------------------
# Goodput accounting
# ---------------------------------------------------------------------------

def test_goodput_tracker_math():
    g = obs_goodput.GoodputTracker()
    g.exclude(10.0, "restore")
    g.exclude(5.0, "compile")
    for _ in range(10):
        g.step(0.1, data_wait_s=0.02, ckpt_s=0.01)
    snap = g.snapshot()
    assert snap["restore_s"] == 10.0 and snap["compile_s"] == 5.0
    assert snap["productive_s"] == 1.0
    assert snap["data_wait_s"] == pytest.approx(0.2)
    assert snap["ckpt_s"] == pytest.approx(0.1)
    # Wall here is milliseconds while exclusions are 15s: the accountable
    # window is <= 0, which must clamp, not divide by a negative.
    assert 0.0 <= g.ratio() <= 1.0


# ---------------------------------------------------------------------------
# Trainer integration: step breakdown, goodput across a fault-injected
# restart, incremental atomic metrics.json, RBT_PROFILE_AT_STEP.
# ---------------------------------------------------------------------------

def _job(artifacts, steps=8, **kw):
    from runbooks_tpu.parallel.mesh import MeshConfig
    from runbooks_tpu.train.optimizer import OptimizerConfig
    from runbooks_tpu.train.trainer import TrainJobConfig

    return TrainJobConfig(
        model="debug", model_overrides={"dtype": "float32"},
        mesh=MeshConfig(data=2, fsdp=2, tensor=2),
        optimizer=OptimizerConfig(learning_rate=1e-3, warmup_steps=0,
                                  total_steps=100, schedule="constant"),
        batch_size=4, seq_len=32, steps=steps, checkpoint_every=3,
        log_every=1, artifacts_dir=str(artifacts), **kw)


def test_goodput_excludes_restart_overhead_after_kill(tmp_path, monkeypatch):
    """Fault-injected restart (RBT_FAULT_INJECT=kill): the resumed run's
    goodput must exclude restore + recompile from the accountable window
    — and metrics.json must already exist after the kill (incremental
    atomic writes), not only at job end."""
    from runbooks_tpu.train.trainer import SimulatedFault, run_training

    monkeypatch.setenv("RBT_FAULT_INJECT", "kill:5")
    with pytest.raises(SimulatedFault):
        run_training(_job(tmp_path))
    monkeypatch.delenv("RBT_FAULT_INJECT")

    # Satellite: the killed run's metrics survived (written incrementally,
    # atomically) even though the process died mid-run.
    mpath = os.path.join(str(tmp_path), "metrics.json")
    assert os.path.exists(mpath)
    partial = json.load(open(mpath))
    assert partial["in_progress"] is True
    assert [e["step"] for e in partial["history"]] == [1, 2, 3, 4, 5]
    assert not os.path.exists(mpath + ".tmp")  # temp never left behind

    resumed = run_training(_job(tmp_path))
    detail = resumed["goodput_detail"]
    # Restore and recompile really happened on resume...
    assert resumed["restore_time_s"] > 0
    assert detail["restore_s"] > 0 and detail["compile_s"] > 0
    # ...and the ratio is computed over wall MINUS that restart overhead:
    accountable = detail["wall_s"] - detail["restore_s"] - detail["compile_s"]
    assert accountable > 0
    want = min(detail["productive_s"] / accountable, 1.0)
    assert resumed["goodput"] == pytest.approx(want, rel=0.05)
    # On CPU the recompile dominates wall: the naive ratio (productive /
    # raw wall) would be far smaller — the exclusion is load-bearing.
    naive = detail["productive_s"] / detail["wall_s"]
    assert resumed["goodput"] > naive
    # Per-step breakdown present in every post-compile history entry and
    # in the file (the compile step is excluded wholesale from goodput, so
    # its entry carries compile_time_s instead of a breakdown).
    final = json.load(open(mpath))
    assert "in_progress" not in final
    assert final["history"][0]["compile_time_s"] > 0
    breakdown = final["history"][1:]
    assert breakdown, "no steady-state entries logged"
    for entry in breakdown:
        assert entry["data_wait_s"] >= 0
        assert entry["step_s"] > 0
        assert 0 <= entry["goodput"] <= 1


def test_trainer_trace_file_loads(tmp_path, monkeypatch):
    """RBT_TRACE=1 training writes a Perfetto-loadable trace.jsonl with
    the step-phase spans (data_wait, step, checkpoint)."""
    from runbooks_tpu.train.trainer import run_training

    monkeypatch.setenv("RBT_TRACE", "1")
    run_training(_job(tmp_path, steps=4))
    events = read_trace_events(tmp_path / "trace.jsonl")
    names = {e["name"] for e in events}
    assert {"data_wait", "step", "checkpoint"} <= names
    steps_traced = {e["args"]["step"] for e in events
                    if e["name"] == "step"}
    assert steps_traced == {0, 1, 2, 3}


def test_profile_at_step_env_capture(tmp_path, monkeypatch):
    from runbooks_tpu.train.trainer import run_training

    monkeypatch.setenv("RBT_PROFILE_AT_STEP", "2:2")
    run_training(_job(tmp_path, steps=4))
    prof = tmp_path / "profiles" / "step2"
    assert prof.is_dir()
    files = [os.path.join(r, f) for r, _, fs in os.walk(prof) for f in fs]
    assert files, "profiler capture produced no files"


def test_parse_profile_at_step_validation():
    assert obs_profile.parse_profile_at_step("7") == (7, 1)
    assert obs_profile.parse_profile_at_step("7:3") == (7, 3)
    assert obs_profile.parse_profile_at_step("") is None
    with pytest.raises(ValueError):
        obs_profile.parse_profile_at_step("x")
    with pytest.raises(ValueError):
        obs_profile.parse_profile_at_step("3:0")


def test_profiler_busy_guard(tmp_path):
    p = obs_profile.Profiler()
    d = p.start(str(tmp_path / "cap"))
    try:
        with pytest.raises(obs_profile.ProfilerBusy):
            p.start(str(tmp_path / "cap2"))
    finally:
        assert p.stop() == d
    assert p.stop() is None  # idempotent


# ---------------------------------------------------------------------------
# Serve latency histograms via the engine smoke path + /metrics exposition
# ---------------------------------------------------------------------------

def test_engine_smoke_populates_latency_histograms():
    from runbooks_tpu.serve.engine import InferenceEngine, Request

    reg = obs_metrics.REGISTRY
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    engine = InferenceEngine(cfg, params, max_slots=2, seed=0)
    before_ttft = _hist_count(reg, "serve_ttft_seconds")
    before_inter = _hist_count(reg, "serve_inter_token_seconds")
    reqs = [Request(prompt_tokens=[1, 2, 3], max_tokens=4)
            for _ in range(3)]
    engine.generate(reqs)
    assert all(len(r.output_tokens) == 4 for r in reqs)
    assert _hist_count(reg, "serve_ttft_seconds") == before_ttft + 3
    # 3 requests x 3 non-first tokens each.
    assert _hist_count(reg, "serve_inter_token_seconds") \
        == before_inter + 9
    text = reg.render()
    lint_exposition(text)
    for family in ("serve_ttft_seconds", "serve_inter_token_seconds",
                   "serve_queue_wait_seconds",
                   "serve_request_duration_seconds",
                   "serve_prefill_dispatch_seconds",
                   "serve_decode_dispatch_seconds"):
        assert f"# TYPE {family} histogram" in text
        assert f"{family}_bucket" in text


def _hist_count(reg, name, **labels):
    total = 0
    with reg._lock:
        for (hname, _), hist in reg._hists.items():
            if hname == name:
                total += hist.count
    return total


def test_http_metrics_renders_from_registry_with_content_type():
    """GET /metrics on the serve API: rendered by runbooks_tpu.obs (no
    hand-built metric strings), proper content type, lints clean, and
    includes the TTFT/inter-token histogram series."""
    from aiohttp.test_utils import TestClient, TestServer

    from runbooks_tpu.serve.api import create_server

    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    app = create_server(cfg, params, max_slots=2, warmup=False)

    async def drive():
        import asyncio  # noqa: F401

        async with TestClient(TestServer(app)) as client:
            r = await client.post("/v1/completions", json={
                "prompt": "hello", "max_tokens": 4, "temperature": 0.0})
            assert r.status == 200
            r = await client.get("/metrics")
            assert r.status == 200
            assert r.headers["Content-Type"] == CONTENT_TYPE
            text = await r.text()
            types = lint_exposition(text)
            assert types["serve_requests_total"] == "counter"
            assert types["serve_ttft_seconds"] == "histogram"
            assert types["serve_inter_token_seconds"] == "histogram"
            for series in ("serve_ttft_seconds_bucket",
                           "serve_ttft_seconds_sum",
                           "serve_ttft_seconds_count",
                           "serve_inter_token_seconds_bucket",
                           "serve_inter_token_seconds_sum",
                           "serve_inter_token_seconds_count"):
                assert series in text, series
            assert "serve_requests_total 1" in text
            assert "serve_tokens_generated_total 4" in text

    import asyncio

    asyncio.run(drive())


def test_http_debug_profile_endpoint(tmp_path, monkeypatch):
    """POST /debug/profile?seconds=N captures a trace under
    {artifacts}/profiles/ and rejects concurrent/malformed captures."""
    from aiohttp.test_utils import TestClient, TestServer

    from runbooks_tpu.serve.api import create_server

    monkeypatch.setenv("RBT_CONTENT_DIR", str(tmp_path))
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    app = create_server(cfg, params, max_slots=2, warmup=False)

    async def drive():
        async with TestClient(TestServer(app)) as client:
            r = await client.post("/debug/profile?seconds=0.2")
            assert r.status == 200
            body = await r.json()
            assert body["seconds"] == 0.2
            assert os.path.isdir(body["path"])
            files = [f for _, _, fs in os.walk(body["path"]) for f in fs]
            assert files, "capture produced no files"
            r = await client.post("/debug/profile?seconds=oops")
            assert r.status == 400
            r = await client.post("/debug/profile?seconds=0")
            assert r.status == 400
            r = await client.post("/debug/profile?seconds=9999")
            assert r.status == 400

    import asyncio

    asyncio.run(drive())


# ---------------------------------------------------------------------------
# Controller exposition end-to-end (manager increments -> lint)
# ---------------------------------------------------------------------------

def test_controller_reconcile_metrics_lint():
    from runbooks_tpu.controller.metrics import REGISTRY

    REGISTRY.inc("controller_reconcile_total", kind="Model")
    REGISTRY.observe("controller_reconcile_seconds", 0.004, kind="Model")
    text = REGISTRY.render()
    types = lint_exposition(text)
    assert types["controller_reconcile_total"] == "counter"
    assert types["controller_reconcile_seconds"] == "histogram"
