"""Device-level observability tests (ISSUE 7, obs/device.py).

Covers: the recompilation sentinel (fires on a post-warmup shape-busted
request, stays silent across a steady decode loop), live-array attribution
math, cost-analysis roofline classification on known matmuls, the CPU
degradation path (memory_stats() absent), GET /debug/memory and
/debug/programs, the /debug/profile memory-snapshot bundle, the fleet
mirror of the new xla_*/device_* families, and the `rbt top` HBM/SLOTS
columns.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbooks_tpu.models.config import get_config
from runbooks_tpu.models.transformer import init_params
from runbooks_tpu.obs import device as obs_device
from runbooks_tpu.obs import metrics as obs_metrics
from runbooks_tpu.obs.metrics import CATALOG, Registry


def tiny_cfg():
    return dataclasses.replace(
        get_config("llama2-7b"), vocab_size=128, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, max_seq_len=64, dtype="float32",
    )


@pytest.fixture(autouse=True)
def clean_sentinel_state():
    """Process-global steadiness must not leak between tests (or from a
    trainer/engine test that ran earlier in the session)."""
    obs_device.SENTINEL.clear_steady()
    yield
    obs_device.SENTINEL.clear_steady()


# ---------------------------------------------------------------------------
# Recompilation sentinel
# ---------------------------------------------------------------------------

def test_sentinel_counts_compiles_and_flags_post_steady():
    sentinel = obs_device.SENTINEL
    assert sentinel.install()  # idempotent; True = monitoring feed live
    reg = obs_metrics.REGISTRY
    t0, u0 = sentinel.total, sentinel.unexpected
    c0 = reg.counter_value("xla_compilations_total")

    f = jax.jit(lambda x: x * 2 + 1)
    # Inputs built up front: array creation itself compiles tiny
    # broadcast programs, which must not confound the counts below.
    x7, x9, x11 = jnp.ones(7), jnp.ones(9), jnp.ones(11)
    f(x7).block_until_ready()                   # fresh shape -> compile
    assert sentinel.total > t0
    assert reg.counter_value("xla_compilations_total") > c0
    assert sentinel.unexpected == u0            # nothing steady yet

    sentinel.mark_steady("test")
    try:
        f(x7).block_until_ready()               # cache hit: silent
        assert sentinel.unexpected == u0
        f(x9).block_until_ready()               # new shape: flagged
        assert sentinel.unexpected == u0 + 1
        assert sentinel.last_unexpected[-1]["steady"] == ["test"]
        # expected() masks intentional compiles on this thread.
        with sentinel.expected():
            f(x11).block_until_ready()
        assert sentinel.unexpected == u0 + 1
    finally:
        sentinel.clear_steady("test")


def test_sentinel_silent_across_steady_decode_loop(capsys):
    """Full warmup -> generate traffic across admissions and decode
    chunks -> zero unexpected compiles (the engine's compile discipline,
    measured)."""
    from runbooks_tpu.serve.engine import InferenceEngine, Request

    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    engine = InferenceEngine(cfg, params, max_slots=2, seed=0)
    engine.warmup()
    assert "serve" in obs_device.SENTINEL.steady_components()
    assert engine.warmup_census["compiles"] > 0
    # Rows {1, max_slots}, but for the largest bucket: no tick's budget
    # (the window) holds two of it (engine.dispatch_shapes).
    assert engine.warmup_census["prefill_programs"] == \
        len(engine.prefill_buckets) * 2 - 1
    out = capsys.readouterr().out
    assert "warmup census" in out          # grep-able line kept
    assert "compiles in" in out            # ...now with compile seconds

    u0 = obs_device.SENTINEL.unexpected
    reqs = [Request(prompt_tokens=[1, 2, 3], max_tokens=4)
            for _ in range(3)]
    engine.generate(reqs)
    assert all(len(r.output_tokens) == 4 for r in reqs)
    assert obs_device.SENTINEL.unexpected == u0
    # Occupancy/prefix instrumentation advanced with the traffic.
    assert engine.prefix_lookups == 3 and engine.prefix_hits == 0


def test_sentinel_fires_on_shape_busted_request():
    """A warmed engine hit with a shape its warmup never compiled (a
    same-tick burst after a rows=(1,) warmup) stalls on a compile — the
    sentinel must make that loud."""
    from runbooks_tpu.serve.engine import InferenceEngine, Request

    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    engine = InferenceEngine(cfg, params, max_slots=2, seed=0)
    engine.warmup(rows=(1,))               # burst shape left cold
    reg = obs_metrics.REGISTRY
    u0 = obs_device.SENTINEL.unexpected
    c0 = reg.counter_value("xla_unexpected_compiles_total")
    reqs = [Request(prompt_tokens=[1, 2, 3], max_tokens=2)
            for _ in range(2)]
    engine.generate(reqs)                  # rows=2 prefill: cold compile
    assert obs_device.SENTINEL.unexpected == u0 + 1
    assert reg.counter_value("xla_unexpected_compiles_total") == c0 + 1
    assert obs_device.SENTINEL.last_unexpected[-1]["seconds"] > 0


def test_sentinel_unexpected_compile_emits_trace_instant(tmp_path,
                                                         monkeypatch):
    from runbooks_tpu.obs import trace as obs_trace

    monkeypatch.setenv("RBT_TRACE", "1")
    path = tmp_path / "trace.jsonl"
    obs_trace.configure(str(path))
    sentinel = obs_device.SENTINEL
    sentinel.install()
    sentinel.mark_steady("test")
    try:
        jax.jit(lambda x: x - 3)(jnp.ones(13)).block_until_ready()
    finally:
        sentinel.clear_steady("test")
        obs_trace.close()
        obs_trace.configure(None)
    events = [json.loads(ln.rstrip(",\n"))
              for ln in path.read_text().splitlines()[1:]]
    hits = [e for e in events if e["name"] == "unexpected_compile"]
    assert hits and hits[-1]["args"]["steady"] == "test"


def test_steady_claims_are_refcounted():
    """Two colocated engines both claim 'serve'; the first one stopping
    must not blind the sentinel for the survivor."""
    s = obs_device.SENTINEL
    s.mark_steady("serve")
    s.mark_steady("serve")
    s.clear_steady("serve")
    assert "serve" in s.steady_components()
    s.clear_steady("serve")
    assert "serve" not in s.steady_components()


def test_program_tracker_drops_dead_programs():
    """The tracker holds its jitted fns WEAKLY: a discarded engine's
    decode closures (which pin params + KV pool) must not survive via
    the census."""
    import gc

    tracker = obs_device.ProgramTracker()
    f = jax.jit(lambda x: x + 1)
    tracker.register("serve", "tmp", f)
    assert [e["name"] for e in tracker.census("serve")] == ["tmp"]
    del f
    gc.collect()
    assert tracker.census("serve") == []


def test_program_tracker_reregistration_resets_costs():
    """A rebuilt engine/run re-registers its entry points; the previous
    model's roofline costs must not survive into the new program's
    gauges (same shape sig, different model = silently wrong FLOPs)."""
    tracker = obs_device.ProgramTracker()
    tracker.register("serve", "prefill", None)
    tracker.record_cost("serve", "prefill", "b16r1", {"flops": 1.0})
    assert tracker.has_cost("serve", "prefill", "b16r1")
    tracker.register("serve", "prefill", None)   # engine rebuilt
    assert not tracker.has_cost("serve", "prefill", "b16r1")
    (entry,) = tracker.census("serve")
    assert entry["costs"] == {}


# ---------------------------------------------------------------------------
# Live-array attribution + CPU degradation
# ---------------------------------------------------------------------------

def test_live_array_census_attribution_math():
    weights = {"w": jnp.ones((32, 32), jnp.float32),     # 4096 B
               "b": jnp.ones((64,), jnp.float32)}        # 256 B
    cache = [jnp.zeros((16, 16), jnp.int8)]              # 256 B
    census = obs_device.live_array_census(
        {"weights": weights, "kv_cache": cache})
    cats = census["by_category"]
    assert cats["weights"] == 4096 + 256
    assert cats["kv_cache"] == 256
    # Categories + other sum EXACTLY to the total (acceptance: within
    # 5%; the construction makes it exact).
    assert sum(cats.values()) == census["total_bytes"]
    assert census["arrays"] >= 3
    # A group tree that shares no live arrays attributes zero.
    assert obs_device.live_array_census(
        {"ghost": {"x": np.ones(4)}})["by_category"]["ghost"] == 0


def test_device_memory_stats_cpu_degradation():
    """CPU has no memory_stats(): entries carry identity only, gauges
    stay unset, and memory_snapshot still answers via the census."""
    entries = obs_device.device_memory_stats()
    assert entries and entries[0]["platform"] == "cpu"
    assert "bytes_in_use" not in entries[0]
    reg = Registry()
    obs_device.set_memory_gauges(reg)
    assert "device_memory_bytes_in_use" not in reg.render()
    anchor = jnp.ones((8, 8))  # something live for the census to count
    snap = obs_device.memory_snapshot()
    assert snap["live_arrays"]["total_bytes"] >= anchor.nbytes


# ---------------------------------------------------------------------------
# Roofline
# ---------------------------------------------------------------------------

V5E_PEAKS = (197e12, 819e9)


@pytest.fixture
def v5e_peaks(monkeypatch):
    """Classify against a v5e's peaks: the CPU the suite runs on has none
    (see test_no_peaks_off_tpu), so the fields that need one are absent
    unless a test supplies them."""
    monkeypatch.setattr(obs_device, "device_peaks", lambda: V5E_PEAKS)


def test_no_peaks_off_tpu():
    """A CPU has no device peak: nothing is computed from a stand-in, the
    peak-derived fields are absent, and an unknown TPU raises."""
    from types import SimpleNamespace

    from runbooks_tpu.utils import hw

    assert hw.chip_peaks(jax.devices()[0]) is None
    assert obs_device.device_peaks() is None
    roof = obs_device.classify_roofline(1e9, 1e6)
    assert roof == {"arithmetic_intensity": 1000.0}
    assert hw.chip_peaks(SimpleNamespace(
        platform="tpu", device_kind="TPU v5 lite")) == V5E_PEAKS
    with pytest.raises(ValueError, match="TPU v99"):
        hw.chip_peaks(SimpleNamespace(platform="tpu",
                                      device_kind="TPU v99"))
    assert not hasattr(obs_device, "NOMINAL_PEAK_FLOPS")


def test_roofline_classification_on_known_matmuls():
    # Square matmul: AI = 2n^3 / (3 * 4n^2) = n/6 flops/byte — far right
    # of a ridge of 10 at n=1024.
    n = 1024
    f = jax.jit(lambda a, b: a @ b)
    a = jnp.ones((n, n), jnp.float32)
    cost = obs_device.cost_analysis_of(f, a, a)
    assert cost is not None
    assert cost["flops"] == pytest.approx(2 * n**3, rel=0.01)
    roof = obs_device.classify_roofline(cost["flops"], cost["hbm_bytes"],
                                        peak_flops=1e12,
                                        hbm_bytes_per_sec=100e9)
    assert roof["bound"] == "compute"
    assert roof["arithmetic_intensity"] > roof["ridge"] == 10.0

    # Matvec (decode-shaped): AI ~= 2 flops/byte — left of the ridge.
    g = jax.jit(lambda a, v: a @ v)
    v = jnp.ones((n,), jnp.float32)
    cost_v = obs_device.cost_analysis_of(g, a, v)
    roof_v = obs_device.classify_roofline(
        cost_v["flops"], cost_v["hbm_bytes"],
        peak_flops=1e12, hbm_bytes_per_sec=100e9)
    assert roof_v["bound"] == "bandwidth"
    assert roof_v["arithmetic_intensity"] < 10.0


def test_engine_decode_measures_bandwidth_bound(v5e_peaks):
    """The engine's 'decode is HBM-bound' analysis (serve/engine.py) is
    now a recorded cost: warmup captures per-program roofline costs and
    the decode program classifies bandwidth-bound."""
    from runbooks_tpu.serve.engine import InferenceEngine

    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    engine = InferenceEngine(cfg, params, max_slots=2, seed=0)
    engine.warmup()
    census = {c["name"]: c for c in obs_device.PROGRAMS.census("serve")}
    decode = census[f"decode_v{engine.view_buckets[0]}"]
    assert decode["programs"] == 1
    (cost,) = decode["costs"].values()
    assert cost["bound"] == "bandwidth"
    assert cost["flops"] > 0 and cost["hbm_bytes"] > 0
    # Census gauges mirror into a registry.
    reg = Registry()
    obs_device.PROGRAMS.set_gauges(reg, component="serve")
    text = reg.render()
    assert 'xla_programs{component="serve"' in text
    assert "xla_program_bandwidth_bound" in text


# ---------------------------------------------------------------------------
# Serve HTTP endpoints
# ---------------------------------------------------------------------------

def test_http_debug_memory_endpoint():
    from aiohttp.test_utils import TestClient, TestServer

    from runbooks_tpu.serve.api import create_server

    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    app = create_server(cfg, params, max_slots=2, warmup=False)

    async def drive():
        async with TestClient(TestServer(app)) as client:
            r = await client.post("/v1/completions", json={
                "prompt": "hello", "max_tokens": 4, "temperature": 0.0})
            assert r.status == 200
            r = await client.get("/debug/memory")
            assert r.status == 200
            body = await r.json()
            cats = body["live_arrays"]["by_category"]
            total = body["live_arrays"]["total_bytes"]
            # Attribution sums to the census total (acceptance: 5%).
            assert sum(cats.values()) == total
            assert cats["weights"] > 0 and cats["kv_cache"] > 0
            assert body["kv_occupancy"]["slots_total"] == 2
            assert body["devices"][0]["platform"] == "cpu"

    import asyncio

    asyncio.run(drive())


def test_http_debug_programs_endpoint_and_metrics_families(v5e_peaks):
    from aiohttp.test_utils import TestClient, TestServer

    from runbooks_tpu.serve.api import create_server

    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    app = create_server(cfg, params, max_slots=2, warmup=True)

    async def drive():
        async with TestClient(TestServer(app)) as client:
            r = await client.post("/v1/completions", json={
                "prompt": "hello", "max_tokens": 4, "temperature": 0.0})
            assert r.status == 200
            r = await client.get("/debug/programs")
            assert r.status == 200
            body = await r.json()
            assert body["compiles"]["total"] > 0
            assert "serve" in body["compiles"]["steady"]
            assert body["warmup_census"]["compiles"] > 0
            by_name = {p["name"]: p for p in body["programs"]}
            # The tracker is process-global: earlier tests' engines may
            # have registered other decode views — pick THIS engine's
            # (the one whose warmup recorded costs).
            decode = next(v for k, v in sorted(by_name.items())
                          if k.startswith("decode_v") and v["costs"])
            (cost,) = decode["costs"].values()
            # Traffic ran: analytic MFU joins the measured dispatch mean.
            assert cost["bound"] == "bandwidth"
            assert cost["measured_mean_seconds"] > 0
            assert cost["analytic_mfu"] > 0
            assert body["peaks"]["ridge_flops_per_byte"] > 0
            r = await client.get("/metrics")
            text = await r.text()
            for family in ("serve_slots_total", "serve_kv_cache_tokens",
                           "serve_kv_cache_capacity_tokens",
                           "serve_kv_occupancy_ratio",
                           "serve_prefix_lookups_total",
                           "serve_prefix_hits_total",
                           "xla_compilations_total",
                           "xla_unexpected_compiles_total",
                           "xla_programs", "xla_program_flops",
                           "xla_program_bandwidth_bound"):
                assert f"\n{family}" in text or \
                    text.startswith(family), family

    import asyncio

    asyncio.run(drive())


def test_debug_profile_bundles_memory_snapshot(tmp_path, monkeypatch):
    """A profile capture is self-contained: memory.json (devices + live
    census) lands beside the XLA trace."""
    from runbooks_tpu.obs import profile as obs_profile

    monkeypatch.setenv("RBT_CONTENT_DIR", str(tmp_path))
    log_dir = str(tmp_path / "cap")
    obs_profile.PROFILER.capture(log_dir, 0.05)
    snap_path = os.path.join(log_dir, "memory.json")
    assert os.path.exists(snap_path)
    snap = json.load(open(snap_path))
    assert snap["devices"][0]["platform"] == "cpu"
    assert snap["live_arrays"]["total_bytes"] >= 0


# ---------------------------------------------------------------------------
# Trainer integration
# ---------------------------------------------------------------------------

def test_trainer_device_obs_summary(tmp_path, v5e_peaks):
    from runbooks_tpu.parallel.mesh import MeshConfig
    from runbooks_tpu.train.optimizer import OptimizerConfig
    from runbooks_tpu.train.trainer import TrainJobConfig, run_training

    job = TrainJobConfig(
        model="debug", mesh=MeshConfig(), batch_size=4, seq_len=64,
        steps=4, checkpoint_every=4, log_every=2,
        artifacts_dir=str(tmp_path),
        optimizer=OptimizerConfig(total_steps=100, warmup_steps=0))
    summary = run_training(job)
    dev = summary["device_obs"]
    # The steady step loop ran clean; the roofline cost is attributed.
    assert dev["unexpected_compiles"] == 0
    assert dev["compiles"] >= 1
    assert dev["cost"]["flops"] > 0
    assert dev["cost"]["bound"] in ("compute", "bandwidth")
    # cost_analysis FLOPs and the 3x-forward formula must agree to ~2x —
    # they count different things (XLA fuses/elides) but catch either
    # being wildly wrong.
    ratio = dev["cost"]["flops"] / dev["formula_flops_per_step"]
    assert 0.3 < ratio < 3.0
    # Steadiness does not leak past the run.
    assert "train" not in obs_device.SENTINEL.steady_components()
    # metrics.json carries the same block.
    metrics = json.load(open(tmp_path / "metrics.json"))
    assert metrics["device_obs"]["cost"]["flops"] == dev["cost"]["flops"]


# ---------------------------------------------------------------------------
# Fleet mirror + rbt top columns
# ---------------------------------------------------------------------------

def _device_obs_replica_registry():
    reg = Registry()
    reg.set_gauge("serve_active_slots", 3)
    reg.set_gauge("serve_slots_total", 8)
    reg.set_gauge("serve_kv_occupancy_ratio", 0.25)
    reg.set_counter("serve_requests_total", 10)
    reg.set_counter("xla_compilations_total", 12)
    reg.set_counter("xla_unexpected_compiles_total", 1)
    reg.observe("xla_compile_seconds", 0.5)
    reg.set_gauge("xla_programs", 6, component="serve", program="prefill")
    reg.set_gauge("device_memory_bytes_in_use", 6e9, device="0")
    reg.set_gauge("device_memory_bytes_limit", 16e9, device="0")
    reg.set_gauge("device_memory_bytes_in_use", 3e9, device="1")
    reg.set_gauge("device_memory_bytes_limit", 16e9, device="1")
    return reg


def test_fleet_mirrors_device_obs_families():
    from runbooks_tpu.api.types import Server
    from runbooks_tpu.cloud.base import CommonConfig
    from runbooks_tpu.cloud.local import LocalCloud
    from runbooks_tpu.controller import fleet as fl
    from runbooks_tpu.controller.manager import Ctx
    from runbooks_tpu.k8s.fake import FakeCluster
    from runbooks_tpu.obs.metrics import serve_metrics
    from runbooks_tpu.sci.base import FakeSCI

    client = FakeCluster()
    ctx = Ctx(client=client, cloud=LocalCloud(CommonConfig(
        cluster_name="t", artifact_bucket_url="file:///tmp/b",
        registry_url="r:5000")), sci=FakeSCI())
    client.create(Server.new("srv", spec={"image": "x"}).obj)
    reg_replica = _device_obs_replica_registry()
    httpd = serve_metrics(0, reg_replica)
    client.create({
        "apiVersion": "v1", "kind": "Pod",
        "metadata": {"name": "srv-a", "namespace": "default",
                     "labels": {"server": "srv", "role": "run"},
                     "annotations": {fl.METRICS_PORT_ANNOTATION:
                                     str(httpd.server_address[1])}},
        "spec": {"containers": [{"name": "c"}]},
        "status": {"phase": "Running", "podIP": "127.0.0.1"},
    })
    registry, state = Registry(), fl.FleetState()
    scraper = fl.FleetScraper(ctx, state=state, registry=registry)
    try:
        assert scraper.scrape_once() == 1
    finally:
        httpd.shutdown()
        httpd.server_close()
    text = registry.render()
    # xla_* and device_* mirror per replica like serve_*/train_*.
    assert ('xla_unexpected_compiles_total{kind="Server",name="srv",'
            'namespace="default",replica="srv-a"} 1.0') in text
    assert ('device_memory_bytes_in_use{device="0",kind="Server",'
            'name="srv",namespace="default",replica="srv-a"}') in text
    assert 'xla_compile_seconds_bucket' in text
    # And everything mirrored is cataloged (docs drift check covers docs).
    families = obs_metrics.parse_exposition(text)
    runtime = {n for n in families
               if n.startswith(("serve_", "train_", "xla_", "device_"))}
    assert runtime <= set(CATALOG), runtime - set(CATALOG)


def test_rbt_top_hbm_and_slot_columns(capsys):
    """`rbt top` renders HBM% (summed across a replica's devices) and
    slot-utilization columns from the fleet exposition."""
    from runbooks_tpu.cli.main import _top_rows_from_metrics

    reg = _device_obs_replica_registry()
    labels = {"kind": "Server", "namespace": "default", "name": "srv",
              "replica": "srv-a"}
    fleet = Registry()
    fleet.set_gauge("fleet_scrape_up", 1, **labels)
    fleet.set_gauge("fleet_scrape_age_seconds", 0.0, **labels)
    for fam in ("serve_active_slots", "serve_slots_total",
                "serve_kv_occupancy_ratio"):
        fleet.set_gauge(fam, {"serve_active_slots": 3,
                              "serve_slots_total": 8,
                              "serve_kv_occupancy_ratio": 0.25}[fam],
                        **labels)
    fleet.set_gauge("device_memory_bytes_in_use", 6e9, device="0",
                    **labels)
    fleet.set_gauge("device_memory_bytes_limit", 16e9, device="0",
                    **labels)
    fleet.set_gauge("device_memory_bytes_in_use", 3e9, device="1",
                    **labels)
    fleet.set_gauge("device_memory_bytes_limit", 16e9, device="1",
                    **labels)
    header, rows = _top_rows_from_metrics(fleet.render())
    assert header[5] == "HBM" and header[6] == "SLOTS"
    (row,) = rows
    assert row[0] == "servers/srv"
    assert row[5] == "28%"           # (6+3)/(16+16) GB
    assert row[6] == "3/8 kv=25%"
    # A CPU replica (no device_memory_* series) degrades to '-'.
    bare = Registry()
    bare.set_gauge("fleet_scrape_up", 1, **labels)
    bare.set_gauge("serve_active_slots", 1, **labels)
    _, rows = _top_rows_from_metrics(bare.render())
    assert rows[0][5] == "-" and rows[0][6] == "-"


def test_catalog_covers_device_obs_families():
    """Every family obs/device.py + the engine/api emit is cataloged, so
    the PR-6 docs drift check extends to the device plane."""
    for name in ("xla_compilations_total", "xla_unexpected_compiles_total",
                 "xla_compile_seconds", "xla_programs",
                 "xla_program_flops", "xla_program_hbm_bytes",
                 "xla_program_arithmetic_intensity",
                 "xla_program_bandwidth_bound",
                 "device_memory_bytes_in_use", "device_memory_peak_bytes",
                 "device_memory_bytes_limit",
                 "device_memory_headroom_bytes",
                 "serve_slots_total", "serve_kv_cache_tokens",
                 "serve_kv_cache_capacity_tokens",
                 "serve_kv_occupancy_ratio", "serve_prefix_lookups_total",
                 "serve_prefix_hits_total", "train_analytic_mfu"):
        assert name in CATALOG, name


# ---------------------------------------------------------------------------
# Bench axis
# ---------------------------------------------------------------------------

def test_bench_device_obs_axis(monkeypatch, capsys):
    """RBT_BENCH_DEVICE_OBS=1 runs the steady-loop compile gate and
    cross-checks analytic vs formula FLOPs. It refuses a CPU unless
    forced, and a forced CPU run reports no MFU and nests its numbers."""
    import bench

    monkeypatch.setenv("RBT_BENCH_DEVICE_OBS", "1")
    with pytest.raises(SystemExit, match="not a TPU"):
        bench.inner()
    monkeypatch.setenv("RBT_BENCH_FORCE_CPU", "1")
    monkeypatch.setenv("RBT_BENCH_BS", "2")
    monkeypatch.setenv("RBT_BENCH_SEQ", "64")
    bench.inner()
    line = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")][-1]
    line = json.loads(line)
    assert line["platform"] == "cpu" and "value" not in line
    out = line["cpu_functional_run"]
    assert out["value"] == 0                 # zero unexpected compiles
    assert out["vs_baseline"] == 1.0
    assert 0.3 < out["flops_ratio"] < 3.0
    assert not {"mfu_analytic", "mfu_formula", "bound"} & set(out)
