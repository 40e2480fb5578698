"""Headline benchmark: llama-architecture causal-LM training throughput on one
TPU chip (tokens/sec/chip and MFU).

The reference publishes no perf numbers (BASELINE.md); the north-star target
from BASELINE.json is a llama fine-tune at >=35% MFU. This bench runs the
full training step (fwd+bwd+adamw, remat, bf16 compute) on the largest
single-chip-friendly llama config and reports MFU vs the 0.35 target:
vs_baseline = MFU / 0.35 (>1.0 beats the target).

Structure: invoked with no args it is a stdlib-only orchestrator (benchkit)
that runs ``bench.py --inner`` in a subprocess and prints ONE JSON line:
{"metric", "value", "unit", "vs_baseline", "platform", ...extras}. It exits
non-zero when the backend is not a TPU (RBT_BENCH_FORCE_CPU=1 asks for a CPU
functional run, whose numbers are nested under "cpu_functional_run").
"""

from __future__ import annotations

import json
import os
import sys
import time

import benchkit


def resume_inner() -> None:
    """RBT_BENCH_RESUME=1: restart-to-first-step overhead. A preempted/
    restarted trainer pays restore (newest intact checkpoint + cursor
    fast-forward) plus recompile (cheap when the persistent JAX cache is
    warm, see utils/jax_cache.py) before its first resumed step completes.
    That window is the restart cost the fault-tolerance design optimizes
    (docs/fault-tolerance.md); at pod scale it dominates effective
    throughput on preemptible fleets."""
    from runbooks_tpu.parallel.mesh import MeshConfig
    from runbooks_tpu.train.optimizer import OptimizerConfig
    from runbooks_tpu.train.trainer import TrainJobConfig, run_training

    _, on_tpu = benchkit.bench_device()
    if on_tpu:
        model, batch_size, seq, steps = "bench-410m-d128", 8, 2048, 6
    else:
        model, batch_size, seq, steps = "debug", 4, 128, 6
    model = os.environ.get("RBT_BENCH_MODEL", model)
    batch_size = int(os.environ.get("RBT_BENCH_BS", batch_size))
    seq = int(os.environ.get("RBT_BENCH_SEQ", seq))

    workdir = benchkit.work_dir("resume")

    def job(n_steps):
        return TrainJobConfig(
            model=model, mesh=MeshConfig(),
            optimizer=OptimizerConfig(total_steps=10_000,
                                      warmup_steps=10),
            batch_size=batch_size, seq_len=seq, steps=n_steps,
            checkpoint_every=steps, log_every=1,
            artifacts_dir=workdir)

    t0 = time.perf_counter()
    cold = run_training(job(steps))
    cold_wall = time.perf_counter() - t0
    # Resume for exactly ONE more step: wall time ~= process-restart
    # cost (restore + recompile + one step + final save).
    t1 = time.perf_counter()
    resumed = run_training(job(steps + 1))
    resume_wall = time.perf_counter() - t1

    restore_s = resumed.get("restore_time_s") or 0.0
    recompile_s = resumed.get("compile_time_s") or 0.0
    value = restore_s + recompile_s  # restart-to-first-step
    cold_first = (cold.get("compile_time_s") or cold_wall)
    benchkit.emit({
        "metric": f"{model} restart-to-first-step (restore + recompile)",
        "value": round(value, 3),
        "unit": "s",
        # >1 = resuming beats paying the cold first step again.
        "vs_baseline": round(cold_first / max(value, 1e-9), 3),
        "restore_s": round(restore_s, 3),
        "recompile_s": round(recompile_s, 3),
        "resume_wall_s": round(resume_wall, 3),
        "cold_first_step_s": round(cold_first, 3),
        "resumed_from_step": steps,
        "batches_consumed": resumed.get("batches_consumed"),
        # Goodput of the resumed run (obs subsystem): restart overhead
        # excluded, so this should match an uninterrupted run's ratio.
        "goodput": resumed.get("goodput"),
        "goodput_detail": resumed.get("goodput_detail"),
    })


def obs_inner() -> None:
    """RBT_BENCH_OBS=1: observability instrumentation overhead.

    The obs subsystem (docs/observability.md) adds per-step work to the
    training hot loop: two trace spans, three histogram observes, and a
    goodput update. This axis measures that cost two ways: (a) a
    deterministic microbench of the exact per-step obs call sequence
    (trace ON, writing a real trace.jsonl), and (b) wall-clock steps/s of
    the train step loop with the obs calls on vs off. The headline value
    is (a) as a percent of the measured plain step time — acceptance is
    < 1% overhead (the wall-clock pair is reported too, but on CPU its
    run-to-run noise exceeds the effect being measured).

    It also bounds the FLEET SCRAPER's cost on the scraped process: a
    background loop fetches + parses this process's /metrics exposition
    at 5 Hz (50x the controller's default interval) while the step loop
    re-runs — `scrape_wall_delta_pct` must stay inside the same noise
    band as the obs on/off pair (the scrape handler renders on its own
    thread; the step path is untouched)."""
    import jax
    import jax.numpy as jnp

    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.obs import trace as obs_trace
    from runbooks_tpu.obs.goodput import GoodputTracker
    from runbooks_tpu.obs.metrics import Registry
    from runbooks_tpu.obs.trace import span
    from runbooks_tpu.parallel.mesh import single_device_mesh
    from runbooks_tpu.train.optimizer import OptimizerConfig, make_optimizer
    from runbooks_tpu.train.step import create_train_state, make_train_step

    device, on_tpu = benchkit.bench_device()
    if on_tpu:
        model, batch_size, seq, steps = "bench-410m-d128", 8, 2048, 20
    else:
        model, batch_size, seq, steps = "debug", 4, 128, 30
    model = os.environ.get("RBT_BENCH_MODEL", model)
    batch_size = int(os.environ.get("RBT_BENCH_BS", batch_size))
    seq = int(os.environ.get("RBT_BENCH_SEQ", seq))

    cfg = get_config(model)
    mesh = single_device_mesh()
    opt = make_optimizer(OptimizerConfig(total_steps=10_000, warmup_steps=10))
    state, shardings = create_train_state(cfg, opt, mesh, jax.random.key(0))
    step = make_train_step(cfg, opt, mesh, shardings)
    tokens = jax.random.randint(jax.random.key(1), (batch_size, seq + 1), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:],
             "loss_mask": jnp.ones((batch_size, seq), jnp.float32)}

    workdir = benchkit.work_dir("obs")
    os.environ["RBT_TRACE"] = "1"
    obs_trace.configure(os.path.join(workdir, "trace.jsonl"))
    reg = Registry()
    goodput = GoodputTracker()

    def obs_calls(i, step_s):
        # The exact per-step sequence run_training adds (train/trainer.py):
        # data-wait + step spans, three observes, one goodput update.
        with span("data_wait", step=i):
            pass
        reg.observe("train_data_wait_seconds", 0.0001)
        reg.observe("train_step_seconds", step_s)
        reg.observe("train_checkpoint_seconds", 0.0)
        goodput.step(step_s, 0.0001, 0.0)

    try:
        with jax.set_mesh(mesh):
            # Compile + warmup outside every measured window.
            state, metrics = step(state, batch)
            float(metrics["loss"])
            state, metrics = step(state, batch)
            float(metrics["loss"])

            t0 = time.perf_counter()
            for _ in range(steps):
                state, metrics = step(state, batch)
            float(metrics["loss"])
            dt_off = time.perf_counter() - t0

            t0 = time.perf_counter()
            for i in range(steps):
                t_step = time.perf_counter()
                with span("step", step=i):
                    state, metrics = step(state, batch)
                obs_calls(i, time.perf_counter() - t_step)
            float(metrics["loss"])
            dt_on = time.perf_counter() - t0

            # Scraper-overhead bound: fetch + parse this process's live
            # /metrics exposition at 5 Hz from a background thread (50x
            # the fleet scraper's default cadence) while the plain step
            # loop re-runs.
            import threading
            import urllib.request

            from runbooks_tpu.obs.metrics import (
                parse_exposition,
                serve_metrics,
            )

            httpd = serve_metrics(0, reg)
            scrape_port = httpd.server_address[1]
            stop_scrape = threading.Event()
            scrapes = {"n": 0}

            def scrape_loop():
                while not stop_scrape.is_set():
                    try:
                        with urllib.request.urlopen(
                                f"http://127.0.0.1:{scrape_port}/metrics",
                                timeout=2) as resp:
                            parse_exposition(
                                resp.read().decode("utf-8", "replace"))
                        scrapes["n"] += 1
                    except OSError:
                        pass
                    stop_scrape.wait(0.2)

            scraper = threading.Thread(target=scrape_loop, daemon=True)
            scraper.start()
            t0 = time.perf_counter()
            for _ in range(steps):
                state, metrics = step(state, batch)
            float(metrics["loss"])
            dt_scrape = time.perf_counter() - t0
            stop_scrape.set()
            scraper.join(timeout=3)
            httpd.shutdown()
            httpd.server_close()

        # Deterministic microbench: the obs call sequence alone, amortized.
        n_micro = 2000
        t0 = time.perf_counter()
        for i in range(n_micro):
            with span("step", step=i):
                pass
            obs_calls(i, 0.01)
        obs_us_per_step = (time.perf_counter() - t0) / n_micro * 1e6
        # span("step") is separate above because in the real loop it wraps
        # the step dispatch; obs_calls covers the rest.

        step_time_s = dt_off / steps
        overhead_pct = (obs_us_per_step / 1e6) / step_time_s * 100.0
        trace_path = os.path.join(workdir, "trace.jsonl")
        trace_events = 0
        if os.path.exists(trace_path):
            with open(trace_path) as f:
                trace_events = sum(1 for ln in f if ln.startswith("{"))
        benchkit.emit({
            "metric": f"{model} obs instrumentation overhead "
                      f"(bs{batch_size}x{seq})",
            "value": round(overhead_pct, 4),
            "unit": "% of step time",
            # Acceptance: < 1% overhead; > 1.0 here = beats that bound.
            "vs_baseline": round(1.0 / max(overhead_pct, 1e-9), 2),
            "obs_us_per_step": round(obs_us_per_step, 2),
            "step_time_s": round(step_time_s, 5),
            "steps_per_sec_obs_off": round(steps / dt_off, 3),
            "steps_per_sec_obs_on": round(steps / dt_on, 3),
            "wall_delta_pct": round((dt_on - dt_off) / dt_off * 100.0, 2),
            "steps_per_sec_scrape_on": round(steps / dt_scrape, 3),
            "scrape_wall_delta_pct": round(
                (dt_scrape - dt_off) / dt_off * 100.0, 2),
            "scrapes_during_window": scrapes["n"],
            "trace_events_written": trace_events,
        })
    finally:
        obs_trace.close()
        obs_trace.configure(None)
        os.environ.pop("RBT_TRACE", None)
        pass


def flight_inner() -> None:
    """RBT_BENCH_FLIGHT=1: flight-recorder + tail-sampling overhead.

    The flight recorder (obs/flight.py) is ALWAYS ON: every serve span
    (prefill, decode chunk, queue-wait) now also appends to a bounded
    in-memory ring, and every request finish runs the tail-sampling
    decision. This axis bounds that cost three ways on a real warmed
    engine: (a) a deterministic microbench of the exact per-decode-chunk
    recording sequence (span enter/exit + ring append), reported as a
    percent of the measured steady decode-chunk time — acceptance is
    < 1%; (b) wall-clock decode throughput with the recorder on vs off
    (RBT_FLIGHT=0), reported for the noise band; (c) the compile
    sentinel across both windows — recording must add ZERO unexpected
    XLA compiles (it is host-side only) — plus the boundedness proof:
    the ring is resized small enough that the measured traffic MUST
    wrap it, and the gate checks it actually DID (dropped > 0, length
    pinned at capacity); an identity like len <= maxlen would pass
    vacuously. RBT_BENCH_GATE_STRICT=1 exits 5 when any gate fails."""
    import jax

    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.models.transformer import init_params
    from runbooks_tpu.obs import device as obs_device
    from runbooks_tpu.obs import flight as obs_flight
    from runbooks_tpu.obs import trace as obs_trace
    from runbooks_tpu.serve.engine import InferenceEngine, Request

    benchkit.bench_device()
    model = os.environ.get("RBT_BENCH_MODEL", "debug")
    slots = int(os.environ.get("RBT_BENCH_SLOTS", "4"))
    waves = int(os.environ.get("RBT_BENCH_WAVES", "6"))
    cfg = get_config(model)
    params = jax.jit(lambda r: init_params(cfg, r))(jax.random.key(0))

    workdir = benchkit.work_dir("flight")
    os.environ["RBT_CONTENT_DIR"] = workdir  # tail promotions land here
    os.environ.pop("RBT_TRACE", None)
    # Tail threshold high enough that nothing promotes in the measured
    # windows: steady state pays only the classification check.
    os.environ["RBT_TRACE_TAIL_MS"] = "60000"
    obs_trace.configure(os.path.join(workdir, "trace.jsonl"))
    # Small ring so the measured windows genuinely WRAP it: the
    # boundedness gate below proves the wrap happened, not the deque
    # identity.
    ring_cap = int(os.environ.get("RBT_BENCH_FLIGHT_RING", "128"))
    obs_flight.RING.resize(ring_cap)
    engine = InferenceEngine(cfg, params, max_slots=slots, seed=0)
    engine.warmup()
    sentinel = obs_device.SENTINEL
    monitoring_live = sentinel.install()
    unexpected_before = sentinel.unexpected

    def wave(n_reqs, max_tokens=32):
        reqs = [Request(prompt_tokens=list(range(1, 9)),
                        max_tokens=max_tokens,
                        request_id=f"bench-{i}")
                for i in range(n_reqs)]
        engine.generate(reqs)

    def window():
        steps0 = engine.steps
        t0 = time.perf_counter()
        for _ in range(waves):
            wave(slots)
        dt = time.perf_counter() - t0
        return dt, engine.steps - steps0

    # Warm one wave in each mode, then measure: recorder OFF first.
    os.environ["RBT_FLIGHT"] = "0"
    wave(slots)
    dt_off, steps_off = window()
    os.environ.pop("RBT_FLIGHT", None)  # default: recording ON
    wave(slots)
    dt_on, steps_on = window()
    unexpected = sentinel.unexpected - unexpected_before
    ring_stats = obs_flight.RING.stats()
    # Meaningful boundedness: the traffic wrapped the ring (events were
    # really dropped) AND the live length sits pinned at capacity.
    ring_bounded = (ring_stats["dropped"] > 0
                    and ring_stats["events"] == ring_stats["capacity"])

    # Deterministic microbench: the per-decode-chunk recording sequence
    # (one span with the engine's decode attrs) plus one tail-sampling
    # decision, amortized.
    from runbooks_tpu.obs.trace import span

    n_micro = 5000
    rids = [f"bench-{i}" for i in range(slots)]
    t0 = time.perf_counter()
    for i in range(n_micro):
        with span("decode", view=256, active=slots, request_ids=rids):
            pass
        obs_flight.tail_sample(f"bench-{i % slots}", 0.001, "stop")
    flight_us = (time.perf_counter() - t0) / n_micro * 1e6

    step_time_s = dt_on / max(steps_on, 1)
    overhead_pct = (flight_us / 1e6) / step_time_s * 100.0
    obs_trace.close()
    obs_trace.configure(None)
    obs_flight.RING.resize(obs_flight.ring_capacity())

    ok = (overhead_pct < 1.0 and unexpected == 0 and ring_bounded
          and monitoring_live)
    benchkit.emit({
        "metric": f"{model} flight-recorder overhead "
                  f"({slots} slots, ring {ring_stats['capacity']})",
        "value": round(overhead_pct, 4),
        "unit": "% of decode-chunk time",
        # Acceptance < 1%: vs_baseline > 1 beats the bound (zeroed when
        # a gate condition fails so the sweep table shows it).
        "vs_baseline": (round(1.0 / max(overhead_pct, 1e-9), 2)
                        if ok else 0.0),
        "flight_us_per_step": round(flight_us, 2),
        "decode_step_time_s": round(step_time_s, 6),
        "steps_per_sec_flight_off": round(steps_off / dt_off, 3),
        "steps_per_sec_flight_on": round(steps_on / dt_on, 3),
        "wall_delta_pct": round((dt_on - dt_off) / dt_off * 100.0, 2),
        "ring_events": ring_stats["events"],
        "ring_capacity": ring_stats["capacity"],
        "ring_recorded": ring_stats["recorded"],
        "ring_dropped": ring_stats["dropped"],
        "ring_bounded": ring_bounded,
        "unexpected_compiles": unexpected,
        "sentinel_monitoring": monitoring_live,
    })
    if os.environ.get("RBT_BENCH_GATE_STRICT") == "1" and not ok:
        print("FLIGHT GATE: "
              + (f"overhead {overhead_pct:.3f}% >= 1%" if
                 overhead_pct >= 1.0 else
                 f"{unexpected} unexpected compile(s)" if unexpected else
                 "ring never wrapped / exceeded capacity"
                 if not ring_bounded else
                 "jax.monitoring feed unavailable")
              + " (strict mode)", file=sys.stderr, flush=True)
        raise SystemExit(5)


def history_inner() -> None:
    """RBT_BENCH_HISTORY=1: fleet-history append+rollup overhead.

    The fleet scraper (controller/fleet.py) now appends every mirrored
    series into the obs/history.py rings inside the same mirror loop.
    This axis bounds that cost on the REAL scrape path: N fake replicas
    serve realistic expositions (latency histograms + counters + gauges)
    over live HTTP, and the sweep is measured with history ON vs with a
    no-op history (identical code path, appends stubbed) — plus a
    deterministic microbench of the exact ingest sequence (parse ->
    append_scalar/append_histogram per family) amortized per sweep.
    Acceptance: the append+rollup share is < 1% of the scrape wall.
    The compile sentinel runs across the measured loop — the history is
    pure host-side bookkeeping and must add ZERO XLA compiles — and one
    /metrics/history query proves the read path stays bounded.
    RBT_BENCH_GATE_STRICT=1 exits 6 when any gate fails."""
    import jax  # noqa: F401 — backend up before the sentinel installs

    from runbooks_tpu.api.types import Server
    from runbooks_tpu.cloud.base import CommonConfig
    from runbooks_tpu.cloud.local import LocalCloud
    from runbooks_tpu.controller import fleet as fl
    from runbooks_tpu.controller.manager import Ctx
    from runbooks_tpu.k8s.fake import FakeCluster
    from runbooks_tpu.obs import device as obs_device
    from runbooks_tpu.obs import metrics as obs_metrics
    from runbooks_tpu.obs.history import FleetHistory
    from runbooks_tpu.sci.base import FakeSCI

    replicas = int(os.environ.get("RBT_BENCH_HISTORY_REPLICAS", "4"))
    sweeps = int(os.environ.get("RBT_BENCH_HISTORY_SWEEPS", "50"))

    sentinel = obs_device.SENTINEL
    monitoring_live = sentinel.install()
    unexpected_before = sentinel.unexpected

    client = FakeCluster()
    ctx = Ctx(client=client,
              cloud=LocalCloud(CommonConfig(
                  cluster_name="bench",
                  artifact_bucket_url="file:///tmp/bench-bucket",
                  registry_url="registry.local:5000")),
              sci=FakeSCI())
    client.create(Server.new("bench", spec={"image": "x"}).obj)
    httpds = []
    for i in range(replicas):
        reg = obs_metrics.Registry()
        for v in (0.005, 0.02, 0.08, 0.3):
            for _ in range(50):
                reg.observe("serve_ttft_seconds", v)
                reg.observe("serve_queue_wait_seconds", v / 10)
                reg.observe("serve_inter_token_seconds", v / 20)
        reg.set_counter("serve_requests_total", 2000 + i)
        reg.set_counter("serve_requests_failed_total", 3)
        reg.set_counter("serve_tokens_generated_total", 90000 + i)
        reg.set_gauge("serve_active_slots", 3)
        reg.set_gauge("serve_queue_depth", 1)
        reg.set_gauge("serve_kv_occupancy_ratio", 0.4)
        httpd = obs_metrics.serve_metrics(0, reg)
        httpds.append(httpd)
        client.create({
            "apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": f"bench-{i}", "namespace": "default",
                         "labels": {"server": "bench", "role": "run"},
                         "annotations": {fl.METRICS_PORT_ANNOTATION:
                                         str(httpd.server_address[1])}},
            "spec": {"containers": [{"name": "c"}]},
            "status": {"phase": "Running", "podIP": "127.0.0.1"},
        })

    class _NoopHistory(FleetHistory):
        """Same object shape, every write path stubbed (ingest is the
        one the mirror actually ships): isolates the ring tax."""

        def ingest(self, *a, **k):
            return None

        def append_scalar(self, *a, **k):
            return None

        def append_histogram(self, *a, **k):
            return None

    def sweep_wall(history):
        scraper = fl.FleetScraper(ctx, state=fl.FleetState(),
                                  registry=obs_metrics.Registry(),
                                  history=history, timeout_s=2.0)
        scraper.scrape_once()  # warm connections + series dicts
        t0 = time.perf_counter()
        for _ in range(sweeps):
            scraper.scrape_once()
        return (time.perf_counter() - t0) / sweeps, scraper

    try:
        wall_off, _ = sweep_wall(_NoopHistory())
        history = FleetHistory()
        wall_on, scraper = sweep_wall(history)

        # Deterministic microbench of the MARGINAL cost: exactly the
        # per-replica `history.ingest` call _mirror ships — one lock,
        # memoized label keys, O(1) deque appends — isolated from
        # HTTP/parse noise.
        sample = next(iter(
            scraper.state.replicas("Server", "default",
                                   "bench").values()))
        labels = {"kind": "Server", "namespace": "default",
                  "name": "bench", "replica": "bench-0"}
        micro_hist = FleetHistory()
        micro_hist.ingest(sample.families, labels, time.time(),
                          fl.MIRROR_PREFIXES)  # warm the label-key memo
        n_micro = 200
        t0 = time.perf_counter()
        for i in range(n_micro):
            micro_hist.ingest(sample.families, labels, time.time(),
                              fl.MIRROR_PREFIXES)
        ingest_us = (time.perf_counter() - t0) / n_micro * 1e6
    finally:
        for httpd in httpds:
            httpd.shutdown()
            httpd.server_close()

    # One replica's ingest x N replicas, as a share of the real sweep.
    append_pct = (ingest_us * replicas / 1e6) / wall_on * 100.0
    # The /metrics/history read path: one full-family query, bounded.
    query = history.query("serve_ttft_seconds", 900, 10, q=0.99,
                          sel={"name": "bench"})
    query_bounded = len(query["points"]) <= 720
    unexpected = sentinel.unexpected - unexpected_before
    stats = history.stats()
    ok = (append_pct < 1.0 and unexpected == 0 and query_bounded
          and monitoring_live)
    print(json.dumps({
        "metric": f"fleet-history append+rollup overhead "
                  f"({replicas} replicas, {sweeps} sweeps)",
        "value": round(append_pct, 4),
        "unit": "% of scrape wall",
        # Acceptance < 1%: vs_baseline > 1 beats the bound (zeroed when
        # a gate fails so the sweep table shows it).
        "vs_baseline": (round(1.0 / max(append_pct, 1e-9), 2)
                        if ok else 0.0),
        "scrape_wall_history_on_ms": round(wall_on * 1e3, 3),
        "scrape_wall_history_off_ms": round(wall_off * 1e3, 3),
        "wall_delta_pct": round((wall_on - wall_off) / wall_off * 100.0,
                                2),
        "ingest_us_per_replica_sweep": round(ingest_us, 2),
        "history_series": stats["series"],
        "history_points": stats["points"],
        "query_points": len(query["points"]),
        "query_bounded": query_bounded,
        "unexpected_compiles": unexpected,
        "sentinel_monitoring": monitoring_live,
        "platform": "host",
    }))
    if os.environ.get("RBT_BENCH_GATE_STRICT") == "1" and not ok:
        print("HISTORY GATE: "
              + (f"append share {append_pct:.3f}% >= 1%"
                 if append_pct >= 1.0 else
                 f"{unexpected} unexpected compile(s)" if unexpected else
                 "query response unbounded" if not query_bounded else
                 "jax.monitoring feed unavailable")
              + " (strict mode)", file=sys.stderr, flush=True)
        raise SystemExit(6)


def device_obs_inner() -> None:
    """RBT_BENCH_DEVICE_OBS=1: compile discipline + analytic MFU.

    Two assertions about the device layer (docs/observability.md,
    "Device-level metrics"): (a) the steady-state train step loop runs
    ZERO unexpected XLA compiles — the compile sentinel is armed after
    the first (compile-folding) step and any recompile in the measured
    window is a stall the at-scale papers warn about; the JSON line
    reports the count and RBT_BENCH_GATE_STRICT=1 exits 4 on a nonzero
    one. (b) analytic MFU from the compiled step's cost_analysis FLOPs
    sits beside the formula MFU (3 * model FLOPs/token) the trainer
    reports — the two must agree to ~10% or one of them is lying
    (flops_ratio in the JSON line is that cross-check), and the roofline
    classification (compute- vs bandwidth-bound) rides along."""
    import jax
    import jax.numpy as jnp

    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.obs import device as obs_device
    from runbooks_tpu.parallel.mesh import single_device_mesh
    from runbooks_tpu.train.optimizer import OptimizerConfig, make_optimizer
    from runbooks_tpu.train.step import create_train_state, make_train_step
    from runbooks_tpu.utils.hw import chip_peaks

    device, on_tpu = benchkit.bench_device()
    if on_tpu:
        model, batch_size, seq, steps = "bench-410m-d128", 8, 2048, 20
    else:
        model, batch_size, seq, steps = "debug", 4, 128, 30
    model = os.environ.get("RBT_BENCH_MODEL", model)
    batch_size = int(os.environ.get("RBT_BENCH_BS", batch_size))
    seq = int(os.environ.get("RBT_BENCH_SEQ", seq))

    cfg = get_config(model)
    mesh = single_device_mesh()
    opt = make_optimizer(OptimizerConfig(total_steps=10_000, warmup_steps=10))
    state, shardings = create_train_state(cfg, opt, mesh, jax.random.key(0))
    step = make_train_step(cfg, opt, mesh, shardings)
    tokens = jax.random.randint(jax.random.key(1), (batch_size, seq + 1), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:],
             "loss_mask": jnp.ones((batch_size, seq), jnp.float32)}

    sentinel = obs_device.SENTINEL
    # install() returns False when this jax build exposes no monitoring
    # feed — the sentinel then observes NOTHING, and "0 unexpected
    # compiles" would be vacuous; the gate must fail loudly, not pass.
    monitoring_live = sentinel.install()
    try:
        with jax.set_mesh(mesh):
            # Compile + warmup, then arm the sentinel: from here on every
            # compile in the measured loop is a stall.
            state, metrics = step(state, batch)
            float(metrics["loss"])
            state, metrics = step(state, batch)
            float(metrics["loss"])
            cost = obs_device.cost_analysis_of(step, state, batch)
            sentinel.mark_steady("bench")
            unexpected_before = sentinel.unexpected

            t0 = time.perf_counter()
            for _ in range(steps):
                state, metrics = step(state, batch)
            float(metrics["loss"])
            dt = time.perf_counter() - t0
        unexpected = sentinel.unexpected - unexpected_before
    finally:
        sentinel.clear_steady("bench")

    step_time_s = dt / steps
    peaks = chip_peaks(device)  # None off-TPU: no MFU, no roofline bound
    formula_flops = 3.0 * cfg.flops_per_token(seq) * batch_size * seq
    out = {
        "metric": f"{model} device-obs: unexpected compiles in "
                  f"{steps}-step steady loop (bs{batch_size}x{seq})",
        "value": unexpected,
        "unit": "compiles",
        # Pass = zero recompiles once steady, OBSERVED by a live feed.
        "vs_baseline": (1.0 if unexpected == 0 and monitoring_live
                        else 0.0),
        "sentinel_monitoring": monitoring_live,
        "compiles_total": sentinel.total,
        "step_time_s": round(step_time_s, 5),
    }
    if peaks:
        out["mfu_formula"] = round(
            formula_flops / step_time_s / peaks[0], 4)
    if cost is not None:
        roof = obs_device.classify_roofline(cost["flops"],
                                            cost["hbm_bytes"])
        out.update({
            "analytic_flops_per_step": cost["flops"],
            "formula_flops_per_step": formula_flops,
            # cost_analysis vs the 3x-forward formula: the cross-check.
            "flops_ratio": round(cost["flops"] / formula_flops, 3),
            "hbm_bytes_per_step": cost["hbm_bytes"],
            "arithmetic_intensity": roof["arithmetic_intensity"],
        })
        if peaks:
            out["mfu_analytic"] = round(
                cost["flops"] / step_time_s / peaks[0], 4)
            out["bound"] = roof["bound"]
    benchkit.emit(out)
    if os.environ.get("RBT_BENCH_GATE_STRICT") == "1" \
            and (unexpected or not monitoring_live):
        print(f"DEVICE-OBS GATE: "
              + (f"{unexpected} unexpected compile(s) in the "
                 "steady-state loop" if unexpected else
                 "jax.monitoring feed unavailable — nothing was "
                 "observed") + " (strict mode)", file=sys.stderr,
              flush=True)
        raise SystemExit(4)


def inner() -> None:
    if os.environ.get("RBT_BENCH_RESUME") == "1":
        return resume_inner()
    if os.environ.get("RBT_BENCH_OBS") == "1":
        return obs_inner()
    if os.environ.get("RBT_BENCH_FLIGHT") == "1":
        return flight_inner()
    if os.environ.get("RBT_BENCH_HISTORY") == "1":
        return history_inner()
    if os.environ.get("RBT_BENCH_DEVICE_OBS") == "1":
        return device_obs_inner()
    import jax
    import jax.numpy as jnp

    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.parallel.mesh import single_device_mesh
    from runbooks_tpu.train.optimizer import OptimizerConfig, make_optimizer
    from runbooks_tpu.train.step import create_train_state, make_train_step
    from runbooks_tpu.utils.hw import chip_peaks

    device, on_tpu = benchkit.bench_device()

    if on_tpu:
        # d128 variant: same params/FLOPs as bench-410m, but 8 heads x d128
        # keeps MXU contractions full-width.
        model, batch_size, seq = "bench-410m-d128", 8, 2048
        steps, warmup = 20, 3
    else:  # CPU smoke so the bench is runnable anywhere
        model, batch_size, seq = "debug", 4, 128
        steps, warmup = 3, 1

    # Tuning knobs without code edits (e.g. RBT_BENCH_MODEL=bench-1b
    # RBT_BENCH_BS=4 RBT_BENCH_IMPL=flash).
    model = os.environ.get("RBT_BENCH_MODEL", model)
    batch_size = int(os.environ.get("RBT_BENCH_BS", batch_size))
    seq = int(os.environ.get("RBT_BENCH_SEQ", seq))
    overrides = {}
    if os.environ.get("RBT_BENCH_IMPL"):
        overrides["attention_impl"] = os.environ["RBT_BENCH_IMPL"]
    if os.environ.get("RBT_BENCH_REMAT"):
        overrides["remat_policy"] = os.environ["RBT_BENCH_REMAT"]
    if os.environ.get("RBT_BENCH_BQ"):
        overrides["flash_block_q"] = int(os.environ["RBT_BENCH_BQ"])
    if os.environ.get("RBT_BENCH_BK"):
        overrides["flash_block_k"] = int(os.environ["RBT_BENCH_BK"])
    # State-memory levers (f32 masters + moments force full remat):
    # RBT_BENCH_PARAM_DTYPE=bfloat16 + RBT_BENCH_MU_DTYPE=bfloat16 +
    # RBT_BENCH_REMAT=save_attn_out. Never run on the chip (ROADMAP S8).
    if os.environ.get("RBT_BENCH_PARAM_DTYPE"):
        overrides["param_dtype"] = os.environ["RBT_BENCH_PARAM_DTYPE"]
    # Training fast-path axes (docs/training-performance.md):
    # RBT_BENCH_ACCUM=k scans k microbatches per optimizer step (peak
    # activation memory of one microbatch — run a k-times larger global
    # batch than fits the plain path); RBT_BENCH_CE_CHUNK=c uses the
    # chunked fused CE (no [b, s, vocab] f32 logits tensor).
    accum = int(os.environ.get("RBT_BENCH_ACCUM", "1"))
    ce_chunk = int(os.environ.get("RBT_BENCH_CE_CHUNK", "0"))
    # Overlapped collective-matmul axis (docs/tensor-parallel-performance
    # .md): RBT_BENCH_MESH_TENSOR=k runs the same train step on a k-way
    # tensor-parallel mesh (needs k devices on the platform) and
    # RBT_BENCH_COLLECTIVE=off|ring|auto picks GSPMD blocking collectives
    # vs the ppermute ring — the off/ring pair at equal shape is the
    # overlap win, isolated.
    mesh_tensor = int(os.environ.get("RBT_BENCH_MESH_TENSOR", "1"))
    cm_env = os.environ.get("RBT_BENCH_COLLECTIVE")
    if cm_env:
        overrides["collective_matmul"] = cm_env

    cfg = get_config(model, **overrides)
    if mesh_tensor > 1:
        from runbooks_tpu.parallel.mesh import MeshConfig, make_mesh

        mesh = make_mesh(MeshConfig(tensor=mesh_tensor, fsdp=-1))
    else:
        mesh = single_device_mesh()
    opt = make_optimizer(OptimizerConfig(
        total_steps=10_000, warmup_steps=10,
        mu_dtype=os.environ.get("RBT_BENCH_MU_DTYPE") or None))
    state, shardings = create_train_state(cfg, opt, mesh, jax.random.key(0))
    step = make_train_step(cfg, opt, mesh, shardings,
                           accumulate_steps=accum, loss_chunk=ce_chunk)

    tokens = jax.random.randint(jax.random.key(1), (batch_size, seq + 1), 0,
                                cfg.vocab_size)
    batch = {
        "tokens": tokens[:, :-1],
        "targets": tokens[:, 1:],
        "loss_mask": jnp.ones((batch_size, seq), jnp.float32),
    }

    # Sync by pulling the chained loss to the host: float() waits for every
    # step it depends on, on every backend.
    with jax.set_mesh(mesh):
        # First call = XLA compile + one step; timed separately so the
        # bench reports steady-state AND incl-compile MFU.
        t_compile = time.perf_counter()
        state, metrics = step(state, batch)
        float(metrics["loss"])
        compile_s = time.perf_counter() - t_compile
        for _ in range(max(0, warmup - 1)):
            state, metrics = step(state, batch)
        float(metrics["loss"])

        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = step(state, batch)
        float(metrics["loss"])
        dt = time.perf_counter() - t0

    tokens_per_step = batch_size * seq
    tokens_per_sec = tokens_per_step * steps / dt
    out = {
        "tokens_per_sec_per_chip": round(tokens_per_sec, 1),
        "step_time_s": round(dt / steps, 4),
        "compile_time_s": round(compile_s, 2),
        "accumulate_steps": accum,
        "ce_chunk": ce_chunk,
        "mesh_tensor": mesh_tensor,
        "collective_matmul": cfg.collective_matmul,
        "global_batch": batch_size,
        "loss": round(float(metrics["loss"]), 4),
    }
    peaks = chip_peaks(device)
    if peaks is None:
        # No device peak off-TPU, so no MFU: the functional result is that
        # the step runs and the loss is finite.
        out = {"metric": f"{model} train step (bs{batch_size}x{seq})",
               "value": out["loss"], "unit": "loss", **out}
    else:
        # Train FLOPs/token ~= 3x forward matmul FLOPs (bwd ~= 2x fwd). A
        # multi-chip mesh (RBT_BENCH_MESH_TENSOR) measures whole-mesh
        # throughput, so MFU normalizes by the whole mesh's peak.
        train_flops_per_token = 3.0 * cfg.flops_per_token(seq)
        n_chips = len(mesh.devices.flat) if mesh_tensor > 1 else 1
        peak = peaks[0] * n_chips
        mfu = tokens_per_sec * train_flops_per_token / peak
        # What a short job actually sees: steps+1 steps incl. the compile.
        tps_incl = tokens_per_step * (steps + 1) / (dt + compile_s)
        out = {
            "metric": f"{model} train MFU (1 chip, bs{batch_size}x{seq}, "
                      "bf16)",
            "value": round(mfu, 4),
            "unit": "MFU",
            "vs_baseline": round(mfu / 0.35, 4),
            "mfu_incl_compile": round(
                tps_incl * train_flops_per_token / peak, 4),
            **out}
    benchkit.emit(out)


if __name__ == "__main__":
    if "--inner" in sys.argv:
        inner()
    else:
        here = os.path.dirname(os.path.abspath(__file__))
        serve_script = os.path.join(here, "bench_serve.py")
        try:
            result = benchkit.measure_outer(os.path.abspath(__file__))
            # Fold the serving benchmark into the same JSON line.
            if os.environ.get("RBT_BENCH_SKIP_SERVE") != "1":
                result["serve"] = benchkit.measure_outer(serve_script)
                if os.environ.get("RBT_BENCH_SKIP_QUANT") != "1":
                    # Quantized-serving pair: bf16 vs int8 weights + int8
                    # KV at a size where decode is bandwidth-bound.
                    shape = {
                        "RBT_BENCH_MODEL": os.environ.get(
                            "RBT_BENCH_QUANT_MODEL", "bench-410m"),
                        "RBT_BENCH_PROMPT": "16", "RBT_BENCH_MAXTOK": "16",
                        "RBT_BENCH_REQUESTS": "8",
                        "RBT_BENCH_MAXSEQ": "128",
                    }
                    result["serve_quant"] = {
                        q: benchkit.measure_outer(
                            serve_script,
                            {**shape, "RBT_BENCH_QUANTIZE": q})
                        for q in ("none", "int8")}
        except benchkit.BenchFailed as exc:
            print(f"bench failed: {exc}", file=sys.stderr)
            raise SystemExit(1) from exc
        print(json.dumps(result))
