"""One span mechanism, three sinks (ISSUE 24): obs.trace spans inside a
profiler capture, fine spans that stay out of the flight ring, the
program's named scopes in the lowered HLO, and the set-up phases."""

import dataclasses
import glob
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from runbooks_tpu.models.config import get_config
from runbooks_tpu.models.transformer import KVCache, init_params
from runbooks_tpu.obs import flight as obs_flight
from runbooks_tpu.obs import profile as obs_profile
from runbooks_tpu.obs import trace as obs_trace

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


@pytest.fixture(autouse=True)
def clean_obs_state(monkeypatch):
    obs_flight.RING.clear()
    monkeypatch.delenv("RBT_TRACE", raising=False)
    monkeypatch.delenv("RBT_FLIGHT", raising=False)
    yield
    obs_trace.set_annotator(None)
    obs_flight.RING.clear()


def tiny_cfg():
    return dataclasses.replace(
        get_config("llama2-7b"), vocab_size=128, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, max_seq_len=64, dtype="float32")


def make_engine(kind: str, cfg, params):
    from runbooks_tpu.serve.engine import InferenceEngine

    if kind == "paged":
        from runbooks_tpu.serve.paging import PagedInferenceEngine

        return PagedInferenceEngine(cfg, params, max_slots=2, seed=0,
                                    decode_chunk=2, page_size=16)
    return InferenceEngine(cfg, params, max_slots=2, seed=0, decode_chunk=2)


def two_requests():
    from runbooks_tpu.serve.engine import Request

    return [Request(prompt_tokens=[1, 2, 3, 4, 5], max_tokens=5,
                    request_id="r-a"),
            Request(prompt_tokens=[1, 2, 3], max_tokens=3,
                    request_id="r-b")]


# ---------------------------------------------------------------------------
# The profiler sink
# ---------------------------------------------------------------------------

def capture_events(log_dir):
    """{thread line index: [(name, start, end, stats)]} of our spans."""
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for i, line in enumerate(plane.lines):
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                       dict(e.stats)) for e in line.events]
            if any(e[0] == "tick" for e in events):
                lines[i] = events
    return lines


@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_capture_holds_the_tick_and_its_children_nested(kind, tmp_path):
    cfg = tiny_cfg()
    engine = make_engine(kind, cfg, init_params(cfg, jax.random.key(0)))
    engine.generate(two_requests()[:1])      # compile outside the capture
    profiler = obs_profile.Profiler()
    profiler.start(str(tmp_path / "cap"))    # Python tracer off by default
    try:
        assert obs_trace.record_enabled() and obs_trace.fine_enabled()
        engine.generate(two_requests())
    finally:
        profiler.stop()
    assert not obs_trace.fine_enabled()
    lines = capture_events(str(tmp_path / "cap"))
    assert len(lines) == 1, "every engine span on one thread line"
    (events,) = lines.values()
    by_name = {}
    for ev in events:
        by_name.setdefault(ev[0], []).append(ev)
    for parent, children in (
            ("tick", ["tick.admit", "decode", "prefill"]),
            ("decode", ["decode.operands", "decode.dispatch",
                        "decode.sync", "decode.replay"]),
            ("prefill", ["prefill.operands", "prefill.dispatch",
                         "prefill.sync", "prefill.activate"]),
            ("tick.admit", ["prefill"])):
        for child in children:
            assert by_name.get(child), f"no {child} in the capture"
            for _, lo, hi, _ in by_name[child]:
                assert any(p_lo <= lo and hi <= p_hi
                           for _, p_lo, p_hi, _ in by_name[parent]), \
                    f"{child} outside every {parent}"
    # Spans of one request share its id; a list is joined with spaces.
    ids = {rid for _, _, _, st in by_name["decode"]
           for rid in str(st["request_ids"]).split()}
    assert ids == {"r-a", "r-b"}
    assert all(int(st["tokens"]) >= 1
               for _, _, _, st in by_name["decode.replay"])
    assert sum(int(st["admitted"])
               for _, _, _, st in by_name["tick.admit"]) == 2


def test_profile_start_carries_both_clocks(tmp_path):
    profiler = obs_profile.Profiler()
    log_dir = profiler.start(str(tmp_path / "cap"))
    profiler.stop()
    starts = [e for e in obs_flight.RING.snapshot()
              if e["name"] == "profile.start"]
    assert starts and starts[0]["args"]["dir"] == log_dir
    # time.time_ns() beside the directory: ring and trace.jsonl events lie
    # on that clock, the same instant is an event inside the capture.
    assert abs(starts[0]["args"]["unix_ns"] / 1e3 - starts[0]["ts"]) < 5e6


# ---------------------------------------------------------------------------
# Outside a capture
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ring,old_is_null", [("1", False), ("0", True)])
def test_null_spans_outside_a_capture(ring, old_is_null, monkeypatch):
    monkeypatch.setenv("RBT_FLIGHT", ring)
    null = obs_trace.fine("decode.sync")
    assert null is obs_trace.fine("tick", active=1)   # the shared object
    assert (obs_trace.span("decode", view=128) is null) == old_is_null
    null.set(tokens=3)                                # accepted, dropped
    with null:
        pass
    assert not obs_flight.RING.snapshot()


def test_fine_spans_reach_the_trace_file_but_never_the_ring(tmp_path,
                                                           monkeypatch):
    monkeypatch.setenv("RBT_TRACE", "1")
    obs_trace.configure(str(tmp_path / "trace.jsonl"))
    try:
        with obs_trace.span("decode", view=64):
            with obs_trace.fine("decode.replay") as replay:
                replay.set(tokens=2)
    finally:
        obs_trace.close()
        obs_trace.configure(None)
    text = (tmp_path / "trace.jsonl").read_text()
    assert '"decode.replay"' in text and '"tokens":2' in text
    assert [e["name"] for e in obs_flight.RING.snapshot()] == ["decode"]


def test_obs_trace_imports_without_jax():
    code = ("import sys; import runbooks_tpu.obs.trace as t; "
            "assert 'jax' not in sys.modules, 'jax imported'; "
            "assert t.fine('x') is t.fine('y')")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60,
                   env={**os.environ, "RBT_TRACE": "0"})


@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_ring_events_of_a_request_are_what_they_were(kind):
    """Name for name what the parent commit records (PR 23's tree, same
    engine, same requests): the new spans never enter the ring."""
    cfg = tiny_cfg()
    engine = make_engine(kind, cfg, init_params(cfg, jax.random.key(0)))
    engine.generate(two_requests())
    names = {rid: [e["name"] for e in
                   obs_flight.RING.snapshot(request_id=rid)]
             for rid in ("r-a", "r-b")}
    assert names == {
        "r-a": ["queue_wait", "prefill", "decode", "decode"],
        "r-b": ["queue_wait", "prefill", "decode"]}
    # (A test before this one may have left the compile sentinel steady:
    # its instant is the ring's own business, not a span of the engine.)
    assert {e["name"] for e in obs_flight.RING.snapshot()} \
        - {"unexpected_compile"} == {"queue_wait", "prefill", "decode"}


# ---------------------------------------------------------------------------
# A request's instants, from the HTTP handler to its first SSE write
# (ISSUE 36)
# ---------------------------------------------------------------------------

PHASES = ("serve_request_parse_seconds", "serve_pending_wait_seconds",
          "serve_queue_wait_seconds", "serve_first_token_seconds")


def hist(name):
    """(count, sum) of a label-less family of the process's registry."""
    from runbooks_tpu.obs import metrics as obs_metrics

    return obs_metrics.REGISTRY.histogram_stats(name) or (0, 0.0)


def grew(before, names):
    return {n: tuple(a - b for a, b in zip(hist(n), before[n]))
            for n in names}


def test_streamed_completion_stamps_six_instants_in_order(monkeypatch,
                                                          tmp_path):
    """Through the app: received <= handed <= submitted <= admitted <=
    first token <= first write, every phase observed once, and the first
    four add up to serve_ttft_seconds (which starts at handler entry)."""
    import asyncio
    import json

    from aiohttp.test_utils import TestClient, TestServer

    from runbooks_tpu.serve import api as serve_api
    from runbooks_tpu.train.data import ByteTokenizer

    cfg = dataclasses.replace(tiny_cfg(), vocab_size=512)
    app = serve_api.create_server(
        cfg, init_params(cfg, jax.random.key(0)), ByteTokenizer(),
        max_slots=2, decode_chunk=2, warmup=False)
    seen = []
    submit_many = app["worker"].submit_many
    monkeypatch.setattr(app["worker"], "submit_many",
                        lambda reqs: seen.extend(reqs) or submit_many(reqs))
    monkeypatch.setenv("RBT_TRACE", "1")
    obs_trace.configure(str(tmp_path / "trace.jsonl"))
    names = PHASES + ("serve_first_write_seconds", "serve_ttft_seconds")
    before = {n: hist(n) for n in names}

    async def drive():
        async with TestClient(TestServer(app)) as client:
            r = await client.post("/v1/completions", json={
                "prompt": "hello", "max_tokens": 6, "temperature": 0.0,
                "stream": True}, headers={"X-Request-Id": "r-stamps"})
            assert r.status == 200
            lines = [ln async for ln in r.content]
            return [json.loads(ln[6:]) for ln in lines
                    if ln.startswith(b"data: {")]

    try:
        chunks = asyncio.run(drive())
    finally:
        app["worker"].stop()
        obs_trace.close()
        obs_trace.configure(None)
    assert chunks[-1]["choices"][0]["finish_reason"] == "length"
    (req,) = seen
    instants = [req._received, req._handed, req._submitted, req._admitted,
                req._first_token, req._first_write]
    assert all(t > 0 for t in instants)
    assert instants == sorted(instants)
    delta = grew(before, names)
    assert {n: c for n, (c, _) in delta.items()} == dict.fromkeys(names, 1)
    assert abs(sum(delta[n][1] for n in PHASES)
               - delta["serve_ttft_seconds"][1]) < 1e-3
    assert abs(delta["serve_ttft_seconds"][1]
               - (req._first_token - req._received)) < 1e-6
    # The trace file holds the request's way with no hole: the handler's
    # span, both backdated waits, the prefill, the write of a delta.
    events = [json.loads(ln.rstrip(",\n")) for ln in
              (tmp_path / "trace.jsonl").read_text().splitlines()[1:]]
    mine = [e["name"] for e in events
            if "r-stamps" in str(e.get("args", {}).get("request_id", ""))
            or "r-stamps" in str(e.get("args", {}).get("request_ids", ""))]
    for name in ("api.submit", "pending_wait", "queue_wait", "prefill",
                 "api.write"):
        assert name in mine, f"no {name} event of the request: {mine}"


@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_a_request_handed_over_during_a_tick_waits_as_pending(kind):
    """The wait the parent's accounting left out: a request handed to the
    worker while engine.step runs reaches engine.submit only at the next
    intake. That is serve_pending_wait_seconds, not the queue's wait."""
    import threading
    import time

    from runbooks_tpu.serve.api import EngineWorker
    from runbooks_tpu.serve.engine import Request

    cfg = tiny_cfg()
    engine = make_engine(kind, cfg, init_params(cfg, jax.random.key(0)))
    step, held, hold = engine.step, threading.Event(), [True]

    def held_step():
        out = step()
        if hold[0]:
            hold[0] = False
            held.set()
            time.sleep(0.05)        # the tick goes on for 50 ms
        return out

    engine.step = held_step
    worker = EngineWorker(engine)
    names = ("serve_pending_wait_seconds", "serve_queue_wait_seconds")
    try:
        first = worker.submit(Request(prompt_tokens=[1, 2, 3],
                                      max_tokens=24, request_id="r-first"))
        assert held.wait(timeout=60)
        before = {n: hist(n) for n in names}
        late = Request(prompt_tokens=[4, 5, 6], max_tokens=2,
                       request_id="r-late")
        late_fut = worker.submit(late)
        assert late_fut.result(timeout=60) is late
        first.result(timeout=60)
    finally:
        worker.stop()
    delta = grew(before, names)
    assert delta["serve_pending_wait_seconds"][0] == 1
    assert delta["serve_pending_wait_seconds"][1] >= 0.040
    assert late._submitted - late._handed >= 0.040
    assert delta["serve_queue_wait_seconds"] == (
        1, pytest.approx(late._admitted - late._submitted))
    assert delta["serve_queue_wait_seconds"][1] < 0.040
    # No handler stamped it: TTFT counts from engine.submit.
    assert late._received == 0.0 and late._first_write == 0.0
    assert [e["name"] for e in
            obs_flight.RING.snapshot(request_id="r-late")][:2] \
        == ["pending_wait", "queue_wait"]


# ---------------------------------------------------------------------------
# What the host waits for inside *.sync; what a prefill carried (ISSUE 36)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_sync_children_and_prefill_tokens_under_a_capture(kind, tmp_path):
    cfg = tiny_cfg()
    engine = make_engine(kind, cfg, init_params(cfg, jax.random.key(0)))
    engine.generate(two_requests()[:1])      # compile outside the capture
    profiler = obs_profile.Profiler()
    profiler.start(str(tmp_path / "cap"))
    try:
        engine.generate(two_requests())
    finally:
        profiler.stop()
    (events,) = capture_events(str(tmp_path / "cap")).values()
    by_name = {}
    for ev in events:
        by_name.setdefault(ev[0], []).append(ev)
    for parent in ("decode.sync", "prefill.sync"):
        assert len(by_name[parent + ".ready"]) == len(by_name[parent]) \
            == len(by_name[parent + ".pull"])
        for _, p_lo, p_hi, _ in by_name[parent]:
            (ready,) = [e for e in by_name[parent + ".ready"]
                        if p_lo <= e[1] and e[2] <= p_hi]
            (pull,) = [e for e in by_name[parent + ".pull"]
                       if p_lo <= e[1] and e[2] <= p_hi]
            assert ready[2] <= pull[1], "ready ends before the pull begins"
    # Both prompts in one dispatch, or one each: either way the spans
    # carry the prompts' real tokens (no prefix here), padding left out.
    assert sum(int(st["tokens"]) for _, _, _, st in by_name["prefill"]) \
        == sum(len(r.prompt_tokens) for r in two_requests())


def test_prefill_span_tokens_leave_out_a_registered_prefix(monkeypatch):
    from runbooks_tpu.serve.engine import InferenceEngine, Request

    cfg = tiny_cfg()
    engine = InferenceEngine(cfg, init_params(cfg, jax.random.key(0)),
                             max_slots=2, seed=0, decode_chunk=2)
    prefix = list(range(1, 17))
    assert engine.register_prefix(prefix, warmup=False) == 16
    obs_flight.RING.clear()
    engine.generate([Request(prompt_tokens=prefix + [20, 21, 22],
                             max_tokens=2, request_id="r-p")])
    (prefill,) = [e for e in obs_flight.RING.snapshot(request_id="r-p")
                  if e["name"] == "prefill"]
    assert prefill["args"]["prefix"] == 16
    assert prefill["args"]["tokens"] == 3


def test_one_pull_and_no_wait_call_with_recording_off(monkeypatch):
    """Outside a capture and without RBT_TRACE a decode chunk syncs as it
    did: one np.asarray of a device array, no block_until_ready."""
    import numpy as np

    from runbooks_tpu.serve import engine as engine_mod

    cfg = tiny_cfg()
    engine = make_engine("dense", cfg, init_params(cfg, jax.random.key(0)))
    from runbooks_tpu.serve.engine import Request

    engine.generate(two_requests()[:1])
    engine.submit(Request(prompt_tokens=[1, 2, 3], max_tokens=20))
    engine.step()                  # admits: the prefill's pull, a chunk
    calls = {"pulls": 0, "waits": 0}

    class CountingNumpy:
        def __getattr__(self, name):
            return getattr(np, name)

        @staticmethod
        def asarray(a, *args, **kwargs):
            calls["pulls"] += isinstance(a, jax.Array)
            return np.asarray(a, *args, **kwargs)

    block_until_ready = jax.block_until_ready

    def wait(x):
        calls["waits"] += 1
        return block_until_ready(x)

    monkeypatch.setattr(engine_mod, "np", CountingNumpy())
    monkeypatch.setattr(engine_mod.jax, "block_until_ready", wait)
    assert not obs_trace.fine_enabled()
    assert engine._decode_chunk_step() > 0
    assert calls == {"pulls": 1, "waits": 0}
    monkeypatch.setenv("RBT_TRACE", "1")     # recorded: the split appears
    assert engine._decode_chunk_step() >= 0
    assert calls == {"pulls": 2, "waits": 1}
    engine.deliver_parked()


# ---------------------------------------------------------------------------
# Named scopes in the lowered programs
# ---------------------------------------------------------------------------

def scopes_in(lowered) -> set:
    text = lowered.as_text(debug_info=True)
    return {tok for name in re.findall(r'loc\("([^"]+)"', text)
            for tok in re.split(r"[/()]", name) if tok}


def lower_serve(which: str):
    from runbooks_tpu.serve.engine import make_decode_fn, make_prefill_fn

    cfg = tiny_cfg()
    params = jax.eval_shape(lambda r: init_params(cfg, r),
                            jax.random.key(0))
    pool = jax.eval_shape(lambda: KVCache.create(cfg, 2, 65))
    key = jax.eval_shape(lambda: jax.random.key(0))

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    i32, f32 = jnp.int32, jnp.float32
    if which == "decode_fn":
        return jax.jit(make_decode_fn(cfg, 2, 64, 64, 64)).lower(
            params, pool, arr(i32, 2), arr(i32, 2), key, arr(f32, 2),
            arr(i32, 2), arr(f32, 2), arr(i32, 2), arr(i32, 2),
            arr(bool, 2))
    return jax.jit(make_prefill_fn(cfg, 65)).lower(
        params, pool, arr(i32, 2, 16), arr(i32, 2, 16), arr(i32, 2),
        arr(i32, 2), key, arr(f32, 2), arr(i32, 2), arr(f32, 2))


def lower_lora_step():
    from runbooks_tpu.parallel.mesh import MeshConfig, make_mesh
    from runbooks_tpu.train.lora import (
        LoraConfig,
        create_lora_train_state,
        make_lora_train_step,
    )
    from runbooks_tpu.train.optimizer import OptimizerConfig, make_optimizer

    cfg = tiny_cfg()
    mesh = make_mesh(MeshConfig())
    optimizer = make_optimizer(OptimizerConfig(learning_rate=1e-3,
                                               warmup_steps=0,
                                               total_steps=10))
    base = init_params(cfg, jax.random.key(0))
    lora_cfg = LoraConfig(rank=2)
    state, shardings = create_lora_train_state(
        cfg, lora_cfg, base, optimizer, mesh, jax.random.key(1))
    step_fn = make_lora_train_step(cfg, lora_cfg, optimizer, mesh,
                                   shardings, None)
    batch = {k: jnp.zeros((2, 16), jnp.int32)
             for k in ("tokens", "targets")}
    with jax.set_mesh(mesh):
        return step_fn.lower(state, base, batch)


@pytest.mark.parametrize("program,expected", [
    ("decode_fn", {"embed", "layers", "block", "attn", "attn.qkv", "attn.rope",
                   "attn.kv_write", "attn.core", "attn.out", "ffn", "head",
                   "sample"}),
    ("prefill_fn", {"embed", "attn", "attn.core", "ffn", "head", "sample",
                    "kv_splice"}),
    ("lora_step_fn", {"attn", "ffn", "head", "loss", "optimizer", "lora"}),
])
def test_lowered_programs_carry_the_layer_scopes(program, expected):
    lowered = (lower_lora_step() if program == "lora_step_fn"
               else lower_serve(program))
    assert expected <= scopes_in(lowered)


# ---------------------------------------------------------------------------
# Set-up phases and the published decode chunk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_warmup_census_phases_cover_the_warmup(kind):
    cfg = tiny_cfg()
    engine = make_engine(kind, cfg, init_params(cfg, jax.random.key(0)))
    engine.warmup()
    census = engine.warmup_census
    phases = census["phases"]
    assert set(phases) == {"warmup.trace", "warmup.compile", "warmup.run",
                           "warmup.cost_capture"}
    assert all(v >= 0 for v in phases.values())
    assert 0 < sum(phases.values()) <= census["warmup_seconds"] + 0.05
    # Most of a warm-up is its programs' calls: the phases leave little
    # of the wall time unnamed.
    assert sum(phases.values()) >= 0.5 * census["warmup_seconds"]
    assert census["decode_chunk"] == engine.decode_chunk == 2
    engine.release_steady()


def test_cost_capture_stops_after_a_backend_without_analysis(monkeypatch):
    """One probe, not one re-trace per program, where cost_analysis_of
    gives nothing (as on a TPU)."""
    from runbooks_tpu.obs import device as obs_device
    from runbooks_tpu.serve.engine import WarmupRun

    asked = []
    monkeypatch.setattr(obs_device, "cost_analysis_of",
                        lambda fn, *a, **k: asked.append(fn))
    run = WarmupRun()
    fn = jax.jit(lambda x: x + 1)
    for i in range(3):
        assert int(run.program("probe_test", f"s{i}", fn, jnp.int32(i))) \
            == i + 1
    assert len(asked) == 1
    assert set(run.finish(None)["phases"]) >= {"warmup.cost_capture",
                                                  "warmup.trace"}


def test_phase_seconds_sum_by_name():
    phases = obs_trace.PhaseSeconds()
    phases.add("warmup.trace", 0.25, program="prefill")
    phases.add("warmup.trace", 0.5, program="decode_v64")
    with phases.timed("startup.weights"):
        pass
    snap = phases.snapshot()
    assert snap["warmup.trace"] == 0.75 and snap["startup.weights"] >= 0
    age = obs_trace.process_age_s()
    assert age is None or age > 0
