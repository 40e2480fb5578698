"""Deterministic fault-injection harness (docs/fault-tolerance.md).

The property everything else hangs off: kill training at an arbitrary step,
restart, and the resumed per-step loss history must match an uninterrupted
run batch-for-batch (the checkpoint carries the data cursor, restore picks
the newest intact checkpoint, and the data pipeline fast-forwards to the
exact batch the next step would have consumed). Faults are injected through
the trainer's RBT_FAULT_INJECT hook so every run is reproducible.

All tests here are tier-1 (fast, CPU, not slow).
"""

import dataclasses
import json
import os
import signal

import numpy as np
import pytest

from runbooks_tpu.parallel.mesh import MeshConfig
from runbooks_tpu.train.checkpoint import CheckpointManager
from runbooks_tpu.train.optimizer import OptimizerConfig
from runbooks_tpu.train.trainer import (
    SimulatedFault,
    TrainJobConfig,
    exit_code_for,
    run_training,
)
from runbooks_tpu.utils.contract import EXIT_PREEMPTED

MESH = MeshConfig(data=2, fsdp=2, sequence=1, tensor=2)


def job(artifacts, steps=8, checkpoint_every=3, **kw):
    return TrainJobConfig(
        model="debug", model_overrides={"dtype": "float32"},
        mesh=MESH,
        optimizer=OptimizerConfig(learning_rate=1e-3, warmup_steps=0,
                                  total_steps=100, schedule="constant"),
        batch_size=4, seq_len=32, steps=steps,
        checkpoint_every=checkpoint_every, log_every=1,
        artifacts_dir=str(artifacts), **kw,
    )


def losses(summary):
    return {e["step"]: e["loss"] for e in summary["history"]}


def assert_matching_tail(base, resumed):
    """Every step the resumed run logged must match the uninterrupted run's
    loss at the same step (fp tolerance on CPU)."""
    want, got = losses(base), losses(resumed)
    assert got, "resumed run logged no steps"
    for step in got:
        assert abs(got[step] - want[step]) < 2e-4, (
            step, got[step], want[step])


# ---------------------------------------------------------------------------
# Step-exact resume
# ---------------------------------------------------------------------------

def test_step_exact_resume_after_kill(tmp_path, monkeypatch):
    """Kill at step k, restart: steps k'..N (k' = last checkpoint + 1) land
    on exactly the batches — and thus the losses — of an uninterrupted
    run, instead of replaying the data stream from batch 0."""
    base = run_training(job(tmp_path / "base"))

    monkeypatch.setenv("RBT_FAULT_INJECT", "kill:5")
    with pytest.raises(SimulatedFault):
        run_training(job(tmp_path / "faulted"))
    monkeypatch.delenv("RBT_FAULT_INJECT")

    resumed = run_training(job(tmp_path / "faulted"))
    # Last periodic checkpoint before the kill was step 3.
    assert sorted(losses(resumed)) == [4, 5, 6, 7, 8]
    assert_matching_tail(base, resumed)
    assert resumed["batches_consumed"] == base["batches_consumed"] == 8


def test_step_exact_resume_with_accum_prefetch_and_jsonl(tmp_path,
                                                         monkeypatch):
    """The same property with gradient accumulation, the async prefetcher,
    and a real jsonl dataset (the cursor must replay tokenize/pack state,
    not just a synthetic RNG stream). Batches the prefetcher had in flight
    beyond the cursor at kill time are regenerated, not double-consumed."""
    data = tmp_path / "data"
    os.makedirs(data)
    rng = np.random.default_rng(0)
    with open(data / "docs.jsonl", "w") as f:
        for i in range(40):
            words = " ".join(f"w{i}x{j}" for j in range(int(rng.integers(
                4, 40))))
            f.write(json.dumps({"text": words}) + "\n")
    kw = dict(data_path=str(data), accumulate_steps=2, prefetch_depth=2)

    base = run_training(job(tmp_path / "base", **kw))
    monkeypatch.setenv("RBT_FAULT_INJECT", "kill:4")
    with pytest.raises(SimulatedFault):
        run_training(job(tmp_path / "faulted", **kw))
    monkeypatch.delenv("RBT_FAULT_INJECT")
    resumed = run_training(job(tmp_path / "faulted", **kw))
    assert sorted(losses(resumed)) == [4, 5, 6, 7, 8]
    assert_matching_tail(base, resumed)


# ---------------------------------------------------------------------------
# SIGTERM -> emergency checkpoint + documented exit code
# ---------------------------------------------------------------------------

def test_sigterm_emergency_checkpoint_and_exit_code(tmp_path, monkeypatch):
    # checkpoint_every past the horizon: the only checkpoint is the
    # emergency one the handler forces.
    monkeypatch.setenv("RBT_FAULT_INJECT", "sigterm:5")
    summary = run_training(job(tmp_path, steps=10, checkpoint_every=100))
    assert summary["exit_reason"] == "sigterm"
    assert exit_code_for(summary) == EXIT_PREEMPTED
    # Handlers restored after the run (pytest's own handlers survive).
    assert signal.getsignal(signal.SIGTERM) in (signal.SIG_DFL,
                                                signal.default_int_handler)

    ckpt = CheckpointManager(str(tmp_path))
    try:
        assert ckpt.latest_intact_step() == 5
        assert ckpt.read_cursor(5) == {"batches_consumed": 5}
    finally:
        ckpt.close()

    monkeypatch.delenv("RBT_FAULT_INJECT")
    # And the emergency checkpoint resumes step-exactly.
    base = run_training(job(tmp_path / "base", steps=10,
                            checkpoint_every=100))
    resumed = run_training(job(tmp_path, steps=10, checkpoint_every=100))
    assert sorted(losses(resumed)) == [6, 7, 8, 9, 10]
    assert_matching_tail(base, resumed)
    assert exit_code_for(resumed) == 0


def test_maintenance_event_poller_stops_training(tmp_path, monkeypatch):
    """A pending GCE maintenance event (served by a local metadata fake)
    is treated like SIGTERM: emergency checkpoint + preempted exit."""
    import http.server
    import threading

    class Fake(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            body = (b"TERMINATE_ON_HOST_MAINTENANCE"
                    if "maintenance-event" in self.path else b"")
            self.send_response(200)
            self.send_header("Metadata-Flavor", "Google")
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    srv = http.server.HTTPServer(("127.0.0.1", 0), Fake)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        monkeypatch.setenv("GCE_METADATA_HOST",
                           f"127.0.0.1:{srv.server_address[1]}")
        summary = run_training(job(tmp_path, steps=500,
                                   checkpoint_every=1000,
                                   maintenance_poll_s=0.2))
        assert summary["exit_reason"] == "maintenance"
        assert exit_code_for(summary) == EXIT_PREEMPTED
        ckpt = CheckpointManager(str(tmp_path))
        try:
            assert ckpt.latest_intact_step() is not None
        finally:
            ckpt.close()
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# Non-finite guard
# ---------------------------------------------------------------------------

def test_nonfinite_step_leaves_params_bitwise_unchanged():
    """A NaN-poisoned batch must skip the update wholesale: params AND
    optimizer state bitwise identical, step counter advanced, and training
    continues to learn on the next good batch."""
    import jax
    import jax.numpy as jnp

    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.parallel.mesh import make_mesh
    from runbooks_tpu.train.optimizer import make_optimizer
    from runbooks_tpu.train.step import create_train_state, make_train_step

    cfg = get_config("debug", dtype="float32")
    mesh = make_mesh(MESH)
    opt = make_optimizer(OptimizerConfig(learning_rate=1e-3, warmup_steps=0,
                                         total_steps=100,
                                         schedule="constant"))
    state, shardings = create_train_state(cfg, opt, mesh, jax.random.key(0))
    step = make_train_step(cfg, opt, mesh, shardings)
    toks = np.random.default_rng(0).integers(
        1, cfg.vocab_size, (4, 33), dtype=np.int32)
    good = {"tokens": toks[:, :-1], "targets": toks[:, 1:],
            "loss_mask": np.ones((4, 32), np.float32)}
    bad = dict(good)
    bad["loss_mask"] = good["loss_mask"] * np.float32("nan")

    with jax.set_mesh(mesh):
        state, m = step(state, good)
        assert float(m["nonfinite"]) == 0
        before = jax.tree.map(np.asarray, state.params)
        step_before = int(state.step)

        state, m = step(state, bad)
        assert float(m["nonfinite"]) == 1
        assert not np.isfinite(float(m["loss"]))
        after = jax.tree.map(np.asarray, state.params)
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(a, b), before, after)
        assert int(state.step) == step_before + 1  # counter still advances

        state, m = step(state, good)
        assert float(m["nonfinite"]) == 0
        changed = jax.tree.leaves(jax.tree.map(
            lambda a, b: not np.array_equal(a, np.asarray(b)),
            before, state.params))
        assert any(changed)  # good batch trains again


def test_lora_nonfinite_guard():
    import jax

    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.models.transformer import init_params
    from runbooks_tpu.parallel.mesh import make_mesh
    from runbooks_tpu.train.lora import (
        LoraConfig,
        create_lora_train_state,
        make_lora_train_step,
    )
    from runbooks_tpu.train.optimizer import make_optimizer
    from runbooks_tpu.train.step import infer_state_shardings  # noqa: F401
    from runbooks_tpu.models.transformer import param_logical_axes
    from runbooks_tpu.parallel.sharding import tree_shardings

    cfg = get_config("debug", dtype="float32")
    mesh = make_mesh(MESH)
    opt = make_optimizer(OptimizerConfig(learning_rate=1e-3, warmup_steps=0,
                                         total_steps=100,
                                         schedule="constant"))
    rng = jax.random.key(0)
    base = init_params(cfg, rng)
    base_shardings = tree_shardings(jax.eval_shape(lambda: base),
                                    param_logical_axes(cfg), mesh)
    base = jax.device_put(base, base_shardings)
    lcfg = LoraConfig(rank=2)
    state, shardings = create_lora_train_state(cfg, lcfg, base, opt, mesh,
                                               rng)
    step = make_lora_train_step(cfg, lcfg, opt, mesh, shardings,
                                base_shardings)
    toks = np.random.default_rng(0).integers(
        1, cfg.vocab_size, (4, 33), dtype=np.int32)
    bad = {"tokens": toks[:, :-1], "targets": toks[:, 1:],
           "loss_mask": np.full((4, 32), np.float32("nan"))}
    with jax.set_mesh(mesh):
        before = jax.tree.map(np.asarray, state.params)
        state, m = step(state, base, bad)
        assert float(m["nonfinite"]) == 1
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                     before, jax.tree.map(np.asarray, state.params))


def test_single_nonfinite_step_training_continues(tmp_path, monkeypatch):
    monkeypatch.setenv("RBT_FAULT_INJECT", "nonfinite:2")
    summary = run_training(job(tmp_path, steps=6))
    assert summary["nonfinite_steps"] == 1
    assert summary["exit_reason"] is None
    assert np.isfinite(summary["final_loss"])


def test_consecutive_nonfinite_steps_abort(tmp_path, monkeypatch):
    monkeypatch.setenv("RBT_FAULT_INJECT", "nonfinite:2+")
    with pytest.raises(RuntimeError, match="consecutive non-finite"):
        run_training(job(tmp_path, steps=10, max_bad_steps=3))


# ---------------------------------------------------------------------------
# Checkpoint integrity: corrupt-latest fallback, cross-mesh cursor
# ---------------------------------------------------------------------------

def _truncate_step_dir(step_dir):
    for root, _, files in os.walk(step_dir):
        for name in files:
            with open(os.path.join(root, name), "w"):
                pass  # truncate to 0 bytes


def test_corrupt_latest_checkpoint_falls_back(tmp_path, capsys):
    """Preemption mid-async-save: the newest step dir is garbage; restore
    must pick the previous intact one and say so."""
    run_training(job(tmp_path, steps=6))  # checkpoints at 3 and 6
    _truncate_step_dir(tmp_path / "checkpoints" / "6")

    ckpt = CheckpointManager(str(tmp_path))
    try:
        state, cursor, step = ckpt.restore_with_cursor(None)
    finally:
        ckpt.close()
    assert step == 3
    assert cursor == {"batches_consumed": 3}
    out = capsys.readouterr().out
    assert "falling back" in out

    # And the trainer resumes from it end-to-end (steps 4..8 rerun).
    summary = run_training(job(tmp_path))
    assert sorted(losses(summary)) == [4, 5, 6, 7, 8]


def test_partial_save_without_marker_is_skipped(tmp_path, capsys):
    """A step directory that never got its integrity marker (the save was
    cut mid-flight) is not even attempted when an older intact one
    exists."""
    run_training(job(tmp_path, steps=6))
    marker = tmp_path / "checkpoints" / "6" / CheckpointManager.MARKER
    os.remove(marker)

    ckpt = CheckpointManager(str(tmp_path))
    try:
        assert ckpt.intact_steps() == [3]
        state, cursor, step = ckpt.restore_with_cursor(None)
    finally:
        ckpt.close()
    assert step == 3
    assert "ignoring partial step dir" in capsys.readouterr().out


def test_cursor_survives_restore_onto_different_mesh(tmp_path):
    """Restore onto a different mesh layout reshards the arrays but must
    leave the data-cursor payload untouched."""
    import jax

    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.parallel.mesh import make_mesh
    from runbooks_tpu.train.optimizer import make_optimizer
    from runbooks_tpu.train.step import create_train_state

    cfg = get_config("debug", dtype="float32")
    opt = make_optimizer(OptimizerConfig(learning_rate=1e-3, warmup_steps=0,
                                         total_steps=100,
                                         schedule="constant"))
    mesh_a = make_mesh(MESH)
    state_a, _ = create_train_state(cfg, opt, mesh_a, jax.random.key(0))
    ckpt = CheckpointManager(str(tmp_path))
    try:
        ckpt.save(7, state_a, cursor={"batches_consumed": 7})
        ckpt.wait()
    finally:
        ckpt.close()

    mesh_b = make_mesh(MeshConfig(data=1, fsdp=8, sequence=1, tensor=1))
    state_b, _ = create_train_state(cfg, opt, mesh_b, jax.random.key(1))
    ckpt = CheckpointManager(str(tmp_path))
    try:
        restored, cursor, step = ckpt.restore_with_cursor(state_b)
    finally:
        ckpt.close()
    assert step == 7 and cursor == {"batches_consumed": 7}
    np.testing.assert_allclose(
        np.asarray(restored.params["embed"]),
        np.asarray(state_a.params["embed"]), rtol=0, atol=0)


# ---------------------------------------------------------------------------
# Serving: backpressure, deadlines, graceful drain
# ---------------------------------------------------------------------------

def _tiny_cfg():
    from runbooks_tpu.models.config import get_config

    return dataclasses.replace(
        get_config("llama2-7b"), vocab_size=128, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, max_seq_len=64, dtype="float32")


def test_engine_bounded_queue_raises_typed_overload():
    import jax

    from runbooks_tpu.models.transformer import init_params
    from runbooks_tpu.serve.engine import (
        EngineOverloaded,
        InferenceEngine,
        Request,
    )

    cfg = _tiny_cfg()
    engine = InferenceEngine(cfg, init_params(cfg, jax.random.key(0)),
                             max_slots=1, max_queue=2)
    engine.submit(Request(prompt_tokens=[1, 2], max_tokens=2))
    engine.submit(Request(prompt_tokens=[1, 2], max_tokens=2))
    with pytest.raises(EngineOverloaded, match="queue full"):
        engine.submit(Request(prompt_tokens=[1, 2], max_tokens=2))
    # The bound rejects; it never truncates what was admitted.
    assert len(engine.queue) == 2


def test_engine_deadline_expiry_between_chunks():
    import time

    import jax

    from runbooks_tpu.models.transformer import init_params
    from runbooks_tpu.serve.engine import InferenceEngine, Request

    cfg = _tiny_cfg()
    engine = InferenceEngine(cfg, init_params(cfg, jax.random.key(0)),
                             max_slots=2)
    # Queued expiry: never admitted, finishes empty-handed.
    r_queued = Request(prompt_tokens=[1, 2], max_tokens=5, deadline_s=1e-4)
    engine.submit(r_queued)
    time.sleep(0.01)
    engine.step()
    assert r_queued.finished and r_queued.finish_reason == "deadline"
    assert r_queued.output_tokens == []

    # Mid-generation expiry: keeps the tokens it had.
    r_mid = Request(prompt_tokens=[1, 2], max_tokens=10_000,
                    deadline_s=0.05)
    engine.submit(r_mid)
    while engine.has_work():
        engine.step()
        time.sleep(0.02)
    assert r_mid.finish_reason == "deadline"
    assert 0 < len(r_mid.output_tokens) < 10_000
    assert engine.deadline_expired == 2


def test_worker_drain_finishes_inflight_then_rejects():
    """The SIGTERM drain path, on the engine smoke harness: stop admitting,
    finish every in-flight request, then reject with the typed draining
    error."""
    import jax

    from runbooks_tpu.models.transformer import init_params
    from runbooks_tpu.serve.api import EngineWorker
    from runbooks_tpu.serve.engine import (
        EngineDraining,
        InferenceEngine,
        Request,
    )

    cfg = _tiny_cfg()
    engine = InferenceEngine(cfg, init_params(cfg, jax.random.key(0)),
                             max_slots=2)
    worker = EngineWorker(engine)
    futs = [worker.submit(Request(prompt_tokens=[1, 2, 3], max_tokens=5))
            for _ in range(3)]
    assert worker.drain(timeout_s=120)
    assert all(f.done() for f in futs)
    assert all(len(f.result().output_tokens) == 5 for f in futs)
    with pytest.raises(EngineDraining):
        worker.submit(Request(prompt_tokens=[1], max_tokens=1))
    worker.stop()


def test_http_429_retry_after_and_503_draining():
    import asyncio

    import jax

    from aiohttp.test_utils import TestClient, TestServer

    from runbooks_tpu.models.transformer import init_params
    from runbooks_tpu.serve.api import create_server

    cfg = _tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    # max_queue=0: every admission is an overload — deterministic 429.
    app = create_server(cfg, params, max_slots=1, max_queue=0, warmup=False)

    async def drive():
        async with TestClient(TestServer(app)) as client:
            r = await client.post("/v1/completions", json={
                "prompt": "hello", "max_tokens": 2})
            assert r.status == 429
            assert r.headers["Retry-After"] == "1"
            body = await r.json()
            assert body["error"]["type"] == "overloaded"

            r = await client.get("/metrics")
            text = await r.text()
            assert "serve_requests_rejected_total 1" in text
            assert "serve_queue_limit 0" in text

            # Draining: 503 (terminal for this replica, not a retry-here).
            app["worker"]._draining = True
            r = await client.post("/v1/completions", json={
                "prompt": "hello", "max_tokens": 2})
            assert r.status == 503
            assert (await r.json())["error"]["type"] == "draining"

    asyncio.run(drive())


def test_http_request_timeout_deadline():
    import asyncio

    import jax

    from aiohttp.test_utils import TestClient, TestServer

    from runbooks_tpu.models.transformer import init_params
    from runbooks_tpu.serve.api import create_server

    cfg = _tiny_cfg()
    app = create_server(cfg, init_params(cfg, jax.random.key(0)),
                        max_slots=1, warmup=False)

    async def drive():
        async with TestClient(TestServer(app)) as client:
            r = await client.post("/v1/completions", json={
                "prompt": "hi", "max_tokens": 10_000, "timeout": 0.15})
            assert r.status == 200
            body = await r.json()
            assert body["choices"][0]["finish_reason"] in ("deadline",
                                                           "length")
            r = await client.post("/v1/completions", json={
                "prompt": "hi", "max_tokens": 2, "timeout": -1})
            assert r.status == 400

    asyncio.run(drive())
