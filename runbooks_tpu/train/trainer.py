"""The trainer workload: config -> mesh -> data -> sharded steps -> checkpoints.

This is the TPU-native replacement for the external trainer containers the
reference schedules (reference: examples/llama2-7b/finetuned-model.yaml uses
substratusai/model-trainer-huggingface; here training is in-framework). It
honors the container contract (/content/params.json in, /content/artifacts
out) so the operator layer schedules it exactly like the reference schedules
its trainer images.

Entry point: ``python -m runbooks_tpu.train.trainer`` (reads params.json), or
``run_training(TrainJobConfig(...))`` programmatically.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import signal
import threading
import time
from typing import Any, Dict, Iterator, Optional

import jax
import numpy as np

from runbooks_tpu.models.config import ModelConfig, get_config
from runbooks_tpu.obs import device as obs_device
from runbooks_tpu.obs import trace as obs_trace
from runbooks_tpu.obs.goodput import GoodputTracker
from runbooks_tpu.obs.metrics import REGISTRY
from runbooks_tpu.obs.profile import PROFILER, parse_profile_at_step
from runbooks_tpu.obs.trace import fine, span
from runbooks_tpu.parallel.mesh import MeshConfig, make_mesh
from runbooks_tpu.train import data as data_mod
from runbooks_tpu.train.checkpoint import CheckpointManager
from runbooks_tpu.train.lora import (
    LoraConfig,
    create_lora_train_state,
    make_lora_train_step,
)
from runbooks_tpu.train.optimizer import OptimizerConfig, make_optimizer
from runbooks_tpu.train.step import create_train_state, make_train_step
from runbooks_tpu.utils import contract
from runbooks_tpu.utils.contract import EXIT_PREEMPTED


class SimulatedFault(RuntimeError):
    """Raised by the RBT_FAULT_INJECT hook's `kill` mode: a deterministic
    stand-in for an abrupt process death (no emergency checkpoint, no
    cleanup beyond `finally`), used by tests/test_fault_tolerance.py to
    prove step-exact resume."""


def _parse_fault_inject() -> Optional[dict]:
    """RBT_FAULT_INJECT=<mode>:<step>[+] — the deterministic fault-injection
    hook (docs/fault-tolerance.md). Modes:

      kill:K       raise SimulatedFault at the top of step K (the run dies
                   as a preemption would, mid-stream, without the graceful
                   paths)
      sigterm:K    deliver SIGTERM to this process at the top of step K
                   (exercises the real handler: emergency checkpoint +
                   preempted exit)
      nonfinite:K  poison step K's batch with NaN (exercises the non-finite
                   guard); `K+` poisons every step from K on (exercises the
                   consecutive-bad-step abort)
    """
    spec = os.environ.get("RBT_FAULT_INJECT", "")
    if not spec:
        return None
    mode, _, step = spec.partition(":")
    if mode not in ("kill", "sigterm", "nonfinite") or not step:
        raise ValueError(
            f"RBT_FAULT_INJECT={spec!r}: expected kill:K|sigterm:K|"
            "nonfinite:K[+]")
    return {"mode": mode, "step": int(step.rstrip("+")),
            "repeat": step.endswith("+")}


@dataclasses.dataclass(frozen=True)
class TrainJobConfig:
    model: str = "debug"
    model_overrides: Dict[str, Any] = dataclasses.field(default_factory=dict)
    mesh: MeshConfig = MeshConfig()
    optimizer: OptimizerConfig = OptimizerConfig()
    lora: Optional[LoraConfig] = None

    batch_size: int = 8           # global batch (microbatched when
                                  # accumulate_steps > 1)
    seq_len: int = 512
    steps: int = 100
    # Training fast path (docs/training-performance.md):
    # accumulate_steps=k runs k microbatches of batch_size/k per optimizer
    # step (peak activation memory of one microbatch); loss_chunk=c
    # computes the loss via the chunked fused cross-entropy (the
    # [b, s, vocab] f32 logits tensor is never materialized); 0 = off.
    # prefetch_depth>0 tokenizes/packs ahead on a background thread and
    # double-buffers jax.device_put with the mesh batch shardings.
    accumulate_steps: int = 1
    loss_chunk: int = 0
    prefetch_depth: int = 2
    # Overlapped collective-matmul tensor parallelism ("off"|"ring"|"auto",
    # docs/tensor-parallel-performance.md): overrides the model config's
    # collective_matmul when set. "auto" rings whenever mesh_tensor > 1.
    collective_matmul: Optional[str] = None
    data_path: Optional[str] = None       # default: contract data dir
    tokenizer: Optional[str] = None
    text_key: str = "text"                # jsonl field holding the document
    # str.format template over jsonl record fields (reference analog: the
    # trainer images' prompt_template param).
    prompt_template: Optional[str] = None
    seed: int = 0

    checkpoint_every: int = 50
    artifacts_dir: Optional[str] = None   # default: contract artifacts dir
    log_every: int = 10
    resume: bool = True
    # Fault tolerance (docs/fault-tolerance.md): abort after this many
    # CONSECUTIVE non-finite loss/grad steps (each bad step skips the
    # update — params bitwise unchanged — so a transient bad batch costs
    # one step, not the run). maintenance_poll_s > 0 polls the GCE
    # metadata server for a pending maintenance event/preemption and
    # treats one like SIGTERM (emergency checkpoint + clean exit);
    # main() turns it on automatically when running on GCE.
    max_bad_steps: int = 3
    maintenance_poll_s: float = 0.0
    # XLA/JAX profiler capture: trace steps [profile_start, profile_stop)
    # into {artifacts}/profile (viewable in XProf/TensorBoard). Net-new vs
    # the reference, which has no profiling hooks (SURVEY.md §5.1).
    profile_start: int = 0
    profile_stop: int = 0

    @classmethod
    def from_params(cls, params: Dict[str, Any]) -> "TrainJobConfig":
        """Build from a flat params.json dict (the operator-facing config
        surface, like the reference's params -> PARAM_* convention)."""
        kwargs: Dict[str, Any] = {}
        params = dict(params)
        # The reference's spec style is camelCase; the env round-trip
        # (PARAM_ACCUMULATESTEPS) lowercases it. Accept both spellings for
        # the controller-validated key so a validated spec cannot silently
        # train without accumulation.
        for alias in ("accumulateSteps", "accumulatesteps"):
            if alias in params:
                params.setdefault("accumulate_steps", params.pop(alias))
        for alias in ("maxBadSteps", "maxbadsteps"):
            if alias in params:
                params.setdefault("max_bad_steps", params.pop(alias))
        from runbooks_tpu.models.config import COLLECTIVE_MATMUL_PARAM_KEYS

        for alias in COLLECTIVE_MATMUL_PARAM_KEYS[1:]:
            if alias in params:
                params.setdefault("collective_matmul", params.pop(alias))
        simple = {f.name for f in dataclasses.fields(cls)
                  if f.name not in ("mesh", "optimizer", "lora",
                                    "model_overrides")}
        for k, v in params.items():
            if k in simple:
                kwargs[k] = v
        # YAML specs quote freely ("8"); a str here would TypeError deep in
        # run_training instead of at the validated boundary.
        for key in ("accumulate_steps", "loss_chunk", "prefetch_depth",
                    "batch_size", "seq_len", "steps", "max_bad_steps"):
            if key in kwargs:
                kwargs[key] = int(kwargs[key])
        if "maintenance_poll_s" in kwargs:
            kwargs["maintenance_poll_s"] = float(kwargs["maintenance_poll_s"])
        mesh_keys = {f.name for f in dataclasses.fields(MeshConfig)}
        mesh_args = {k[len("mesh_"):]: int(v) for k, v in params.items()
                     if k.startswith("mesh_") and k[len("mesh_"):] in mesh_keys}
        if mesh_args:
            kwargs["mesh"] = MeshConfig(**mesh_args)
        opt_keys = {f.name for f in dataclasses.fields(OptimizerConfig)}
        opt_args = {k: v for k, v in params.items() if k in opt_keys}
        if opt_args:
            kwargs["optimizer"] = OptimizerConfig(**opt_args)
        if params.get("lora"):
            lora = params["lora"]
            kwargs["lora"] = (LoraConfig(**lora) if isinstance(lora, dict)
                              else LoraConfig())
        if params.get("model_overrides"):
            kwargs["model_overrides"] = dict(params["model_overrides"])
        return cls(**kwargs)


def _batches(job: TrainJobConfig, model_cfg: ModelConfig,
             skip: int = 0) -> Iterator[dict]:
    path = job.data_path or contract.data_dir()

    if path and os.path.exists(path):
        tok = data_mod.load_tokenizer(job.tokenizer)
        vocab = getattr(tok, "vocab_size", model_cfg.vocab_size)
        if vocab > model_cfg.vocab_size:
            # A real error, not an assert: `python -O` strips asserts and
            # out-of-range token ids would then index-wrap into garbage
            # embeddings mid-training.
            raise ValueError(
                f"tokenizer vocab {vocab} exceeds model vocab "
                f"{model_cfg.vocab_size}")
        it = data_mod.dataset(path, job.seq_len, job.batch_size,
                              tokenizer=tok, epochs=None,
                              text_key=job.text_key,
                              prompt_template=job.prompt_template)
    else:
        it = data_mod.synthetic_batches(model_cfg.vocab_size, job.seq_len,
                                        job.batch_size, job.seed)
    if skip:
        # Resume at the checkpoint's data cursor: batch `skip` comes first,
        # exactly as the uninterrupted run would have seen it.
        print(f"data: advancing to batch cursor {skip} "
              "(step-exact resume)", flush=True)
        it = data_mod.skip_batches(it, skip)
    return it


def run_training(job: TrainJobConfig,
                 base_params=None) -> Dict[str, Any]:
    """Run the training job; returns final metrics summary (also written to
    {artifacts}/metrics.json).

    Preemption-tolerant (docs/fault-tolerance.md): SIGTERM/SIGINT (and a
    pending GCE maintenance event, when polled) stop the loop at the next
    step boundary, force an emergency checkpoint carrying the data cursor,
    and return with summary["exit_reason"] set — main() maps that to the
    documented EXIT_PREEMPTED code so the controller's Job policy restarts
    the pod instead of failing the run."""
    model_cfg = get_config(job.model, **job.model_overrides)
    if job.collective_matmul is not None:
        # Fail at the validated boundary, not mid-compile: the
        # controller's validate_params enforces the same enum.
        from runbooks_tpu.models.config import check_collective_matmul

        model_cfg = dataclasses.replace(
            model_cfg,
            collective_matmul=check_collective_matmul(job.collective_matmul))
    if job.accumulate_steps < 1:
        raise ValueError(
            f"accumulate_steps must be >= 1, got {job.accumulate_steps}")
    if job.batch_size % job.accumulate_steps:
        raise ValueError(
            f"accumulate_steps={job.accumulate_steps} must divide "
            f"batch_size={job.batch_size}")
    # Set-up seconds by phase (obs/trace.py PhaseSeconds): in the summary
    # and metrics.json as "phases", main()'s startup.imports with them.
    phases = obs_trace.PhaseSeconds()
    with phases.timed("startup.backend"):
        mesh = make_mesh(job.mesh)     # the first touch of the backend
    optimizer = make_optimizer(job.optimizer)
    artifacts = job.artifacts_dir or contract.artifacts_dir()
    os.makedirs(artifacts, exist_ok=True)
    # Flight/trace identity (obs/flight.py): this run's span events —
    # in the always-on ring, in tail-sampled promotions, and in any
    # incident bundle — label as the training tier.
    from runbooks_tpu.obs import flight as obs_flight

    obs_flight.set_component("train")
    # The trace path is configured unconditionally: RBT_TRACE=1 writes
    # live spans there, and tail-sampling/incident promotion needs the
    # same per-run destination even when live tracing is off.
    obs_trace.configure(os.path.join(artifacts, "trace.jsonl"))
    # Persistent compile cache (placed from outside: utils/jax_cache.py):
    # a restarted Job (slice restart / resume) skips the XLA recompile.
    from runbooks_tpu.models.transformer import (
        flash_blocks,
        flash_heads_per_step,
        resolve_attention_impl,
    )
    from runbooks_tpu.utils.hw import chip_peaks, device_identity
    from runbooks_tpu.utils.jax_cache import enable_compilation_cache

    with jax.set_mesh(mesh):
        attention_impl = resolve_attention_impl(model_cfg)
    # What this run executes on — the start-up line every log carries, and
    # the same fields the final summary repeats.
    identity = {
        **device_identity(mesh),
        "compile_cache_dir": enable_compilation_cache(),
        "attention_impl": attention_impl,
    }
    if attention_impl in ("flash", "ring"):
        # Query heads a grid step of the flash kernels holds and the
        # block shape of the forward and the backward, by kind of
        # attention layer: functions of the step's shapes alone.
        shard_len = job.seq_len // int(mesh.shape.get("sequence", 1))
        tp = int(mesh.shape.get("tensor", 1))
        identity["flash_head_block"] = flash_heads_per_step(
            model_cfg, shard_len, shard_len, tp)
        identity["flash_blocks"] = flash_blocks(
            model_cfg, shard_len, shard_len, tp, backward=True)
    print(json.dumps({"startup": "train", "model": job.model, **identity,
                      "phases": {**obs_trace.STARTUP.snapshot(),
                                 **phases.snapshot()}}),
          flush=True)
    ckpt = CheckpointManager(artifacts)

    rng = jax.random.key(job.seed)
    lora_mode = job.lora is not None
    t_weights = time.perf_counter()
    if lora_mode:
        if base_params is None:
            from runbooks_tpu.models.transformer import init_params
            from runbooks_tpu.models.transformer import param_logical_axes
            from runbooks_tpu.parallel.sharding import tree_shardings

            shapes = jax.eval_shape(
                lambda r: init_params(model_cfg, r), rng)
            base_shardings = tree_shardings(
                shapes, param_logical_axes(model_cfg), mesh)
            from runbooks_tpu.train.step import layout_invariant_init

            with jax.set_mesh(mesh), layout_invariant_init():
                base_params = jax.jit(
                    lambda r: init_params(model_cfg, r),
                    out_shardings=base_shardings)(rng)
        else:
            from runbooks_tpu.models.transformer import param_logical_axes
            from runbooks_tpu.parallel.sharding import tree_shardings

            base_shardings = tree_shardings(
                jax.eval_shape(lambda: base_params),
                param_logical_axes(model_cfg), mesh)
            base_params = jax.device_put(base_params, base_shardings)
        state, shardings = create_lora_train_state(
            model_cfg, job.lora, base_params, optimizer, mesh, rng)
        step_fn = make_lora_train_step(
            model_cfg, job.lora, optimizer, mesh, shardings, base_shardings,
            accumulate_steps=job.accumulate_steps, loss_chunk=job.loss_chunk)
    else:
        state, shardings = create_train_state(model_cfg, optimizer, mesh, rng)
        step_fn = make_train_step(model_cfg, optimizer, mesh, shardings,
                                  accumulate_steps=job.accumulate_steps,
                                  loss_chunk=job.loss_chunk)

    jax.block_until_ready(state)       # made AND placed
    phases.add("startup.weights", time.perf_counter() - t_weights)

    # Device-level observability (obs/device.py): compile sentinel +
    # program census. After the first step folds the XLA compile, any
    # further compile in the steady loop is a stall the sentinel flags
    # (xla_unexpected_compiles_total) — exactly the failure mode the
    # at-scale postmortems lead with (PAPERS.md).
    obs_device.SENTINEL.install()
    obs_device.PROGRAMS.register("train", "train_step", step_fn)

    # May raise on a malformed value — before any state needing cleanup.
    fault = _parse_fault_inject()
    # RBT_PROFILE_AT_STEP=n[:k]: on-demand capture of k steps starting at
    # step n into {artifacts}/profiles/ (docs/observability.md). Parsed
    # here for the same reason as the fault hook.
    profile_at = parse_profile_at_step()

    start_step = 0
    consumed = 0          # batches pulled from the data stream (the cursor)
    restore_time_s = None
    stop = {"reason": None}
    restore_sigs = []
    poller_stop = None
    prefetcher = None
    history = []
    tokens_per_step = job.batch_size * job.seq_len
    flops_per_token = 3.0 * model_cfg.flops_per_token(job.seq_len)
    # None off-TPU: the log lines then carry no mfu/analytic_mfu at all.
    chip = chip_peaks(jax.devices()[0])
    peak_flops = chip[0] * len(jax.devices()) if chip else None
    tokens_done = 0
    compile_time_s = None

    profiling = False
    profiling_at = False   # RBT_PROFILE_AT_STEP capture in flight
    exit_reason = None
    bad_streak = 0
    nonfinite_steps = 0
    pending_nf = None      # previous step's (index, nonfinite flag)
    last_saved = -1
    device_cost = None     # roofline attribution of the train step
    compiles_before = obs_device.SENTINEL.total
    unexpected_before = obs_device.SENTINEL.unexpected
    hbm_peak_bytes = 0
    hbm_per_device = None  # bytes in use on each device at the last log

    # Goodput accounting (obs/goodput.py): productive step time ÷ wall
    # clock, with restart overhead (restore + compile) excluded so a
    # preempted-and-resumed run reports steady-state goodput, not a ratio
    # dragged down by however long the restore took. The clock starts
    # here — before restore — so restore genuinely lands inside the wall.
    goodput = GoodputTracker()
    # Per-log-window phase sums; each history entry reports window means.
    win = {"data": 0.0, "step": 0.0, "ckpt": 0.0, "steps": 0}

    def _summary_dict(in_progress: bool = False) -> Dict[str, Any]:
        s = {
            "final_loss": history[-1]["loss"] if history else None,
            "steps": job.steps,
            "tokens_per_sec": (history[-1]["tokens_per_sec"]
                               if history else None),
            "compile_time_s": compile_time_s,
            "restore_time_s": restore_time_s,
            "accumulate_steps": job.accumulate_steps,
            "model": job.model,
            **identity,
            "lora": lora_mode,
            "exit_reason": exit_reason,
            "nonfinite_steps": nonfinite_steps,
            "batches_consumed": consumed,
            "goodput": goodput.ratio() if goodput.steps else None,
            "goodput_detail": goodput.snapshot(),
            "phases": {**obs_trace.STARTUP.snapshot(), **phases.snapshot()},
            "device_obs": {
                # Analytic cross-check of the wall-clock MFU: FLOPs and
                # HBM bytes from the compiled step's cost_analysis, with
                # the roofline classification (docs/observability.md).
                "cost": device_cost,
                "formula_flops_per_step": flops_per_token * tokens_per_step,
                "compiles": obs_device.SENTINEL.total - compiles_before,
                "unexpected_compiles":
                    obs_device.SENTINEL.unexpected - unexpected_before,
                "hbm_peak_bytes": hbm_peak_bytes or None,
                "hbm_bytes_in_use_per_device": hbm_per_device,
            },
            "history": history,
        }
        if in_progress:
            s["in_progress"] = True
        return s

    def _write_metrics(summary: Optional[Dict[str, Any]] = None) -> None:
        # Atomic (temp + os.replace) AND incremental (every log point):
        # a preempted run keeps its metrics history up to the last log
        # line instead of losing all of it — the checkpoint survived
        # preemption since PR 4; now the telemetry does too. A torn write
        # can never be observed: readers see the old file or the new one.
        path = os.path.join(artifacts, "metrics.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(summary if summary is not None
                      else _summary_dict(in_progress=True), f, indent=2)
        os.replace(tmp, path)

    def _check_nonfinite(pending) -> None:
        # Checked one step LATE on purpose: pulling the flag then only
        # waits on an already-finished step, so the guard adds no host/
        # device sync to the steady-state pipeline.
        nonlocal bad_streak, nonfinite_steps
        if pending is None:
            return
        step_idx, nf = pending
        if nf is None or float(nf) == 0.0:
            bad_streak = 0
            return
        bad_streak += 1
        nonfinite_steps += 1
        print(json.dumps({"step": step_idx + 1, "nonfinite": True,
                          "consecutive_bad": bad_streak}), flush=True)
        if bad_streak >= max(1, job.max_bad_steps):
            # The abort is an incident: bundle the flight ring, metrics,
            # and memory/program census beside the artifacts BEFORE
            # raising (debounced; capture never raises).
            from runbooks_tpu.obs import incident as obs_incident

            obs_incident.capture(
                "train_max_bad_steps", artifacts=artifacts,
                component="train",
                extra={"step": step_idx + 1, "bad_streak": bad_streak,
                       "nonfinite_steps": nonfinite_steps})
            raise RuntimeError(
                f"aborting: {bad_streak} consecutive non-finite loss/grad "
                f"steps (last at step {step_idx + 1}). Params were left "
                "unchanged by every bad step — inspect the data shard / "
                "learning rate and resume from the last checkpoint "
                "(docs/fault-tolerance.md)")

    def _fault_due(i: int, mode: str) -> bool:
        return (fault is not None and fault["mode"] == mode
                and (i == fault["step"]
                     or (fault["repeat"] and i >= fault["step"])))

    # Everything from here runs under the cleanup block: a failure in
    # restore, data-pipeline setup, or the loop itself must still restore
    # the signal handlers and wait/close the async checkpoint manager.
    try:
        if job.resume and ckpt.latest_intact_step() is not None:
            t_restore = time.perf_counter()
            with span("restore"):
                state, cursor, _ckpt_step = ckpt.restore_with_cursor(state)
            restore_time_s = time.perf_counter() - t_restore
            # Restart overhead, not steady-state time: excluded from the
            # goodput window, reported separately in goodput_detail.
            goodput.exclude(restore_time_s, "restore")
            start_step = int(state.step)
            last_saved = start_step
            # Legacy (pre-cursor) checkpoints: every step consumes exactly
            # one batch from a stream that starts at 0, so the step count
            # is the correct cursor for any run this trainer produced.
            consumed = int(cursor.get("batches_consumed", start_step))

        # Preemption handling: SIGTERM/SIGINT (and a pending GCE
        # maintenance event, when polling is on) set the stop reason; the
        # loop notices at the next step boundary and takes the
        # emergency-checkpoint path.
        if threading.current_thread() is threading.main_thread():
            def _on_signal(signum, frame):
                name = signal.Signals(signum).name
                if stop["reason"] is None:
                    stop["reason"] = ("sigint" if signum == signal.SIGINT
                                      else "sigterm")
                    print(f"trainer: caught {name}; emergency checkpoint "
                          "at the next step boundary", flush=True)
            for sig in (signal.SIGTERM, signal.SIGINT):
                restore_sigs.append((sig, signal.signal(sig, _on_signal)))
        if job.maintenance_poll_s > 0:
            poller_stop = threading.Event()
            poller_wait = poller_stop

            def _poll_maintenance():
                from runbooks_tpu.cloud import metadata

                while not poller_wait.wait(job.maintenance_poll_s):
                    try:
                        event = metadata.maintenance_event()
                    except Exception:  # noqa: BLE001 — flake != stop
                        continue
                    if event and stop["reason"] is None:
                        stop["reason"] = "maintenance"
                        print(f"trainer: GCE maintenance event {event!r}; "
                              "emergency checkpoint at the next step "
                              "boundary", flush=True)
                        return

            threading.Thread(target=_poll_maintenance,
                             name="rbt-maintenance", daemon=True).start()

        batches = _batches(job, model_cfg, skip=consumed)
        if job.prefetch_depth > 0:
            # Async input pipeline: tokenize/pack runs ahead on a
            # background thread and batches land on device (sharded
            # device_put) while the previous step computes — host work
            # overlaps device compute instead of serializing with it
            # inside the step loop.
            batches = prefetcher = data_mod.Prefetcher(
                batches, depth=job.prefetch_depth,
                place=data_mod.device_placer(mesh))
        t_start = time.perf_counter()
        compiled_before = obs_device.SENTINEL.compile_seconds
        with jax.set_mesh(mesh):
            for i in range(start_step, job.steps):
                if _fault_due(i, "kill"):
                    raise SimulatedFault(
                        f"RBT_FAULT_INJECT: simulated death at step {i}")
                if _fault_due(i, "sigterm"):
                    os.kill(os.getpid(), signal.SIGTERM)
                if stop["reason"]:
                    exit_reason = stop["reason"]
                    break
                if job.profile_stop > job.profile_start \
                        and i == job.profile_start and not profiling_at:
                    PROFILER.start(os.path.join(artifacts, "profile"))
                    profiling = True
                if profile_at is not None and i == profile_at[0] \
                        and not (profiling or profiling_at):
                    PROFILER.start(os.path.join(
                        artifacts, "profiles", f"step{profile_at[0]}"))
                    profiling_at = True
                t_data = time.perf_counter()
                with span("data_wait", step=i):
                    batch = next(batches)
                    consumed += 1
                    if prefetcher is None:
                        batch = {k: np.asarray(v) for k, v in batch.items()}
                data_wait_s = time.perf_counter() - t_data
                if _fault_due(i, "nonfinite"):
                    batch = dict(batch)
                    batch["loss_mask"] = batch["loss_mask"] * float("nan")
                t_step = time.perf_counter()
                # The first step folds this run's intended XLA compile:
                # with a colocated component already steady (a serve
                # engine sharing the process), it must not read as a
                # stall. Later steps run unwrapped — a compile THERE is
                # exactly what the sentinel exists to catch.
                expected_cm = (obs_device.SENTINEL.expected()
                               if i == start_step
                               else contextlib.nullcontext())
                with span("step", step=i), expected_cm:
                    if lora_mode:
                        state, metrics = step_fn(state, base_params, batch)
                    else:
                        state, metrics = step_fn(state, batch)
                step_s = time.perf_counter() - t_step
                with fine("step.sync", step=i):
                    _check_nonfinite(pending_nf)
                pending_nf = (i, metrics.get("nonfinite"))
                if i == start_step:
                    # The first call traced, lowered and compiled (or
                    # loaded) the step program: the set-up phases split it
                    # by the sentinel's compile clock, as the serve
                    # warm-up does (engine.WarmupRun).
                    compiled = (obs_device.SENTINEL.compile_seconds
                                - compiled_before)
                    phases.add("warmup.compile", compiled)
                    phases.add("warmup.trace", max(step_s - compiled, 0.0))
                    # The first step folds the XLA compile; pulling the
                    # loss waits for it, then the throughput window resets
                    # so tokens/sec and MFU report steady-state compute
                    # (compile time lands in its own field). The whole
                    # window is restart/startup overhead for goodput.
                    with phases.timed("warmup.run"):
                        float(metrics["loss"])
                    compile_time_s = time.perf_counter() - t_start
                    goodput.exclude(compile_time_s, "compile")
                    # Compile phase over: from here a compile in the step
                    # loop is a stall the sentinel flags loudly.
                    obs_device.SENTINEL.mark_steady("train")
                    if os.environ.get("RBT_DEVICE_OBS", "1") != "0":
                        # Roofline attribution of the step program: FLOPs
                        # + HBM bytes from the lowering's cost_analysis
                        # (a re-trace, no second backend compile) — the
                        # analytic cross-check for the wall-clock MFU.
                        # The re-trace is startup overhead like the
                        # compile itself: excluded from goodput's window.
                        t_cost = time.perf_counter()
                        args = ((state, base_params, batch) if lora_mode
                                else (state, batch))
                        device_cost = obs_device.cost_analysis_of(
                            step_fn, *args)
                        if device_cost is not None:
                            device_cost.update(obs_device.classify_roofline(
                                device_cost["flops"],
                                device_cost["hbm_bytes"]))
                            obs_device.PROGRAMS.record_cost(
                                "train", "train_step",
                                f"b{job.batch_size}s{job.seq_len}",
                                device_cost)
                        phases.add("warmup.cost_capture",
                                   time.perf_counter() - t_cost)
                        goodput.exclude(
                            time.perf_counter() - t_cost, "compile")
                    t_start = time.perf_counter()
                else:
                    tokens_done += tokens_per_step
                if profiling and i + 1 == job.profile_stop:
                    jax.block_until_ready(metrics["loss"])
                    PROFILER.stop()
                    profiling = False
                if profiling_at \
                        and i + 1 == profile_at[0] + profile_at[1]:
                    jax.block_until_ready(metrics["loss"])
                    PROFILER.stop()
                    profiling_at = False
                is_log = (i + 1) % job.log_every == 0 or i + 1 == job.steps
                if is_log:
                    # Only log points sync on the device (float pulls the
                    # scalar); between them steps dispatch async with
                    # metrics buffered as device arrays. The sync wait is
                    # device compute finishing — step time, not overhead.
                    t_sync = time.perf_counter()
                    with fine("log", step=i + 1):
                        loss = float(metrics["loss"])
                    t_synced = time.perf_counter()
                    dt = t_synced - t_start
                    if i != start_step:
                        step_s += t_synced - t_sync
                ckpt_s = 0.0
                if (i + 1) % job.checkpoint_every == 0 or i + 1 == job.steps:
                    t_ckpt = time.perf_counter()
                    # expected(): checkpoint plumbing may compile small
                    # host programs; that is not a step-loop stall.
                    with span("checkpoint", step=i + 1), \
                            obs_device.SENTINEL.expected():
                        ckpt.save(i + 1, state,
                                  cursor={"batches_consumed": consumed})
                    ckpt_s = time.perf_counter() - t_ckpt
                    last_saved = i + 1
                if i != start_step:
                    # Per-step breakdown: registry histograms + the
                    # goodput accumulator. The compile step is excluded
                    # wholesale above — recording it here too would count
                    # the same seconds twice.
                    goodput.step(step_s, data_wait_s, ckpt_s)
                    REGISTRY.observe(
                        "train_step_seconds", step_s,
                        help_text="Per-step compute wall time (dispatch "
                                  "+ device sync share).")
                    REGISTRY.observe(
                        "train_data_wait_seconds", data_wait_s,
                        help_text="Per-step input-pipeline wait.")
                    if ckpt_s:
                        REGISTRY.observe(
                            "train_checkpoint_seconds", ckpt_s,
                            help_text="Blocking checkpoint save time.")
                    win["data"] += data_wait_s
                    win["step"] += step_s
                    win["ckpt"] += ckpt_s
                    win["steps"] += 1
                if is_log:
                    # The log point: line printed, metrics.json rewritten
                    # (every step at log_every: 1).
                    with fine("log", step=i + 1):
                        if tokens_done:
                            tps = tokens_done / max(dt, 1e-9)
                        else:  # single measured step: only the compile window
                            tps = tokens_per_step / max(compile_time_s, 1e-9)
                        achieved = tps * flops_per_token
                        entry = {"step": i + 1, "loss": round(loss, 4),
                                 "tokens_per_sec": round(tps, 1),
                                 "tflops_per_sec": round(achieved / 1e12, 2)}
                        if peak_flops:
                            entry["mfu"] = round(achieved / peak_flops, 4)
                        if not history and compile_time_s is not None:
                            entry["compile_time_s"] = round(compile_time_s, 2)
                        if win["steps"]:
                            # Step-time breakdown (window means) + running
                            # goodput: the is-it-input-bound answer, on every
                            # log line instead of behind a debugger.
                            entry["data_wait_s"] = round(
                                win["data"] / win["steps"], 4)
                            entry["step_s"] = round(
                                win["step"] / win["steps"], 4)
                            if win["ckpt"]:
                                entry["ckpt_s"] = round(
                                    win["ckpt"] / win["steps"], 4)
                            entry["goodput"] = round(goodput.ratio(), 4)
                            REGISTRY.set_gauge(
                                "train_goodput_ratio", entry["goodput"],
                                help_text="Productive step time / wall clock "
                                          "(restart overhead excluded).")
                        # Progress gauges: what the controller's fleet
                        # scraper folds into Model .status.telemetry
                        # (step/loss/goodput on `rbt get`).
                        REGISTRY.set_gauge(
                            "train_step", i + 1,
                            help_text="Last completed training step.")
                        REGISTRY.set_gauge(
                            "train_loss", round(loss, 6),
                            help_text="Loss at the last logged step.")
                        # Per-step HBM watermark (device_memory_* gauges;
                        # absent on CPU where memory_stats() is None) and the
                        # analytic-MFU cross-check from the step program's
                        # cost_analysis.
                        hbm = [m["bytes_in_use"]
                               for m in obs_device.set_memory_gauges()
                               if "bytes_in_use" in m]
                        if hbm:
                            hbm_per_device = hbm
                            entry["hbm_used_bytes"] = max(hbm)
                            hbm_peak_bytes = max(hbm_peak_bytes, max(hbm))
                        if device_cost and win["steps"] and peak_flops:
                            entry["analytic_mfu"] = round(
                                device_cost["flops"]
                                / (win["step"] / win["steps"]) / peak_flops, 4)
                            REGISTRY.set_gauge(
                                "train_analytic_mfu", entry["analytic_mfu"],
                                help_text="cost_analysis FLOPs / measured "
                                          "step time / peak — the analytic "
                                          "cross-check of the wall-clock "
                                          "MFU.")
                        obs_device.PROGRAMS.set_gauges(component="train")
                        win = {"data": 0.0, "step": 0.0, "ckpt": 0.0,
                               "steps": 0}
                        history.append(entry)
                        print(json.dumps(entry), flush=True)
                        _write_metrics()
            if exit_reason is None:
                _check_nonfinite(pending_nf)
            else:
                # Emergency checkpoint: the work since the last periodic
                # save must survive the preemption. Carries the data
                # cursor like every save; force=True overwrites a same-step
                # periodic save if the stop landed right after one.
                step_now = int(state.step)
                if step_now != last_saved:
                    with span("emergency_save", step=step_now,
                              reason=exit_reason), \
                            obs_device.SENTINEL.expected():
                        ckpt.save(step_now, state,
                                  cursor={"batches_consumed": consumed},
                                  force=True)
                obs_trace.instant("preempted", reason=exit_reason,
                                  step=step_now)
                print(json.dumps({"preempted": exit_reason,
                                  "emergency_checkpoint_step": step_now}),
                      flush=True)
    finally:
        if prefetcher is not None:
            prefetcher.close()
        if poller_stop is not None:
            poller_stop.set()
        # This run's steady claim dies with it: a follow-up run (resume,
        # tests, a second job in-process) recompiles legitimately.
        obs_device.SENTINEL.clear_steady("train")
        # Async-checkpoint cleanup belongs HERE: an exception mid-run must
        # not leave the orbax save thread dangling with a half-written step
        # directory (wait() also stamps the integrity markers; close()
        # releases the manager even if wait itself blows up). Signal
        # handlers restore only AFTER the saves land — a SIGTERM during
        # the final wait must not kill the process mid-save (observed: a
        # kernel-default 143 death leaving an orbax tmp dir).
        try:
            try:
                ckpt.wait()
            finally:
                ckpt.close()
        finally:
            for sig, old in restore_sigs:
                signal.signal(sig, old)
            # Flush the run's trace file — live spans (RBT_TRACE=1) or
            # tail-sampled/incident promotions may have opened it (the
            # writer reopens in append mode if anything traces after
            # this).
            obs_trace.close()

    if profiling or profiling_at:  # profile window ran past the last step
        PROFILER.stop()
    summary = _summary_dict()
    _write_metrics(summary)
    if lora_mode:
        # Export merged params reference for serving (artifact contract).
        merged_note = {"note": "merged weights = base + lora; see checkpoints"}
        with open(os.path.join(artifacts, "lora.json"), "w") as f:
            json.dump(dataclasses.asdict(job.lora) | merged_note, f)
    return summary


def exit_code_for(summary: Dict[str, Any]) -> int:
    """Container exit code for a finished run: EXIT_PREEMPTED (42) when the
    run stopped for a preemption-shaped reason (SIGTERM/SIGINT/maintenance
    event, after its emergency checkpoint), 0 otherwise. The controller's
    train-Job podFailurePolicy restarts on 42 but fails the Job on any
    other non-zero code (docs/fault-tolerance.md)."""
    if summary.get("exit_reason") in ("sigterm", "sigint", "maintenance"):
        return EXIT_PREEMPTED
    return 0


def main() -> int:
    # Interpreter start to here: this module's imports (set-up phases).
    age = obs_trace.process_age_s()
    if age is not None:
        obs_trace.STARTUP.add("startup.imports", age)
    params = contract.load_params()
    job = TrainJobConfig.from_params(params)
    # Metrics exposition for the controller's fleet scraper: RBT_METRICS_PORT
    # (injected by the Model reconciler's Job template) serves the shared
    # registry — train_step/train_loss/goodput + the step histograms — on
    # GET /metrics. Env-gated so library callers of run_training never bind
    # a port.
    metrics_port = int(os.environ.get("RBT_METRICS_PORT", "0") or 0)
    if metrics_port:
        from runbooks_tpu.obs.metrics import serve_metrics

        serve_metrics(metrics_port)
    if job.maintenance_poll_s == 0 and "maintenance_poll_s" not in params:
        # Container entry point on GCE: watch for maintenance events /
        # preemptions by default (a quick single-attempt probe — an off-GCE
        # box must not stall startup on a dead metadata address).
        from runbooks_tpu.cloud import metadata

        if metadata.on_gce(timeout=0.5, attempts=1):
            job = dataclasses.replace(job, maintenance_poll_s=5.0)
    summary = run_training(job)
    print(json.dumps({"done": True, **{k: v for k, v in summary.items()
                                       if k != "history"}}))
    return exit_code_for(summary)


if __name__ == "__main__":
    raise SystemExit(main())
