"""OpenAI-compatible HTTP serving on the container contract.

Serves /v1/completions and /v1/chat/completions on port 8080 with readiness
at GET / — the exact surface the reference's Server resource expects of a
serving container (reference: internal/controller/server_controller.go
readiness probe GET / port 8080 "http-serve"; test/system.sh curls
/v1/completions; the reference's documented basaran server streams, and so
does this one: `"stream": true` returns SSE chunks). The engine behind it
does slot-based continuous batching (serve/engine.py).

Run: ``python -m runbooks_tpu.serve.api`` (reads /content/params.json:
model, checkpoint, max_slots, port, tokenizer) or programmatically via
``create_server``.
"""

from __future__ import annotations

import asyncio
import dataclasses
import functools
import json
import threading
import time
import uuid
from concurrent.futures import Future
from typing import Any, Optional, Tuple

from aiohttp import web

from runbooks_tpu.api.serve_params import ServeOptions
from runbooks_tpu.models.config import ModelConfig, get_config
from runbooks_tpu.obs import flight as obs_flight
from runbooks_tpu.obs import incident as obs_incident
from runbooks_tpu.obs import metrics as obs_metrics
# request_scope lives in obs/trace.py (shared with the gateway, which
# must not import this module's JAX engine stack); re-exported here for
# back-compat with existing importers.
from runbooks_tpu.obs.trace import complete as trace_complete
from runbooks_tpu.obs.trace import fine, request_scope  # noqa: F401
from runbooks_tpu.serve.engine import (
    PRIORITY_RANK,
    EngineDraining,
    EngineOverloaded,
    EngineStepFailed,
    InferenceEngine,
    Request,
    request_start,
)
from runbooks_tpu.train.data import load_tokenizer
from runbooks_tpu.utils import contract

# Top-level body fields /v1/completions understands (the chat endpoint
# adds messages and the internal _chat marker before delegating).
# Anything else 400s by name — constraint fields especially must never
# fail open (a typo'd `response_format` silently serving unconstrained
# text defeats the whole structured-output contract).
_KNOWN_BODY_FIELDS = frozenset({
    "prompt", "messages", "max_tokens", "temperature", "top_p", "top_k",
    "timeout", "adapter", "priority", "stream", "response_format",
    "model", "user", "_chat",
})


def _observe_parse(reqs: list) -> None:
    """Once the worker took a body's requests: handler entry to the
    hand-over (JSON, tokenizer, stream set-up), a request."""
    for r in reqs:
        obs_metrics.REGISTRY.observe(
            "serve_request_parse_seconds", r._handed - r._received,
            help_text="HTTP handler entry to the hand-over to the engine "
                      "worker (EngineWorker.submit_many): JSON, "
                      "tokenizer, stream set-up.")


def _encode(tok, text: str) -> list:
    """One tokenize path for completions AND prefix registration — they
    must agree exactly or registered prefixes never match prompts."""
    ids = tok.encode(text, add_bos=True, add_eos=False) \
        if hasattr(tok, "bos_id") else tok.encode(text)
    return list(ids)


def _eos_id(tok) -> Optional[int]:
    """Tokenizer EOS id across both tokenizer flavors (ByteTokenizer's
    eos_id, HF's eos_token_id). Explicit None checks: an EOS id of 0 is
    legitimate and must not read as missing."""
    for attr in ("eos_id", "eos_token_id"):
        val = getattr(tok, attr, None)
        if val is not None:
            return int(val)
    return None


def load_model(params: dict, mesh=None) -> Tuple[ModelConfig, Any]:
    """Model from params.json: named config + optional orbax checkpoint under
    the model mount (falls back to random init for smoke serving, mirroring
    the reference's opt-125m kind-cluster smoke test).

    With a serving ``mesh`` the weights come back laid out on it (the layout
    the engine uses) and no unsharded copy stays behind: random weights are
    initialised shard by shard, loaded ones are moved with their buffers
    donated. Otherwise the caller's reference keeps a whole second model
    alive on device 0 — 9.2 GB beside a 2.6 GB shard on the four-chip run
    that found it, enough to fail the first large prefill.

    params.quantize ("none"|"int8"|"int4", the reference Server contract's
    `quantize:` field) selects weight-only quantization: checkpoints saved
    pre-quantized by the loader restore packed directly; anything else is
    quantized here layer-by-layer before serving, so host RAM peaks ~one
    f32 layer above the packed size instead of holding bf16 and packed
    copies of a 70B model at once."""
    import jax

    from runbooks_tpu.ops.quantization import (
        quantize_params,
        quantized_logical_axes,
        resolve_quantize_mode,
        tree_quantize_mode,
        unpack_from_checkpoint,
    )

    cfg = get_config(params.get("model", "debug"),
                     **params.get("model_overrides", {}))
    quantize = resolve_quantize_mode(params, cfg)
    overrides = {"quantize": quantize}
    # Overlapped ring tensor parallelism for the serve engine's
    # prefill/decode programs (docs/tensor-parallel-performance.md);
    # takes effect with a mesh_tensor > 1 serving mesh. One shared
    # resolver covers every spelling the controller validates — a
    # validated spec must not silently serve without the ring — and
    # rejects typos here, before warmup compiles anything.
    from runbooks_tpu.models.config import resolve_collective_matmul_param

    cm = resolve_collective_matmul_param(params)
    if cm is not None:
        overrides["collective_matmul"] = cm
    cfg = dataclasses.replace(cfg, **overrides)
    ckpt_dir = params.get("checkpoint") or contract.model_dir()
    import os

    from runbooks_tpu.models.transformer import (
        init_params,
        param_logical_axes,
    )
    from runbooks_tpu.parallel.sharding import tree_shardings

    def mesh_shardings(tree):
        """The layout InferenceEngine gives the weights under ``mesh``."""
        return tree_shardings(
            tree, quantized_logical_axes(tree, param_logical_axes(cfg)),
            mesh)

    model_params = None
    have_ckpt = os.path.isdir(os.path.join(ckpt_dir, "checkpoints"))
    if have_ckpt:
        from runbooks_tpu.train.checkpoint import CheckpointManager

        mgr = CheckpointManager(ckpt_dir)
        try:
            if mgr.latest_step() is None:
                have_ckpt = False
            else:
                # Checkpoints store a TrainState {step, params, opt_state};
                # serving needs only params.
                full = mgr.restore(None)
                model_params = (full["params"] if isinstance(full, dict)
                                else full.params)
                # Loader-quantized checkpoints store QuantizedArrays as
                # plain dict nodes (orbax restores without a target);
                # reconstruct them before use. No-op otherwise.
                model_params = unpack_from_checkpoint(model_params)
        finally:
            mgr.close()
    if model_params is None:
        # Random init is only acceptable when there is genuinely nothing to
        # load (smoke serving, like the reference's opt-125m kind test). A
        # present-but-unreadable checkpoint must fail loudly, not serve
        # garbage weights behind a healthy readiness probe.
        if have_ckpt:
            raise RuntimeError(
                f"checkpoint exists under {ckpt_dir} but restore returned "
                "no params")
        def init(rng):
            return init_params(cfg, rng)

        from runbooks_tpu.train.step import layout_invariant_init

        key = jax.random.key(params.get("seed", 0))
        with layout_invariant_init():  # same values on every layout
            model_params = jax.jit(init, out_shardings=(
                None if mesh is None
                else mesh_shardings(jax.eval_shape(init, key))))(key)
    # Baseline single-adapter path (docs/multi-tenant-lora.md): with the
    # adapter POOL off, `adapter: <path>` folds the LoRA deltas into the
    # base weights at load time (train/lora.py apply_lora) — one tenant,
    # zero serve-time overhead, and the parity oracle the batched pooled
    # path is tested against. Folding happens BEFORE quantization so the
    # quantizer sees the merged weights; a pre-quantized checkpoint has
    # no headroom to fold into and must use the pool instead.
    # (Beside a pool the key is refused: ServeOptions.from_params.)
    adapter = params.get("adapter")
    if adapter:
        if tree_quantize_mode(model_params) != "none":
            raise RuntimeError(
                "cannot fold adapter into a pre-quantized checkpoint "
                "(packed int8/int4 weights have no headroom); serve it "
                "with adapter_pool >= 1 instead "
                "(docs/multi-tenant-lora.md)")
        from runbooks_tpu.serve.lora_pool import load_merge_adapter

        model_params = load_merge_adapter(str(adapter), cfg, model_params)
    stored = tree_quantize_mode(model_params)
    if stored == "none" and quantize != "none":
        model_params = quantize_params(model_params, quantize)
    elif stored != quantize:
        # An already-packed checkpoint cannot be re-quantized to a
        # different tier (int4 -> int8 has no information to recover);
        # serve what is stored, but say so loudly instead of silently
        # serving a different precision than configured.
        print(f"serve: checkpoint is quantized {stored} but params "
              f"requested quantize={quantize}; serving the stored "
              f"{stored} weights", flush=True)
        cfg = dataclasses.replace(cfg, quantize=stored)
    if mesh is not None:
        model_params = jax.device_put(
            model_params, mesh_shardings(model_params), donate=True)
    return cfg, model_params


class EngineWorker:
    """Single thread that owns the engine: admits requests, steps the decode
    loop, resolves futures of finished requests."""

    def __init__(self, engine: InferenceEngine,
                 warn_cold_prefix: bool = False):
        self.engine = engine
        # One-time operator warning when a runtime /v1/prefix registration
        # is about to compile the prefix-KV builder on THIS thread (which
        # stalls every in-flight decode for the compile). Servers started
        # with warmup+warm_prefix pre-compile the builder per bucket and
        # never hit it.
        self._warn_cold_prefix = warn_cold_prefix
        self._pending: list[Tuple[Request, Future]] = []      # guarded-by: _lock
        self._inflight: list[Tuple[Request, Future]] = []     # guarded-by: _lock
        self._prefix_jobs: list[Tuple[list, Future]] = []     # guarded-by: _lock
        self._prefix_warm_queue: list[tuple] = []
        self._prefix_warm_buffers = None  # threaded through warm calls
        # (plen, bucket, rows) shapes already executed once: XLA keys
        # compiles on shapes, so re-warming them is pure wasted device
        # work (auto_prefix_chat registers a new KEY per turn but the
        # same shapes; the jit cache survives engine.reset()).
        self._warmed_shapes: set = set()
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._stop = False
        self._draining = False
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def submit(self, req: Request) -> Future:
        return self.submit_many([req])[0]

    def submit_many(self, reqs: list) -> list:
        """Admit a batch of requests ATOMICALLY: either every request is
        accepted or none is (a multi-prompt HTTP body must not leave some
        prompts decoding with dropped futures after a 429). Validation runs
        first so unservable requests raise (-> 400) before admission
        control; a draining server (503) or a full queue (429 +
        Retry-After) rejects here, before the requests cost anything."""
        if self._draining:
            raise EngineDraining(
                "server is draining (shutdown in progress); "
                "not accepting new requests")
        for req in reqs:
            self.engine.validate(req)
        with self._lock:
            backlog = len(self.engine.queue) + len(self._pending)
            if backlog + len(reqs) > self.engine.max_queue:
                raise EngineOverloaded(
                    f"admission queue full ({backlog} waiting, bound "
                    f"{self.engine.max_queue}); retry later")
            futs = []
            handed = time.monotonic()
            for req in reqs:
                req._handed = handed
                fut: Future = Future()
                # Resolved the moment the engine finishes the request —
                # which may be while its next dispatch runs — not when
                # that step returns (_finish keeps the rest).
                req.on_finish = functools.partial(self._resolve, fut)
                self._pending.append((req, fut))
                futs.append(fut)
        self._wake.set()
        return futs

    @staticmethod
    def _resolve(fut: Future, req: Request) -> None:
        if not fut.done():
            fut.set_result(req)

    def register_prefix(self, tokens: list) -> Future:
        """Register a shared prompt prefix on the worker thread (the
        engine is single-threaded by design; touching it from an HTTP
        handler would race the step loop). Resolves to the cached
        length."""
        fut: Future = Future()
        with self._lock:
            self._prefix_jobs.append((tokens, fut))
        self._wake.set()
        return fut

    def _run(self) -> None:
        # Every stretch of this thread lies under one span, so that a
        # profiler capture can say what the host did in each gap the
        # device waited: worker.intake | tick (engine.step) |
        # worker.finish, or worker.idle when there is nothing to run.
        while not self._stop:
            try:
                with fine("worker.intake"):
                    self._intake()
                if not self.engine.has_work():
                    if self._prefix_warm_queue:
                        self._warm_one()
                        continue
                    with fine("worker.idle"):
                        self._wake.wait(timeout=0.05)
                        self._wake.clear()
                    continue
                self.engine.step()
                with fine("worker.finish") as finish:
                    finish.set(finished=self._finish())
            except Exception as exc:  # noqa: BLE001 — engine step blew up
                # Fail every waiting request AND queued prefix job with
                # the error (hanging futures would wedge HTTP handlers
                # forever), drop pending warm shapes, and reset the slot
                # state so subsequent requests get a clean engine.
                # First the tokens the engine took from its last decoded
                # chunk and had not handed over yet (host work only): a
                # request that chunk finished has its result, whatever
                # the step after it did.
                try:
                    self.engine.deliver_parked()
                except Exception as hook_exc:  # noqa: BLE001 — a failing on_token hook must not stop the reset below
                    print(f"serve: handing over the last chunk's tokens "
                          f"failed: {hook_exc!r}", flush=True)
                with self._lock:
                    doomed = self._inflight + self._pending
                    doomed_prefix = self._prefix_jobs
                    self._inflight, self._pending = [], []
                    self._prefix_jobs = []
                self._prefix_warm_queue.clear()
                self._prefix_warm_buffers = None
                now = time.monotonic()
                for req, fut in doomed:
                    if not fut.done():
                        fut.set_exception(exc)
                    # Error tail sampling: each doomed request's flight
                    # timeline is worth keeping — these are exactly the
                    # traces a postmortem needs.
                    start = request_start(req)
                    obs_flight.tail_sample(
                        req.request_id, now - start if start else 0.0,
                        req.finish_reason or "error", error=True)
                for _tokens, fut in doomed_prefix:
                    if not fut.done():
                        fut.set_exception(exc)
                # Automatic incident snapshot (debounced/rate-limited in
                # obs/incident.py) BEFORE reset() reallocates the cache:
                # the bundle's memory census shows the crashed state.
                # capture() never raises — the reset below must run.
                try:
                    groups = self.engine.memory_groups()
                except Exception:  # noqa: BLE001 — torn engine state
                    groups = None
                obs_incident.capture(
                    "engine_crash", component="serve",
                    memory_groups=groups,
                    extra={"error": repr(exc),
                           "doomed_requests": [r.request_id
                                               for r, _ in doomed],
                           "doomed_prefix_jobs": len(doomed_prefix)})
                # Donated buffers (cache) may have been invalidated by the
                # failed call — full reset reallocates them.
                self.engine.reset()

    def _intake(self) -> None:
        """Hand pending requests to the engine's queue and run queued
        prefix registrations (top of every loop iteration)."""
        with self._lock:
            prefix_jobs, self._prefix_jobs = self._prefix_jobs, []
            for req, fut in self._pending:
                try:
                    self.engine.submit(req)
                except (EngineOverloaded, ValueError) as exc:
                    # Race between the synchronous admission check
                    # and this enqueue: reject this request only,
                    # don't let it reach the crash catch-all.
                    # ValueError covers validate() flipping
                    # between the HTTP-thread check and here —
                    # e.g. an adapter artifact deleted in the gap
                    # (validation stats the filesystem).
                    if not fut.done():
                        fut.set_exception(exc)
                    continue
                # Behind the tick the worker was in when the request was
                # handed over: engine.submit stamped `_submitted` just now.
                pending_s = req._submitted - req._handed
                obs_metrics.REGISTRY.observe(
                    "serve_pending_wait_seconds", pending_s,
                    help_text="Hand-over by the HTTP handler "
                              "(EngineWorker.submit_many) to engine.submit "
                              "on the worker's thread: the rest of the "
                              "tick the worker was in.")
                trace_complete("pending_wait", pending_s,
                               request_id=req.request_id)
                self._inflight.append((req, fut))
            self._pending.clear()
        for job_i, (tokens, fut) in enumerate(prefix_jobs):
            try:
                # Register WITHOUT the inline warmup sweep (each
                # shape is an XLA compile; the whole sweep inline
                # would freeze every in-flight stream). Shapes
                # queue and warm one per loop iteration,
                # interleaved with decode steps.
                fresh = not self.engine.has_prefix(tokens)
                # Paged engines compile nothing at registration
                # (prefix_warmup_shapes() is empty: warmup already
                # covered every reachable shape) — the stall
                # warning would be a false alarm there.
                if fresh and self._warn_cold_prefix \
                        and self.engine.prefix_warmup_shapes(
                            len(tokens)):
                    self._warn_cold_prefix = False
                    print(
                        "serve: runtime /v1/prefix registration "
                        "compiles the prefix-KV builder on the "
                        "engine worker thread — in-flight decodes "
                        "stall until it finishes. Start the server "
                        "with warm_prefix: true (with warmup) to "
                        "pre-compile it per bucket.", flush=True)
                plen = self.engine.register_prefix(tokens,
                                                   warmup=False)
                if plen and fresh:
                    key = tuple(int(t) for t in tokens[:plen])
                    self._queue_warm(key, plen)
                fut.set_result(plen)
            except Exception as exc:  # noqa: BLE001
                if not fut.done():
                    fut.set_exception(exc)
                if isinstance(exc, EngineStepFailed):
                    # The paged register_prefix drives jitted
                    # steps that donate the cache: a failure
                    # there poisons the engine like a crash in
                    # the main step loop would. Fail the jobs
                    # not yet reached (the crash handler of _run
                    # only sees _prefix_jobs still on the
                    # instance) and route to it for the full
                    # doom + reset.
                    for _t, f in prefix_jobs[job_i + 1:]:
                        if not f.done():
                            f.set_exception(exc)
                    raise

    def _finish(self) -> int:
        """After a step: warm one queued prefix shape, resolve the
        futures of finished requests (lifting a chat turn's prompt KV
        first). Returns how many finished."""
        if self._prefix_warm_queue:
            self._warm_one()
        # Under the lock: drain() (HTTP thread) and the crash
        # handler both read _inflight concurrently, and the
        # reshuffle below is a read-then-replace, not an atomic
        # swap (`rbt check` lock-discipline caught this).
        with self._lock:
            done = [(r, f) for r, f in self._inflight
                    if r.finished]
            if done:
                self._inflight = [(r, f) for r, f in self._inflight
                                  if not r.finished]
        for req, fut in done:
            # Adapter requests never seed the shared-prefix
            # cache: their slot KV was computed through the
            # tenant's LoRA deltas and must not serve base (or
            # other-tenant) prompts. (The paged engine's radix
            # adoption namespaces by adapter instead.)
            if req.auto_prefix and req._slot >= 0 \
                    and req.adapter is None:
                # Multi-turn chat: lift the prompt's KV out of
                # the slot before the next admission can
                # recycle it (safe here: admissions happen at
                # the next step(), and this thread owns the
                # engine). Zero forward passes.
                try:
                    plen = self.engine.register_prefix_from_slot(
                        req._slot, req.prompt_tokens)
                    if plen:
                        key = tuple(
                            int(t)
                            for t in req.prompt_tokens[:plen])
                        self._queue_warm(key, plen)
                except Exception as exc:  # noqa: BLE001
                    print(f"serve: auto-prefix registration "
                          f"failed: {exc!r}", flush=True)
            if not fut.done():
                fut.set_result(req)
        return len(done)

    def _queue_warm(self, key: tuple, plen: int) -> None:
        """Queue only shapes not already executed or in flight: compiles
        are keyed on shapes, not prefix keys, so a steady-state chat
        service (same plen every turn) queues nothing after the first
        turn. Shapes join _warmed_shapes only once their warm SUCCEEDS
        (_warm_one) — marking at queue time would permanently skip shapes
        whose warm got dropped (key evicted first, sweep failure, crash
        reset), leaving the compile stall for the first live admission."""
        queued = {(len(k), b, r) for k, b, r in self._prefix_warm_queue}
        for b, r in self.engine.prefix_warmup_shapes(plen):
            sig = (plen, b, r)
            if sig not in self._warmed_shapes and sig not in queued:
                self._prefix_warm_queue.append((key, b, r))

    def _warm_one(self) -> None:
        """Warm one queued prefix shape. Best-effort: a failed speculative
        compile must never doom live traffic, so failures log and drop the
        rest of that sweep instead of reaching the run-loop catch-all."""
        key, bucket, rows = self._prefix_warm_queue.pop(0)
        try:
            self._prefix_warm_buffers = self.engine.warm_prefix_shape(
                key, bucket, rows, self._prefix_warm_buffers)
            if key in self.engine._prefix_cache:  # actually executed
                self._warmed_shapes.add((len(key), bucket, rows))
        except Exception as exc:  # noqa: BLE001
            print(f"serve: prefix warmup shape ({bucket}x{rows}) failed, "
                  f"dropping remaining sweep: {exc!r}", flush=True)
            self._prefix_warm_queue.clear()
            self._prefix_warm_buffers = None
        if not self._prefix_warm_queue:
            self._prefix_warm_buffers = None  # free the throwaway pool

    def drain(self, timeout_s: float = 30.0) -> bool:
        """Graceful drain (SIGTERM path): stop admitting (submit raises
        EngineDraining -> HTTP 503) and wait for every in-flight and
        already-queued request to finish, bounded by timeout_s. Returns
        True when fully drained. Call stop() afterwards."""
        self._draining = True
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with self._lock:
                busy = bool(self._pending or self._inflight)
            if not busy and not self.engine.has_work():
                return True
            time.sleep(0.02)
        return False

    def stop(self) -> None:
        self._stop = True
        self._wake.set()
        self._thread.join(timeout=5)
        # This engine's steady claim ends with its worker: a successor
        # engine (or any later workload in the process) compiles its own
        # warmup without being flagged as a serve-time stall. Claims are
        # refcounted per component, so stopping one of two colocated
        # servers does not blind the sentinel for the survivor.
        self.engine.release_steady()
        # Queued prefix jobs the loop never reached must not hang their
        # awaiting HTTP handlers.
        with self._lock:
            doomed = self._prefix_jobs
            self._prefix_jobs = []
        for _tokens, fut in doomed:
            if not fut.done():
                fut.set_exception(RuntimeError("engine worker stopped"))


def create_server(cfg: ModelConfig, model_params, tokenizer=None, *,
                  mesh=None, **options) -> web.Application:
    """The serving app over a new engine. ``options`` are fields of
    ServeOptions (api/serve_params.py, where each is documented);
    `kv_paging` picks the engine class."""
    options = ServeOptions(**options)
    # 0 disables, like the other *_s knobs — a validated config of 0
    # must mean "no deadline", not "400 every deadline-less request".
    request_timeout_s = options.request_timeout_s or None
    tokenizer = tokenizer or load_tokenizer(None)
    engine_cls = InferenceEngine
    if options.kv_paging == "paged":
        from runbooks_tpu.serve.paging import PagedInferenceEngine

        engine_cls = PagedInferenceEngine
    engine = engine_cls(cfg, model_params, mesh=mesh, tokenizer=tokenizer,
                        **dataclasses.asdict(options))
    # The engine's tree is the weights' from here on: it re-placed the
    # leaves its decode program wants elsewhere (serve/weight_layout.py),
    # and a second holder would keep their sources beside the warm-up.
    del model_params
    if options.auto_prefix_chat:
        engine._refuse_prefix()
    if options.warmup:
        engine.warmup(prefix_build=options.warm_prefix)
    worker = EngineWorker(engine, warn_cold_prefix=not (
        options.warmup and options.warm_prefix))
    # Flight/trace identity: this process's events label as the serving
    # tier in merged timelines and /debug/flight envelopes.
    obs_flight.set_component("serve")
    app = web.Application()
    app["worker"] = worker
    app["tokenizer"] = tokenizer
    app["model_name"] = cfg.name
    app["requests_total"] = 0
    app["requests_failed_total"] = 0
    app["requests_rejected_total"] = 0
    app["tokens_total"] = 0
    started = time.time()

    def _reject(app_, exc: EngineOverloaded, n: int = 1) -> web.Response:
        """Typed backpressure -> HTTP: draining = 503 (terminal for this
        process), overloaded = 429 + Retry-After (client should back
        off and retry against a healthy replica)."""
        app_["requests_rejected_total"] += n
        if isinstance(exc, EngineDraining):
            return web.json_response(
                {"error": {"message": str(exc), "type": "draining"}},
                status=503, headers={"Retry-After": "5"})
        # Load-derived backoff: queue depth in slot-drain units, clamped
        # to [1, 30] (engine.retry_after_hint) — a deep backlog tells
        # clients (and the gateway's per-class retry budget) how long
        # this replica actually needs, instead of a constant "1".
        return web.json_response(
            {"error": {"message": str(exc), "type": "overloaded"}},
            status=429,
            headers={"Retry-After": str(worker.engine.retry_after_hint())})

    async def root(request: web.Request) -> web.Response:
        # Readiness probe target (reference probes GET / on the serve port).
        return web.json_response({"status": "ok", "model": cfg.name,
                                  "uptime_s": round(time.time() - started, 1)})

    async def healthz(request: web.Request) -> web.Response:
        return web.json_response({"ok": True})

    async def metrics(request: web.Request) -> web.Response:
        """Prometheus exposition from the unified registry
        (runbooks_tpu.obs): request/engine totals mirrored at scrape time
        from this app's engine (absolute values, so concurrent server
        instances in one process each scrape their own truth), plus the
        latency histograms (TTFT, inter-token, queue-wait, end-to-end,
        prefill/decode dispatch) the engine records as it serves."""
        reg = obs_metrics.REGISTRY
        eng = worker.engine
        reg.set_counter("serve_requests_total", app["requests_total"],
                        help_text="Requests accepted by the HTTP API.")
        reg.set_counter("serve_requests_failed_total",
                        app["requests_failed_total"],
                        help_text="Requests that errored or timed out.")
        reg.set_counter("serve_tokens_generated_total", app["tokens_total"],
                        help_text="Completion tokens returned to clients.")
        reg.set_counter("serve_decode_steps_total", eng.steps,
                        help_text="Engine decode chunks executed.")
        for delivery, n in eng.decode_deliveries.items():
            reg.set_counter(
                "serve_decode_chunks_total", n, delivery=delivery,
                help_text="Decoded chunks by when their tokens were "
                          "handed over: deferred = while the next "
                          "dispatch ran on the device, inline = before "
                          "it (grammar, speculation, nothing to follow).")
        for kind, n in eng.operand_places.items():
            reg.set_counter(
                "serve_decode_operand_places_total", n, kind=kind,
                help_text="Decode chunks by their per-slot operands: "
                          "carry = the device arrays the chunk before "
                          "returned, rebuilt = placed from the host "
                          "after a slot changed hands.")
        reg.set_gauge("serve_active_slots", int(eng.active.sum()),
                      help_text="Slots currently decoding.")
        reg.set_gauge("serve_queue_depth", len(eng.queue),
                      help_text="Requests waiting for a slot.")
        reg.set_gauge("serve_queue_limit", eng.max_queue,
                      help_text="Admission queue bound (429 past this).")
        reg.set_counter("serve_requests_rejected_total",
                        app["requests_rejected_total"],
                        help_text="Requests shed with 429/503.")
        reg.set_counter("serve_preemptions_total", eng.preemptions,
                        help_text="Active slots preempted for a higher-"
                                  "priority queue head (pages swapped to "
                                  "the radix tree / host tier).")
        reg.set_counter("serve_preempted_resumed_total",
                        eng.preempted_resumed,
                        help_text="Preempted requests re-admitted and "
                                  "resumed from their cached history.")
        reg.set_counter("serve_deadline_expired_total", eng.deadline_expired,
                        help_text="Requests finished by wall-clock "
                                  "deadline.")
        reg.set_gauge("serve_draining", int(worker._draining),
                      help_text="1 while the server drains for shutdown.")
        reg.set_counter("serve_prefix_tokens_reused_total",
                        eng.prefix_tokens_reused,
                        help_text="Prompt tokens served from the shared-"
                                  "prefix KV cache instead of prefill.")
        # Device-level families (obs/device.py, docs/observability.md):
        # KV slot-pool occupancy + prefix hit rate (the paged-KV design
        # baseline), per-device HBM gauges (absent on CPU), and the
        # compiled-program census/roofline gauges.
        from runbooks_tpu.obs import device as obs_device

        occ = eng.kv_occupancy()
        reg.set_gauge("serve_slots_total", occ["slots_total"],
                      help_text="Engine slot-pool size (max concurrent "
                                "decodes).")
        reg.set_gauge("serve_kv_cache_tokens", occ["kv_tokens"],
                      help_text="Tokens currently held in active KV "
                                "slots.")
        reg.set_gauge("serve_kv_cache_capacity_tokens",
                      occ["kv_capacity_tokens"],
                      help_text="Dense KV reservation: max_slots x "
                                "max_seq_len.")
        reg.set_gauge("serve_kv_occupancy_ratio",
                      round(occ["occupancy_ratio"], 6),
                      help_text="Cached tokens / dense KV reservation "
                                "(the paged-KV headroom signal).")
        # KV pool HBM bytes, aggregate (logical) AND per-device: under a
        # serving mesh (mesh_tensor > 1) the pool shards its kv-head
        # axis, so each chip holds only pool/tensor bytes — the number
        # capacity planning and OOM headroom actually see. Equal on a
        # single device.
        reg.set_gauge("serve_kv_pool_bytes", occ["kv_pool_bytes"],
                      help_text="KV pool HBM bytes, aggregate across "
                                "the serving mesh (logical size).")
        reg.set_gauge("serve_kv_pool_bytes_per_device",
                      occ["kv_pool_bytes_per_device"],
                      help_text="KV pool HBM bytes each device holds "
                                "(its shard under the serving mesh; "
                                "equals the aggregate unsharded).")
        reg.set_gauge("serve_recurrent_state_bytes",
                      occ.get("recurrent_state_bytes", 0),
                      help_text="Recurrent state and conv tails of "
                                "linear-attention layers, or the tails "
                                "of short-convolution layers, all slots "
                                "(0 without such layers); apart from "
                                "serve_kv_pool_bytes.")
        reg.set_gauge("serve_latent_cache_bytes",
                      occ.get("latent_cache_bytes", 0),
                      help_text="The latent (MLA) cache leaf, all slots "
                                "(0 for per-head K/V): the part of "
                                "serve_kv_pool_bytes with no head axis.")
        # Weights put into the layout decode reads them in, once at load
        # (serve/weight_layout.py); 0 where the client's layouts serve.
        reg.set_gauge("serve_weight_leaves_replaced",
                      eng.weight_layout["leaves_replaced"],
                      help_text="Weight leaves the engine put into another "
                                "layout at load because its decode program "
                                "asked for it.")
        reg.set_gauge("serve_weight_bytes_replaced",
                      eng.weight_layout["bytes_replaced"],
                      help_text="Bytes of the weight leaves re-placed at "
                                "load (serve_weight_leaves_replaced).")
        for program, kinds in eng.flash_head_block.items():
            for kind, heads in kinds.items():
                reg.set_gauge(
                    "serve_flash_heads_per_step", heads, program=program,
                    kind=kind,
                    help_text="Query heads of one KV head's group that a "
                              "grid step of the flash forward holds in "
                              "this prefill program, by kind of attention "
                              "layer (ops/flash_attention.head_block: a "
                              "function of the program's shapes; 1 = a "
                              "head a step).")
        for program, kinds in eng.flash_blocks.items():
            for kind, kernels in kinds.items():
                for side, block in zip("qk", kernels["fwd"]):
                    reg.set_gauge(
                        "serve_flash_block_shape", block, program=program,
                        kind=kind, side=side,
                        help_text="Rows of a query block (side q) and of "
                                  "a key block (side k) of the flash "
                                  "forward in this prefill program, by "
                                  "kind of attention layer (ops/"
                                  "flash_attention.block_shape: a "
                                  "function of the program's shapes).")
        reg.set_gauge("serve_kv_ring_bytes", occ.get("kv_ring_bytes", 0),
                      help_text="Window layers' ring caches, all slots (0 "
                                "without such layers): the part of "
                                "serve_kv_pool_bytes that does not grow "
                                "with max_seq_len.")
        reg.set_gauge("serve_kv_compressed_bytes",
                      occ.get("kv_compressed_bytes", 0),
                      help_text="Compressed keys of the sparse-read "
                                "attention layers, all slots (0 without "
                                "such layers): the part of "
                                "serve_kv_pool_bytes a query scores to "
                                "choose its key blocks.")
        moe = eng.moe_stats()
        if moe is not None:
            # Sparse layers (models/moe.py, docs/sparse-latent-models.md):
            # the engine sums what its programs return; mirrored here.
            here = sum(moe["expert_tokens"])
            for held, n in (("here", here), ("elsewhere", moe["elsewhere"])):
                reg.set_counter(
                    "serve_moe_assignments_total", n, held=held,
                    help_text="(token, expert) assignments of real tokens, "
                              "by whether this process holds the expert.")
            for i, n in enumerate(moe["expert_tokens"]):
                reg.set_counter(
                    "serve_moe_expert_tokens_total", n,
                    expert=str(moe["first_expert"] + i),
                    help_text="Assignments by held expert, summed over "
                              "the sparse layers.")
            reg.set_counter(
                "serve_moe_layer_peak_assignments_total", moe["peak"],
                help_text="Sum over dispatches and sparse layers of the "
                          "assignments of the most loaded held expert; "
                          "over serve_moe_assignments_total{held=here} / "
                          "experts held it is the load imbalance a "
                          "dispatch sees (max over mean).")
            for prog in moe["hits"]:
                reg.set_counter(
                    "serve_moe_expert_hits_total", moe["hits"][prog],
                    program=prog,
                    help_text="(layer, expert) pairs that got at least "
                              "one token in a forward, by program: the "
                              "expert weights a forward had to read.")
                reg.set_counter(
                    "serve_moe_expert_calls_total", moe["calls"][prog],
                    program=prog,
                    help_text="(layer, expert) pairs offered, a forward, "
                              "by program.")
                reg.set_counter(
                    "serve_moe_rows_moved_total", moe["rows_moved"][prog],
                    program=prog,
                    help_text="Rows of the order by expert that the "
                              "sparse layers gathered, ran through their "
                              "experts and brought back, by program: the "
                              "held assignments in whole windows "
                              "(moe_row_window), or all a forward's "
                              "tokens x top_k rows. Over serve_moe_"
                              "assignments_total: rows moved a real "
                              "assignment.")
                reg.set_counter(
                    "serve_moe_all_rows_total", moe["all_rows"][prog],
                    program=prog,
                    help_text="(layer, forward) pairs whose window was "
                              "all the forward's rows, whatever it held "
                              "(a decode step, every expert held), by "
                              "program.")
        reg.set_counter("serve_prefix_lookups_total", eng.prefix_lookups,
                        help_text="Admissions that checked the shared-"
                                  "prefix cache.")
        reg.set_counter("serve_prefix_hits_total", eng.prefix_hits,
                        help_text="Admissions whose prompt matched a "
                                  "registered prefix.")
        if eng.options.speculative != "off":
            # Speculative decoding (serve/engine.py verify path,
            # docs/speculative-decoding.md): draft volume vs verified
            # acceptance — the accept rate is the whole economics of
            # drafting, so it mirrors to the fleet with the other
            # serve_* families. serve_spec_accept_len (histogram) is
            # observed by the engine at replay time.
            reg.set_counter("serve_spec_drafted_total", eng.spec_drafted,
                            help_text="Draft tokens proposed by the "
                                      "prompt-lookup drafter.")
            reg.set_counter("serve_spec_accepted_total",
                            eng.spec_accepted,
                            help_text="Draft tokens verified-accepted "
                                      "by the batched verify forward.")
        if eng.options.grammar != "off":
            # Grammar-constrained structured output (serve/grammar.py,
            # docs/structured-output.md): request volume, compile-cache
            # economics, and spec-draft truncation — absolute mirrors of
            # the engine's own counters at scrape time, like the spec
            # family above. serve_grammar_mask_build_seconds (histogram)
            # is observed by the engine as it builds mask operands.
            gs = eng.grammar_stats()
            reg.set_counter("serve_grammar_requests_total",
                            gs["requests_total"],
                            help_text="Requests admitted with a "
                                      "response_format grammar "
                                      "constraint.")
            reg.set_counter("serve_grammar_cache_hits_total",
                            gs["hits"],
                            help_text="Grammar compiles served from the "
                                      "token-DFA LRU cache.")
            reg.set_counter("serve_grammar_cache_misses_total",
                            gs["misses"],
                            help_text="Grammar compiles that built a "
                                      "fresh token DFA (host-side; "
                                      "never an XLA compile).")
            reg.set_counter("serve_grammar_draft_truncations_total",
                            gs["draft_truncations_total"],
                            help_text="Speculative drafts cut at the "
                                      "first grammar-illegal token "
                                      "before verify dispatch.")
        adapters = eng.adapter_stats()
        if adapters is not None:
            # Multi-tenant LoRA pool (serve/lora_pool.py,
            # docs/multi-tenant-lora.md): residency churn + per-tenant
            # request volume. Exported only by pooled engines, like the
            # spec/page families above.
            reg.set_counter("serve_adapter_loads_total",
                            adapters["loads"],
                            help_text="Adapters paged into the HBM pool "
                                      "from artifact storage.")
            reg.set_counter("serve_adapter_evictions_total",
                            adapters["evictions"],
                            help_text="Resident adapters displaced from "
                                      "their pool lane (LRU, unpinned "
                                      "lanes only).")
            reg.set_counter("serve_adapter_hits_total",
                            adapters["hits"],
                            help_text="Adapter acquisitions served from "
                                      "residency (no artifact read).")
            reg.set_gauge("serve_adapters_resident",
                          len(adapters["resident"]),
                          help_text="Adapters currently resident in the "
                                    "HBM pool.")
            for name, count in adapters["requests"].items():
                reg.set_counter(
                    "serve_adapter_requests_total", count, adapter=name,
                    help_text="Requests accepted per adapter name "
                              "(base-model requests are not counted).")
        if occ.get("paged"):
            # Paged engine (serve/paging.py): page-pool pressure + radix
            # sharing, the per-PAGE extension of the admission-level hit
            # counters above (docs/paged-kv.md).
            reg.set_gauge("serve_kv_pages_free", occ["pages_free"],
                          help_text="Allocatable KV pages currently on "
                                    "the free list.")
            reg.set_gauge("serve_kv_pages_used", occ["pages_used"],
                          help_text="KV pages held by live slots or the "
                                    "radix prefix tree.")
            reg.set_gauge("serve_kv_pages_shared", occ["pages_shared"],
                          help_text="KV pages owned by the radix prefix "
                                    "tree (shareable across requests).")
            reg.set_counter("serve_prefix_pages_reused_total",
                            occ["pages_reused_total"],
                            help_text="Physical KV pages mapped from the "
                                      "radix tree into admissions instead "
                                      "of being re-prefilled (counted per "
                                      "page, not per admission).")
            if occ.get("host_pages_total"):
                # Host-RAM KV swap tier (docs/paged-kv.md "Host tier and
                # preemption"): swap traffic + host-pool pressure.
                # Exported only when kv_host_pages > 0, like the paged
                # families above.
                reg.set_gauge("serve_kv_host_pages_used",
                              occ["host_pages_used"],
                              help_text="Host-tier page slots holding "
                                        "swapped-out KV pages.")
                reg.set_gauge("serve_kv_host_pages_free",
                              occ["host_pages_free"],
                              help_text="Host-tier page slots on the "
                                        "free list.")
                reg.set_counter("serve_kv_swap_out_pages_total",
                                occ["swap_out_pages_total"],
                                help_text="KV pages copied HBM -> host "
                                          "at radix eviction instead of "
                                          "being dropped.")
                reg.set_counter("serve_kv_swap_in_pages_total",
                                occ["swap_in_pages_total"],
                                help_text="KV pages copied host -> HBM "
                                          "at admission (radix match on "
                                          "the host tier).")
                reg.set_counter("serve_kv_swap_dropped_pages_total",
                                occ["swap_dropped_pages_total"],
                                help_text="Evicted pages dropped because "
                                          "the host tier was full or the "
                                          "copy failed (recompute on "
                                          "return).")
        obs_device.set_memory_gauges(reg)
        obs_device.PROGRAMS.set_gauges(reg, component="serve")
        # Flight recorder + incident freshness (docs/observability.md):
        # ring depth mirrors to the fleet (MIRROR_PREFIXES carries
        # flight_*), and the last-incident age feeds `rbt top`.
        reg.set_gauge("flight_ring_events",
                      obs_flight.RING.stats()["events"],
                      help_text="Events currently held in the in-memory "
                                "flight-recorder ring.")
        inc_age = obs_incident.MANAGER.last_age()
        if inc_age is not None:
            reg.set_gauge("serve_incident_age_seconds", round(inc_age, 1),
                          help_text="Seconds since this process captured "
                                    "its last incident bundle.")
        body = reg.render().encode("utf-8")
        return web.Response(
            body=body, headers={"Content-Type": obs_metrics.CONTENT_TYPE})

    async def debug_profile(request: web.Request) -> web.Response:
        """On-demand TPU/XLA profiler capture: POST /debug/profile
        ?seconds=N (or JSON body {"seconds": N}) traces N seconds of live
        traffic into {artifacts}/profiles/<stamp>-serve (XProf/
        TensorBoard-loadable). One capture at a time -> 409 while busy.
        The capture holds the device planes and the program's own spans
        (obs/trace.py); ?python=1 adds the Python tracer's frames, which
        slow the threads they instrument."""
        from runbooks_tpu.obs import profile as obs_profile

        python_tracer = request.query.get("python", "0") == "1"

        seconds = request.query.get("seconds")
        if seconds is None and request.can_read_body:
            try:
                seconds = (await request.json()).get("seconds")
            except (json.JSONDecodeError, AttributeError):
                seconds = None
        try:
            seconds = float(seconds if seconds is not None else 3.0)
        except (TypeError, ValueError):
            return web.json_response(
                {"error": {"message": "seconds must be a number"}},
                status=400)
        if not 0 < seconds <= 300:
            return web.json_response(
                {"error": {"message": "seconds must be in (0, 300]"}},
                status=400)
        log_dir = obs_profile.capture_dir(tag="serve")
        try:
            # Blocking timed capture off the event loop: SSE streams and
            # new admissions keep flowing while the profiler records them.
            await asyncio.get_running_loop().run_in_executor(
                None, obs_profile.PROFILER.capture, log_dir, seconds,
                python_tracer)
        except obs_profile.ProfilerBusy as exc:
            return web.json_response(
                {"error": {"message": str(exc)}}, status=409)
        except Exception as exc:  # noqa: BLE001 — profiler plumbing failed
            return web.json_response(
                {"error": {"message": f"profile capture failed: {exc}"}},
                status=500)
        return web.json_response({"path": log_dir, "seconds": seconds,
                                  "python_tracer": python_tracer})

    async def debug_memory(request: web.Request) -> web.Response:
        """GET /debug/memory: per-device allocator stats (HBM in use /
        peak / limit — absent on CPU, where memory_stats() is None) plus
        the live-array census attributing bytes to weights / KV cache /
        prefix cache / other. The answer to "what is eating HBM" without
        waiting for the OOM (docs/observability.md)."""
        from runbooks_tpu.obs import device as obs_device

        eng = worker.engine
        try:
            snap = await asyncio.get_running_loop().run_in_executor(
                None, obs_device.memory_snapshot, eng.memory_groups())
        except Exception as exc:  # noqa: BLE001 — diagnostics, not serving
            return web.json_response(
                {"error": {"message": f"memory snapshot failed: {exc}"}},
                status=500)
        snap["kv_occupancy"] = eng.kv_occupancy()
        return web.json_response(snap)

    async def debug_programs(request: web.Request) -> web.Response:
        """GET /debug/programs: the compiled-program census (live XLA
        variants per jitted entry point) with per-shape roofline
        attribution — FLOPs, HBM bytes, arithmetic intensity, compute- vs
        bandwidth-bound — plus analytic MFU for programs with a measured
        dispatch-time distribution, and the compile-sentinel state."""
        from runbooks_tpu.obs import device as obs_device
        from runbooks_tpu.obs import metrics as obs_metrics_mod
        from runbooks_tpu.obs import trace as obs_trace

        # None off-TPU: the fields that need a peak are then absent.
        peaks = obs_device.device_peaks()
        reg = obs_metrics_mod.REGISTRY
        census = obs_device.PROGRAMS.census("serve")
        for entry in census:
            for sig, cost in entry["costs"].items():
                # Measured mean dispatch for this program family, from
                # the live histograms, keyed the way the engine labels
                # them (decode by view, prefill by bucket).
                stats = None
                if entry["name"].startswith("decode_v"):
                    stats = reg.histogram_stats(
                        "serve_decode_dispatch_seconds",
                        view=entry["name"][len("decode_v"):])
                elif entry["name"].startswith("verify_v"):
                    stats = reg.histogram_stats(
                        "serve_verify_dispatch_seconds",
                        view=entry["name"][len("verify_v"):])
                elif entry["name"] == "prefill" and sig.startswith("b"):
                    bucket, _, rows_sig = sig[1:].partition("r")
                    stats = reg.histogram_stats(
                        "serve_prefill_dispatch_seconds", bucket=bucket,
                        rows=rows_sig)
                if stats and stats[0]:
                    mean_s = stats[1] / stats[0]
                    cost["measured_mean_seconds"] = round(mean_s, 6)
                    if peaks is not None:
                        cost["analytic_mfu"] = round(
                            cost["flops"] / (mean_s * peaks[0]), 9)
                    cost["achieved_gbps"] = round(
                        cost["hbm_bytes"] / mean_s / 1e9, 3)
        sentinel = obs_device.SENTINEL
        warmed = worker.engine.warmup_census
        return web.json_response({
            "programs": census,
            # Set-up seconds by phase: the entry point's startup.* joined
            # with the warm-up's own (docs/observability.md).
            "warmup_census": warmed and {
                **warmed,
                "phases": {**obs_trace.STARTUP.snapshot(),
                           **warmed.get("phases", {})}},
            # Decode steps per dispatch: what turns a dispatch time into
            # a step time (also inside warmup_census once warmed).
            "decode_chunk": worker.engine.decode_chunk,
            # Speculation economics (docs/speculative-decoding.md):
            # accept rate + decode tok/s per accept-rate bucket, so the
            # "is drafting paying on this traffic" question is one GET.
            "speculative": worker.engine.spec_stats(),
            # Adapter-pool residency/churn (docs/multi-tenant-lora.md);
            # None on pool-less engines.
            "adapters": worker.engine.adapter_stats(),
            # Grammar-constrained decoding (docs/structured-output.md):
            # DFA compile-cache economics + the vocab content hash that
            # keys it. The fingerprint is exposed even with grammar off
            # so a fleet audit can prove two replicas serve the same
            # vocabulary before enabling constrained routing.
            "grammar": worker.engine.grammar_stats(),
            "tokenizer_fingerprint": worker.engine.tokenizer_fingerprint,
            "compiles": {"total": sentinel.total,
                         "unexpected": sentinel.unexpected,
                         "compile_seconds": round(
                             sentinel.compile_seconds, 3),
                         "steady": sentinel.steady_components(),
                         "last_unexpected": sentinel.recent_unexpected()},
            "peaks": (None if peaks is None else
                      {"flops_per_sec": peaks[0],
                       "hbm_bytes_per_sec": peaks[1],
                       "ridge_flops_per_byte": round(
                           peaks[0] / peaks[1], 3)}),
        })

    async def debug_flight(request: web.Request) -> web.Response:
        """GET /debug/flight[?request_id=]: the always-on flight-recorder
        ring (obs/flight.py) — the last N span/instant events, filtered
        to one request's timeline when a request_id is given. The
        envelope carries host/pid/component so `rbt trace` can merge
        rings from the gateway and every replica into one clock-ordered
        timeline."""
        rid = request.query.get("request_id")
        return web.json_response({
            **obs_flight.identity(),
            "stats": obs_flight.RING.stats(),
            "events": obs_flight.RING.snapshot(request_id=rid or None),
        })

    async def debug_incident(request: web.Request) -> web.Response:
        """POST /debug/incident {"reason": ...}: capture an incident
        bundle on demand (the controller fires this at every replica on
        an SLOViolated onset). Debounced server-side — a repeat inside
        the window returns {"debounced": true} instead of a second
        bundle."""
        reason = "manual"
        if request.can_read_body:
            try:
                reason = str((await request.json()).get("reason")
                             or "manual")
            except (json.JSONDecodeError, AttributeError):
                reason = "manual"
        eng = worker.engine
        try:
            groups = eng.memory_groups()
        except Exception:  # noqa: BLE001 — diagnostics, not serving
            groups = None
        # Off the event loop: the memory census walks jax.live_arrays.
        path = await asyncio.get_running_loop().run_in_executor(
            None, lambda: obs_incident.capture(
                reason, component="serve", memory_groups=groups,
                extra={"source": "http"}))
        return web.json_response({"path": path,
                                  "debounced": path is None})

    async def debug_incidents(request: web.Request) -> web.Response:
        """GET /debug/incidents: list captured bundles (newest first);
        ?name=<bundle> fetches one bundle's full JSON (`rbt incidents`
        drives both)."""
        name = request.query.get("name")
        if name:
            bundle = obs_incident.read_incident(name)
            if bundle is None:
                return web.json_response(
                    {"error": {"message": f"no incident bundle {name!r}"}},
                    status=404)
            return web.json_response(bundle)
        return web.json_response(
            {"incidents": obs_incident.list_incidents(),
             "last_path": obs_incident.MANAGER.last_path()})

    async def completions(request: web.Request) -> web.Response:
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response(
                {"error": {"message": "invalid JSON body"}}, status=400)
        return await _complete(request.app, body, http_request=request)

    def _parse_requests(app_, body, default_priority=None):
        """Shared validation: body -> list[Request] or an error Response.
        default_priority is the X-Priority header value (the body field
        `priority` wins when both are set); None/absent -> standard."""
        # Strict top-level field check: a typo'd constraint field (e.g.
        # `respose_format`) must 400 with the offending names, never
        # silently serve unconstrained output that the client then
        # parses as schema-conforming. `model`/`user` pass through for
        # OpenAI-client compatibility (accepted, unused).
        unknown = sorted(set(body) - _KNOWN_BODY_FIELDS)
        if unknown:
            return None, web.json_response(
                {"error": {"message": "unknown body field(s): "
                                      + ", ".join(unknown),
                           "type": "unknown_field",
                           "fields": unknown}},
                status=400)
        prompt = body.get("prompt")
        if prompt is None:
            return None, web.json_response(
                {"error": {"message": "missing required field: prompt"}},
                status=400)
        prompts = prompt if isinstance(prompt, list) else [prompt]
        if not prompts or not all(isinstance(p, str) for p in prompts):
            return None, web.json_response(
                {"error": {"message": "prompt must be a string or a "
                                      "non-empty list of strings"}},
                status=400)
        try:
            max_tokens = int(body.get("max_tokens", 16))
            temperature = float(body.get("temperature", 1.0))
            top_p = float(body.get("top_p", 1.0))
            top_k = int(body.get("top_k", 0))
            # Per-request wall-clock deadline (seconds); the server-level
            # request_timeout_s is the default. Enforced between decode
            # chunks: expiry finishes with finish_reason "deadline".
            deadline = (float(body["timeout"]) if body.get("timeout")
                        is not None else request_timeout_s)
        except (TypeError, ValueError):
            return None, web.json_response(
                {"error": {"message": "malformed sampling parameters"}},
                status=400)
        if max_tokens < 1:
            return None, web.json_response(
                {"error": {"message": "max_tokens must be >= 1"}},
                status=400)
        if deadline is not None and deadline <= 0:
            return None, web.json_response(
                {"error": {"message": "timeout must be > 0 seconds"}},
                status=400)
        # Multi-tenant LoRA (docs/multi-tenant-lora.md): the adapter
        # this request decodes through. Validated against the engine's
        # pool at submit (pool off / unresolvable artifact -> 400).
        adapter = body.get("adapter")
        if adapter is not None and not isinstance(adapter, str):
            return None, web.json_response(
                {"error": {"message": "adapter must be a string"}},
                status=400)
        # QoS class (docs/paged-kv.md "Host tier and preemption"): body
        # field beats the X-Priority header beats the standard default.
        priority = body.get("priority")
        if priority is None:
            priority = default_priority or "standard"
        if (not isinstance(priority, str)
                or priority.lower() not in PRIORITY_RANK):
            return None, web.json_response(
                {"error": {"message": "priority must be one of "
                                      "interactive, standard, batch"}},
                status=400)
        priority = priority.lower()
        # Grammar-constrained output (docs/structured-output.md): the
        # shape is validated here; the grammar itself compiles (or LRU-
        # hits) at engine submit, where an unsupported construct raises
        # GrammarError -> the existing ValueError -> 400 path with the
        # offending JSON-pointer path in the message.
        response_format = body.get("response_format")
        if response_format is not None and not isinstance(response_format,
                                                          dict):
            return None, web.json_response(
                {"error": {"message": "response_format must be an "
                                      "object"}},
                status=400)

        tok = app_["tokenizer"]
        eos = _eos_id(tok)
        reqs = []
        for p in prompts:
            reqs.append(Request(
                prompt_tokens=_encode(tok, p), max_tokens=max_tokens,
                temperature=temperature, top_k=top_k, top_p=top_p,
                eos_id=eos, deadline_s=deadline, adapter=adapter,
                priority=priority, response_format=response_format))
        return reqs, None

    async def _stream(app_, body, reqs, http_request, chat: bool = False,
                      rid: str = "", tp_out: Optional[str] = None,
                      ) -> web.StreamResponse:
        """SSE streaming (OpenAI `stream: true`): one chunk per text delta,
        then a finish chunk per choice, then `data: [DONE]`. The engine's
        on_token hook fires on its worker thread; call_soon_threadsafe
        bridges into this handler's event loop. Deltas come from an
        incremental decoder: only tokens since the last committed delta are
        re-decoded (a token is not a fixed string — multibyte chars resolve
        only once their continuation lands, signalled by a trailing
        U+FFFD), so per-request cost is O(tokens), not O(tokens^2)."""
        tok = app_["tokenizer"]
        eos = _eos_id(tok)
        loop = asyncio.get_running_loop()
        events: asyncio.Queue = asyncio.Queue()
        for i, r in enumerate(reqs):
            r.on_token = (lambda t, i=i: loop.call_soon_threadsafe(
                events.put_nowait, i))
        worker = app_["worker"]
        app_["requests_total"] += len(reqs)
        try:
            futs = [asyncio.wrap_future(f)
                    for f in worker.submit_many(reqs)]
        except EngineOverloaded as exc:  # draining (503) / queue full (429)
            return _reject(app_, exc, len(reqs))
        except ValueError as exc:
            app_["requests_failed_total"] += len(reqs)
            return web.json_response(
                {"error": {"message": str(exc)}}, status=400)
        _observe_parse(reqs)
        for i, f in enumerate(futs):
            f.add_done_callback(
                lambda fut, i=i: events.put_nowait(("done", i, fut)))

        headers = {
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
            "X-Accel-Buffering": "no",
        }
        if rid:
            headers["X-Request-Id"] = rid
        if tp_out:
            headers["traceparent"] = tp_out
        resp = web.StreamResponse(headers=headers)
        await resp.prepare(http_request)
        rid = (f"chatcmpl-{uuid.uuid4().hex[:24]}" if chat
               else f"cmpl-{uuid.uuid4().hex[:24]}")
        created = int(time.time())
        role_sent = [False] * len(reqs)

        def chunk(i, text=None, finish=None):
            if chat:
                delta = {} if text is None else {"content": text}
                if not role_sent[i]:
                    role_sent[i] = True
                    delta = {"role": "assistant", **delta}
                choice = {"index": i, "delta": delta,
                          "finish_reason": finish}
            else:
                choice = {"index": i, "text": text or "",
                          "finish_reason": finish}
            payload = {"id": rid, "created": created,
                       "model": app_["model_name"],
                       "object": ("chat.completion.chunk" if chat
                                  else "text_completion"),
                       "choices": [choice]}
            return f"data: {json.dumps(payload)}\n\n".encode()

        start = [0] * len(reqs)  # first output token not yet committed

        def next_delta(i, flush=False):
            """Decode tokens committed since last delta; hold back a
            trailing incomplete multibyte sequence unless flushing."""
            ids = reqs[i].output_tokens
            if eos is not None and ids and ids[-1] == eos:
                ids = ids[:-1]
            pending = ids[start[i]:]
            if not pending:
                return None
            text = tok.decode(pending)
            if not flush and text.endswith("�"):
                return None  # wait for the rest of the character
            start[i] = len(ids)
            return text or None

        async def write_delta(i, flush=False):
            """One token event of request i, its synchronous part under a
            span of this thread (decode since the last delta, encode, the
            write call); the first delta written stamps `_first_write`."""
            with fine("api.write", request_id=reqs[i].request_id):
                delta = next_delta(i, flush=flush)
                if delta is None:
                    return
                await resp.write(chunk(i, text=delta))
            req = reqs[i]
            if not req._first_write:
                req._first_write = time.monotonic()
                obs_metrics.REGISTRY.observe(
                    "serve_first_write_seconds",
                    req._first_write - req._first_token,
                    help_text="First token's hand-over by the engine to "
                              "the first SSE write of a delta of the "
                              "request: the way to the event loop, "
                              "detokenise, encode, write (streamed "
                              "requests only).")

        remaining = len(reqs)
        try:
            while remaining:
                ev = await asyncio.wait_for(events.get(), timeout=600)
                if isinstance(ev, tuple):  # ("done", i, future)
                    _, i, fut = ev
                    remaining -= 1
                    exc = fut.exception()
                    if exc is not None:
                        # Mid-stream failure: the HTTP status is already
                        # 200, so signal in-band (OpenAI's error-event
                        # shape) instead of a silent fake "stop".
                        app_["requests_failed_total"] += 1
                        await resp.write(
                            b'data: ' + json.dumps({"error": {
                                "message": str(exc), "index": i,
                            }}).encode() + b"\n\n")
                        continue
                    await write_delta(i, flush=True)
                    app_["tokens_total"] += len(reqs[i].output_tokens)
                    await resp.write(chunk(
                        i, finish=reqs[i].finish_reason or "stop"))
                    continue
                await write_delta(ev)
            await resp.write(b"data: [DONE]\n\n")
            await resp.write_eof()
        except (asyncio.TimeoutError, ConnectionResetError):
            # Client went away (or generation stalled): retrieve the
            # remaining futures' exceptions so asyncio doesn't log
            # "exception was never retrieved", and don't touch the dead
            # transport again.
            app_["requests_failed_total"] += remaining
            for f in futs:
                if f.done():
                    f.exception()
                else:
                    f.add_done_callback(lambda fut: fut.exception())
        return resp

    async def _complete(app_, body, http_request=None) -> web.Response:
        """Request-scope wrapper: resolve/generate the request id, run
        the completion, stamp the id (and child traceparent) on the
        response, and emit one access-log line per HTTP request."""
        rid, tp_out = request_scope(
            http_request.headers if http_request is not None else {})
        t0 = time.monotonic()
        resp = await _complete_scoped(app_, body, http_request, rid, tp_out,
                                      t0)
        if not resp.prepared:  # SSE responses already carry the headers
            resp.headers["X-Request-Id"] = rid
            if tp_out:
                resp.headers["traceparent"] = tp_out
        path = http_request.path if http_request is not None else "-"
        print(f"serve: access {path} rid={rid} "
              f"status={getattr(resp, 'status', 200)} "
              f"dur_ms={(time.monotonic() - t0) * 1000:.1f}", flush=True)
        return resp

    async def _complete_scoped(app_, body, http_request, rid, tp_out,
                               received: float) -> web.Response:
        hdr_priority = (http_request.headers.get("X-Priority")
                        if http_request is not None else None)
        # Handler entry -> hand-over to the engine worker: parse and
        # tokenize, on the event loop's thread.
        with fine("api.submit", request_id=rid) as submit_span:
            reqs, err = _parse_requests(app_, body,
                                        default_priority=hdr_priority)
            if err is not None:
                return err
            submit_span.set(prompt_tokens=sum(len(r.prompt_tokens)
                                              for r in reqs))
            # Thread the id through admission -> engine slot -> prefill/
            # decode spans; multi-prompt bodies get per-prompt suffixes so
            # each choice's spans stay distinguishable.
            for i, r in enumerate(reqs):
                r.request_id = rid if len(reqs) == 1 else f"{rid}/{i}"
                r._received = received
        if options.auto_prefix_chat and body.get("_chat"):
            # Multi-turn chat: this turn's prompt KV becomes the next
            # turn's prefix (the rendered history strictly extends).
            for r in reqs:
                r.auto_prefix = True
        if body.get("stream") and http_request is not None:
            return await _stream(app_, body, reqs, http_request,
                                 chat=bool(body.pop("_chat", False)),
                                 rid=rid, tp_out=tp_out)
        tok = app_["tokenizer"]
        eos = _eos_id(tok)
        worker = app_["worker"]
        app_["requests_total"] += len(reqs)
        try:
            futs = [asyncio.wrap_future(f)
                    for f in worker.submit_many(reqs)]
        except EngineOverloaded as exc:  # draining (503) / queue full (429)
            return _reject(app_, exc, len(reqs))
        except ValueError as exc:  # e.g. prompt exceeds the context window
            app_["requests_failed_total"] += len(reqs)
            return web.json_response(
                {"error": {"message": str(exc)}}, status=400)
        _observe_parse(reqs)
        try:
            done_reqs = await asyncio.wait_for(
                asyncio.gather(*futs), timeout=600)
        except asyncio.TimeoutError:
            app_["requests_failed_total"] += len(reqs)
            return web.json_response(
                {"error": {"message": "generation timed out"}}, status=504)
        except EngineOverloaded as exc:
            # Should be unreachable: submit_many's lock-held backlog check
            # maintains len(queue)+len(pending) <= max_queue, so the
            # worker-side enqueue cannot overflow. Defense-in-depth only:
            # retrieve sibling futures so asyncio doesn't log
            # "exception was never retrieved" for admitted prompts.
            for f in futs:
                f.add_done_callback(lambda fut: fut.cancelled()
                                    or fut.exception())
            return _reject(app_, exc, len(reqs))
        except ValueError as exc:
            app_["requests_failed_total"] += len(reqs)
            return web.json_response(
                {"error": {"message": str(exc)}}, status=400)
        except Exception as exc:  # noqa: BLE001 — engine failure surfaced
            app_["requests_failed_total"] += len(reqs)
            return web.json_response(
                {"error": {"message": f"engine failure: {exc}"}}, status=500)

        choices = []
        prompt_tokens = completion_tokens = 0
        for i, done in enumerate(done_reqs):
            out_ids = done.output_tokens
            if eos is not None and out_ids and out_ids[-1] == eos:
                out_ids = out_ids[:-1]
            choices.append({
                "index": i,
                "text": tok.decode(out_ids),
                "finish_reason": done.finish_reason,
                "logprobs": None,
            })
            prompt_tokens += len(reqs[i].prompt_tokens)
            completion_tokens += len(done.output_tokens)
        app_["tokens_total"] += completion_tokens
        return web.json_response({
            "id": f"cmpl-{uuid.uuid4().hex[:24]}",
            "object": "text_completion",
            "created": int(time.time()),
            "model": app_["model_name"],
            "choices": choices,
            "usage": {
                "prompt_tokens": prompt_tokens,
                "completion_tokens": completion_tokens,
                "total_tokens": prompt_tokens + completion_tokens,
            },
        })

    async def chat_completions(request: web.Request) -> web.Response:
        """Minimal OpenAI-compatible chat endpoint: messages are rendered
        with a plain role-prefix template (model-specific templates come from
        the tokenizer when it has one)."""
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response(
                {"error": {"message": "invalid JSON body"}}, status=400)
        messages = body.get("messages")
        if not isinstance(messages, list) or not messages:
            return web.json_response(
                {"error": {"message": "missing required field: messages"}},
                status=400)
        tok = request.app["tokenizer"]
        if hasattr(tok, "apply_chat_template"):
            try:
                prompt = tok.apply_chat_template(
                    messages, tokenize=False, add_generation_prompt=True)
            except Exception:
                prompt = None
        else:
            prompt = None
        if prompt is None:
            parts = [f"{m.get('role', 'user')}: {m.get('content', '')}"
                     for m in messages]
            prompt = "\n".join(parts) + "\nassistant:"
        body["prompt"] = prompt
        body["_chat"] = True
        resp = await _complete(request.app, body, http_request=request)
        if not isinstance(resp, web.Response):
            return resp  # SSE stream already written
        if resp.status != 200:
            return resp
        payload = json.loads(resp.body)
        payload["object"] = "chat.completion"
        payload["choices"] = [{
            "index": c["index"],
            "message": {"role": "assistant", "content": c["text"]},
            "finish_reason": c["finish_reason"],
        } for c in payload["choices"]]
        out = web.json_response(payload)
        # Preserve the request scope across the payload rewrite.
        for header in ("X-Request-Id", "traceparent"):
            if header in resp.headers:
                out.headers[header] = resp.headers[header]
        return out

    async def register_prefix(request: web.Request) -> web.Response:
        """Register a shared prompt prefix (e.g. a deployment's chat
        system prompt) so subsequent requests that start with it prefill
        only their suffix. Body: {"prompt": "..."} (tokenized like
        /v1/completions) or {"tokens": [...]}. Returns the cached prefix
        length (0 = too short to cache)."""
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response(
                {"error": {"message": "invalid JSON body"}}, status=400)
        tokens = body.get("tokens")
        if tokens is None:
            prompt = body.get("prompt")
            if not isinstance(prompt, str):
                return web.json_response(
                    {"error": {"message": "provide prompt (string) or "
                                          "tokens (list of ints)"}},
                    status=400)
            tokens = _encode(request.app["tokenizer"], prompt)
        if not (isinstance(tokens, list)
                and all(isinstance(t, int) for t in tokens)):
            return web.json_response(
                {"error": {"message": "tokens must be a list of ints"}},
                status=400)
        fut = worker.register_prefix(tokens)
        try:
            plen = await asyncio.wait_for(asyncio.wrap_future(fut), 600)
        except asyncio.TimeoutError:
            return web.json_response(
                {"error": {"message": "prefix registration timed out"}},
                status=504)
        except RuntimeError as exc:
            return web.json_response(
                {"error": {"message": str(exc)}}, status=503)
        return web.json_response({"cached_prefix_len": plen})

    app.router.add_get("/", root)
    app.router.add_get("/healthz", healthz)
    app.router.add_get("/metrics", metrics)
    app.router.add_post("/debug/profile", debug_profile)
    app.router.add_get("/debug/memory", debug_memory)
    app.router.add_get("/debug/programs", debug_programs)
    app.router.add_get("/debug/flight", debug_flight)
    app.router.add_post("/debug/incident", debug_incident)
    app.router.add_get("/debug/incidents", debug_incidents)
    app.router.add_post("/v1/completions", completions)
    app.router.add_post("/v1/chat/completions", chat_completions)
    app.router.add_post("/v1/prefix", register_prefix)

    async def on_cleanup(app):
        # Graceful drain (SIGTERM path): stop admitting, let in-flight
        # slots finish, then stop the worker thread. Run off the event
        # loop so SSE streams can keep flushing while we wait.
        print("serve: draining (no new admissions; finishing in-flight "
              "requests)", flush=True)
        drained = await asyncio.get_running_loop().run_in_executor(
            None, worker.drain, options.drain_timeout_s)
        if not drained:
            print(f"serve: drain timed out after "
                  f"{options.drain_timeout_s}s; "
                  "abandoning remaining requests", flush=True)
        # stop() joins the worker thread (up to 5 s) — off the loop too,
        # or the join stalls the final SSE flushes it is waiting behind
        # (`rbt check` async-blocking caught the inline version).
        await asyncio.get_running_loop().run_in_executor(None, worker.stop)

    app.on_cleanup.append(on_cleanup)
    return app


def main() -> int:
    import jax

    from runbooks_tpu.obs.trace import STARTUP, process_age_s

    # Set-up phases (obs/trace.py PhaseSeconds): interpreter start to
    # here is this module's imports; the engine's warm-up adds warmup.*.
    age = process_age_s()
    if age is not None:
        STARTUP.add("startup.imports", age)
    params = contract.load_params()
    # A bad spec fails here, before the backend and the weights.
    options = ServeOptions.from_params(params)
    # Multi-host slices: form the jax.distributed runtime before any JAX use.
    from runbooks_tpu.parallel.distributed import initialize

    with STARTUP.timed("startup.backend"):
        initialize()
        jax.devices()          # the first touch of the backend
    # Persistent compile cache (placed from outside: utils/jax_cache.py): a
    # restarted serve worker skips the prefill/decode bucket recompiles.
    from runbooks_tpu.utils.jax_cache import enable_compilation_cache

    cache_dir = enable_compilation_cache()

    # mesh_* params select sharded serving (e.g. mesh_tensor: 8 for TP).
    mesh = None
    from runbooks_tpu.parallel.mesh import MeshConfig, make_mesh

    mesh_keys = {f.name for f in dataclasses.fields(MeshConfig)}
    mesh_args = {k[len("mesh_"):]: int(v) for k, v in params.items()
                 if k.startswith("mesh_") and k[len("mesh_"):] in mesh_keys}
    with STARTUP.timed("startup.weights"):
        if mesh_args:
            mesh = make_mesh(MeshConfig(**mesh_args))
        cfg, model_params = load_model(params, mesh)
        jax.block_until_ready(model_params)   # made AND placed
    with STARTUP.timed("startup.tokenizer"):
        tokenizer = load_tokenizer(params.get("tokenizer"))
    # What this server executes on — one line, before warmup compiles, so
    # any log says what it ran on.
    from runbooks_tpu.models.transformer import (
        FLASH_CACHED_PREFILL_MIN_Q,
        use_flash_cached_prefill,
    )
    from runbooks_tpu.utils.hw import device_identity

    print(json.dumps({
        "startup": "serve", "model": cfg.name, "mesh": {},
        **device_identity(mesh), "compile_cache_dir": cache_dir,
        # Prefill's attention as resolved; decode (q_len 1) is always XLA.
        "attention_impl": ("flash" if use_flash_cached_prefill(
            cfg, FLASH_CACHED_PREFILL_MIN_Q) else "xla"),
        "phases": STARTUP.snapshot(),
    }), flush=True)

    t_engine = time.perf_counter()
    # Handed over, not shared: this frame lives as long as the server.
    weights = [model_params]
    del model_params
    app = create_server(cfg, weights.pop(), tokenizer, mesh=mesh,
                        **dataclasses.asdict(options))
    # Engine construction (KV cache allocation, jit wrappers): what
    # create_server took outside the warm-up it ran.
    STARTUP.add("startup.engine", time.perf_counter() - t_engine
                - (app["worker"].engine.warmup_census or {}).get(
                    "warmup_seconds", 0.0))
    port = int(params.get("port", contract.SERVE_PORT))

    # Graceful drain on SIGTERM (docs/fault-tolerance.md): run_app's
    # default handle_signals=True registers SIGTERM/SIGINT to raise
    # GracefulExit, which tears the site down and runs on_cleanup — our
    # cleanup drains the engine worker (stop admitting, finish in-flight)
    # before the process exits 0. No custom handler needed; installing one
    # here would just be overwritten when run_app sets up its loop.
    web.run_app(app, port=port, print=lambda *a: None)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
