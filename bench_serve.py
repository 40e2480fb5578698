"""Serving benchmark: TTFT percentiles + decode throughput.

BASELINE.json tracks "Server p50 TTFT" as a headline serving metric; this
bench measures it against the in-process engine (no HTTP overhead): N
concurrent requests through the continuous-batching worker, reporting TTFT
p50/p90 (time to first generated token) and aggregate decode tokens/sec.

Same outer/inner structure as bench.py (see benchkit.py): the stdlib-only
orchestrator subprocesses the real bench with a timeout and prints ONE JSON
line; no TPU means a non-zero exit (RBT_BENCH_FORCE_CPU=1 asks for a CPU
functional run). Knobs: RBT_BENCH_MODEL /
RBT_BENCH_SLOTS / RBT_BENCH_REQUESTS / RBT_BENCH_PROMPT / RBT_BENCH_MAXTOK.

RBT_BENCH_QUANTIZE={none,int8,int4} quantizes the weights (blockwise
weight-only, ops/quantization.py) AND switches the KV cache to int8 +
per-slot-per-head scales — the serving fast path. The JSON reports
weight_bytes and kv_cache_bytes next to decode tok/s and TTFT so the
bandwidth-for-throughput trade is auditable (decode is memory-bound:
fewer bytes streamed per token = more tok/s at equal batch).

RBT_BENCH_PAGED=1 runs the paged-KV capacity axis instead
(docs/paged-kv.md): a shared-system-prompt workload against the dense
slot pool, then against the paged engine sized to the SAME (or fewer)
KV HBM bytes, reporting peak concurrent sequences and decode tok/s for
both plus the radix-sharing counters. Acceptance: the paged engine
sustains >= 2x the dense concurrency at equal KV HBM
(value = concurrency ratio, vs_baseline = ratio / 2) with zero
unexpected XLA compiles across its steady loop.

RBT_BENCH_ROUTER=1 runs the multi-replica routing axis
(docs/serving-dataplane.md): the SAME multi-tenant shared-prefix
workload (P distinct system prompts x M requests each, in waves)
against 3 paged replicas routed randomly (what a k8s Service does) vs
prefix-aware (serve/gateway.py's Router with per-replica shadow radix
indexes), reporting per-replica `serve_prefix_pages_reused_total` per
routed request for both. Acceptance: prefix-aware routing reuses
>= 1.5x the pages per request (value = uplift, vs_baseline =
uplift / 1.5) with zero unexpected XLA compiles throughout.

RBT_BENCH_MESH_SERVE=1 runs the sharded-serving-mesh axis
(docs/tensor-parallel-performance.md "Sharded serving"): the same
shared-prefix paged workload on a single device, then on a
mesh_tensor=K serving mesh (K from RBT_BENCH_MESH_TENSOR, default 2 —
benchkit virtualizes that many devices on a forced-CPU run), reporting
decode tok/s for both AND the max-fit model multiplier: per-chip
weights+KV bytes single-device over per-chip bytes under the mesh —
i.e. how much more model one chip's HBM bound admits when the replica
shards. Acceptance at K=2: >= 1.6x (weights and the kv-head-sharded
pool split ~2x; replicated norms/host state cap it below 2), value =
multiplier, vs_baseline = multiplier / 1.6, forced to 0 on any
unexpected compile in the mesh steady loop. Greedy outputs vs
single-device are reported (greedy_token_mismatches) but not gated:
at bf16 serving precision GSPMD's sharded partial-sum order can flip
an argmax tie — byte-exact parity is asserted where it is a theorem,
in tests/test_mesh_serving.py under pinned exact precision.

RBT_BENCH_LORA=1 runs the multi-tenant LoRA density axis
(docs/multi-tenant-lora.md): N adapters on ONE pooled engine vs N
dedicated merged-weights engines serving the same workload, reporting
tenants-per-HBM-byte (weights + KV + pool vs N x weights + KV) and
decode tok/s for both, with greedy token parity asserted inline (f32 —
the runtime delta equals the load-time fold exactly) and the pool sized
below N so the steady loop swaps adapters under the compile sentinel.
Acceptance: >= 2x density at 4 tenants (value = uplift, vs_baseline =
uplift / 2, zeroed on any unexpected compile).

RBT_BENCH_KV_TIER=1 runs the host-KV-tier + QoS axis
(docs/paged-kv.md "Host tier and preemption"): first the returning-
session TTFT comparison — the same shared-prefix prompt admitted with
its prefix fully dropped (recompute) vs host-resident (swap-in), token
outputs asserted identical — then an overload run: a flood of batch
requests saturates every slot while interactive requests arrive, so
the engine preempts batch slots to host-backed radix state and resumes
them later. Reports TTFT p50 for both admission paths, interactive
p50/max TTFT under overload, preemption/resume counters, and batch
token parity against a quiet reference run. Acceptance: swap-in TTFT
>= 1.1x faster than recompute (value = speedup, vs_baseline =
speedup / 1.1), forced to 0 on any unexpected compile, any token
mismatch, or an overload run that never preempted.

RBT_BENCH_SPEC=1 runs the speculative-decoding axis
(docs/speculative-decoding.md): greedy decode tok/s per accept-rate
bucket, speculation on vs off at EQUAL batch. The spec-off pass
records each request's greedy output (deterministic); the spec-on
passes replay the same requests through the REAL batched verify path
with an oracle drafter whose per-token accuracy is tuned to land the
measured accept rate near ~0% / ~50% / ~90% (the n-gram hit-rate
knob synthesized deterministically — random-init bench weights have
no learnable repetition for a real index to exploit, and the verify
forward, not the draft source, is what costs and what this axis
measures). Every spec-on pass asserts token-for-token output parity
against the recorded spec-off outputs — a corrupted draft can change
throughput, never content. A final pass runs the real n-gram drafter
on a self-repeating prompt and reports its measured accept rate.
Acceptance: >= 1.5x decode tok/s at the high-accept bucket
(value = speedup, vs_baseline = speedup / 1.5) with zero unexpected
XLA compiles across every steady loop (gate: vs_baseline forced to 0
on any unexpected compile).

RBT_BENCH_GRAMMAR=1 runs the grammar-constrained decoding axis
(docs/structured-output.md): the SAME workload on one grammar-on
engine, first unconstrained (all-allow mask rows — the identity
operand) then constrained by a bounded JSON schema, reporting decode
tok/s for both plus the parse rate over constrained completions (every
output must finish grammar_complete and json.loads). The mask apply is
one elementwise `where` per dispatch and the masked program variants
REPLACE the plain set, so the constrained pass must neither compile
anything new nor fall off the throughput cliff. Acceptance:
constrained >= 0.7x unconstrained decode tok/s (value = ratio,
vs_baseline = ratio / 0.7), forced to 0 on any unexpected compile or
any constrained output that fails to parse (parse rate < 100%).
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import threading
import time

import benchkit


def paged_inner() -> None:
    """Dense-vs-paged capacity at equal KV HBM on a shared-prefix load.

    Both engines serve the SAME workload — n_requests greedy requests
    whose prompts share a prefix_len-token system prompt — driven by a
    direct step loop so peak concurrency is observable. The paged pool
    is sized to the dense pool's byte budget (num_pages = dense KV bytes
    // bytes-per-page, i.e. never MORE HBM), so the concurrency ratio is
    pure paging + radix sharing, not a bigger cache."""
    import jax
    import numpy as np

    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.models.transformer import init_params
    from runbooks_tpu.obs import device as obs_device
    from runbooks_tpu.serve.engine import InferenceEngine, Request
    from runbooks_tpu.serve.paging import PagedInferenceEngine, PagePool

    device, on_tpu = benchkit.bench_device()
    model = os.environ.get("RBT_BENCH_MODEL",
                           "bench-410m" if on_tpu else "debug")
    dense_slots = int(os.environ.get("RBT_BENCH_SLOTS", 4))
    max_seq = int(os.environ.get("RBT_BENCH_MAXSEQ", 128))
    page_size = int(os.environ.get("RBT_BENCH_PAGE_SIZE", 16))
    prompt_len = int(os.environ.get("RBT_BENCH_PROMPT", 64))
    prefix_len = int(os.environ.get("RBT_BENCH_PREFIX", 48))
    max_tokens = int(os.environ.get("RBT_BENCH_MAXTOK", 16))
    # Enough load to saturate either pool; the paged slot count is an
    # upper bound, not the capacity claim — pages gate admission.
    paged_slots = 4 * dense_slots
    n_requests = paged_slots

    cfg = get_config(model, param_dtype="bfloat16")
    params = jax.jit(lambda r: init_params(cfg, r))(jax.random.key(0))
    rng = np.random.default_rng(0)
    shared = rng.integers(1, cfg.vocab_size, prefix_len).tolist()
    prompts = [shared + rng.integers(
        1, cfg.vocab_size, prompt_len - prefix_len).tolist()
        for _ in range(n_requests)]

    def run_workload(engine):
        reqs = [Request(prompt_tokens=list(p), max_tokens=max_tokens,
                        temperature=0.0) for p in prompts]
        for r in reqs:
            engine.submit(r)
        peak = 0
        t0 = time.perf_counter()
        for _ in range(200000):
            engine.step()
            peak = max(peak, int(engine.active.sum()))
            if all(r.finished for r in reqs):
                break
        else:
            raise RuntimeError("paged bench workload did not converge")
        wall = time.perf_counter() - t0
        toks = sum(len(r.output_tokens) for r in reqs)
        return reqs, peak, wall, toks

    # -- dense baseline ------------------------------------------------
    dense = InferenceEngine(cfg, params, max_slots=dense_slots,
                            max_seq_len=max_seq, max_queue=n_requests)
    dense_kv_bytes = sum(
        x.nbytes for x in (dense.cache.k, dense.cache.v,
                           dense.cache.k_scale, dense.cache.v_scale)
        if x is not None)
    # Register BEFORE warmup: registration compiles the prefix builder
    # + splice shapes, and pre-steady they are ordinary startup compiles
    # (a post-warmup registration is the documented cold-prefix stall —
    # docs/troubleshooting.md). warmup() keeps the prefix cache.
    dense.register_prefix(shared)  # the single-prefix auto_prefix path
    dense.warmup()
    _, dense_peak, dense_wall, dense_toks = run_workload(dense)
    # Drop the dense engine's process-wide steady claim before building
    # the paged engine: its pool allocation is a legitimate startup
    # compile, not a serving stall.
    dense.release_steady()
    del dense

    # -- paged at the same byte budget ---------------------------------
    probe = PagePool.create(cfg, 1, page_size)
    bytes_per_page = probe.nbytes // 2   # 1 allocatable + 1 trash page
    # -1: the pool allocates num_pages + 1 (trash page); counting it
    # keeps paged_kv_bytes <= dense_kv_bytes, so the concurrency ratio
    # can never be bought with a bigger cache.
    num_pages = dense_kv_bytes // bytes_per_page - 1
    paged = PagedInferenceEngine(
        cfg, params, max_slots=paged_slots, max_seq_len=max_seq,
        page_size=page_size, num_pages=int(num_pages),
        max_queue=n_requests)
    paged_kv_bytes = paged.cache.nbytes
    paged.warmup()
    paged.register_prefix(shared)  # seeds the radix tree
    unexpected_before = obs_device.SENTINEL.unexpected
    _, paged_peak, paged_wall, paged_toks = run_workload(paged)
    unexpected = obs_device.SENTINEL.unexpected - unexpected_before
    occ = paged.kv_occupancy()

    ratio = paged_peak / max(dense_peak, 1)
    benchkit.emit({
        "metric": f"{model} paged KV concurrency vs dense at equal KV "
                  f"HBM ({n_requests} reqs, prompt {prompt_len}, "
                  f"prefix {prefix_len}, page_size {page_size})",
        "value": round(ratio, 2),
        "unit": "x",
        # Acceptance is >= 2x concurrent sequences at equal KV HBM
        # (docs/paged-kv.md), so > 1.0 here means the claim holds.
        "vs_baseline": round(ratio / 2.0, 4),
        "dense_peak_concurrent": dense_peak,
        "paged_peak_concurrent": paged_peak,
        "dense_kv_bytes": dense_kv_bytes,
        "paged_kv_bytes": paged_kv_bytes,
        "num_pages": int(num_pages),
        "dense_decode_tokens_per_sec": round(dense_toks / dense_wall, 1),
        "paged_decode_tokens_per_sec": round(paged_toks / paged_wall, 1),
        "prefix_pages_reused_total": occ["pages_reused_total"],
        "pages_shared": occ["pages_shared"],
        "pages_evicted_total": occ["pages_evicted_total"],
        "unexpected_compiles_steady_loop": unexpected,
    })


def kv_tier_inner() -> None:
    """Host KV tier + QoS preemption (docs/paged-kv.md "Host tier and
    preemption").

    Phase 1 — returning-session TTFT: the same shared-prefix prompts
    admitted twice, once with the prefix fully dropped from both tiers
    (full recompute prefill) and once host-resident (swap-in rides the
    radix-match admission path, paying a device_put per page instead of
    the prefill). Greedy outputs are asserted identical between arms —
    the swap tier buys latency, never content.

    Phase 2 — graceful degradation under overload: batch-class requests
    saturate every slot, interactive requests keep arriving; the engine
    preempts batch slots (pages adopt into the HBM/host hierarchy) and
    resumes them loss-free. Batch outputs are asserted identical to a
    quiet sequential reference run."""
    import jax
    import numpy as np

    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.models.transformer import init_params
    from runbooks_tpu.obs import device as obs_device
    from runbooks_tpu.serve.engine import Request
    from runbooks_tpu.serve.paging import PagedInferenceEngine

    device, on_tpu = benchkit.bench_device()
    model = os.environ.get("RBT_BENCH_MODEL",
                           "bench-410m" if on_tpu else "debug")
    slots = int(os.environ.get("RBT_BENCH_SLOTS", 4))
    max_seq = int(os.environ.get("RBT_BENCH_MAXSEQ", 512))
    page_size = int(os.environ.get("RBT_BENCH_PAGE_SIZE", 16))
    # A long shared prefix is the workload this tier exists for (a
    # returning session's history): recompute pays a 240-token prefill,
    # swap-in pays 15 page device_puts + a 16-token suffix.
    prompt_len = int(os.environ.get("RBT_BENCH_PROMPT", 256))
    prefix_len = int(os.environ.get("RBT_BENCH_PREFIX", 240))
    max_tokens = int(os.environ.get("RBT_BENCH_MAXTOK", 16))
    num_pages = int(os.environ.get("RBT_BENCH_PAGES", 96))
    host_pages = int(os.environ.get("RBT_BENCH_HOST_PAGES", 128))
    trials = int(os.environ.get("RBT_BENCH_TRIALS", 5))
    # Small decode chunks keep batch slots mid-flight across several
    # step boundaries, so the overload phase actually preempts.
    chunk = int(os.environ.get("RBT_BENCH_CHUNK", 4))

    cfg = get_config(model, param_dtype="bfloat16")
    params = jax.jit(lambda r: init_params(cfg, r))(jax.random.key(0))
    rng = np.random.default_rng(0)
    shared = rng.integers(1, cfg.vocab_size, prefix_len).tolist()

    engine = PagedInferenceEngine(
        cfg, params, max_slots=slots, max_seq_len=max_seq,
        page_size=page_size, num_pages=num_pages,
        kv_host_pages=host_pages, preemption="swap", max_queue=64,
        decode_chunk=chunk)
    engine.warmup()
    engine.register_prefix(shared)
    unexpected_before = obs_device.SENTINEL.unexpected

    def ttft_once(prompt, tokens):
        r = Request(prompt_tokens=list(prompt), max_tokens=tokens,
                    temperature=0.0)
        engine.submit(r)
        t0 = time.perf_counter()
        ttft = None
        for _ in range(200000):
            engine.step()
            if ttft is None and r.output_tokens:
                ttft = time.perf_counter() - t0
            if r.finished:
                return ttft, list(r.output_tokens)
        raise RuntimeError("kv-tier bench request did not converge")

    # -- phase 1: recompute vs swap-in TTFT ----------------------------
    suffixes = [rng.integers(1, cfg.vocab_size,
                             prompt_len - prefix_len).tolist()
                for _ in range(trials)]
    recompute_ttfts, recompute_outs = [], []
    for sfx in suffixes:
        # drop the prefix from BOTH tiers: this admission recomputes
        # the full prompt_len prefill
        engine.pager.radix.evict(10 ** 9)
        engine.pager.radix.evict_host(10 ** 9)
        t, out = ttft_once(shared + sfx, max_tokens)
        recompute_ttfts.append(t)
        recompute_outs.append(out)
    swapin_ttfts = []
    token_parity = True
    engine.register_prefix(shared)
    for sfx, ref in zip(suffixes, recompute_outs):
        # push every HBM-resident page (the prefix + the previous
        # trial's adoption) to the host tier: this admission's radix
        # match lands on host nodes and swaps them back in
        engine.pager.radix.evict(10 ** 9)
        t, out = ttft_once(shared + sfx, max_tokens)
        swapin_ttfts.append(t)
        token_parity = token_parity and out == ref
    occ_mid = engine.kv_occupancy()

    # -- phase 2: overload — batch floods, interactive preempts --------
    n_batch = 2 * slots
    n_inter = max(2, slots // 2)
    batch_prompts = [rng.integers(1, cfg.vocab_size, 32).tolist()
                     for _ in range(n_batch)]
    inter_prompts = [rng.integers(1, cfg.vocab_size, 32).tolist()
                     for _ in range(n_inter)]
    # quiet sequential reference: the loss-free-resume claim is token
    # identity between an undisturbed run and the preempted one
    ref_outs = [ttft_once(p, 24)[1] for p in batch_prompts]
    ref_inter = [ttft_once(p, 8)[1] for p in inter_prompts]
    preempt_before = engine.preemptions
    batch_reqs = [Request(prompt_tokens=list(p), max_tokens=24,
                          temperature=0.0, priority="batch")
                  for p in batch_prompts]
    inter_reqs = [Request(prompt_tokens=list(p), max_tokens=8,
                          temperature=0.0, priority="interactive")
                  for p in inter_prompts]
    for r in batch_reqs:
        engine.submit(r)
    inter_t0, inter_ttft = {}, {}
    pending = list(inter_reqs)
    steps = 0
    while engine.has_work() or pending:
        if pending and steps >= 2 and steps % 3 == 0:
            r = pending.pop(0)
            engine.submit(r)
            inter_t0[r.request_id] = time.perf_counter()
        engine.step()
        now = time.perf_counter()
        for r in inter_reqs:
            if (r.request_id in inter_t0 and r.output_tokens
                    and r.request_id not in inter_ttft):
                inter_ttft[r.request_id] = now - inter_t0[r.request_id]
        steps += 1
        if steps > 200000:
            raise RuntimeError("kv-tier overload run did not converge")
    preemptions = engine.preemptions - preempt_before
    for r, ref in zip(batch_reqs, ref_outs):
        token_parity = token_parity and list(r.output_tokens) == ref
    for r, ref in zip(inter_reqs, ref_inter):
        token_parity = token_parity and list(r.output_tokens) == ref
    unexpected = obs_device.SENTINEL.unexpected - unexpected_before
    occ = engine.kv_occupancy()

    recompute_p50 = statistics.median(recompute_ttfts)
    swapin_p50 = statistics.median(swapin_ttfts)
    inter_ts = sorted(inter_ttft.values())
    speedup = recompute_p50 / max(swapin_p50, 1e-9)
    gate = (1.0 if not unexpected and token_parity and preemptions >= 1
            else 0.0)
    benchkit.emit({
        "metric": f"{model} returning-session TTFT, host-tier swap-in "
                  f"vs full recompute (prefix {prefix_len}, prompt "
                  f"{prompt_len}, page_size {page_size}, "
                  f"{trials} trials)",
        "value": round(speedup, 2),
        "unit": "x",
        # Acceptance: swap-in is measurably faster than recomputing the
        # prefix (>= 1.1x, docs/paged-kv.md); forced to 0 on unexpected
        # compiles, any token divergence, or an overload phase that
        # never exercised preemption.
        "vs_baseline": round(speedup / 1.1 * gate, 4),
        "recompute_ttft_p50_ms": round(recompute_p50 * 1e3, 2),
        "swapin_ttft_p50_ms": round(swapin_p50 * 1e3, 2),
        "swap_in_pages_total": occ["swap_in_pages_total"],
        "swap_out_pages_total": occ["swap_out_pages_total"],
        "swap_dropped_pages_total": occ["swap_dropped_pages_total"],
        "host_pages_used_mid": occ_mid["host_pages_used"],
        "overload_preemptions": preemptions,
        "overload_resumed": engine.preempted_resumed,
        "interactive_ttft_p50_ms": round(
            statistics.median(inter_ts) * 1e3, 2) if inter_ts else None,
        "interactive_ttft_max_ms": round(
            inter_ts[-1] * 1e3, 2) if inter_ts else None,
        "token_parity": token_parity,
        "unexpected_compiles_steady_loop": unexpected,
    })


def mesh_serve_inner() -> None:
    """Sharded serving mesh: decode tok/s + max-fit multiplier,
    mesh_tensor=K vs single device on the shared-prefix paged workload.

    The max-fit multiplier is the HBM claim made concrete: per-chip
    bytes (weights + KV pool, measured from actual shard shapes) on one
    device divided by per-chip bytes under the mesh. That ratio is how
    much bigger a model the same chip HBM serves when one replica spans
    K chips — the reason the mesh exists."""
    import jax
    import numpy as np

    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.models.transformer import init_params
    from runbooks_tpu.obs import device as obs_device
    from runbooks_tpu.parallel.mesh import MeshConfig, make_mesh
    from runbooks_tpu.serve.engine import Request
    from runbooks_tpu.serve.paging import PagedInferenceEngine

    device, on_tpu = benchkit.bench_device()
    model = os.environ.get("RBT_BENCH_MODEL",
                           "bench-410m" if on_tpu else "debug")
    tp = int(os.environ.get("RBT_BENCH_MESH_TENSOR", 2))
    if len(jax.devices()) < tp:
        raise RuntimeError(
            f"mesh serve axis needs {tp} devices, have "
            f"{len(jax.devices())} (a forced-CPU run through benchkit "
            f"sets --xla_force_host_platform_device_count from "
            f"RBT_BENCH_MESH_TENSOR)")
    slots = int(os.environ.get("RBT_BENCH_SLOTS", 4))
    max_seq = int(os.environ.get("RBT_BENCH_MAXSEQ", 128))
    page_size = int(os.environ.get("RBT_BENCH_PAGE_SIZE", 16))
    prompt_len = int(os.environ.get("RBT_BENCH_PROMPT", 64))
    prefix_len = int(os.environ.get("RBT_BENCH_PREFIX", 48))
    max_tokens = int(os.environ.get("RBT_BENCH_MAXTOK", 16))
    n_requests = 2 * slots

    cfg = get_config(model, param_dtype="bfloat16")
    params = jax.jit(lambda r: init_params(cfg, r))(jax.random.key(0))
    rng = np.random.default_rng(0)
    shared = rng.integers(1, cfg.vocab_size, prefix_len).tolist()
    prompts = [shared + rng.integers(
        1, cfg.vocab_size, prompt_len - prefix_len).tolist()
        for _ in range(n_requests)]

    def run(mesh):
        engine = PagedInferenceEngine(
            cfg, params, max_slots=slots, max_seq_len=max_seq,
            page_size=page_size, max_queue=n_requests, mesh=mesh)
        engine.register_prefix(shared)
        engine.warmup()
        reqs = [Request(prompt_tokens=list(p), max_tokens=max_tokens,
                        temperature=0.0) for p in prompts]
        for r in reqs:
            engine.submit(r)
        unexpected_before = obs_device.SENTINEL.unexpected
        t0 = time.perf_counter()
        for _ in range(200000):
            engine.step()
            if all(r.finished for r in reqs):
                break
        else:
            raise RuntimeError("mesh bench workload did not converge")
        wall = time.perf_counter() - t0
        unexpected = obs_device.SENTINEL.unexpected - unexpected_before
        toks = sum(len(r.output_tokens) for r in reqs)
        weights_local = sum(
            obs_device.shard_local_nbytes(a)
            for a in jax.tree.leaves(engine.params))
        occ = engine.kv_occupancy()
        per_chip = weights_local + occ["kv_pool_bytes_per_device"]
        outputs = [list(r.output_tokens) for r in reqs]
        engine.release_steady()
        return outputs, toks / wall, per_chip, unexpected

    single_out, single_tps, single_chip_bytes, single_unexpected = \
        run(None)
    mesh = make_mesh(MeshConfig(data=1, fsdp=1, tensor=tp))
    mesh_out, mesh_tps, mesh_chip_bytes, mesh_unexpected = run(mesh)

    # Informational, not gated: at bf16 the sharded partial-sum order
    # can flip an argmax tie. The byte-exact parity claim lives in
    # tests/test_mesh_serving.py (pinned exact matmul precision).
    mismatches = sum(a != b for a, b in zip(single_out, mesh_out))
    multiplier = single_chip_bytes / mesh_chip_bytes
    gated = mesh_unexpected > 0
    benchkit.emit({
        "metric": f"{model} mesh_tensor={tp} serving max-fit model "
                  f"footprint vs single chip ({n_requests} reqs, "
                  f"prompt {prompt_len}, page_size {page_size})",
        "value": round(multiplier, 2),
        "unit": "x",
        # Acceptance >= 1.6x at tensor=2 (see module docstring), so
        # > 1.0 here means the claim holds.
        "vs_baseline": 0.0 if gated else round(multiplier / 1.6, 4),
        "mesh_tensor": tp,
        "single_decode_tokens_per_sec": round(single_tps, 1),
        "mesh_decode_tokens_per_sec": round(mesh_tps, 1),
        "single_per_chip_bytes": int(single_chip_bytes),
        "mesh_per_chip_bytes": int(mesh_chip_bytes),
        "greedy_token_mismatches": mismatches,
        "unexpected_compiles_steady_loop": (single_unexpected
                                            + mesh_unexpected),
    })


def lora_inner() -> None:
    """Multi-tenant LoRA density: N adapters on ONE pooled engine vs N
    dedicated merged-weights engines (docs/multi-tenant-lora.md).

    Both sides serve the SAME workload — R greedy requests per tenant —
    and the pooled outputs are asserted token-for-token identical to the
    dedicated engines' inline (float32, where the runtime delta and the
    load-time fold agree exactly; a corrupted gather can change
    throughput, never content). The headline number is tenant density at
    equal service: serving N tenants costs the dedicated fleet
    N x (weights + KV) bytes and the pooled engine 1 x (weights + KV)
    + pool bytes — the uplift is bytes_dedicated / bytes_pooled
    (acceptance >= 2x at N=4).

    Two pooled phases: (A) pool = N — every tenant resident after its
    first load; the density + decode tok/s numbers, measuring the
    grouped-matmul cost, not artifact IO. (B) pool = N/2 — the steady
    ADAPTER-SWAPPING loop (every admission churns lanes: loads,
    evictions, zero residency hits), whose whole point is the compile
    sentinel staying silent; its tok/s is reported separately as the
    thrash floor (artifact reads land in the decode loop — the
    adapter-miss latency docs/troubleshooting.md triages)."""

    import jax
    import numpy as np

    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.models.transformer import init_params
    from runbooks_tpu.obs import device as obs_device
    from runbooks_tpu.serve.engine import InferenceEngine, Request
    from runbooks_tpu.serve.lora_pool import save_adapter
    from runbooks_tpu.train.lora import LoraConfig, apply_lora, init_lora

    device, on_tpu = benchkit.bench_device()
    model = os.environ.get("RBT_BENCH_MODEL",
                           "bench-410m" if on_tpu else "debug")
    n_tenants = int(os.environ.get("RBT_BENCH_TENANTS", 4))
    pool_size = int(os.environ.get("RBT_BENCH_ADAPTER_POOL",
                                   max(2, n_tenants // 2)))
    slots = int(os.environ.get("RBT_BENCH_SLOTS", 4))
    max_seq = int(os.environ.get("RBT_BENCH_MAXSEQ", 128))
    prompt_len = int(os.environ.get("RBT_BENCH_PROMPT", 32))
    max_tokens = int(os.environ.get("RBT_BENCH_MAXTOK", 16))
    per_tenant = int(os.environ.get("RBT_BENCH_REQUESTS", 3))
    rank = int(os.environ.get("RBT_BENCH_LORA_RANK", 8))

    # float32 end to end: the inline parity assert compares the pooled
    # runtime delta against merged-weights engines, exact at f32.
    cfg = get_config(model, dtype="float32", param_dtype="float32",
                     adapter_pool=pool_size, lora_rank=rank)
    params = jax.jit(lambda r: init_params(cfg, r))(jax.random.key(0))
    weight_bytes = sum(x.nbytes for x in jax.tree.leaves(params))

    tmp = benchkit.work_dir("lora")
    rng = np.random.default_rng(0)
    adapter_paths, merged = [], []
    for i in range(n_tenants):
        lcfg = LoraConfig(rank=rank, alpha=2.0 * rank)
        lora = init_lora(params, lcfg, jax.random.key(100 + i))
        lora = jax.tree.map(
            lambda x, i=i: x + 0.02 * jax.random.normal(
                jax.random.key(200 + i), x.shape, x.dtype), lora)
        path = os.path.join(tmp, f"tenant{i}")
        save_adapter(path, lora, rank=rank, alpha=2.0 * rank)
        adapter_paths.append(path)
        merged.append(apply_lora(params, lora, lcfg))

    prompts = {i: [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
                   for _ in range(per_tenant)]
               for i in range(n_tenants)}

    def drive(engine, reqs):
        for r in reqs:
            engine.submit(r)
        t0 = time.perf_counter()
        for _ in range(200000):
            engine.step()
            if all(r.finished for r in reqs):
                break
        else:
            raise RuntimeError("lora bench workload did not converge")
        wall = time.perf_counter() - t0
        return wall, sum(len(r.output_tokens) for r in reqs)

    # -- dedicated fleet: one merged-weights engine per tenant ---------
    dedicated_out = {}
    dedicated_wall = dedicated_toks = 0.0
    kv_bytes = None
    for i in range(n_tenants):
        eng = InferenceEngine(
            get_config(model, dtype="float32", param_dtype="float32"),
            merged[i], max_slots=slots, max_seq_len=max_seq,
            max_queue=4 * slots * n_tenants)
        if kv_bytes is None:
            kv_bytes = sum(x.nbytes for x in (eng.cache.k, eng.cache.v,
                                              eng.cache.k_scale,
                                              eng.cache.v_scale)
                           if x is not None)
        eng.warmup()
        reqs = [Request(prompt_tokens=list(p), max_tokens=max_tokens,
                        temperature=0.0) for p in prompts[i]]
        wall, toks = drive(eng, reqs)
        dedicated_wall += wall
        dedicated_toks += toks
        dedicated_out[i] = [r.output_tokens for r in reqs]
        eng.release_steady()
        del eng

    def pooled_run(pool_n):
        """One pooled-engine pass over the tenant-interleaved workload
        (heterogeneous batches by construction). Returns (wall, tokens,
        adapter stats, unexpected compiles) with inline token parity
        against the dedicated fleet."""
        eng = InferenceEngine(
            get_config(model, dtype="float32", param_dtype="float32",
                       adapter_pool=pool_n, lora_rank=rank),
            params, max_slots=slots, max_seq_len=max_seq,
            max_queue=4 * slots * n_tenants)
        pool_bytes = eng.adapters.pool_bytes()
        eng.warmup()
        reqs = []
        for j in range(per_tenant):
            for i in range(n_tenants):
                reqs.append((i, j, Request(
                    prompt_tokens=list(prompts[i][j]),
                    max_tokens=max_tokens, temperature=0.0,
                    adapter=adapter_paths[i])))
        unexpected_before = obs_device.SENTINEL.unexpected
        wall, toks = drive(eng, [r for _, _, r in reqs])
        unexpected = obs_device.SENTINEL.unexpected - unexpected_before
        for i, j, r in reqs:
            assert r.output_tokens == dedicated_out[i][j], (
                f"PARITY VIOLATION tenant {i} req {j}: "
                f"{r.output_tokens} != {dedicated_out[i][j]}")
        stats = eng.adapter_stats()
        eng.release_steady()
        return wall, toks, stats, unexpected, pool_bytes

    # Phase A: every tenant resident (pool = N) — density + throughput.
    res_wall, res_toks, res_stats, res_unexpected, pool_bytes = \
        pooled_run(n_tenants)
    # Phase B: pool = N/2 — the steady adapter-SWAPPING loop (loads +
    # evictions on the decode path; the sentinel must stay silent).
    swap_wall, swap_toks, swap_stats, swap_unexpected, _ = \
        pooled_run(pool_size)
    assert swap_stats["evictions"] > 0, "swap phase never churned lanes"
    unexpected = res_unexpected + swap_unexpected

    bytes_dedicated = n_tenants * (weight_bytes + kv_bytes)
    bytes_pooled = weight_bytes + kv_bytes + pool_bytes
    density = bytes_dedicated / bytes_pooled
    benchkit.emit({
        "metric": f"{model} LoRA tenant density: {n_tenants} adapters on "
                  f"one pooled engine (rank {rank}) vs "
                  f"{n_tenants} dedicated merged engines",
        "value": round(density, 2),
        "unit": "x",
        # Acceptance >= 2x tenants-per-HBM-byte at equal service, with
        # inline token parity and a silent compile sentinel across BOTH
        # pooled phases; any unexpected compile zeroes the gate.
        "vs_baseline": (0.0 if unexpected
                        else round(density / 2.0, 4)),
        "tenants": n_tenants,
        "adapter_pool_resident": n_tenants,
        "adapter_pool_swap": pool_size,
        "lora_rank": rank,
        "weight_bytes": weight_bytes,
        "kv_bytes": kv_bytes,
        "adapter_pool_bytes": pool_bytes,
        "bytes_dedicated_fleet": bytes_dedicated,
        "bytes_pooled_engine": bytes_pooled,
        "pooled_decode_tokens_per_sec": round(res_toks / res_wall, 1),
        "dedicated_decode_tokens_per_sec": round(
            dedicated_toks / dedicated_wall, 1),
        "swap_loop_decode_tokens_per_sec": round(
            swap_toks / swap_wall, 1),
        "resident_phase": {k: res_stats[k]
                           for k in ("loads", "evictions", "hits")},
        "swap_phase": {k: swap_stats[k]
                       for k in ("loads", "evictions", "hits")},
        "greedy_parity": "ok",
        "unexpected_compiles_steady_loops": unexpected,
    })


def router_inner() -> None:
    """Random vs prefix-aware routing over 3 paged replicas.

    The engines are shared between the two runs (engine.reset() between
    policies rebuilds the pool, radix tree, and reuse counters; the jit
    cache survives, so the whole comparison costs one warmup per
    replica). Requests arrive in waves — one request per tenant prefix
    per wave, waves drained in between — the steady shape of multi-user
    chat traffic, where each tenant's next turn lands after its last
    one finished."""
    import jax
    import numpy as np

    from runbooks_tpu.obs import device as obs_device
    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.models.transformer import init_params
    from runbooks_tpu.serve.engine import Request
    from runbooks_tpu.serve.gateway import Router, token_blocks
    from runbooks_tpu.serve.paging import PagedInferenceEngine

    device, on_tpu = benchkit.bench_device()
    model = os.environ.get("RBT_BENCH_MODEL",
                           "bench-410m" if on_tpu else "debug")
    replicas = int(os.environ.get("RBT_BENCH_REPLICAS", 3))
    max_seq = int(os.environ.get("RBT_BENCH_MAXSEQ", 64))
    page_size = int(os.environ.get("RBT_BENCH_PAGE_SIZE", 16))
    prefixes = int(os.environ.get("RBT_BENCH_PREFIXES", 8))
    waves = int(os.environ.get("RBT_BENCH_WAVES", 4))
    max_tokens = int(os.environ.get("RBT_BENCH_MAXTOK", 4))

    cfg = get_config(model, param_dtype="bfloat16")
    params = jax.jit(lambda r: init_params(cfg, r))(jax.random.key(0))
    engines = {}
    for i in range(replicas):
        eng = PagedInferenceEngine(
            cfg, params, max_slots=4, max_seq_len=max_seq,
            page_size=page_size, num_pages=64, max_queue=64)
        eng.warmup()
        engines[f"r{i}"] = eng

    rng = np.random.default_rng(0)
    # 2 full pages of shared prefix per tenant + a short private suffix.
    prefix_toks = [rng.integers(1, cfg.vocab_size,
                                2 * page_size).tolist()
                   for _ in range(prefixes)]

    def run_policy(policy: str):
        router = Router({n: f"mem://{n}" for n in engines},
                        policy=policy)
        routed = 0
        for _ in range(waves):
            pending = []
            for p in range(prefixes):
                toks = prefix_toks[p] + rng.integers(
                    1, cfg.vocab_size, 8).tolist()
                blocks = token_blocks(toks, page_size)
                name = router.pick(blocks)[0][0]
                req = Request(prompt_tokens=toks, max_tokens=max_tokens,
                              temperature=0.0)
                engines[name].submit(req)
                router.inflight_add(name, 1)
                router.record_route(name, blocks)
                pending.append((name, req))
                routed += 1
            for _ in range(100000):
                busy = [e for e in engines.values() if e.has_work()]
                if not busy:
                    break
                for e in busy:
                    e.step()
            else:
                raise RuntimeError("router bench wave did not converge")
            for name, _req in pending:
                router.inflight_add(name, -1)
        per_replica = {n: e.pager.occupancy()["pages_reused_total"]
                       for n, e in engines.items()}
        return sum(per_replica.values()) / max(routed, 1), per_replica

    unexpected_before = obs_device.SENTINEL.unexpected
    random_reuse, random_detail = run_policy("random")
    for eng in engines.values():
        eng.reset()  # fresh pool + radix + counters; jit cache survives
    prefix_reuse, prefix_detail = run_policy("prefix")
    unexpected = obs_device.SENTINEL.unexpected - unexpected_before

    uplift = prefix_reuse / max(random_reuse, 1e-9)
    benchkit.emit({
        "metric": f"{model} prefix-aware vs random routing page reuse "
                  f"({replicas} replicas, {prefixes} prefixes x "
                  f"{waves} waves)",
        "value": round(uplift, 2),
        "unit": "x",
        # Acceptance: >= 1.5x pages reused per routed request
        # (docs/serving-dataplane.md), so > 1.0 means the claim holds.
        "vs_baseline": round(uplift / 1.5, 4),
        "prefix_pages_reused_per_request": round(prefix_reuse, 3),
        "random_pages_reused_per_request": round(random_reuse, 3),
        "prefix_per_replica": prefix_detail,
        "random_per_replica": random_detail,
        "unexpected_compiles": unexpected,
    })


def spec_inner() -> None:
    """Speculative decoding: greedy decode tok/s per accept-rate bucket.

    One spec-off engine records outputs + baseline tok/s; one spec-on
    engine (same params, same batch) replays the workload at each
    controlled drafter accuracy. Between passes only host state resets
    (fresh requests), so the jit cache is shared and the whole axis
    costs two warmups."""
    import jax
    import numpy as np

    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.models.transformer import init_params
    from runbooks_tpu.obs import device as obs_device
    from runbooks_tpu.serve.engine import InferenceEngine, Request

    device, on_tpu = benchkit.bench_device()
    model = os.environ.get("RBT_BENCH_MODEL",
                           "bench-410m" if on_tpu else "debug")
    slots = int(os.environ.get("RBT_BENCH_SLOTS", 4))
    n_requests = int(os.environ.get("RBT_BENCH_REQUESTS", 8))
    max_seq = int(os.environ.get("RBT_BENCH_MAXSEQ", 256))
    prompt_len = int(os.environ.get("RBT_BENCH_PROMPT", 32))
    max_tokens = int(os.environ.get("RBT_BENCH_MAXTOK", 64))
    draft_k = int(os.environ.get("RBT_BENCH_DRAFT_K", 4))

    cfg = get_config(model, param_dtype="bfloat16")
    params = jax.jit(lambda r: init_params(cfg, r))(jax.random.key(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
               for _ in range(n_requests)]

    def run(engine, oracle=None):
        reqs = []
        for i, p in enumerate(prompts):
            r = Request(prompt_tokens=list(p), max_tokens=max_tokens,
                        temperature=0.0)
            if oracle is not None:
                r._bench_oracle = oracle[i]
            reqs.append(r)
        for r in reqs:
            engine.submit(r)
        t0 = time.perf_counter()
        for _ in range(200000):
            engine.step()
            if all(r.finished for r in reqs):
                break
        else:
            raise RuntimeError("spec bench workload did not converge")
        wall = time.perf_counter() - t0
        toks = sum(len(r.output_tokens) for r in reqs)
        return [list(r.output_tokens) for r in reqs], toks / wall

    # -- spec-off baseline (records the greedy ground truth) -----------
    off = InferenceEngine(cfg, params, max_slots=slots,
                          max_seq_len=max_seq, max_queue=n_requests,
                          speculative="off")
    off.warmup()
    truth, off_tps = run(off)
    off.release_steady()
    del off

    class OracleSpecEngine(InferenceEngine):
        """Real engine + real verify path; only the DRAFT SOURCE is an
        oracle reading the recorded greedy continuation, corrupted at a
        controlled per-token rate (a corrupted token always differs
        from the truth, so it is always rejected)."""

        accuracy = 1.0
        _draft_rng = np.random.default_rng(1)

        def _draft_for(self, slot, max_tokens_):
            req = self.slot_req[slot]
            future = req._bench_oracle[len(req.output_tokens):
                                       len(req.output_tokens)
                                       + max_tokens_]
            return [int(t) if self._draft_rng.random() < self.accuracy
                    else (int(t) + 1) % cfg.vocab_size for t in future]

    on = OracleSpecEngine(cfg, params, max_slots=slots,
                          max_seq_len=max_seq, max_queue=n_requests,
                          speculative="ngram", draft_tokens=draft_k)
    on.warmup()
    unexpected_before = obs_device.SENTINEL.unexpected
    # Per-token accuracies chosen so the MEASURED accept rate over a
    # K-token window lands near the 0% / 50% / 90% buckets (a window
    # dies at its first corrupted token, so rate(p) = mean prefix
    # survival, not p itself).
    buckets = {}
    for name, acc in (("acc0", 0.0), ("acc50", 0.75), ("acc90", 0.97)):
        OracleSpecEngine.accuracy = acc
        OracleSpecEngine._draft_rng = np.random.default_rng(1)
        drafted0, accepted0 = on.spec_drafted, on.spec_accepted
        outs, tps = run(on, oracle=truth)
        if outs != truth:
            raise RuntimeError(
                f"speculative outputs diverged from greedy truth at "
                f"accuracy {acc} — verify path broken")
        d = on.spec_drafted - drafted0
        a = on.spec_accepted - accepted0
        buckets[name] = {
            "drafter_accuracy": acc,
            "accept_rate": round(a / d, 3) if d else 0.0,
            "decode_tokens_per_sec": round(tps, 1),
            "speedup_vs_off": round(tps / off_tps, 2),
        }

    # -- real n-gram drafting on self-repeating traffic (informational):
    # the prompt is one repeated motif, so prompt-lookup fires from the
    # first decode step; the measured accept rate is whatever the
    # random-init model's actual continuations give it.
    real = InferenceEngine(cfg, params, max_slots=slots,
                           max_seq_len=max_seq, max_queue=n_requests,
                           speculative="ngram", draft_tokens=draft_k)
    motif = rng.integers(1, cfg.vocab_size, 4).tolist()
    rep_prompts = [motif * (prompt_len // 4) for _ in range(n_requests)]
    reqs = [Request(prompt_tokens=list(p), max_tokens=max_tokens,
                    temperature=0.0) for p in rep_prompts]
    real.warmup()
    for r in reqs:
        real.submit(r)
    for _ in range(200000):
        real.step()
        if all(r.finished for r in reqs):
            break
    ngram_rate = (real.spec_accepted / real.spec_drafted
                  if real.spec_drafted else 0.0)
    unexpected = obs_device.SENTINEL.unexpected - unexpected_before

    speedup = buckets["acc90"]["speedup_vs_off"]
    gate = 0.0 if unexpected else 1.0
    benchkit.emit({
        "metric": f"{model} speculative decode tok/s vs spec-off at "
                  f"~90% accept ({n_requests} reqs, {slots} slots, "
                  f"K={draft_k}, greedy)",
        "value": round(speedup, 2),
        "unit": "x",
        # Acceptance: >= 1.5x on the high-accept greedy workload
        # (docs/speculative-decoding.md); forced to 0 when the steady
        # loops compiled anything unexpected.
        "vs_baseline": round(speedup / 1.5 * gate, 4),
        "spec_off_decode_tokens_per_sec": round(off_tps, 1),
        "by_accept_rate": buckets,
        "greedy_parity": True,   # run() raised otherwise
        "ngram_real_accept_rate": round(ngram_rate, 3),
        "ngram_real_drafted": real.spec_drafted,
        "draft_tokens": draft_k,
        "unexpected_compiles_steady_loop": unexpected,
    })


def grammar_inner() -> None:
    """Grammar-constrained vs unconstrained decode tok/s on ONE engine.

    Both passes share the grammar-on engine (and therefore the jit
    cache): the unconstrained pass dispatches all-allow mask rows (the
    identity operand), the constrained pass real DFA masks from a
    bounded JSON schema, so the throughput delta is pure mask build +
    apply cost. Parse rate over the constrained completions is the
    correctness gate — the DFA guarantees 100%, anything less is a
    masking bug, not a model quality question."""
    import jax
    import numpy as np

    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.models.transformer import init_params
    from runbooks_tpu.obs import device as obs_device
    from runbooks_tpu.serve.engine import InferenceEngine, Request
    from runbooks_tpu.train.data import ByteTokenizer

    device, on_tpu = benchkit.bench_device()
    model = os.environ.get("RBT_BENCH_MODEL",
                           "bench-410m" if on_tpu else "debug")
    slots = int(os.environ.get("RBT_BENCH_SLOTS", 4))
    n_requests = int(os.environ.get("RBT_BENCH_REQUESTS", 8))
    max_seq = int(os.environ.get("RBT_BENCH_MAXSEQ", 256))
    prompt_len = int(os.environ.get("RBT_BENCH_PROMPT", 32))
    max_tokens = int(os.environ.get("RBT_BENCH_MAXTOK", 64))

    cfg = get_config(model, param_dtype="bfloat16")
    if cfg.vocab_size < 258:          # ByteTokenizer eos id is 257
        import dataclasses
        cfg = dataclasses.replace(cfg, vocab_size=258)
    params = jax.jit(lambda r: init_params(cfg, r))(jax.random.key(0))
    tok = ByteTokenizer()
    rng = np.random.default_rng(0)
    # Byte-id prompts so the constrained rows decode as text the DFA
    # walked; the model is random-init — content is irrelevant, the
    # grammar owns the output language.
    prompts = [rng.integers(32, 127, prompt_len).tolist()
               for _ in range(n_requests)]
    # Finite language (no stars): every path reaches the terminal state
    # within max_tokens, so the 100% parse-rate gate is a theorem about
    # the masking path, not a bet on sampling luck. An unbounded field
    # (integer, string) would let temp-0.8 sampling pad until
    # max_tokens and finish "length" — a workload bug, not a mask bug.
    schema = {"type": "json_schema", "json_schema": {"schema": {
        "type": "object",
        "properties": {"verdict": {"type": "boolean"},
                       "label": {"enum": ["low", "medium", "high"]},
                       "score": {"enum": [0, 1, 2, 3]},
                       "note": {"type": "null"}},
        "required": ["verdict", "label", "score", "note"],
        "additionalProperties": False}}}

    engine = InferenceEngine(cfg, params, max_slots=slots,
                             max_seq_len=max_seq, max_queue=n_requests,
                             grammar="on", tokenizer=tok, seed=0)
    engine.warmup()
    unexpected_before = obs_device.SENTINEL.unexpected

    def run(rf):
        reqs = [Request(prompt_tokens=list(p), max_tokens=max_tokens,
                        temperature=0.8, eos_id=tok.eos_id,
                        response_format=rf) for p in prompts]
        for r in reqs:
            engine.submit(r)
        t0 = time.perf_counter()
        for _ in range(200000):
            engine.step()
            if all(r.finished for r in reqs):
                break
        else:
            raise RuntimeError("grammar bench workload did not converge")
        wall = time.perf_counter() - t0
        toks = sum(len(r.output_tokens) for r in reqs)
        return reqs, toks / wall

    _, plain_tps = run(None)                 # all-allow mask rows
    creqs, grammar_tps = run(schema)         # real DFA masks

    parsed = 0
    for r in creqs:
        text = bytes(t for t in r.output_tokens if t < 256).decode()
        try:
            if r.finish_reason == "grammar_complete":
                json.loads(text)
                parsed += 1
        except ValueError:
            pass
    parse_rate = parsed / len(creqs)
    unexpected = obs_device.SENTINEL.unexpected - unexpected_before
    engine.release_steady()

    ratio = grammar_tps / plain_tps
    gate = 1.0 if (parse_rate == 1.0 and unexpected == 0) else 0.0
    gs = engine.grammar_stats()
    benchkit.emit({
        "metric": f"{model} constrained vs unconstrained decode tok/s "
                  f"({n_requests} reqs, {slots} slots, temp 0.8)",
        "value": round(ratio, 3),
        "unit": "x",
        # Acceptance: constrained decode sustains >= 0.7x unconstrained
        # (docs/structured-output.md cost model — one elementwise where
        # per dispatch plus host-side mask gathers); forced to 0 on any
        # parse failure or unexpected compile.
        "vs_baseline": round(ratio / 0.7 * gate, 4),
        "unconstrained_decode_tokens_per_sec": round(plain_tps, 1),
        "constrained_decode_tokens_per_sec": round(grammar_tps, 1),
        "parse_rate": parse_rate,
        "grammar_cache": {k: gs[k] for k in
                          ("hits", "misses", "compile_seconds_total")},
        "constrained_requests": gs["requests_total"],
        "draft_truncations": gs["draft_truncations_total"],
        "unexpected_compiles_steady_loop": unexpected,
    })


def inner() -> None:
    import jax
    import numpy as np

    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.models.transformer import init_params
    from runbooks_tpu.serve.api import EngineWorker
    from runbooks_tpu.serve.engine import InferenceEngine, Request

    device, on_tpu = benchkit.bench_device()
    model = os.environ.get("RBT_BENCH_MODEL",
                           "bench-410m" if on_tpu else "debug")
    slots = int(os.environ.get("RBT_BENCH_SLOTS", 8))
    n_requests = int(os.environ.get("RBT_BENCH_REQUESTS", 16))
    prompt_len = int(os.environ.get("RBT_BENCH_PROMPT",
                                    128 if on_tpu else 16))
    max_tokens = int(os.environ.get("RBT_BENCH_MAXTOK",
                                    64 if on_tpu else 8))

    chunk = os.environ.get("RBT_BENCH_CHUNK")
    chunk = int(chunk) if chunk else None  # None => engine auto (8 on TPU)
    # Engine context window: bounds the warmup compile set (every prefill
    # bucket × {1, slots} rows + every decode view is its own XLA program
    # — ROADMAP S3). 512 covers prompt+max_tokens with a bucket to spare.
    max_seq = int(os.environ.get("RBT_BENCH_MAXSEQ", 512 if on_tpu else 0))

    # Shared-prefix load: RBT_BENCH_PREFIX=P makes every request share a
    # P-token registered prefix (chat-system-prompt shape); the engine
    # prefills only the (prompt_len - P)-token suffix. 0 = off.
    prefix_len = int(os.environ.get("RBT_BENCH_PREFIX", 0))

    # Quantized serving axis: int8/int4 weight-only + int8 KV cache.
    quantize = os.environ.get("RBT_BENCH_QUANTIZE", "none")
    # The bf16-vs-quantized comparison must hold weights dtype-equal at the
    # baseline: bf16 params on both platforms (the serving dtype), so the
    # quantized speedup is bandwidth, not a f32->bf16 cast artifact.
    cfg = get_config(model, param_dtype="bfloat16")
    params = jax.jit(lambda r: init_params(cfg, r))(jax.random.key(0))
    if quantize != "none":
        from runbooks_tpu.ops.quantization import quantize_params

        params = quantize_params(params, quantize)
    from runbooks_tpu.ops.quantization import tree_weight_bytes

    weight_bytes = tree_weight_bytes(params)
    engine = InferenceEngine(cfg, params, max_slots=slots,
                             max_seq_len=max_seq or None,
                             decode_chunk=chunk,
                             quantize_kv=quantize != "none")
    kv_cache_bytes = sum(
        x.nbytes for x in (engine.cache.k, engine.cache.v,
                           engine.cache.k_scale, engine.cache.v_scale)
        if x is not None)
    engine.warmup()
    worker = EngineWorker(engine)

    class TimedList(list):
        """List that records the time of its first append (= first token)."""

        def __init__(self, start, sink):
            super().__init__()
            self._start, self._sink = start, sink

        def append(self, tok):
            if not self:
                self._sink(time.perf_counter() - self._start)
            super().append(tok)

    rng = np.random.default_rng(0)
    shared = []
    if prefix_len:
        # Leave >= 16 suffix tokens so prompts stay inside the context
        # window, and only keep the prefix the engine actually cached
        # (rounds down to 16; < 16 caches nothing).
        prefix_len = min(prefix_len, prompt_len - 16)
        if prefix_len >= 16:
            shared = rng.integers(1, cfg.vocab_size, prefix_len).tolist()
            cached = engine.register_prefix(shared)  # compiles pre-traffic
            if not cached:
                shared = []
    ttfts = []
    lock = threading.Lock()

    def sink(dt):
        with lock:
            ttfts.append(dt)

    t_all = time.perf_counter()
    futs = []
    for _ in range(n_requests):
        suffix_n = max(prompt_len - len(shared), 1)
        toks = shared + rng.integers(1, cfg.vocab_size, suffix_n).tolist()
        req = Request(prompt_tokens=toks, max_tokens=max_tokens,
                      temperature=0.0)
        req.output_tokens = TimedList(time.perf_counter(), sink)
        futs.append(worker.submit(req))
    done = [f.result(timeout=600) for f in futs]
    wall = time.perf_counter() - t_all
    worker.stop()

    total_tokens = sum(len(r.output_tokens) for r in done)
    ttft_p50_ms = statistics.median(ttfts) * 1000
    # No reference baseline exists (BASELINE.json publishes none for
    # serving); score against a 250 ms p50-TTFT target so >1.0 = beats
    # target, and a failed run (run_outer's 0.0 sentinel) stays
    # distinguishable from any real measurement.
    benchkit.emit({
        "metric": f"{model} serve TTFT p50 ({n_requests} reqs, "
                  f"{slots} slots, prompt {prompt_len}, "
                  f"quantize {quantize})",
        "value": round(ttft_p50_ms, 1),
        "unit": "ms",
        "vs_baseline": round(250.0 / max(ttft_p50_ms, 1e-6), 4),
        "ttft_p90_ms": round(sorted(ttfts)[int(0.9 * len(ttfts)) - 1] * 1000,
                             1),
        "decode_tokens_per_sec": round(total_tokens / wall, 1),
        "decode_chunk": engine.decode_chunk,
        "prefix_tokens_reused": engine.prefix_tokens_reused,
        "quantize": quantize,
        "weight_bytes": weight_bytes,
        "kv_cache_bytes": kv_cache_bytes,
    })


if __name__ == "__main__":
    paged_axis = os.environ.get("RBT_BENCH_PAGED") == "1"
    router_axis = os.environ.get("RBT_BENCH_ROUTER") == "1"
    spec_axis = os.environ.get("RBT_BENCH_SPEC") == "1"
    lora_axis = os.environ.get("RBT_BENCH_LORA") == "1"
    mesh_axis = os.environ.get("RBT_BENCH_MESH_SERVE") == "1"
    kv_tier_axis = os.environ.get("RBT_BENCH_KV_TIER") == "1"
    grammar_axis = os.environ.get("RBT_BENCH_GRAMMAR") == "1"
    if "--inner" in sys.argv:
        if grammar_axis:
            grammar_inner()
        elif kv_tier_axis:
            kv_tier_inner()
        elif mesh_axis:
            mesh_serve_inner()
        elif lora_axis:
            lora_inner()
        elif spec_axis:
            spec_inner()
        elif router_axis:
            router_inner()
        elif paged_axis:
            paged_inner()
        else:
            inner()
    else:
        benchkit.run_outer(os.path.abspath(__file__))
