"""Slot-based continuous-batching inference engine.

The reference serves models via external HTTP containers (reference:
examples/llama2-7b/server.yaml uses substratusai/model-server-basaran behind
a Deployment on port 8080 — internal/controller/server_controller.go). Here
inference is in-framework and TPU-shaped:

- Static shapes everywhere: a fixed pool of B slots, a fixed cache length,
  bucketed prefill lengths and row counts — so the compiled-program set is
  small and fixed (prefill per (bucket, rows) + one decode chunk) and there
  are no recompiles at serve time.
- Continuous batching at slot granularity: between decode chunks, finished
  slots are freed and queued requests prefill into free slots; every decode
  step advances all active slots at once (one [B,1] forward).
- Decode runs ``decode_chunk`` steps per host round-trip (a lax.scan with
  on-device EOS/limit tracking), because on TPU a per-step host sync
  dominates small-batch inter-token latency. chunk=1 reproduces classic
  step-at-a-time behavior exactly; the host takes each slot's tokens by
  the device's count of its live steps, so slot bookkeeping matches the
  single-step semantics token for token.
- Between the pull of a chunk and the next dispatch the host does only
  what that dispatch depends on (the slot half, _take_chunk): the tokens
  are handed over (on_token, histograms, finished) while the next program
  runs, and the scan's carry and the per-slot sampling operands stay on
  the device until a slot changes hands (_decode_chunk_step).
- Prefill is batched: requests admitted in the same tick are grouped by
  length bucket and prefilled as one [rows, bucket] forward (rows padded to
  a power of two), so a burst costs one dispatch per bucket instead of one
  per request.
- Per-slot cache writes use the transformer's position-scatter mode with a
  trash slot for padding (see models/transformer.KVCache).
- A model whose layer pattern has recurrent layers (linear-attention: a
  state and a conv tail; short-convolution: a tail alone) keeps that, of
  fixed size a slot, beside the K/V rows. It has no trash slot, so the
  programs mask, freeze and reset it themselves: the invariant is written
  out in make_prefill_fn (docs/hybrid-models.md).
- A model whose attention layers are latent (MLA) caches one leaf
  `latent` [layers, slots, cache_len, width] with no head axis in place of
  K/V: same slots, trash slot, splice and views; a sparse model's programs
  also return the experts' assignment counts, which ride the pull a
  dispatch makes anyway (docs/sparse-latent-models.md).
- Sampling is jitted with per-slot temperature/top_k/top_p so mixed request
  parameters batch together.
- Quantized fast path: params may be weight-only int8/int4
  (ops/quantization.py QuantizedArray — the transformer dispatches on the
  type), and quantize_kv=True stores the slot pool as int8 with
  per-slot-per-head scales. Decode is HBM-bandwidth-bound (see the view
  buckets below), so fewer bytes streamed per token is directly more
  tok/s — and the int4 tier is what fits 70B-class models on one v5e-8
  host (docs/quantized-serving.md).
"""

from __future__ import annotations

import dataclasses
import functools
import os
import time
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from runbooks_tpu.api.serve_params import QOS_CLASSES, ServeOptions
from runbooks_tpu.models.config import ModelConfig
from runbooks_tpu.models.moe import chunk_window, gmm_tilings, rows_moved
from runbooks_tpu.models.transformer import (
    LEAF_TRAITS,
    KVCache,
    cache_leaves,
    forward,
    project_logits,
    flash_blocks,
    flash_heads_per_step,
    use_flash_cached_prefill,
)
from runbooks_tpu.obs import device as obs_device
from runbooks_tpu.obs import flight as obs_flight
from runbooks_tpu.obs import metrics as obs_metrics
from runbooks_tpu.obs.trace import complete as trace_complete
from runbooks_tpu.obs.trace import (
    PhaseSeconds,
    fine,
    fine_enabled,
    record_enabled,
    span,
)
from runbooks_tpu.ops.sampling import sample, speculative_verify
from runbooks_tpu.serve.speculative import NgramDraftIndex, legal_draft_prefix
from runbooks_tpu.serve.weight_layout import (
    Placement,
    asked_formats,
    stack_layouts,
)
from runbooks_tpu.serve.weight_layout import place as place_weights
from runbooks_tpu.utils.hw import backend_tuning

Params = Any

# Accept-length histogram buckets (tokens accepted per slot per verify
# step): small ints up to the largest plausible draft window. Fixed so
# the exposition stays comparable across K configurations.
_ACCEPT_LEN_BUCKETS = (0.0, 1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0)

# Per-verify-step accept-rate buckets for the host-side tok/s breakdown
# (/debug/programs "speculative" block): each verify step's
# accepted/drafted ratio lands in one of these, and the step's emitted
# tokens + wall time accumulate there — decode throughput BY accept
# rate, the number that says whether drafting pays on this traffic.
_ACCEPT_RATE_BUCKETS = ("0-25%", "25-50%", "50-75%", "75-100%")

# Inter-token gaps run from microseconds (host replay inside a decode
# chunk) to chunk wall time; the default latency buckets start at 1 ms and
# would flatten the distribution's whole left half into one bucket.
_INTER_TOKEN_BUCKETS = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5)


def request_start(req: "Request") -> float:
    """The instant a request's latency counts from: the HTTP handler's
    entry where one stamped it (`_received`), else engine.submit (direct
    users of the engine). `deadline_s` counts from `_submitted` always."""
    return req._received or req._submitted


def _pull(name: str, *arrays) -> list:
    """A dispatch's small results on the host, one np.asarray each: the
    sync of a dispatch boundary. While fine spans are recorded the wait
    has two children under the caller's span: `<name>.ready` (the device
    still works, or the runtime has not woken this thread) and
    `<name>.pull` (the copy to the host and its conversion)."""
    if fine_enabled():
        with fine(name + ".ready"):
            # rbt-check: ignore[device-sync] the same dispatch boundary, split in two while a capture or the trace file records it
            jax.block_until_ready(arrays)
    with fine(name + ".pull"):     # the shared no-op with recording off
        # rbt-check: ignore[device-sync] dispatch boundary: one sync a dispatch, not a token
        return [np.asarray(a) for a in arrays]


def _finish_request(req: "Request", reason: str, now: float) -> None:
    """The one place a request becomes finished (normal finish, error or
    deadline expiry): the reason, then `finished` — last, the worker and
    the stream handlers act on it — then the terminal latency accounting:
    end-to-end duration, labeled by finish reason, plus the tail-sampling
    decision (obs/flight.py): a slow or deadline-expired request's
    flight-ring timeline is promoted to trace.jsonl even with
    RBT_TRACE=0. Then the request's own hook."""
    req.finish_reason = reason
    req.finished = True
    duration = now - request_start(req)
    obs_metrics.REGISTRY.observe(
        "serve_request_duration_seconds", duration,
        reason=req.finish_reason or "stop",
        help_text="End-to-end request latency (HTTP handler entry, or "
                  "engine.submit where no handler stamped one, to "
                  "finish).")
    obs_flight.tail_sample(req.request_id, duration,
                           req.finish_reason or "stop")
    if req.on_finish is not None:
        req.on_finish(req)


# QoS classes, best first. Admission orders the queue by class (FIFO
# within a class) and — on the paged engine with preemption enabled —
# a blocked higher-class head preempts the worst-class active slot
# (docs/paged-kv.md "Host tier and preemption"). The gateway forwards
# the class as X-Priority and spills batch traffic first
# (serve/gateway.py); the strings are the public API surface
# (docs/api.md `priority`).
PRIORITY_RANK = {cls: rank for rank, cls in enumerate(QOS_CLASSES)}


class EngineOverloaded(RuntimeError):
    """Typed admission rejection: the bounded queue is full. Backpressure
    instead of unbounded queue growth — serve/api.py maps this to HTTP 429
    with a Retry-After header so well-behaved clients back off
    (docs/fault-tolerance.md)."""


class EngineDraining(EngineOverloaded):
    """The server is draining (SIGTERM): no new admissions; in-flight
    requests finish before exit. Maps to HTTP 503."""


class EngineStepFailed(RuntimeError):
    """A jitted engine step raised: the donated KV cache buffers may be
    invalid and slot/page bookkeeping half-applied, so the engine needs a
    full reset() before it can serve again. Raised by paths that drive
    step() on behalf of a single caller (paged register_prefix) so the
    worker routes them to its crash handler instead of swallowing them
    per-job (serve/api.py)."""


@dataclasses.dataclass
class Request:
    """One generation request (engine-internal)."""
    prompt_tokens: List[int]
    max_tokens: int = 64
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    eos_id: Optional[int] = None
    # Multi-turn hint: after this request finishes, its prompt's KV is
    # registered as a shared prefix straight from the slot cache (the
    # next turn's prompt extends this one). Consumed by the serving
    # worker; no effect inside the engine itself.
    auto_prefix: bool = False
    # Wall-clock budget in seconds from submit(). Enforced between decode
    # chunks (a chunk in flight is never interrupted): an expired request
    # finishes with finish_reason "deadline" and whatever tokens it has —
    # queued requests that expire before admission finish empty-handed.
    deadline_s: Optional[float] = None
    # Request-scoped trace/correlation id (serve/api.py: accepted or
    # generated from X-Request-Id / traceparent, echoed in response
    # headers). Carried into the queue/prefill/decode span args so one
    # Perfetto trace follows this request end to end.
    request_id: str = ""
    # Multi-tenant LoRA serving (serve/lora_pool.py,
    # docs/multi-tenant-lora.md): name/path of the adapter this request
    # decodes through, or None for the base model. Admission pins the
    # adapter's pool lane (paging it into HBM if needed) and the slot
    # carries the lane index into every batched dispatch.
    adapter: Optional[str] = None
    # QoS class (PRIORITY_RANK): orders the admission queue and selects
    # preemption victims under page/slot pressure — batch work yields
    # to interactive work instead of degrading every tenant equally.
    priority: str = "standard"
    # Grammar-constrained structured output (serve/grammar.py,
    # docs/structured-output.md): {"type": "json_schema"|"ebnf", ...}.
    # validate() compiles it (LRU-cached) into a token DFA and pins the
    # per-request cursor below; None decodes unconstrained.
    response_format: Optional[dict] = None
    # Filled by the engine:
    output_tokens: List[int] = dataclasses.field(default_factory=list)
    finished: bool = False
    finish_reason: str = ""
    # Streaming hook: called (from the engine/worker thread) after each
    # generated token lands in output_tokens. Keep it cheap and non-blocking
    # — it runs inside the decode loop (SSE uses call_soon_threadsafe).
    on_token: Optional[Callable[[int], None]] = None
    # Called once (same thread) when the request finishes, after its last
    # on_token: the serving worker resolves the request's future here, so
    # that a finish handed over while the next dispatch runs on the device
    # (engine.deliver_parked) does not wait for that dispatch to end.
    on_finish: Optional[Callable[["Request"], None]] = None
    _slot: int = -1
    _adapter_lane: int = -1   # pool lane pinned at admission (-1 = base)
    # Compiled DFA cursor (serve/grammar.GrammarCursor) when
    # response_format is set: one int of decode state riding the request
    # object, so preemption/swap-resume continues mid-grammar loss-free.
    _grammar: Any = None
    # Preempted and re-queued (paged engine, preemption="swap"): the
    # request's generated-so-far tokens stay in output_tokens and its
    # written pages live on in the radix tree (HBM or host tier), so
    # re-admission resumes via a radix match on its own history — no
    # token loss, no resample of already-recorded tokens.
    _preempted: bool = False
    # A request's instants (time.monotonic), each stamped where a phase
    # ends; 0.0 = not reached. The first two and the last are the HTTP
    # layer's (serve/api.py, event loop), the others the engine's (worker).
    _received: float = 0.0    # handler entry (TTFT and duration anchor)
    _handed: float = 0.0      # joined EngineWorker._pending (parse end)
    _submitted: float = 0.0   # engine.submit (deadline anchor)
    _admitted: float = 0.0    # slot assignment (queue-wait end)
    _first_token: float = 0.0  # first token handed over (TTFT end)
    _first_write: float = 0.0  # first SSE write of a delta (streams only)
    _last_token_t: float = 0.0  # previous token's host-observed time


def _buckets(max_prefill: int) -> List[int]:
    out, b = [], 16
    while b < max_prefill:
        out.append(b)
        b *= 2
    out.append(max_prefill)
    return out


def bucket_for(buckets: List[int], n: int) -> int:
    """Smallest bucket covering n tokens (last bucket when none do)."""
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def dispatch_shapes(prefill_buckets: List[int], prefill_budget: int,
                    max_slots: int) -> List[tuple]:
    """The (rows, bucket) prefill shapes admission can dispatch: what
    warm-up compiles and the census counts, no more and no less. A tick's
    first admission always goes through, alone: [1, bucket] for every
    bucket. A second request joins it only while the tick's budget in
    bucket-padded tokens holds both (_admit), and a group of two or more is
    padded to max_slots rows (prefill_rows): [max_slots, bucket] exists iff
    2 x bucket <= prefill_budget. With the default budget (max_seq_len)
    that leaves the largest bucket without its [max_slots, bucket]
    program, which no tick could fill and which is the largest program a
    server would compile (at 16 384 tokens a row it does not fit a chip)."""
    return [(rows, bucket) for bucket in prefill_buckets
            for rows in dict.fromkeys((1, max_slots))
            if rows == 1 or 2 * bucket <= prefill_budget]


def prefill_rows(group_size: int, max_slots: int) -> int:
    """Rows of the prefill program a group of same-bucket admissions runs
    as: one request alone, any burst padded to max_slots."""
    return 1 if group_size == 1 else max_slots


def view_buckets_for(max_seq_len: int) -> List[int]:
    """Decode cache-view buckets for a given context window (see the
    view discussion in InferenceEngine.__init__)."""
    return sorted({v for v in (256, 1024) if v < max_seq_len}
                  | {max_seq_len})


def auto_prefix_plens(buckets: List[int], max_seq_len: int) -> List[int]:
    """The bounded prefix lengths the quantized (auto_prefix) path can
    register: prefill buckets that leave >= 16 prompt tokens. The
    compiled splice-program census is keyed on these (static-analysis
    and warmup both walk this set)."""
    return [b for b in buckets if b <= max_seq_len - 16]


# ---------------------------------------------------------------------------
# Jitted program bodies, as module-level factories.
#
# The engine jits these in __init__; `rbt check` (runbooks_tpu/analysis/
# program.py) traces the same factories ABSTRACTLY (jax.make_jaxpr over
# ShapeDtypeStructs — zero device arrays, zero backend compiles) to audit
# the steady-state program set for host callbacks, silent dtype
# promotions, embedded constants, and census drift. Keeping one body
# shared by both is what makes the audit honest: the engine cannot ship
# a program the auditor never saw.
# ---------------------------------------------------------------------------


def make_prefill_fn(cfg: ModelConfig, cache_len: int):
    """Batched prefill + splice + first-token sample (one jit dispatch
    per admission group). See the inline commentary for the invariants;
    pk/pv (when given) splice a registered shared prefix into every
    scratch row first.

    apool/aslots (when given — engines with an adapter pool pass them on
    EVERY dispatch): the stacked LoRA adapter pool and the per-row int32
    lane indices (-1 = base-only, the all-zero trash lane). A batch
    mixing tenants is one program; the lane values are operands
    (docs/multi-tenant-lora.md).

    gmask (when given — engines with grammar: on pass it on EVERY
    dispatch): [rows, vocab] bool allowed-token rows for the first
    sampled token; all-True rows are the identity, so unconstrained
    requests ride the same program (serve/grammar.py)."""

    sparse = bool(cfg.moe_num_experts)

    def prefill_fn(params, pool, tokens, positions, slots,
                   last_pos, rng, temps, top_ks, top_ps,
                   pk=None, pv=None, apool=None, aslots=None,
                   gmask=None):
        # Prefill `rows` requests into fresh zero rows at once, then
        # splice each row into the pool cache (donated => in-place, no
        # full-cache copy). Padding rows (beyond the real requests)
        # carry slots[0] as their destination; the splice loop runs in
        # DESCENDING row order so the real row 0 is written last and
        # overwrites any padding garbage at that slot.
        #
        # The invariant, by kind of per-slot state:
        #
        # Keys and values (full-attention layers). Stale data from a
        # slot's previous occupant needs no clearing: this request's
        # queries only ever attend slots <= their own position, all of
        # which this prefill/decode has (re)written; pad tokens land in
        # the trash slot, which no real query ever attends. A token parked
        # there is nobody's query either: nothing reads its attention
        # output (the head runs on last_pos, the splice copies K/V), so
        # the flash forward is handed a position for it that sees no key,
        # and a bucket's padded tail costs no kv block
        # (models/transformer._cached_attention).
        #
        # Recurrent state and conv tail (linear-attention layers;
        # short-convolution layers keep the tail alone; KVCache). They have
        # no slot axis, so no trash slot, and every token that reaches them
        # changes the answer.
        # The programs therefore keep four rules themselves:
        #  (a) reset: every scratch row starts from ZERO state and tail,
        #      and both are spliced into the pool at the slot with the
        #      K/V rows — a slot's previous occupant cannot leak;
        #  (b) mask: tokens of a bucket beyond a row's last real one (and
        #      whole padding rows) are named by `token_mask` and leave
        #      state and tail exactly as the last real token left them
        #      (decay 1, write strength 0, tail taken at the real length);
        #  (c) freeze: in the decode chunk a row that is not `alive`
        #      keeps its state bit for bit (make_decode_fn);
        #  (d) `cache_view` slices only k / v.
        # Nothing that rewinds a cursor or shares K/V between requests is
        # sound for such a layer: InferenceEngine refuses speculation,
        # prefix registration, adapter pools and paging for these models.
        #
        # Compressed keys (full-attention layers with a sparse read;
        # KVCache.ckeys). The prefill writes every entry of the scratch row
        # from the row's keys, and the row is spliced into the pool like
        # the K/V row. An entry is read only by a query at or behind the
        # token that completes it, so a previous occupant's entries are
        # hidden without clearing, as its keys are; a parked token
        # completes none.
        #
        # Ring leaves (sliding-attention layers; KVCache). A window layer
        # attends this call's own keys
        # (the prompt is prefilled whole), writes the row's last `ring`
        # real tokens at position mod ring and drops the rest — padding
        # too: a ring has no trash slot. The scratch row's ring is spliced
        # into the pool at the slot like the K/V row. A slot of the ring
        # is valid by its age against the row's length alone, so a
        # previous occupant's tokens are hidden without clearing. A
        # spliced prefix would lie UNDER the call's own keys: refused.
        #
        # First-token sampling lives INSIDE the jit: an eager sampling
        # chain here compiled ~20 tiny programs at the first admission
        # that warmup never hit.
        # One dispatch also means one host round-trip per admission
        # group. rng advances functionally (split in, successor out).
        rows = tokens.shape[0]
        # Scratch rows stay in the activation dtype even when the pool
        # is int8: prefill attention then runs at full precision, and
        # each row is quantized exactly once at the splice below.
        cache1 = KVCache.create(cfg, rows, cache_len)
        if pk is not None:
            # Shared-prefix reuse: the registered prefix's K/V
            # [L, plen, kv_h, d] lands in slots [0, plen) of every
            # scratch row (exact length — no pad keys a suffix query
            # could wrongly attend), and `tokens` holds only the
            # SUFFIX, positions starting at plen.
            plen = pk.shape[1]
            cache1 = dataclasses.replace(
                cache1,
                k=cache1.k.at[:, :, :plen].set(
                    pk[:, None].astype(cfg.activation_dtype)),
                v=cache1.v.at[:, :, :plen].set(
                    pv[:, None].astype(cfg.activation_dtype)))
        adapters = None if apool is None else (apool, aslots)
        # Rule (b): padding is parked at the trash slot, cache_len - 1. A
        # sparse FFN routes such a token to no expert.
        token_mask = (positions < cache_len - 1
                      if cfg.has_recurrent_state or sparse else None)
        # The head runs on each row's LAST prompt position only: the
        # sampled token's logits are the same numbers, and the
        # [rows, bucket, vocab] float32 tensor (6.6 GB at 16 384 tokens of
        # a 100 352 vocabulary) is never made.
        # No row is longer than the bucket (and a prefix under it).
        acts, cache1, *moe = forward(
            cfg, params, tokens, positions=positions, cache=cache1,
            adapters=adapters, token_mask=token_mask,
            return_activations=True, with_moe_counts=sparse,
            row_len_bound=tokens.shape[1] + (0 if pk is None
                                             else pk.shape[1]))
        last_acts = jnp.take_along_axis(
            acts, last_pos[:, None, None], axis=1)[:, 0]
        last_logits = project_logits(cfg, params, last_acts)
        with jax.named_scope("kv_splice"):
            leaves = cache_leaves(cfg, pool.quantized)
            scratch = {leaf.name: getattr(cache1, leaf.name)
                       for leaf in leaves}
            if pool.quantized:
                from runbooks_tpu.ops.quantization import quantize_kv

                scratch["k"], scratch["k_scale"] = quantize_kv(cache1.k)
                scratch["v"], scratch["v_scale"] = quantize_kv(cache1.v)

            def splice(leaf):
                """A leaf's scratch rows into the pool's, at their slots."""
                new = getattr(pool, leaf.name)
                for r in range(rows - 1, -1, -1):
                    new = jax.lax.dynamic_update_slice_in_dim(
                        new, scratch[leaf.name][:, r:r + 1], slots[r],
                        axis=1)
                return new

            recurrent = [leaf for leaf in leaves
                         if leaf.group == "recurrent_state"]
            spliced = {leaf.name: splice(leaf) for leaf in leaves
                       if leaf not in recurrent}
            with jax.named_scope("state_splice"):    # rule (a)
                spliced.update((leaf.name, splice(leaf))
                               for leaf in recurrent)
        rng, sub = jax.random.split(rng)
        first = sample(last_logits, sub, temps, top_ks, top_ps,
                       gmask=gmask)
        # A sparse model's programs return one thing more: (counts, hits).
        return (first, dataclasses.replace(pool, **spliced), rng,
                *map(dispatch_stats, moe))

    return prefill_fn


def dispatch_stats(counts):
    """(counts, hits) of one forward of a sparse model: counts [sparse
    layers, experts held + 1] as forward(with_moe_counts=True) gives them,
    hits the (layer, expert) pairs that got at least one token — whose
    weights this forward had to read."""
    return counts, jnp.sum(counts[:, :-1] > 0, dtype=jnp.int32)


def make_prefix_build_fn(cfg: ModelConfig, cache_len: int):
    """Prefix-KV builder: one full bucket-width row; the caller slices
    to the actual prefix length eagerly. Keeping plen OUT of the jit key
    means one compiled program per bucket — a bounded set
    warmup(prefix_build=True) can pre-compile, so a runtime /v1/prefix
    registration never compiles on the serving worker thread (a cold
    compile there stalls every stream)."""

    def prefix_build_fn(params, tokens, positions):
        row_shape = (cfg.num_layers, 1, cache_len, cfg.num_kv_heads,
                     cfg.head_dim)
        c1 = KVCache(k=jnp.zeros(row_shape, cfg.activation_dtype),
                     v=jnp.zeros(row_shape, cfg.activation_dtype),
                     index=jnp.zeros((), jnp.int32))
        _, c1 = forward(cfg, params, tokens, positions=positions,
                        cache=c1)
        return c1.k[:, 0], c1.v[:, 0]

    return prefix_build_fn


# The two per-slot operand blocks of a decode program, by row: one int32
# [7, slots] and one float32 [2, slots]. The first four int rows are the
# scan's carry, which the program returns advanced; the rest change only
# when a slot changes hands. The engine's host mirror of the blocks IS its
# per-slot state (InferenceEngine.__init__).
ROW_TOKEN, ROW_POS, ROW_LEFT, ROW_ALIVE, ROW_TOP_K, ROW_EOS, ROW_ASLOT = \
    range(7)
ROW_TEMP, ROW_TOP_P = range(2)


def advance_rows(nxt, pos, alive, left, eos_ids, max_len: int):
    """The device's copy of the finish rules (EOS, max_tokens budget,
    cache out of room — the host's is InferenceEngine._take_tokens), after
    one decode step sampled `nxt`: (pos, alive, left) of the next step."""
    pos = pos + alive
    left = left - alive
    hit_eos = (eos_ids >= 0) & (nxt == eos_ids)
    return pos, alive & ~hit_eos & (left > 0) & (pos < max_len), left


def make_decode_fn(cfg: ModelConfig, chunk: int, max_len: int,
                   pad_slot: int, view: int, weight_layouts=None):
    """`chunk` decode steps in one jit call (lax.scan). Per-slot
    liveness is tracked ON DEVICE with exactly the host's finish rules
    (EOS, max_tokens budget, cache out-of-room), so the host can take
    (tokens, valid) afterwards and land in the same slot state as
    chunk=1 step-at-a-time would, and the scan's final carry — (token,
    position, alive, budget left) a slot — is returned beside them: it is
    the next chunk's operands when no slot changed hands in between
    (pack_decode_fn). rng advances functionally (successor key returned)
    — no eager split on the host per chunk. `weight_layouts` is where the
    weights lie, for forward: the layer loop inside the step loop reads
    them there (serve/weight_layout.py)."""

    sparse = bool(cfg.moe_num_experts)

    def decode_fn(params, cache, tokens, positions, rng,
                  temperature, top_k, top_p, eos_ids, remaining, active,
                  apool=None, aslots=None, gmask=None):
        # gmask [B, vocab] is each slot's allowed-token row AT CHUNK
        # START; it stays fixed across the scan, so it is exact only for
        # the chunk's first step. The host takes exactly one token per
        # chunk for constrained slots (_take_chunk) — chunk=1 (the CPU
        # default) degenerates to fully exact per-step masking.
        rng, step_rng = jax.random.split(rng)
        keys = jax.random.split(step_rng, chunk)
        adapters = None if apool is None else (apool, aslots)

        def body(carry, key):
            cache, tok, pos, alive, left = carry
            p = jnp.where(alive, pos, pad_slot)
            # Rule (c) of make_prefill_fn's invariant: a parked row's
            # recurrent state does not move.
            logits, cache, *moe = forward(
                cfg, params, tok[:, None], positions=p[:, None],
                cache=cache, cache_view=view, adapters=adapters,
                token_mask=(alive[:, None]
                            if cfg.has_recurrent_state or sparse else None),
                with_moe_counts=sparse, weight_layouts=weight_layouts)
            nxt = sample(logits[:, -1], key, temperature, top_k, top_p,
                         gmask=gmask)
            nxt = jnp.where(alive, nxt, tok)
            # Every step reads the weights of the experts it hits: the
            # steps' counts and hits add up.
            out = (nxt, alive, *map(dispatch_stats, moe))
            pos, alive, left = advance_rows(nxt, pos, alive, left, eos_ids,
                                            max_len)
            return (cache, nxt, pos, alive, left), out

        init = (cache, tokens, positions, active, remaining)
        (cache, *carry), (toks, valid, *moe) = jax.lax.scan(body, init, keys)
        return (toks, valid, tuple(carry), cache, rng,
                *jax.tree.map(lambda a: a.sum(axis=0), moe))

    return decode_fn


def pack_decode_fn(decode_fn):
    """The decode program as the engine runs it: `decode_fn`
    (make_decode_fn, or the paged one, whose page table stays the leading
    operand) over the two per-slot blocks. Returns (pulled, ints,
    cache, rng, *moe): `pulled` int32 [chunk + 2, slots] is everything the
    host reads of a chunk in ONE transfer — the tokens a step, then the
    count of tokens a slot emitted (a row is alive for a prefix of the
    chunk, so they are its first ones), then who is alive after it —
    and `ints` is the block with the carry rows advanced, which stays on
    the device and goes straight into the next chunk."""

    def packed(params, cache, *operands, apool=None, gmask=None):
        *table, ints, floats, rng = operands
        toks, valid, (tok, pos, alive, left), cache, rng, *moe = decode_fn(
            params, cache, *table, ints[ROW_TOKEN], ints[ROW_POS], rng,
            floats[ROW_TEMP], ints[ROW_TOP_K], floats[ROW_TOP_P],
            ints[ROW_EOS], ints[ROW_LEFT], ints[ROW_ALIVE] != 0,
            apool=apool, aslots=None if apool is None else ints[ROW_ASLOT],
            gmask=gmask)
        pulled = jnp.concatenate([
            toks, valid.sum(axis=0, dtype=jnp.int32)[None],
            alive.astype(jnp.int32)[None]])
        ints = jnp.stack([tok, pos, left, alive.astype(jnp.int32),
                          *ints[ROW_TOP_K:]])
        return (pulled, ints, cache, rng, *moe)

    # The compiled module is named after the function (jit_decode_fn): a
    # profiler capture's readers find the program by it.
    packed.__name__ = decode_fn.__name__
    return packed


def make_verify_fn(cfg: ModelConfig, draft_tokens: int, pad_slot: int,
                   view: int):
    """One batched draft-verify forward for speculative decoding
    (docs/speculative-decoding.md): score K drafted tokens per slot in a
    single ``[B, K+1]`` dispatch. ``tokens[:, 0]`` is each slot's
    carry-in token (the last sampled token, whose KV the next step owes
    the cache anyway) and ``tokens[:, 1:1+d]`` its d proposed draft
    tokens; rows park positions past their draft length (and inactive
    rows entirely) at the trash slot, so a mixed batch — some slots
    drafting K tokens, some none — runs as ONE program.

    The forward writes KV for all live positions; the HOST accepts the
    longest verified prefix per slot and rolls the write cursor back by
    simply not advancing ``lengths`` past it — rejected-draft KV beyond
    the cursor is rewritten by the next dispatch before anything can
    attend it (the same stale-data invariant prefill relies on), so
    rollback costs zero device work. Verdicts come from
    ops/sampling.speculative_verify: greedy accepts exact argmax
    matches; temperature sampling uses exact rejection sampling against
    the engine's own filtered distribution, so speculation never changes
    the output distribution."""
    K = draft_tokens

    def verify_fn(params, cache, tokens, positions, draft_len, rng,
                  temperature, top_k, top_p, active,
                  apool=None, aslots=None, gmask=None):
        offs = jnp.arange(K + 1, dtype=jnp.int32)[None, :]
        live = active[:, None] & (offs <= draft_len[:, None])
        pos = jnp.where(live, positions[:, None] + offs, pad_slot)
        adapters = None if apool is None else (apool, aslots)
        logits, cache = forward(cfg, params, tokens, positions=pos,
                                cache=cache, cache_view=view,
                                adapters=adapters)
        rng, sub = jax.random.split(rng)
        accept, resid, full = speculative_verify(
            logits, tokens[:, 1:], sub, temperature, top_k, top_p,
            gmask=gmask)
        return accept, resid, full, cache, rng

    return verify_fn


class WarmupRun:
    """The clocks of one warm-up sweep, shared by the dense and the paged
    engine: compile counts from the sentinel, the set-up phases
    (``warmup.trace`` / ``.compile`` / ``.run`` / ``.cost_capture``,
    obs/trace.py PhaseSeconds) and the roofline cost capture."""

    def __init__(self):
        self.sentinel = obs_device.SENTINEL
        self._compiles = self.sentinel.total
        self._seconds = self.sentinel.compile_seconds
        self._hits = self.sentinel.cache_hits
        self._t0 = time.perf_counter()
        self.phases = PhaseSeconds()
        # Roofline cost capture re-traces each shape once (no second
        # backend compile); RBT_DEVICE_OBS=0 skips it when even that
        # startup cost matters.
        self._capture_costs = os.environ.get("RBT_DEVICE_OBS", "1") != "0"

    def program(self, name: str, sig: str, fn, *args, **kwargs):
        """Warm one program shape and return what the call returned. The
        call's wall time splits into the backend compile (or cache load:
        the sentinel's compile clock) and the rest, which is tracing and
        lowering; its execution is not waited for here (``finish``)."""
        if self._capture_costs:
            with self.phases.timed("warmup.cost_capture", program=name):
                cost = obs_device.program_cost("serve", name, sig, fn,
                                               *args, **kwargs)
            if cost is None and not obs_device.PROGRAMS.has_cost(
                    "serve", name, sig):
                # The backend gives no analysis (a TPU gives none): one
                # probe, not one re-trace per program. The roofline
                # fields of /debug/programs are then absent.
                self._capture_costs = False
        compiled = self.sentinel.compile_seconds
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        wall = time.perf_counter() - t0
        compiled = self.sentinel.compile_seconds - compiled
        self.phases.add("warmup.compile", compiled, program=name)
        self.phases.add("warmup.trace", max(wall - compiled, 0.0),
                        program=name)
        return out

    def finish(self, outputs) -> dict:
        """Wait for the first executions still running (they overlapped
        the tracing of the programs after them) and return the census
        fields both engines report."""
        with self.phases.timed("warmup.run"):
            # rbt-check: ignore[device-sync] end of the warm-up sweep, before any traffic: readiness waits for the warmed programs' first executions
            jax.block_until_ready(outputs)
        return {
            "compiles": self.sentinel.total - self._compiles,
            "compile_seconds": round(
                self.sentinel.compile_seconds - self._seconds, 3),
            # Compile requests the persistent cache answered: > 0 on a
            # warm restart (utils/jax_cache.py), 0 on a cold one.
            "cache_hits": self.sentinel.cache_hits - self._hits,
            "warmup_seconds": round(time.perf_counter() - self._t0, 3),
            # Seconds by set-up phase (docs/observability.md); the entry
            # point's startup.* join them in GET /debug/programs.
            "phases": self.phases.snapshot(),
        }


# What the engine refuses for a model that keeps a group of per-slot state
# beside K/V (models/transformer.LeafTraits.group: the recurrent state and
# tails of docs/hybrid-models.md, the latent of docs/sparse-latent-models.md,
# the rings of docs/window-full-models.md), each by its mechanism:
# {(feature, group): why}. The recurrent-state invariant (make_prefill_fn)
# rules its features out; the rest is only sound, or only written, for keys
# and values a head.
_PREFIX = ("prefix registration (register_prefix, auto_prefix_chat, "
           "warm_prefix)")
_REFUSED = {
    ("speculative decoding", "recurrent_state"):
        "a rejected draft is rolled back by not advancing the K/V cursor, "
        "and a recurrent state has no cursor to hold back",
    ("speculative decoding", "latent_cache"):
        "the verify forward's [slots, K+1] queries take neither of the two "
        "attention paths a test holds (absorbed at one query a row, expanded "
        "over a prefill bucket)",
    ("speculative decoding", "kv_ring"):
        "the verify forward writes K + 1 tokens a row before its first query "
        "reads; the ring's margin would hold them, but no test holds the "
        "[slots, K+1] path or its rollback through a ring",
    ("an adapter pool", "recurrent_state"):
        "pooled LoRA lanes target the attention projections of a homogeneous "
        "stack",
    ("an adapter pool", "latent_cache"):
        "pooled LoRA lanes target the wq / wk / wv / wo of per-head "
        "attention",
    ("an adapter pool", "kv_ring"):
        "pooled LoRA lanes target the attention projections of one "
        "homogeneous stack; window layers have stacks of their own",
    ("kv_paging: paged", "recurrent_state"):
        "a page table has one kind of page and the radix tree shares K/V "
        "pages only; the state after a shared prefix would have to be "
        "snapshotted with them",
    ("kv_paging: paged", "latent_cache"):
        "pages are [page, kv_heads, head_dim] and shard over KV heads; a "
        "latent page has no head axis",
    ("kv_paging: paged", "kv_ring"):
        "a page table maps a row's positions to pages of one kind and one KV "
        "head count; a ring is a row's own, a window long, and has another "
        "head count",
    ("quantize_kv", "latent_cache"):
        "the int8 pool keeps one scale a KV head, and a latent has no head "
        "axis",
    ("quantize_kv", "kv_ring"):
        "the ring leaves have no int8 form (no scales beside them)",
    ("a tensor mesh axis > 1", "latent_cache"):
        "the latent cache has no head axis to shard, and the absorbed "
        "decode's head split is not written",
    ("a tensor mesh axis > 1", "kv_ring"):
        "the flash forward with a window or a sink is not launched per "
        "shard, and the ring leaves' layout by KV head is not held by a test",
    (_PREFIX, "recurrent_state"):
        "a shared prefix splices K/V only; the recurrent state after the "
        "prefix would have to be stored and restored with it",
    (_PREFIX, "latent_cache"):
        "a shared prefix is stored and spliced as K/V a head; the latent "
        "rows have no such path",
    (_PREFIX, "kv_ring"):
        "a window layer prefills a prompt whole against its own keys; a "
        "spliced prefix would lie under them, and its ring is not stored",
    ("speculative decoding", "kv_compressed"):
        "the verify forward's [slots, K+1] queries would rewrite every "
        "compressed key of the pool a step, and no test holds its rollback "
        "through them",
    ("an adapter pool", "kv_compressed"):
        "a tenant's adapter changes the keys, so the compressed keys and "
        "the choice of blocks; no test holds a pooled lane through them",
    ("kv_paging: paged", "kv_compressed"):
        "a page holds keys and values; the compressed keys of a row span "
        "page boundaries (a kernel of 32 keys every 16) and have no page of "
        "their own",
    ("quantize_kv", "kv_compressed"):
        "the compressed keys are means of the keys as stored, and no test "
        "holds the choice of blocks against int8 keys",
    ("a tensor mesh axis > 1", "kv_compressed"):
        "the choice of blocks and the walk by query blocks are not written "
        "per shard of KV heads",
    (_PREFIX, "kv_compressed"):
        "a shared prefix splices K/V only; the row's compressed keys would "
        "have to be stored and spliced with it, and a suffix prefilled "
        "behind it chooses by the whole row's length",
}
# The layers a refusal names, by the kind whose leaf it is.
_REFUSED_LAYERS = {"full_attention": "sparse-read (block-sparse) attention",
                   "linear_attention": "recurrent (linear-attention)",
                   "conv": "recurrent (short-convolution)",
                   "latent_attention": "latent (MLA) attention",
                   "sliding_attention": "sliding (window) attention"}


class InferenceEngine:
    """Batched generation over a fixed slot pool. Thread-unsafe by design;
    drive it from one loop (the API server wraps it in a single worker)."""

    # The ServeOptions.kv_paging this class serves (serve/paging.py:
    # "paged"); create_server picks the class by it.
    kv_paging = "off"

    def __init__(self, cfg: ModelConfig, params: Params, *, seed: int = 0,
                 mesh=None, tokenizer=None, **options):
        """``options`` are fields of ServeOptions (api/serve_params.py,
        where each is documented); the record is kept as ``self.options``.

        mesh: optional jax.sharding.Mesh for sharded serving — params
        shard by the model's logical axes (tensor parallelism over heads/
        mlp, fsdp over embed) and the KV cache shards batch over data/fsdp
        and kv-heads over tensor. All jitted steps then run SPMD under the
        mesh; XLA inserts the per-layer collectives.

        tokenizer: maps grammar bytes onto token ids (needed with grammar:
        on); with grammar off it only feeds `tokenizer_fingerprint`
        (/debug/programs)."""
        self.options = options = ServeOptions(
            **{**options, "kv_paging": self.kv_paging})
        if mesh is not None:
            self._check_mesh(cfg, mesh)
        self.cfg = cfg
        self.mesh = mesh
        max_slots = self.max_slots = options.max_slots
        # What the engine derives where the record says "default": the
        # two backend-tuned shapes here, the rest below beside its input.
        tuning = backend_tuning()
        self.decode_chunk = (options.decode_chunk
                             if options.decode_chunk is not None
                             else tuning["decode_chunk"])
        self.draft_tokens = (options.draft_tokens
                             if options.draft_tokens is not None
                             else tuning["draft_tokens"])
        self._spec_index: Optional[NgramDraftIndex] = None
        if options.speculative != "off":
            self._spec_index = NgramDraftIndex(
                max_slots, options.ngram_max, options.ngram_min)
        # Speculation accounting (cumulative; /metrics + spec_stats()).
        self.spec_drafted = 0        # draft tokens proposed
        self.spec_accepted = 0       # draft tokens verified-accepted
        self.spec_verify_steps = 0   # verify dispatches
        # accept-rate bucket -> [tokens emitted, dispatch seconds]
        self._spec_rate_buckets = {b: [0, 0.0]
                                   for b in _ACCEPT_RATE_BUCKETS}
        if mesh is not None and int(mesh.shape.get("stage", 1)) > 1:
            raise ValueError(
                "pipeline (stage) parallelism is a training-path feature; "
                "serve with tensor/data parallelism instead (mesh_tensor)")
        # The KV pool is int8 when quantize_kv says so, else exactly when
        # the weights are quantized: decode is HBM-bandwidth-bound, so the
        # two halves of the bytes it streams shrink together.
        self.quantize_kv = (options.quantize_kv
                            if options.quantize_kv is not None
                            else cfg.quantize != "none")
        # What a kind of per-slot state rules out (_REFUSED), refused here
        # with the reason: nothing below would fail loudly.
        for feature, asked in (
                ("speculative decoding", options.speculative != "off"),
                ("an adapter pool", options.adapter_pool > 0),
                ("kv_paging: paged", self.kv_paging == "paged"),
                ("quantize_kv", self.quantize_kv),
                ("a tensor mesh axis > 1", mesh is not None
                 and int(mesh.shape.get("tensor", 1)) > 1)):
            if asked:
                self._refuse(feature)
        if mesh is not None:
            import contextlib

            from runbooks_tpu.models.transformer import param_logical_axes
            from runbooks_tpu.ops.quantization import quantized_logical_axes
            from runbooks_tpu.parallel.sharding import (
                spec_for_array,
                tree_shardings,
            )
            from jax.sharding import NamedSharding

            params = jax.device_put(
                params,
                tree_shardings(jax.eval_shape(lambda: params),
                               quantized_logical_axes(
                                   params, param_logical_axes(cfg)), mesh))

            def cache_sharding(shape, logical):
                spec = spec_for_array(shape, logical, mesh)
                return NamedSharding(mesh, spec)

            self._cache_sharding = cache_sharding
            self._mesh_ctx = lambda: jax.set_mesh(mesh)
        else:
            self._cache_sharding = None
            import contextlib

            self._mesh_ctx = contextlib.nullcontext
        self.params = params
        # The engine's tree is the one _place_weights rebuilds; this frame
        # must not keep the sources of the leaves it re-places alive.
        del params
        # Off a mesh: the one device the weights are on (_home).
        first = jax.tree.leaves(self.params)[0]
        self._device_sharding = jax.sharding.SingleDeviceSharding(
            next(iter(first.devices())) if hasattr(first, "devices")
            else jax.devices()[0])
        self.max_seq_len = options.max_seq_len or cfg.max_seq_len
        self._pad_slot = self.max_seq_len  # trash slot index
        # Multi-tenant LoRA adapter pool (serve/lora_pool.py,
        # docs/multi-tenant-lora.md): None when off — the engine then
        # compiles the plain (adapter-free) program set and requests
        # carrying an `adapter` 400 at validation.
        self.adapters = None
        if options.adapter_pool > 0:
            from runbooks_tpu.serve.lora_pool import AdapterPool

            self.adapters = AdapterPool(
                cfg, options.adapter_pool, options.lora_rank,
                options.lora_targets, root=options.adapter_dir)
            if mesh is not None:
                from runbooks_tpu.ops.lora import \
                    adapter_pool_logical_axes
                from runbooks_tpu.parallel.sharding import tree_shardings

                self.adapters.tree = jax.device_put(
                    self.adapters.tree,
                    tree_shardings(
                        jax.eval_shape(lambda: self.adapters.tree),
                        adapter_pool_logical_axes(self.adapters.tree),
                        mesh))
        # The host mirror of a decode program's two per-slot blocks (rows:
        # ROW_*). Its rows ARE the engine's per-slot state — the arrays
        # below are views of them — so placing the blocks copies nothing
        # together first; the sampling rows are written when a slot
        # changes hands (_activate_slot), never rebuilt a chunk.
        self._slot_ints = np.zeros((7, max_slots), np.int32)
        self._slot_floats = np.zeros((2, max_slots), np.float32)
        self._slot_floats[ROW_TOP_P] = 1.0
        self._slot_ints[ROW_EOS] = -1
        # Per-slot adapter lane indices (-1 = base-only/trash lane): the
        # operand every adapter-aware dispatch gathers A/B by.
        self.adapter_slots = self._slot_ints[ROW_ASLOT]
        self.adapter_slots[:] = -1
        # The blocks on the device, (ints, floats), as the last decode
        # chunk returned them: the next chunk's operands as they are. None
        # once the host changed a slot behind the device's back (admission,
        # a finish only the host saw, deadline, preemption, verify, reset);
        # the next chunk then places the host mirror again.
        self._dev_blocks: Optional[tuple] = None
        # Decoded chunks by when their tokens were handed over, and by
        # where their operands came from (/metrics:
        # serve_decode_chunks_total, serve_decode_operand_places_total).
        self.decode_deliveries = {"deferred": 0, "inline": 0}
        self.operand_places = {"carry": 0, "rebuilt": 0}
        # The delivery half of the last decoded chunk, waiting for the next
        # dispatch to hide behind: (request, tokens, finish reason) a row,
        # by request OBJECT — its slot may be another request's by then.
        self._parked: List[tuple] = []
        self._init_cache()
        self.prefill_budget = (options.prefill_budget
                               if options.prefill_budget is not None
                               else self.max_seq_len)
        self.max_queue = (options.max_queue
                          if options.max_queue is not None
                          else max(16, 4 * max_slots))
        # Per-class queued-entry bounds.
        self._class_bounds = {
            cls: max(1, int(np.ceil(self.max_queue * share)))
            for cls, share in options.queue_shares.items()}
        # Grammar-constrained decoding (serve/grammar.py): with
        # grammar="on" every dispatch carries a gmask operand, so the
        # masked program variants REPLACE the plain ones in the census
        # (same discipline as the adapter pool's apool/aslots operands —
        # variants never multiply the compiled set).
        self.tokenizer = tokenizer
        self._token_vocab = None
        self._grammar_cache = None
        self.grammar_requests = 0          # compiled-constraint requests
        self.grammar_completed = 0         # grammar_complete finishes
        self.grammar_draft_truncations = 0  # drafts cut at illegal token
        if options.grammar == "on":
            from runbooks_tpu.serve.grammar import GrammarCache, TokenVocab

            if tokenizer is None:
                raise ValueError(
                    "grammar: on needs the tokenizer (the DFA compiler "
                    "maps grammar bytes onto token ids); pass tokenizer=")
            self._token_vocab = TokenVocab.from_tokenizer(tokenizer)
            self._grammar_cache = GrammarCache(
                self._token_vocab, cfg.vocab_size,
                capacity=options.grammar_cache_size)
        # Sparse layers (models/moe.py): assignments by held expert (and
        # last, those routed elsewhere) summed over layers, and by program
        # the (layer, expert) pairs hit and offered. Fed by the counts the
        # programs return; /metrics reads moe_stats().
        self._moe_counts = np.zeros(
            (cfg.moe_experts_here + 1) if cfg.moe_num_experts else 0,
            np.int64)
        self._moe_hits = {"prefill": [0, 0], "decode": [0, 0]}
        # Rows of the order by expert that the programs sent to the experts
        # and brought back (moe.rows_moved: the held rows in whole windows,
        # or all a forward's rows), a layer and forward; and how many of
        # those (layer, forward) pairs worked all their rows at once.
        self._moe_rows = {"prefill": [0, 0], "decode": [0, 0]}
        # Sum over dispatches and layers of the most loaded held expert's
        # assignments: against the mean (here / held) it is the imbalance
        # a dispatch sees.
        self._moe_peak = 0
        self.deadline_expired = 0   # observability/tests
        self.preemptions = 0          # slots preempted (observability)
        self.preempted_resumed = 0    # preempted requests re-admitted
        self.lengths = self._slot_ints[ROW_POS]            # tokens in cache
        self.active = np.zeros(max_slots, bool)
        self.last_token = self._slot_ints[ROW_TOKEN]
        self.slot_req: List[Optional[Request]] = [None] * max_slots
        self.queue: List[Request] = []
        self.rng = self._commit_key(jax.random.key(seed))
        self.prefill_buckets = _buckets(self.max_seq_len)
        self.steps = 0
        # Shared-prefix KV cache: registered prompt prefixes (chat system
        # prompts) keep their per-layer K/V on device; admissions whose
        # prompt starts with a registered prefix prefill only the SUFFIX.
        # LRU-bounded; keys are token tuples, values (k, v) arrays of
        # static shape [L, plen, kv_h, d]. Decode is bandwidth-bound and
        # prefill compute is quadratic-ish in bucket size, so for a
        # B-token shared system prompt this removes a B-bucket prefill
        # per request — the next TTFT lever after bucketed views.
        # Default scales with concurrency: under auto_prefix_chat every
        # live conversation holds an entry between its turns, so a
        # 4-entry cache behind 8 slots would evict before reuse. Each
        # entry costs <= [L, plen, kv_h, d] x2 in HBM.
        self.prefix_cache_size = (options.prefix_cache_size
                                  if options.prefix_cache_size is not None
                                  else max(4, 2 * max_slots))
        # Ordered dict doubles as the LRU: last key = most recently used
        # (registration AND admission hits refresh), first key evicts.
        self._prefix_cache: "dict[tuple, tuple]" = {}
        self.prefix_tokens_reused = 0   # observability/tests
        # Prefix hit rate (docs/observability.md; the baseline number the
        # paged-KV/radix work must beat): admissions that looked for a
        # registered prefix vs admissions that found one.
        self.prefix_lookups = 0
        self.prefix_hits = 0
        # Device-level observability (obs/device.py): every compile after
        # warmup() is a serve-time stall the sentinel flags; the program
        # tracker carries the live compiled-variant census + roofline
        # costs behind /debug/programs and the xla_* gauge families.
        obs_device.SENTINEL.install()
        self.warmup_census: Optional[dict] = None
        self._marked_steady = False  # one steady claim per engine
        # Deterministic engine-step fault injection
        # (docs/fault-tolerance.md): RBT_FAULT_INJECT=engine:K makes
        # step() raise EngineStepFailed once, at decode step K — the
        # serving worker's crash handler (doom futures, incident
        # capture, reset) is exercisable without a real XLA failure.
        # Parsed once here, not per step: the hot loop must not pay an
        # env read per chunk.
        self._fault_step: Optional[int] = None
        # RBT_FAULT_INJECT=swapfail:K — the Kth host-tier swap copy
        # (swap-out or swap-in, shared count) fails; the engine must
        # degrade to drop/recompute without crashing or leaking pages
        # (docs/fault-tolerance.md). Parsed once, same discipline as
        # engine:K.
        self._swap_fault: Optional[int] = None
        fault = os.environ.get("RBT_FAULT_INJECT", "")
        if fault.startswith("engine:"):
            try:
                self._fault_step = int(fault.split(":", 1)[1])
            except ValueError as exc:
                raise ValueError(
                    f"RBT_FAULT_INJECT={fault!r}: expected engine:K") \
                    from exc
        elif fault.startswith("swapfail:"):
            try:
                self._swap_fault = int(fault.split(":", 1)[1])
            except ValueError as exc:
                raise ValueError(
                    f"RBT_FAULT_INJECT={fault!r}: expected swapfail:K") \
                    from exc
            if self._swap_fault < 1:
                raise ValueError(
                    f"RBT_FAULT_INJECT={fault!r}: K must be >= 1")
        # Where each weight lies (jax.tree.leaves order), once placed.
        self._stack_layouts: Optional[list] = None
        self._init_programs()
        self._place_weights()

    def _check_mesh(self, cfg: ModelConfig, mesh) -> None:
        """What a subclass requires of a serving mesh, raised before
        anything is placed (the paged engine's pool geometry). A hook and
        not a constructor of the subclass's own: a second frame holding
        ``params`` would keep alive the sources of the leaves
        _place_weights re-places."""

    def _init_cache(self) -> None:
        """Allocate the engine's KV storage. Overridable: the paged
        engine (serve/paging.py) replaces the dense slot pool with a
        fixed page pool + allocator + radix tree here."""
        self.cache = self._new_pool_cache()

    def _commit_key(self, key):
        """Pin an rng key's placement (_home). A fresh key traces as an
        UNSPECIFIED-sharding jit operand while the key a dispatch RETURNS
        is committed — two cache entries for the same program, so every
        warmup-compiled program would recompile once under steady
        traffic. Committing the key up front makes warmup and runtime
        signatures identical."""
        return jax.device_put(key, self._home())

    def _init_programs(self) -> None:
        """Build and register the engine's jitted program set. Overridable
        for the same reason as _init_cache (the paged engine jits
        gather-by-page-index variants of prefill/decode instead)."""
        cfg = self.cfg
        cache_len = self.max_seq_len + 1

        prefill_fn = make_prefill_fn(cfg, cache_len)
        self._prefill = jax.jit(prefill_fn, donate_argnums=(1,))
        # Same body with the prefix splice live (jit specializes per
        # (plen, suffix-bucket, rows) shape; registrations are rare and
        # suffix buckets are the same bounded set as prefill buckets).
        self._prefill_prefix = jax.jit(
            lambda params, pool, pk, pv, *rest, **kw: prefill_fn(
                params, pool, *rest, pk=pk, pv=pv, **kw),
            donate_argnums=(1,))
        obs_device.PROGRAMS.register("serve", "prefill", self._prefill)
        obs_device.PROGRAMS.register("serve", "prefill_prefix",
                                     self._prefill_prefix)

        self._prefix_build = jax.jit(make_prefix_build_fn(cfg, cache_len))
        obs_device.PROGRAMS.register("serve", "prefix_build",
                                     self._prefix_build)

        chunk = self.decode_chunk
        max_len = self.max_seq_len

        # Decode reads the cache through a static bucketed VIEW sized to
        # current occupancy (see forward(cache_view=...)): the step is HBM-
        # bandwidth-bound, and low occupancy shouldn't pay for streaming
        # the whole max-length cache. One compiled program per view bucket;
        # writes (incl. trash-slot parking) always target the full cache.
        self.view_buckets = view_buckets_for(self.max_seq_len)
        self._decode_fns: dict = {}

        def make_decode(view: int, weight_layouts=None):
            return make_decode_fn(cfg, chunk, max_len, self._pad_slot, view,
                                  weight_layouts)

        self._make_decode = make_decode

        def decode_for(view: int):
            if view not in self._decode_fns:
                self._decode_fns[view] = self._jit_decode(
                    make_decode(view, self._stack_layouts))
                obs_device.PROGRAMS.register("serve", f"decode_v{view}",
                                             self._decode_fns[view])
            return self._decode_fns[view]

        self._decode_for = decode_for

        # Speculative verify programs: one [B, K+1] forward per view
        # bucket, same lazy-jit + tracker discipline as decode (warmup
        # compiles every view so a draft can never compile under
        # traffic).
        self._verify_fns: dict = {}

        def verify_for(view: int):
            if view not in self._verify_fns:
                self._verify_fns[view] = jax.jit(
                    make_verify_fn(cfg, self.draft_tokens, self._pad_slot,
                                   view),
                    donate_argnums=(1,))
                obs_device.PROGRAMS.register("serve", f"verify_v{view}",
                                             self._verify_fns[view])
            return self._verify_fns[view]

        self._verify_for = verify_for

    def _jit_decode(self, decode_fn):
        """Jit a decode program over the packed blocks (pack_decode_fn).
        Under a mesh the returned int block is pinned replicated, as
        _place_blocks places it: the carry then goes back in with the
        sharding the program was compiled for, not re-laid every chunk."""
        return jax.jit(pack_decode_fn(decode_fn), **self._decode_jit_kwargs())

    def _decode_jit_kwargs(self) -> dict:
        out_shardings = None
        if self.mesh is not None:
            out_shardings = (None, self._replicated(), None, None) + (
                (None,) if self.cfg.moe_num_experts else ())
        return {"donate_argnums": (1,), "out_shardings": out_shardings}

    def _place_weights(self) -> None:
        """Ask the decode program of the largest view which layout it
        wants each weight in, place the weights so, once, before anything
        is warmed, and keep where they lie for the decode programs built
        after this (serve/weight_layout.py): every program then compiles
        against that placement and none re-lays a weight a call. Where the
        program agrees with the layouts the weights have no leaf is
        touched. Whatever the backend cannot say or do leaves the weights
        as they are, counted in ``weight_layout``."""
        key, _ = self._view_key(self.max_seq_len + self.decode_chunk)
        packed = pack_decode_fn(self._make_decode(key))
        operands = (*self._table_operands(),
                    *self._place_blocks(self._slot_ints, self._slot_floats),
                    self.rng)
        kwargs = {**self._decode_kwargs(),
                  **self._grammar_warm_kwargs(
                      (self.max_slots, self.cfg.vocab_size))}

        def program(params, cache, operands, kwargs):
            # in_shardings cannot go with keyword arguments.
            return packed(params, cache, *operands, **kwargs)

        t0 = time.perf_counter()
        placement = Placement()
        with fine("startup.weight_layout") as sp, \
                obs_device.SENTINEL.expected(), self._mesh_ctx():
            # The flat list is the weights' only holder while they move:
            # a source is let go before the next leaf is put.
            leaves, tree = jax.tree.flatten(self.params)
            try:
                wanted = asked_formats(program, self.params, self.cache,
                                       operands, kwargs,
                                       **self._decode_jit_kwargs())
                self.params = None
                placement = place_weights(leaves, wanted)
            except Exception as exc:   # noqa: BLE001 - a backend without layouts
                placement.why = f"{type(exc).__name__}: {exc}"
            self.params = tree.unflatten(leaves)
            # Off a mesh only: the constraint forward() puts on a layer's
            # slice is a custom call, which the SPMD partitioner answers
            # by gathering the slice whole on every device (falcon-40b on
            # four chips: decode's temporaries 258 -> 1938 MiB, AOT).
            if self.mesh is None:
                self._stack_layouts = stack_layouts(leaves)
            sp.set(leaves_replaced=placement.leaves_replaced,
                   bytes_replaced=placement.bytes_replaced)
        # /metrics: serve_weight_leaves_replaced, serve_weight_bytes_
        # replaced; the warm-up census and its line carry the record.
        self.weight_layout = {
            **dataclasses.asdict(placement),
            "seconds": round(time.perf_counter() - t0, 3)}
        if placement.why:
            print(f"serve: weight layout: {placement.leaves_kept} leaves "
                  f"kept where they were: {placement.why}", flush=True)

    def _replicated(self):
        from jax.sharding import NamedSharding, PartitionSpec

        return NamedSharding(self.mesh, PartitionSpec())

    def _home(self):
        """Where the engine commits the state it carries from one program
        to the next (rng, the per-slot blocks, off a mesh the pool too):
        replicated over the serving mesh, or the one device the weights
        are on. Off a mesh too, because a program hands back committed
        arrays as soon as one of its operands is committed, and a weight
        _place_weights re-placed is: state that started uncommitted would
        change its jit signature after the first dispatch, and every
        warmed program would compile once more under traffic."""
        if self.mesh is not None:
            return self._replicated()
        return self._device_sharding

    def _place_blocks(self, ints, floats) -> tuple:
        """The two per-slot blocks on the device: one transfer a dtype.
        Of copies: the host mirror is written in place, and on the CPU
        backend a placed array may share its host buffer's memory."""
        return tuple(jax.device_put((ints.copy(), floats.copy()),
                                    self._home()))

    def _new_pool_cache(self) -> KVCache:
        """Fresh slot-pool cache (int8 + scales when quantize_kv), sharded
        under the serving mesh when one is configured."""
        cache = KVCache.create(self.cfg, self.max_slots, self.max_seq_len,
                               trash_slot=True, quantize_kv=self.quantize_kv)
        if self._cache_sharding is None:
            # Committed off a mesh too (_home).
            return jax.device_put(cache, self._home())

        def put(a, logical):
            return jax.device_put(a, self._cache_sharding(a.shape, logical))

        # index is committed too (the scalar's spec resolves to
        # replicated): a dispatch RETURNS it committed, so a fresh
        # uncommitted one would key a second jit entry and the first
        # prefill after every reset() would recompile under traffic.
        # Each leaf shards by its declared axes (LEAF_TRAITS).
        return dataclasses.replace(
            cache, index=put(cache.index, ()),
            **{leaf.name: put(getattr(cache, leaf.name), leaf.axes)
               for leaf in cache_leaves(self.cfg, self.quantize_kv)})

    def _refuse(self, feature: str) -> None:
        """Raise what _REFUSED says of `feature` for the first group of
        per-slot state the model keeps, in KVCache's field order."""
        for leaf in cache_leaves(self.cfg):
            why = _REFUSED.get((feature, leaf.group))
            if why is not None:
                raise ValueError(
                    f"{feature} is not supported for a model with "
                    f"{_REFUSED_LAYERS[leaf.kind]} layers: {why}")

    def _decode_kwargs(self) -> dict:
        """The adapter pool as a decode program takes it: the lane indices
        are a row of its int block."""
        return {} if self.adapters is None else {"apool": self.adapters.tree}

    def _adapter_kwargs(self, aslots=None) -> dict:
        """Extra operands for adapter-aware dispatches: the pool pytree
        plus per-row lane indices (defaults to the per-slot lanes — the
        decode/verify shape). {} when the pool is off, so the plain
        program set stays untouched."""
        if self.adapters is None:
            return {}
        if aslots is None:
            aslots = self.adapter_slots
        return {"apool": self.adapters.tree,
                "aslots": jnp.asarray(aslots)}

    # -- grammar-constrained decoding (serve/grammar.py) ----------------
    #
    # Mask-operand builders, {} when grammar is off (the plain program
    # set stays untouched — same shape as _adapter_kwargs). When on,
    # EVERY dispatch passes a mask: all-True rows for unconstrained
    # lanes, so the masked program variants are the only ones compiled.

    @property
    def tokenizer_fingerprint(self) -> Optional[str]:
        """Stable vocab content hash (sha256 over id -> bytes), exposed
        at /debug/programs and keying the grammar compile cache — a
        model/tokenizer swap can never serve a stale mask."""
        if self._token_vocab is None:
            if self.tokenizer is None:
                return None
            from runbooks_tpu.serve.grammar import GrammarError, TokenVocab

            try:
                self._token_vocab = TokenVocab.from_tokenizer(self.tokenizer)
            except GrammarError:
                return None
        return self._token_vocab.fingerprint

    def _observe_mask_build(self, t0: float) -> None:
        obs_metrics.REGISTRY.observe(
            "serve_grammar_mask_build_seconds",
            time.perf_counter() - t0,
            buckets=_INTER_TOKEN_BUCKETS,
            help_text="Host-side gmask operand build time per dispatch "
                      "(grammar-constrained decoding).")

    def _grammar_prefill_kwargs(self, group: List[tuple],
                                rows: int) -> dict:
        """[rows, vocab] first-token mask for one admission group.
        Resumed (preempted) rows stay all-True: their prefill-sampled
        token is discarded (_activate_slot), so masking it buys
        nothing."""
        if self._grammar_cache is None:
            return {}
        t0 = time.perf_counter()
        mask = np.ones((rows, self.cfg.vocab_size), bool)
        for i, (_, req) in enumerate(group):
            if (req._grammar is not None
                    and not (req._preempted and req.output_tokens)):
                mask[i] = req._grammar.mask_row()
        self._observe_mask_build(t0)
        return {"gmask": jnp.asarray(mask)}

    def _grammar_decode_kwargs(self) -> dict:
        """[max_slots, vocab] per-slot allowed-token rows at the current
        cursor states (all-True for unconstrained/inactive slots)."""
        if self._grammar_cache is None:
            return {}
        t0 = time.perf_counter()
        mask = np.ones((self.max_slots, self.cfg.vocab_size), bool)
        for slot in range(self.max_slots):
            req = self.slot_req[slot]
            if self.active[slot] and req is not None \
                    and req._grammar is not None:
                mask[slot] = req._grammar.mask_row()
        self._observe_mask_build(t0)
        return {"gmask": jnp.asarray(mask)}

    def _grammar_verify_kwargs(self, drafts: dict) -> dict:
        """[max_slots, K+1, vocab] per-position verify masks: position 0
        is the slot's current cursor state (the token after the carry-in);
        position i the state after consuming the draft prefix d[:i].
        Drafts were pre-truncated to legal prefixes (_collect_drafts), so
        the non-mutating walk covers every drafted position; rows past a
        slot's draft length stay all-True (their samples are parked and
        never emitted)."""
        if self._grammar_cache is None:
            return {}
        t0 = time.perf_counter()
        K = self.draft_tokens
        mask = np.ones((self.max_slots, K + 1, self.cfg.vocab_size), bool)
        for slot, d in drafts.items():
            req = self.slot_req[slot]
            cur = None if req is None else req._grammar
            if cur is None:
                continue
            states = [cur.state] + cur.walk(d)
            for i, state in enumerate(states):
                mask[slot, i] = cur.dfa.masks[state]
        self._observe_mask_build(t0)
        return {"gmask": jnp.asarray(mask)}

    def _grammar_warm_kwargs(self, shape: tuple) -> dict:
        """All-allow mask of the given shape for warmup dispatches, so
        the gmask-live signatures are exactly the warmed ones."""
        if self._grammar_cache is None:
            return {}
        return {"gmask": jnp.ones(shape, bool)}

    def grammar_stats(self) -> dict:
        """Grammar-mode snapshot (/debug/programs): compile-cache
        hit/miss/size, compile seconds, and engine-side counters."""
        out = {"mode": self.options.grammar}
        if self._grammar_cache is None:
            return out
        out.update(self._grammar_cache.stats())
        out.update({"requests_total": self.grammar_requests,
                    "completed_total": self.grammar_completed,
                    "draft_truncations_total":
                        self.grammar_draft_truncations})
        return out

    def _view_for(self, max_pos: int) -> int:
        """Smallest view bucket covering every query position this chunk
        can reach (caller passes max active length + chunk)."""
        for v in self.view_buckets:
            if max_pos <= v:
                return v
        return self.view_buckets[-1]

    def warmup(self, rows: Optional[tuple] = None,
               prefix_build: bool = False) -> None:
        """Compile prefill (the shapes admission can dispatch,
        `dispatch_shapes`: a request alone in every bucket, a burst of
        max_slots rows in the buckets a tick's budget holds twice; `rows`
        keeps those of the given row counts) + the decode chunk ahead of
        traffic (first-request latency otherwise pays 1-2 compiles). Slot
        state is reset afterwards. Each shape is a separate XLA program.

        prefix_build=True also compiles the prefix-KV builder per bucket
        so a runtime /v1/prefix registration never compiles on the
        serving thread; start servers that register prefixes under
        traffic with this on (costs len(buckets) extra warmup compiles)."""
        if prefix_build:
            self._refuse_prefix()
        shapes = self.dispatch_shapes
        if rows is not None:
            keep = {min(r, self.max_slots) for r in rows}
            shapes = [(r, bucket) for r, bucket in shapes if r in keep]
        row_set = list(dict.fromkeys(r for r, _ in shapes))
        n_prefix = n_prefill = 0
        run = WarmupRun()
        sentinel = run.sentinel
        # Warmup compiles are the intended ones — with another component
        # already steady in this process (a trainer sharing it, a second
        # engine) they must not read as stalls.
        with sentinel.expected():
            if self.adapters is not None:
                # The pool's lane-splice program: an adapter paging in
                # under traffic must reuse it, never compile.
                self.adapters.warm()
            if prefix_build:
                for bucket in self.prefill_buckets:
                    toks = np.zeros((1, bucket), np.int32)
                    pos = np.full((1, bucket), self._pad_slot, np.int32)
                    pos[0, 0] = 0
                    with self._mesh_ctx():
                        self._prefix_build(self.params, jnp.asarray(toks),
                                           jnp.asarray(pos))
                    n_prefix += 1
            for r, bucket in shapes:
                padded = np.zeros((r, bucket), np.int32)
                positions = np.full((r, bucket), self._pad_slot, np.int32)
                positions[:, :2] = [0, 1]
                args = (jnp.asarray(padded), jnp.asarray(positions),
                        jnp.zeros(r, jnp.int32), jnp.ones(r, jnp.int32),
                        self._commit_key(jax.random.key(0)),
                        jnp.zeros(r, jnp.float32), jnp.zeros(r, jnp.int32),
                        jnp.ones(r, jnp.float32))
                kw = {**self._adapter_kwargs(np.full(r, -1, np.int32)),
                      **self._grammar_warm_kwargs((r, self.cfg.vocab_size))}
                with self._mesh_ctx():
                    _, self.cache, *_ = run.program(
                        "prefill", f"b{bucket}r{r}", self._prefill,
                        self.params, self.cache, *args, **kw)
                n_prefill += 1
            zeros = np.zeros(self.max_slots, np.int32)
            akw = {**self._decode_kwargs(),
                   **self._grammar_warm_kwargs(
                       (self.max_slots, self.cfg.vocab_size))}
            for view in self.view_buckets:
                # No row alive.
                args = (*self._place_blocks(
                    np.zeros_like(self._slot_ints),
                    np.zeros_like(self._slot_floats)),
                        self._commit_key(jax.random.key(0)))
                with self._mesh_ctx():
                    _, _, self.cache, *_ = run.program(
                        f"decode_v{view}", f"v{view}",
                        self._decode_for(view), self.params, self.cache,
                        *args, **akw)
            n_verify = 0
            if self.options.speculative != "off":
                vtok = np.zeros((self.max_slots, self.draft_tokens + 1),
                                np.int32)
                akw = {**self._adapter_kwargs(),
                       **self._grammar_warm_kwargs(
                           (self.max_slots, self.draft_tokens + 1,
                            self.cfg.vocab_size))}
                for view in self.view_buckets:
                    args = (jnp.asarray(vtok), jnp.asarray(zeros),
                            jnp.asarray(zeros),
                            self._commit_key(jax.random.key(0)),
                            jnp.zeros(self.max_slots, jnp.float32),
                            jnp.zeros(self.max_slots, jnp.int32),
                            jnp.ones(self.max_slots, jnp.float32),
                            jnp.zeros(self.max_slots, bool))
                    with self._mesh_ctx():
                        _, _, _, self.cache, _ = run.program(
                            f"verify_v{view}", f"v{view}",
                            self._verify_for(view), self.params,
                            self.cache, *args, **akw)
                    n_verify += 1
        # Compiled-program census from the tracker (count + names +
        # compile seconds): model-config variants (collective_matmul,
        # quantized tiers) multiply the per-shape program set, and a
        # silently ballooning warmup is a compile-time regression nobody
        # notices until readiness stalls. The one-line print stays for
        # grep-ability; the structured dict feeds /debug/programs.
        census = obs_device.PROGRAMS.census("serve")
        self.warmup_census = {
            "prefill_programs": n_prefill,
            "prefill_buckets": list(self.prefill_buckets),
            "rows": row_set,
            # What admission can dispatch, and so what was compiled.
            "prefill_shapes": [list(shape) for shape in shapes],
            "decode_views": list(self.view_buckets),
            "prefix_builders": n_prefix,
            "verify_programs": n_verify,
            "speculative": self.options.speculative,
            "draft_tokens": self.draft_tokens,
            "adapter_pool": (self.adapters.pool_size
                             if self.adapters is not None else 0),
            "lora_rank": (self.adapters.rank
                          if self.adapters is not None else None),
            "grammar": self.options.grammar,
            "grammar_cache_size": (self._grammar_cache.capacity
                                   if self._grammar_cache is not None
                                   else None),
            # Decode steps per dispatch: turns serve_decode_dispatch_
            # seconds into a step time.
            "decode_chunk": self.decode_chunk,
            **run.finish(self.cache),
            "weight_layout": self.weight_layout,
            "flash_head_block": self.flash_head_block,
            "flash_blocks": self.flash_blocks,
            "gmm_tiling": self.gmm_tiling,
            "moe_row_window": self.moe_row_window,
            "programs": [{"name": c["name"], "programs": c["programs"]}
                         for c in census],
        }
        print(
            f"serve: warmup census: {n_prefill} prefill programs "
            f"({len(self.prefill_buckets)} buckets {self.prefill_buckets} "
            f"x rows {row_set}, those admission can dispatch at a budget of "
            f"{self.prefill_budget}), {len(self.view_buckets)} decode views "
            f"{self.view_buckets}, {n_prefix} prefix builders, "
            f"{n_verify} verify programs; "
            f"{self.warmup_census['compiles']} compiles in "
            f"{self.warmup_census['compile_seconds']}s, "
            f"{self.warmup_census['cache_hits']} from the persistent "
            f"cache ({[(c['name'], c['programs']) for c in census]}); "
            f"weight layout {self.weight_layout}; "
            f"flash heads a step {self.flash_head_block}, blocks "
            f"{self.flash_blocks}; grouped product tiles "
            f"{self.gmm_tiling}, rows to the experts at once "
            f"{self.moe_row_window}; "
            f"phases {self.warmup_census['phases']}",
            flush=True)
        # From here on, a compile is a serve-time stall: the sentinel
        # flags it loudly (xla_unexpected_compiles_total). One refcounted
        # claim per engine, however many times warmup() reruns; the
        # engine worker releases it at stop().
        if not self._marked_steady:
            self._marked_steady = True
            sentinel.mark_steady("serve")
        self.reset()

    def release_steady(self) -> None:
        """Release this engine's steady claim (the worker calls it at
        stop; embedders that warm an engine and discard it should too).
        Idempotent; pairs exactly with warmup()'s one mark."""
        if self._marked_steady:
            self._marked_steady = False
            obs_device.SENTINEL.clear_steady("serve")

    # ------------------------------------------------------------------
    # Request lifecycle
    # ------------------------------------------------------------------

    # -- shared-prefix cache -------------------------------------------

    def _refuse_prefix(self) -> None:
        self._refuse(_PREFIX)

    def _prefix_len_for(self, n: int, quantize: bool = False) -> int:
        """Usable prefix length for an n-token prompt. Explicit
        registrations (rare, usually pre-traffic) round to a multiple of
        16 — maximum reuse. The per-turn auto-prefix path passes
        quantize=True to floor to the prefill bucket set instead, so the
        compiled splice-program set stays bounded when every chat turn
        registers a new length (a fresh program per turn would be a
        serve-time compile stall)."""
        n = min(n, self.max_seq_len - 16)
        if not quantize:
            return n // 16 * 16
        best = 0
        for b in self.prefill_buckets:
            if b <= n:
                best = b
        return best

    def _prefix_cache_hit(self, key: tuple) -> None:
        """LRU refresh: most-recently-used keys live at the dict's end."""
        self._prefix_cache[key] = self._prefix_cache.pop(key)

    def _prefix_cache_put(self, key: tuple, kv: tuple) -> None:
        self._prefix_cache[key] = kv
        if len(self._prefix_cache) > self.prefix_cache_size:
            self._prefix_cache.pop(next(iter(self._prefix_cache)))

    def register_prefix(self, tokens: List[int], warmup: bool = True) -> int:
        """Compute and cache the KV for a shared prompt prefix (e.g. a chat
        system prompt). Returns the cached prefix length (0 = too short).

        The cached length rounds DOWN to a multiple of 16 (bounds the set
        of compiled splice shapes) and leaves at least one prompt token to
        prefill (sampling needs a real suffix logit). Subsequent requests
        whose prompt starts with the registered tokens prefill only their
        suffix — for a B-token system prompt that removes a B-bucket
        prefill from every request's TTFT.

        warmup=True (default) compiles the splice-prefill for every
        (suffix bucket x row count) this prefix can produce, against
        throwaway cache buffers — like warmup(), serve-time compiles are
        the TTFT killer (measured: the uncompiled prefix path turned a
        79 ms CPU p50 into 4.7 s). Registration is one-time per prefix
        shape; do it before traffic."""
        self._refuse_prefix()
        plen = self._prefix_len_for(len(tokens))
        if plen < 16:
            return 0
        key = tuple(int(t) for t in tokens[:plen])
        if key in self._prefix_cache:
            self._prefix_cache_hit(key)
            return plen
        bucket = self._bucket_for(plen)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :plen] = key
        pos = np.full((1, bucket), self._pad_slot, np.int32)
        pos[0, :plen] = np.arange(plen)
        with self._mesh_ctx():
            pk, pv = self._prefix_build(self.params, jnp.asarray(toks),
                                        jnp.asarray(pos))
        self._prefix_cache_put(key, (pk[:, :plen], pv[:, :plen]))
        if warmup:
            buffers = None
            for bucket, rows in self.prefix_warmup_shapes(plen):
                buffers = self.warm_prefix_shape(key, bucket, rows, buffers)
        return plen

    def register_prefix_from_slot(self, slot: int,
                                  tokens: List[int]) -> int:
        """Register tokens[:plen] as a prefix by COPYING its already-
        computed KV out of a slot's pool cache — no forward pass at all.

        The zero-cost path for multi-turn chat: a finished request's
        prompt KV is sitting in its slot (prefill wrote positions
        0..m-1; later decode writes land at higher positions and don't
        disturb it), and the next turn's prompt extends this one. Call
        between the request finishing and the slot's next admission
        (the engine is single-threaded, so 'right after step()' is safe
        — the serving worker does exactly that).

        Returns the cached length (0 = too short / already cached)."""
        self._refuse_prefix()
        plen = self._prefix_len_for(len(tokens), quantize=True)
        if plen < 16:
            return 0
        key = tuple(int(t) for t in tokens[:plen])
        if key in self._prefix_cache:
            self._prefix_cache_hit(key)
            return 0
        # Eager slices materialize fresh buffers, so later donation of
        # the pool cache cannot invalidate the cached prefix. An int8 pool
        # dequantizes here: the prefix cache stays in the activation dtype
        # (the splice-prefill quantizes it back on admission), so the
        # prefix path is dtype-agnostic.
        pk = self.cache.k[:, slot, :plen]
        pv = self.cache.v[:, slot, :plen]
        if self.cache.quantized:
            from runbooks_tpu.ops.quantization import dequantize_kv

            ad = self.cfg.activation_dtype
            pk = dequantize_kv(pk, self.cache.k_scale[:, slot, :plen], ad)
            pv = dequantize_kv(pv, self.cache.v_scale[:, slot, :plen], ad)
        self._prefix_cache_put(key, (pk, pv))
        return plen

    def has_prefix(self, tokens: List[int]) -> bool:
        """True when register_prefix(tokens) would be a cache hit."""
        plen = self._prefix_len_for(len(tokens))
        return (plen >= 16
                and tuple(int(t) for t in tokens[:plen])
                in self._prefix_cache)

    def prefix_warmup_shapes(self, plen: int) -> List[tuple]:
        """(suffix bucket, rows) shapes the splice-prefill can run at for
        a plen-token prefix — the compile set warm-up walks."""
        max_suffix = self._bucket_for(self.max_seq_len - plen)
        rows_set = (1, self.max_slots) if self.max_slots > 1 else (1,)
        return [(b, r) for b in self.prefill_buckets if b <= max_suffix
                for r in rows_set]

    def warm_prefix_shape(self, key: tuple, bucket: int, rows: int,
                          buffers: Optional[tuple] = None):
        """Compile ONE prefix splice-prefill shape against THROWAWAY
        pool-cache buffers (the real pool cache may hold live slots;
        warmup writes must not touch it). Exposed shape-at-a-time so the
        serving worker can interleave compiles with decode steps instead
        of freezing every stream for the whole sweep.

        Returns the throwaway pool cache that came back from the donated
        call — pass it to the next warm call so the sweep holds ONE extra
        pool-sized allocation total, not one per shape (a pool sized to
        fill HBM would otherwise OOM on the first registration under
        load). Drop the returned buffers when done."""
        if key not in self._prefix_cache:
            return buffers  # evicted since queued
        pk, pv = self._prefix_cache[key]
        plen = len(key)
        toks = np.zeros((rows, bucket), np.int32)
        positions = np.full((rows, bucket), self._pad_slot, np.int32)
        positions[:, 0] = plen
        if buffers is None:
            buffers = self._new_pool_cache()
        # An intentional pre-compile by definition — the sentinel must not
        # read the background warm sweep as a serve-time stall (a COLD
        # admission or runtime prefix_build compile still flags).
        with obs_device.SENTINEL.expected(), self._mesh_ctx():
            _, buffers, _ = self._prefill_prefix(
                self.params, buffers, pk, pv,
                jnp.asarray(toks), jnp.asarray(positions),
                jnp.zeros(rows, jnp.int32), jnp.zeros(rows, jnp.int32),
                self._commit_key(jax.random.key(0)),
                jnp.zeros(rows, jnp.float32),
                jnp.zeros(rows, jnp.int32), jnp.ones(rows, jnp.float32),
                **self._adapter_kwargs(np.full(rows, -1, np.int32)),
                **self._grammar_warm_kwargs((rows, self.cfg.vocab_size)))
        return buffers

    def _find_prefix(self, prompt: List[int]):
        """Longest registered prefix this prompt starts with, leaving at
        least one suffix token; None if no match."""
        best = None
        for key in self._prefix_cache:
            if len(key) < len(prompt) and (best is None
                                           or len(key) > len(best)):
                if tuple(prompt[:len(key)]) == key:
                    best = key
        return best

    def validate(self, req: Request) -> None:
        """Raise ValueError for requests that can never be served (callers
        should surface this as a 400, before the request enters the queue)."""
        if len(req.prompt_tokens) >= self.max_seq_len:
            raise ValueError(
                f"prompt of {len(req.prompt_tokens)} tokens exceeds the "
                f"engine's context window ({self.max_seq_len})")
        if req.priority not in PRIORITY_RANK:
            raise ValueError(
                f"priority must be one of {sorted(PRIORITY_RANK)}, got "
                f"{req.priority!r}")
        if req.adapter is not None:
            if self.adapters is None:
                raise ValueError(
                    "this server has no adapter pool (adapter_pool: 0); "
                    "request-level `adapter` needs a pooled engine or a "
                    "dedicated server with the adapter folded at load "
                    "(docs/multi-tenant-lora.md)")
            err = self.adapters.can_resolve(req.adapter)
            if err is not None:
                raise ValueError(err)
        if req.response_format is not None:
            if self._grammar_cache is None:
                raise ValueError(
                    "this server has grammar-constrained decoding off "
                    "(grammar: off); `response_format` needs grammar: on "
                    "(docs/structured-output.md)")
            # Compile (or LRU-hit) here, at the 400 boundary: a
            # GrammarError names the unsupported construct and the
            # request never enters the queue. The cursor pins the
            # compiled DFA so cache eviction cannot strand the slot.
            req._grammar = self._grammar_cache.cursor(req.response_format)
            self.grammar_requests += 1
            reg = obs_metrics.REGISTRY
            reg.inc("serve_grammar_requests_total",
                    help_text="Requests admitted with a compiled "
                              "response_format constraint.")
            st = self._grammar_cache.stats()
            reg.set_counter("serve_grammar_cache_hits_total", st["hits"],
                            help_text="Grammar DFA compile-cache hits.")
            reg.set_counter("serve_grammar_cache_misses_total",
                            st["misses"],
                            help_text="Grammar DFA compile-cache misses "
                                      "(each is one host-side compile).")

    def submit(self, req: Request) -> None:
        self.validate(req)
        if len(self.queue) >= self.max_queue:
            raise EngineOverloaded(
                f"admission queue full ({len(self.queue)} waiting, "
                f"bound {self.max_queue}); retry later")
        bound = self._class_bounds[req.priority]
        queued = sum(1 for q in self.queue if q.priority == req.priority)
        if queued >= bound:
            # Per-class share exhausted: this class sheds while the
            # others keep their queue room — a batch flood cannot fill
            # the whole queue against interactive traffic.
            raise EngineOverloaded(
                f"{req.priority} queue share full ({queued} waiting, "
                f"class bound {bound} of {self.max_queue}); retry later")
        if req.adapter is not None and self.adapters is not None:
            self.adapters.count_request(req.adapter)
        req._submitted = time.monotonic()
        self._queue_insert(req)

    def _queue_insert(self, req: Request) -> None:
        """Class-ordered insert: behind every queued request of the same
        or better class, ahead of strictly worse ones — FIFO within a
        class, interactive ahead of standard ahead of batch."""
        rank = PRIORITY_RANK[req.priority]
        idx = len(self.queue)
        for i, q in enumerate(self.queue):
            if PRIORITY_RANK[q.priority] > rank:
                idx = i
                break
        self.queue.insert(idx, req)

    def retry_after_hint(self) -> int:
        """Load-derived Retry-After seconds for a shed request: the
        queue depth in units of slot drains (each slot that frees
        admits one queued request), clamped to [1, 30] so a deep
        backlog never tells clients to hammer at 1 s or vanish for
        minutes (docs/fault-tolerance.md)."""
        backlog = len(self.queue)
        hint = -(-backlog // max(self.max_slots, 1))
        return int(min(max(hint, 1), 30))

    def reset(self) -> None:
        """Recover from a failed jitted step: donated cache buffers may be
        invalid, so reallocate, and clear all slot state."""
        self._reset_slots()
        self.cache = self._new_pool_cache()

    def _reset_slots(self) -> None:
        """Shared reset head: hand over what the last chunk decoded (its
        tokens were taken; host work only), then forget every request. No
        adapter lane stays pinned; residency survives (the pool tree is
        never donated to an engine step, so its buffers are valid even
        after a crash) — the next admission hits instead of reloading."""
        self.deliver_parked()
        self._dev_blocks = None
        self.lengths[:] = 0
        self.active[:] = False
        self.last_token[:] = 0
        self.slot_req = [None] * self.max_slots
        self.queue.clear()
        if self._spec_index is not None:
            self._spec_index.reset()
        self.adapter_slots[:] = -1
        if self.adapters is not None:
            self.adapters.reset_refs()

    def has_work(self) -> bool:
        return bool(self.queue) or bool(self.active.any())

    # -- device observability hooks ------------------------------------

    def kv_occupancy(self) -> dict:
        """Token-level KV slot-pool occupancy: the dense [max_slots,
        max_seq_len] reservation vs the tokens actually cached — the
        fragmentation number the ROADMAP's paged-KV design exists to fix
        (docs/observability.md)."""
        capacity = self.max_slots * self.max_seq_len
        tokens = int(self.lengths[self.active].sum()) if capacity else 0
        # Aggregate vs per-device bytes: nbytes is the LOGICAL pool size;
        # under a serving mesh each chip holds only its kv-head shard
        # (shard_local_nbytes reads the sharding metadata, no sync).
        # The leaves' bytes by their group (models/transformer.LEAF_TRAITS;
        # 0 for a model without such layers). kv_pool_bytes is every leaf
        # that grows with a row's tokens or holds them: all but the
        # recurrent state and tails, fixed a slot whatever its tokens. The
        # latent (MLA) leaf and the window layers' rings, whose size does
        # not grow with max_seq_len, are the parts of it named beside it.
        by_group = dict.fromkeys(("kv_pool", "recurrent_state",
                                  "latent_cache", "kv_ring",
                                  "kv_compressed"), 0)
        per_device = 0
        for leaf in cache_leaves(self.cfg, self.quantize_kv):
            array = getattr(self.cache, leaf.name)
            by_group[leaf.group] += int(array.nbytes)
            if leaf.group != "recurrent_state":
                per_device += obs_device.shard_local_nbytes(array)
        return {"slots_total": self.max_slots,
                "slots_active": int(self.active.sum()),
                "kv_tokens": tokens,
                "kv_capacity_tokens": capacity,
                "kv_pool_bytes": (sum(by_group.values())
                                  - by_group["recurrent_state"]),
                "kv_pool_bytes_per_device": per_device,
                "recurrent_state_bytes": by_group["recurrent_state"],
                "latent_cache_bytes": by_group["latent_cache"],
                "kv_ring_bytes": by_group["kv_ring"],
                "kv_compressed_bytes": by_group["kv_compressed"],
                "occupancy_ratio": (tokens / capacity) if capacity else 0.0}

    def memory_groups(self) -> dict:
        """Named array groups for the live-array attribution census
        (obs/device.live_array_census): weights, the slot-pool KV cache,
        and the shared-prefix KV cache. The prefix dict is copied first
        (one C-level op): the caller is usually an HTTP handler thread
        while the worker thread registers/evicts prefixes, and iterating
        the live dict mid-mutation raises."""
        groups = {"weights": self.params,
                  "kv_cache": self.cache,
                  "prefix_cache": list(self._prefix_cache.copy().values())}
        # K/V, the recurrent state and a latent are reported apart: a
        # group's fields as they are (None where the model has no such
        # leaf), the one field of a group of one by itself.
        for group in ("recurrent_state", "latent_cache"):
            names = [n for n, t in LEAF_TRAITS.items() if t.group == group]
            arrays = tuple(getattr(self.cache, n, None) for n in names)
            if any(a is not None for a in arrays):
                groups[group] = arrays if len(arrays) > 1 else arrays[0]
                groups["kv_cache"] = dataclasses.replace(
                    groups["kv_cache"], **dict.fromkeys(names))
        if self.adapters is not None:
            groups["adapter_pool"] = self.adapters.tree
        return groups

    def adapter_stats(self) -> Optional[dict]:
        """Adapter-pool snapshot for /metrics and /debug/programs
        (docs/multi-tenant-lora.md); None when the pool is off."""
        return None if self.adapters is None else self.adapters.stats()

    def _free_slots(self, exclude=()) -> List[int]:
        return [i for i in range(self.max_slots)
                if not self.active[i] and i not in exclude]

    @staticmethod
    def _admit_tokens(req: Request) -> List[int]:
        """The token history admission plans against. For a fresh
        request that is the prompt; for a preempted one it is the prompt
        plus every generated token already WRITTEN to the cache — all
        outputs except the last (the carry token lives in last_token,
        not the cache; see _activate_slot's resume branch). Planning
        against this lets the radix match re-cover the request's own
        adopted pages, so resume costs a device_put instead of a full
        re-prefill."""
        if req._preempted and req.output_tokens:
            return req.prompt_tokens + req.output_tokens[:-1]
        return req.prompt_tokens

    @staticmethod
    def _admit_budget(req: Request) -> int:
        """Token budget past _admit_tokens for page reservation. For a
        resumed request the generated-so-far tokens moved into the
        effective prompt, so the budget shrinks by the same amount (+1
        for the carry token) — the total reserve stays exactly the
        original prompt + max_tokens, never over-reserving on resume."""
        if req._preempted and req.output_tokens:
            return req.max_tokens - len(req.output_tokens) + 1
        return req.max_tokens

    def _bucket_for(self, n: int) -> int:
        return bucket_for(self.prefill_buckets, n)

    def _acquire_adapter(self, req: Request) -> bool:
        """Pin the request's adapter lane ahead of admission. True =
        proceed (lane pinned, or no adapter involved); False = pool
        exhausted, the caller stops admitting (queue backpressure). A
        load failure (corrupt artifact) finishes the request with
        finish_reason "error" and returns True with req.finished set —
        the caller drops it from the queue."""
        if req.adapter is None or self.adapters is None:
            return True
        if req._adapter_lane >= 0:
            return True
        from runbooks_tpu.serve.lora_pool import AdapterLoadError

        try:
            lane = self.adapters.acquire(req.adapter)
        except AdapterLoadError as exc:
            print(f"serve: adapter {req.adapter!r} failed to load at "
                  f"admission: {exc}", flush=True)
            _finish_request(req, "error", time.monotonic())
            return True
        if lane is None:
            return False
        req._adapter_lane = lane
        return True

    def _admit(self, exclude_slots=()) -> None:
        budget = self.prefill_budget
        admitted: List[tuple] = []
        for slot in self._free_slots(exclude_slots):
            if not self.queue:
                break
            # Budget in bucket-padded tokens (what the prefill actually
            # computes — only the SUFFIX when a registered prefix covers
            # the front of the prompt). The first admission always goes
            # through so an over-budget prompt cannot starve. Adapter
            # requests never match shared prefixes: the cached prefix KV
            # was computed with BASE weights, and a tenant's adapter
            # changes the K/V projections themselves.
            head = self.queue[0]
            pkey = (None if head.adapter is not None
                    else self._find_prefix(head.prompt_tokens))
            need = self._bucket_for(
                len(head.prompt_tokens) - (len(pkey) if pkey else 0))
            if admitted and need > budget:
                break
            if not self._acquire_adapter(head):
                # Every pool lane is pinned by an in-flight request: the
                # head waits (FIFO) and the queue backs up until
                # submit() sheds with the typed 429 — the same
                # backpressure shape as the paged engine's page
                # exhaustion (docs/multi-tenant-lora.md).
                break
            if head.finished:
                # Adapter artifact failed to load: the request was
                # finished with an error below; drop it and move on.
                self.queue.pop(0)
                continue
            req = self.queue.pop(0)
            req._admitted = time.monotonic()
            obs_metrics.REGISTRY.observe(
                "serve_queue_wait_seconds",
                req._admitted - req._submitted,
                help_text="Admission-queue wait (engine.submit, on the "
                          "worker's thread, to slot assignment; the wait "
                          "for the worker before it is "
                          "serve_pending_wait_seconds).")
            if record_enabled():
                # The queue phase ends here; backdated complete event so
                # the request's trace shows queue -> prefill -> decode.
                trace_complete("queue_wait",
                               req._admitted - req._submitted,
                               request_id=req.request_id, slot=slot)
            budget -= need
            admitted.append((slot, req, pkey))
        if not admitted:
            return
        # Group this tick's admissions by (bucket, prefix): one
        # [rows, bucket] prefill dispatch per group instead of one per
        # request.
        by_group: dict = {}
        for slot, req, pkey in admitted:
            b = self._bucket_for(
                len(req.prompt_tokens) - (len(pkey) if pkey else 0))
            by_group.setdefault((b, pkey), []).append((slot, req))
        for (bucket, pkey), group in by_group.items():
            self._prefill_group(bucket, group, pkey)

    def _prefill_group(self, bucket: int, group: List[tuple],
                       pkey: Optional[tuple] = None) -> None:
        """Prefill same-bucket requests as one batched forward. The row
        count is 1 (single request) or max_slots (any burst) — exactly the
        two shapes warmup() compiles, so a burst can never trigger a
        serve-time compile. Padding rows aim at group[0]'s
        slot and are overwritten by the real row 0 (the jitted splice runs
        rows in descending order).

        With pkey (a registered shared prefix), rows hold only the SUFFIX
        tokens at positions starting after the prefix; the jitted step
        splices the cached prefix K/V into every scratch row first."""
        n = len(group)
        plen = len(pkey) if pkey else 0
        # Prefix hit rate at admission granularity (the auto_prefix
        # effectiveness number the paged-KV work baselines against).
        self.prefix_lookups += n
        if pkey:
            self.prefix_hits += n
        rows = prefill_rows(n, self.max_slots)

        def operands():
            tokens = np.zeros((rows, bucket), np.int32)
            # Real tokens at positions plen..len-1; padding scatters to
            # the trash slot of each row's scratch cache.
            positions = np.full((rows, bucket), self._pad_slot, np.int32)
            slots = np.full(rows, group[0][0], np.int32)
            # First generated token of each row comes from its last *real*
            # prompt position (index into the suffix row); sampling happens
            # inside the jitted prefill (one dispatch, no eager sampling
            # chain — see prefill_fn).
            last_pos = np.zeros(rows, np.int32)
            temps = np.zeros(rows, np.float32)
            top_ks = np.zeros(rows, np.int32)
            top_ps = np.ones(rows, np.float32)
            aslots = np.full(rows, -1, np.int32)
            for i, (slot, req) in enumerate(group):
                m = len(req.prompt_tokens) - plen
                tokens[i, :m] = req.prompt_tokens[plen:]
                positions[i, :m] = np.arange(plen, plen + m)
                slots[i] = slot
                last_pos[i] = m - 1
                temps[i] = req.temperature
                top_ks[i] = req.top_k
                top_ps[i] = req.top_p
                aslots[i] = req._adapter_lane
            args = (jnp.asarray(tokens), jnp.asarray(positions),
                    jnp.asarray(slots), jnp.asarray(last_pos), self.rng,
                    jnp.asarray(temps), jnp.asarray(top_ks),
                    jnp.asarray(top_ps))
            kwargs = {**self._adapter_kwargs(aslots),
                      **self._grammar_prefill_kwargs(group, rows)}
            return args, kwargs, positions

        def program(args, akw):
            if pkey:
                # Admission hit refreshes the LRU position: the prefix
                # serving live traffic must not be the one evicted.
                pk, pv = self._prefix_cache[pkey]
                self._prefix_cache_hit(pkey)
                first, self.cache, self.rng, *moe = self._prefill_prefix(
                    self.params, self.cache, pk, pv, *args, **akw)
                self.prefix_tokens_reused += plen * n
            else:
                first, self.cache, self.rng, *moe = self._prefill(
                    self.params, self.cache, *args, **akw)
            return (first, *moe)

        self._prefill_dispatch(bucket, rows, plen, group, operands, program)

    def _prefill_dispatch(self, bucket: int, rows: int, plen: int,
                          group: List[tuple], operands, program) -> None:
        """One batched prefill under its spans, shared with the paged
        engine: `operands()` builds the host arrays and places them
        (-> args, kwargs, the host `positions` [rows, bucket]),
        `program(args, kwargs)` makes the jitted call and returns a tuple:
        the first tokens still on the device and, for a sparse model, the
        dispatch's (counts, hits)."""
        # Request ids only materialize when tracing is on (same rule as
        # the decode span's active count: no per-dispatch list builds on
        # the hot path for a disabled tracer).
        attrs = ({"request_ids": [r.request_id for _, r in group]}
                 if record_enabled() else {})
        with span("prefill", bucket=bucket, rows=rows, prefix=plen,
                  **attrs) as prefill:
            with fine("prefill.operands"):
                args, kwargs, positions = operands()
            if record_enabled():
                # What this dispatch carries: real prompt tokens, after
                # the prefix, without padding or parked rows.
                prefill.set(tokens=int((positions < self._pad_slot).sum()))
            # Dispatch timing is host-side, outside jit (the np.asarray
            # pull below is the device sync) — zero effect on compiled
            # programs.
            t_dispatch = time.perf_counter()
            with self._mesh_ctx():
                with fine("prefill.dispatch"):
                    first, *moe = program(args, kwargs)
                    # The call has returned and the device is at work.
                    self._count_flash_blocks(bucket, positions)
                    self._count_sparse_prefill(bucket, positions)
                self.deliver_parked(hidden=True)
                with fine("prefill.sync"):
                    # The first token must reach the host to stream.
                    (first,) = _pull("prefill.sync", first)
                    self._count_moe("prefill", moe, bucket * rows)
            # Labeled by (bucket, rows): the two row shapes are different
            # compiled programs with ~rows-proportional FLOPs, and the
            # roofline join (/debug/programs) divides per-program FLOPs by
            # this distribution's mean — blending row shapes would inflate
            # the burst program's analytic MFU by ~max_slots.
            obs_metrics.REGISTRY.observe(
                "serve_prefill_dispatch_seconds",
                time.perf_counter() - t_dispatch, bucket=str(bucket),
                rows=str(rows),
                help_text="Prefill dispatch+sync wall time per admission "
                          "group, labeled by prompt bucket and row count.")
            with fine("prefill.activate"):
                for i, (slot, req) in enumerate(group):
                    self._activate_slot(slot, req, int(first[i]))

    @property
    def dispatch_shapes(self) -> List[tuple]:
        """The (rows, bucket) prefill shapes _admit can dispatch
        (dispatch_shapes): warm-up compiles exactly these."""
        return dispatch_shapes(self.prefill_buckets, self.prefill_budget,
                               self.max_slots)

    def _sparse_core(self, seen: int) -> bool:
        """Whether a program whose rows may be `seen` tokens long runs
        the full-attention layers' sparse core (forward's long_rows)."""
        sp = self.cfg.sparse_read
        return sp is not None and seen >= sp.dense_len

    def _by_flash_program(self, of_shapes) -> dict:
        """{prefill program: of_shapes(cfg, queries, keys, tensor shards)}
        for every bucket whose prefill takes the flash path: what is static
        per compiled program, a function of its shapes, so it is worked out
        and not measured. {} where no prefill takes the flash path."""
        tp = int(self.mesh.shape.get("tensor", 1)) if self.mesh else 1
        return {f"prefill_b{bucket}": of_shapes(
                    self.cfg, bucket, self.max_seq_len + 1, tp)
                for bucket in self.prefill_buckets
                if use_flash_cached_prefill(self.cfg, bucket)
                and not self._sparse_core(bucket)}

    @functools.cached_property
    def flash_head_block(self) -> dict:
        """{prefill program: {kind of attention layer: G}}: query heads a
        grid step of the flash forward holds (ops/flash_attention.
        head_block), for every row count of the bucket's program."""
        return self._by_flash_program(flash_heads_per_step)

    @functools.cached_property
    def flash_blocks(self) -> dict:
        """{prefill program: {kind of attention layer: {"fwd": [block_q,
        block_k]}}}: the block shape the flash forward of the program was
        compiled with (ops/flash_attention.block_shape, or the
        configuration's override). _count_flash_blocks counts at it."""
        return self._by_flash_program(flash_blocks)

    @functools.cached_property
    def gmm_tiling(self) -> dict:
        """{program: {"gate_up": [tm, tk, tn], "down": [tm, tk, tn]}}: the
        tiles the sparse layers' grouped products of each prefill program
        (dispatch_shapes) and decode view compile with (models/moe.
        gmm_tilings: the chooser the program asks, from its shapes). {} for
        a dense model and where the products run as ragged_dot."""
        if not self.cfg.moe_num_experts:
            return {}
        tiles = {f"prefill_b{bucket}r{r}": gmm_tilings(self.cfg, bucket * r)
                 for r, bucket in self.dispatch_shapes}
        tiles.update({f"decode_v{view}": gmm_tilings(self.cfg,
                                                     self.max_slots)
                      for view in self.view_buckets})
        return {program: tile for program, tile in tiles.items() if tile}

    @functools.cached_property
    def moe_row_window(self) -> dict:
        """{program: rows}: the window of the order by expert that a sparse
        layer of each prefill program and decode view sends to its experts
        and brings back at once (models/moe.row_window: what the held share
        can expect of a chunk's tokens x top_k rows; all of them in a
        decode step and where every expert is held). {} for a dense
        model."""
        if not self.cfg.moe_num_experts:
            return {}

        def window(tokens):
            return chunk_window(self.cfg, tokens)[1]

        return {**{f"prefill_b{bucket}r{r}": window(bucket * r)
                   for r, bucket in self.dispatch_shapes},
                **{f"decode_v{view}": window(self.max_slots)
                   for view in self.view_buckets}}

    def _count_flash_blocks(self, bucket: int,
                            positions: np.ndarray) -> None:
        """Which share of the flash forward's grid a prefill dispatch
        computes, by kind of attention layer: one kernel call a row of the
        dispatch, which every layer of the kind repeats. Counted on the
        host from the positions the dispatch was given, by the function
        the kernel takes its ranges from and at the block shape the
        program compiled with (flash_blocks). Full layers: as
        models/transformer._cached_attention hands them over (parked
        tokens at -1 against one scratch row of keys). Window layers: as
        _window_attention does (the call's own keys, parked ones padding,
        the ranges of a window), beside the scores a window needs."""
        blocks = self.flash_blocks.get(f"prefill_b{bucket}")
        if blocks is None:
            return
        from runbooks_tpu.ops.flash_attention import PAD_POS, block_counts

        cfg = self.cfg
        cache_len = self.max_seq_len + 1
        parked = positions >= cache_len - 1
        full = next(kind for kind in blocks if kind != "sliding_attention")
        visited, grid = block_counts(
            np.where(parked, -1, positions),
            np.broadcast_to(np.arange(cache_len, dtype=np.int32),
                            (positions.shape[0], cache_len)),
            None, None, *blocks[full]["fwd"], True)
        obs_metrics.REGISTRY.inc(
            "serve_flash_blocks_visited_total", visited, bucket=str(bucket),
            help_text="(query block, kv block) pairs the flash forward "
                      "computed a head and layer in prefill, by bucket.")
        obs_metrics.REGISTRY.inc(
            "serve_flash_blocks_grid_total", grid, bucket=str(bucket),
            help_text="(query block, kv block) pairs of the flash "
                      "forward's grid a head and layer in prefill, by "
                      "bucket.")
        if not cfg.has_window:
            return
        block_q, block_k = blocks["sliding_attention"]["fwd"]
        visited, grid = block_counts(
            np.where(parked, -1, positions),
            np.where(parked, PAD_POS, positions), None, None,
            block_q, block_k, True, cfg.sliding_window)
        # A real token at position t sees min(t + 1, window) keys.
        needed = int(np.minimum(positions[~parked] + 1,
                                cfg.sliding_window).sum())
        for name, value, what in (
                ("blocks_visited", visited,
                 "(query block, kv block) pairs the flash forward computed"),
                ("blocks_grid", grid,
                 "(query block, kv block) pairs of the flash forward's "
                 "grid"),
                ("scores_visited", visited * block_q * block_k,
                 "scores in the blocks the flash forward computed"),
                ("scores_needed", needed,
                 "scores a window needs (a token at position t sees "
                 "min(t + 1, window) keys)")):
            obs_metrics.REGISTRY.inc(
                f"serve_window_{name}_total", value, bucket=str(bucket),
                help_text=what + " a head and window layer in prefill, by "
                                 "bucket.")

    def _count_sparse_prefill(self, bucket: int,
                              positions: np.ndarray) -> None:
        """Score pairs a prefill dispatch's sparse core needs and computes
        (ops/block_sparse_attention.prefill_counts), a query head and
        sparse-read layer, and the blocks its tokens choose a KV head:
        counted on the host from the positions the dispatch was given, as
        _count_flash_blocks counts the flash forward's. A program that runs
        no sparse core (a bucket under sparse_dense_len) counts nothing
        here: its read is the flash forward's."""
        if not self._sparse_core(bucket):
            return
        from runbooks_tpu.ops.block_sparse_attention import prefill_counts

        cfg = self.cfg
        shape = cfg.attn_shape("full_attention")
        self._count_sparse("prefill", *prefill_counts(
            positions, positions >= self._pad_slot, cfg.sparse_read,
            self.max_seq_len + 1, shape.heads // shape.kv_heads,
            cfg.flash_block_q, cfg.flash_block_k))

    def _count_sparse_decode(self, view: int, before: np.ndarray) -> None:
        """The same for a decode chunk whose program read `view` keys a
        live row and step: the positions its steps wrote are those between
        the slots' lengths `before` the host took the chunk's tokens and
        now (a step of a slot the host cut short is not counted)."""
        if not self._sparse_core(view):
            return
        from runbooks_tpu.ops.block_sparse_attention import decode_counts

        at = np.concatenate([np.arange(lo, hi)
                             for lo, hi in zip(before, self.lengths)])
        self._count_sparse("decode", *decode_counts(
            at, self.cfg.sparse_read, view))

    def _count_sparse(self, program: str, needed: int, visited: int,
                      chosen: int) -> None:
        for name, value, what in (
                ("pairs_needed", needed,
                 "Score pairs the chosen sets require (a token of a long "
                 "row: its window, the initial blocks and the blocks it "
                 "chose, causally clipped; of a short row: every earlier "
                 "key)"),
                ("pairs_visited", visited,
                 "Score pairs the sparse core computed (prefill: every "
                 "(query block, key block) step of its kernel that runs; "
                 "decode: the view's keys a live row)"),
                ("blocks_chosen", chosen,
                 "Key blocks the tokens of long rows read beside their "
                 "windows (a KV head; the initial ones among them)")):
            obs_metrics.REGISTRY.inc(
                f"serve_bsa_{name}_total", value, program=program,
                help_text=what + ", a query head and sparse-read layer, by "
                                 "program.")

    def _count_moe(self, program: str, moe: list, tokens: int,
                   steps: int = 1) -> None:
        """Add one dispatch's (counts, hits) of a sparse model to the
        engine's sums ([] for a dense one): `steps` forwards over `tokens`
        tokens each. Called right after the pull the dispatch makes anyway:
        the arrays came back with it."""
        for counts, hits in moe:
            # rbt-check: ignore[device-sync] same dispatch boundary — the counts ride the pull above
            counts = np.asarray(counts)
            self._moe_counts += counts.sum(axis=0)
            self._moe_peak += int(counts[:, :-1].max(axis=1).sum())
            # rbt-check: ignore[device-sync] same boundary
            self._moe_hits[program][0] += int(hits)
            self._moe_hits[program][1] += steps * counts[:, :-1].size
            # All rows at once whatever is held (a decode step, every
            # expert held): one figure a forward. Windows: a dispatch is
            # one forward, and its counts are that forward's.
            moved, whole = rows_moved(self.cfg, tokens,
                                      counts[:, :-1].sum(axis=1))
            self._moe_rows[program][0] += moved * (steps if whole else 1)
            self._moe_rows[program][1] += steps * whole * len(counts)

    def moe_stats(self) -> Optional[dict]:
        """Sparse-layer counters for /metrics (None for a dense model):
        assignments by held expert summed over layers, those routed to
        experts held elsewhere, and by program the (layer, expert) pairs
        that got a token against the pairs offered, a forward."""
        if not self.cfg.moe_num_experts:
            return None
        return {"first_expert": self.cfg.moe_experts_first,
                "expert_tokens": self._moe_counts[:-1].tolist(),
                "elsewhere": int(self._moe_counts[-1]),
                "peak": self._moe_peak,
                "hits": {k: v[0] for k, v in self._moe_hits.items()},
                "calls": {k: v[1] for k, v in self._moe_hits.items()},
                "rows_moved": {k: v[0] for k, v in self._moe_rows.items()},
                "all_rows": {k: v[1] for k, v in self._moe_rows.items()}}

    def _activate_slot(self, slot: int, req: Request,
                       first_tok: int) -> None:
        """Post-prefill slot activation, shared with the paged engine:
        bookkeeping, the speculative draft index's context start, and
        the first token's recording (which may immediately finish a
        max_tokens=1 request)."""
        resumed = bool(req._preempted and req.output_tokens)
        eff = self._admit_tokens(req)
        self.active[slot] = True
        self.lengths[slot] = len(eff)
        self.slot_req[slot] = req
        self.adapter_slots[slot] = req._adapter_lane
        self._slot_ints[ROW_TOP_K, slot] = req.top_k
        self._slot_ints[ROW_EOS, slot] = (-1 if req.eos_id is None
                                          else req.eos_id)
        self._slot_floats[ROW_TEMP, slot] = req.temperature
        self._slot_floats[ROW_TOP_P, slot] = req.top_p
        self._dev_blocks = None
        req._slot = slot
        if resumed:
            # Resume after preemption: the cache again holds the full
            # written history (prompt + outputs[:-1]), re-established by
            # radix match on the HBM/host hierarchy plus a suffix
            # prefill of whatever fell off page boundaries. The carry
            # token — sampled before preemption, streamed to the
            # client, never written — goes back into last_token so the
            # next decode writes it at position lengths[slot]. The
            # prefill's freshly sampled token is DISCARDED: that
            # position's token was already recorded, and resampling it
            # (different rng state) would fork the sequence.
            carry = int(req.output_tokens[-1])
            self.last_token[slot] = carry
            if self._spec_index is not None:
                self._spec_index.begin(slot, eff)
                self._spec_index.extend(slot, carry)
            req._preempted = False
            self.preempted_resumed += 1
            return
        self.last_token[slot] = first_tok
        if self._spec_index is not None:
            self._spec_index.begin(slot, req.prompt_tokens)
        self._record_token(slot, first_tok)

    def _record_token(self, slot: int, tok: int) -> None:
        """One token, taken and handed over at once (a prefill's first
        token, a verify step's): both halves below, inline."""
        req = self.slot_req[slot]
        assert req is not None
        self._deliver(req, [tok], self._take_tokens(slot, req, [tok]))

    def _take_tokens(self, slot: int, req: Request, toks: List[int]) -> str:
        """The engine's half of recording a slot's newest tokens (the
        caller moved `lengths` and `last_token`): everything the next
        dispatch depends on, nothing the outside sees. Holds the host's
        copy of the finish rules, applied to the last token — a decode
        chunk stops a row at the first token that ends it
        (advance_rows). Returns the finish reason, "" while the request
        goes on; a finished slot is free when this returns."""
        req.output_tokens.extend(toks)
        if self._spec_index is not None:
            for tok in toks:
                self._spec_index.extend(slot, tok)
        tok = toks[-1]
        reason = ""
        if req.eos_id is not None and tok == req.eos_id:
            # EOS is not a grammar token: the mask allows it exactly at
            # accepting states, and it finishes via the normal path.
            reason = "stop"
        elif req._grammar is not None and not req._grammar.advance(tok):
            # Grammar cursor advance — the single mutation point (draft
            # gating and verify masks preview with the non-mutating
            # walk). Masked sampling makes a refusal unreachable; an
            # assert would take the whole engine down for one request.
            reason = "error"
        elif req._grammar is not None and req._grammar.at_terminal:
            # A terminal state (accepting, no legal continuation)
            # finishes the slot HERE — its empty mask row is never
            # dispatched.
            reason = "grammar_complete"
            self.grammar_completed += 1
        elif (len(req.output_tokens) >= req.max_tokens
              # lengths[slot] counts tokens written to the cache; the next
              # decode writes at position lengths[slot], which must stay
              # < max_seq_len (slot max_seq_len is the trash slot).
              or self.lengths[slot] >= self.max_seq_len):
            reason = "length"
        if reason:
            self.active[slot] = False
            self.slot_req[slot] = None
            self._on_slot_finished(slot, req)
        return reason

    def _deliver(self, req: Request, toks: List[int], reason: str) -> None:
        """The outside's half: per token, in order, the latency
        histograms and the streaming hook; then, with a finish reason, the
        finish itself. `finished` is set last — the worker resolves a
        request's future on it, and a stream must not close before its
        last tokens were handed over. Keyed by the request object alone:
        its slot may be another request's by now.

        Latency is host-observed: TTFT on the first token, inter-token
        gaps after. A chunk's tokens are handed over in one host loop, on
        one reading of the clock: within-chunk gaps are zero and the
        chunk's first token carries the chunk wall time — exactly the
        burst cadence an SSE client observes (docs/observability.md)."""
        reg = obs_metrics.REGISTRY
        now = time.monotonic()
        for tok in toks:
            if req._last_token_t:
                reg.observe("serve_inter_token_seconds",
                            now - req._last_token_t,
                            buckets=_INTER_TOKEN_BUCKETS,
                            help_text="Host-observed gap between "
                                      "consecutive generated tokens of one "
                                      "request.")
            else:
                req._first_token = now
                reg.observe("serve_ttft_seconds", now - request_start(req),
                            help_text="Time to first generated token "
                                      "(HTTP handler entry, or "
                                      "engine.submit where no handler "
                                      "stamped one, to the first token's "
                                      "hand-over).")
                reg.observe("serve_first_token_seconds",
                            now - req._admitted,
                            help_text="Slot assignment to the first "
                                      "token's hand-over: other groups' "
                                      "prefills of the tick, operands, "
                                      "dispatch, sync, activation.")
            req._last_token_t = now
            if req.on_token is not None:
                req.on_token(tok)
        if reason:
            _finish_request(req, reason, now)

    def deliver_parked(self, hidden: bool = False) -> None:
        """Hand over the tokens of the last decoded chunk, if they still
        wait (`_parked`). `hidden`: the caller has just dispatched a
        program, so this runs while the device works — the place a chunk's
        delivery is deferred to. Everyone else calls it because nothing
        will be dispatched to hide it behind, or because they are about to
        touch a request whose tokens may be parked (deadline, preemption,
        reset, the worker's crash path)."""
        if not self._parked:
            return
        parked, self._parked = self._parked, []
        self.decode_deliveries["deferred" if hidden else "inline"] += 1
        with fine("decode.replay") as replay:
            for row in parked:
                self._deliver(*row)
            replay.set(tokens=sum(len(toks) for _, toks, _ in parked))

    def _on_slot_finished(self, slot: int, req: Request) -> None:
        """Called once per slot whose request just finished (normal stop,
        length, or deadline expiry), after the slot's bookkeeping is
        cleared but before the slot can be re-admitted. The dense pool
        needs no cache work (the slot's rows simply get overwritten);
        the paged engine additionally releases the slot's page
        references and adopts its completed pages into the radix tree
        (serve/paging.py, which calls super())."""
        if self._spec_index is not None:
            self._spec_index.clear(slot)
        self.adapter_slots[slot] = -1
        if self.adapters is not None and req._adapter_lane >= 0:
            self.adapters.release(req._adapter_lane)
            req._adapter_lane = -1

    def _maybe_inject_fault(self) -> None:
        """RBT_FAULT_INJECT=engine:K hook, called at the top of step()
        (both the dense and paged variants): raise EngineStepFailed once
        when the configured step is reached, exactly like a poisoned
        jitted call would surface. One-shot — after the worker's crash
        handler reset()s, the engine serves normally again."""
        if self._fault_step is not None and self.steps >= self._fault_step:
            self._fault_step = None
            raise EngineStepFailed(
                f"RBT_FAULT_INJECT: simulated engine step failure at "
                f"step {self.steps}")

    def _swap_fault_hit(self) -> bool:
        """RBT_FAULT_INJECT=swapfail:K hook: True exactly once, on the
        Kth host-tier copy attempt (swap-out and swap-in attempts both
        count). The caller treats it as a failed copy and degrades —
        drop instead of swap-out, recompute instead of swap-in — with
        no crash and no leaked host or HBM pages (tests/test_kv_tier.py
        asserts the refcount balance)."""
        if self._swap_fault is None:
            return False
        self._swap_fault -= 1
        if self._swap_fault <= 0:
            self._swap_fault = None
            return True
        return False

    def _expire_deadlines(self) -> List[int]:
        """Finish requests whose wall-clock deadline passed (between decode
        chunks — a dispatched chunk is never interrupted). Queued requests
        expire empty-handed before ever occupying a slot; active requests
        free their slot with the tokens they have (finish_reason
        "deadline" either way). Returns the slots freed by expiry — the
        same step's _admit must NOT reuse them, so the worker's post-step
        finished-request pass (e.g. auto-prefix registration from the
        slot) still sees the expired request's KV, not a new tenant's."""
        now = time.monotonic()

        def expired(r: Request) -> bool:
            return (r.deadline_s is not None
                    and now >= r._submitted + r.deadline_s)

        n = 0
        keep = []
        for r in self.queue:
            if expired(r):
                # A queued request may already hold an adapter lane pin
                # (acquired while waiting for a slot/pages): release it
                # or the lane stays unEvictable forever.
                if self.adapters is not None and r._adapter_lane >= 0:
                    self.adapters.release(r._adapter_lane)
                    r._adapter_lane = -1
                _finish_request(r, "deadline", now)
                n += 1
            else:
                keep.append(r)
        if n:
            self.queue[:] = keep
        freed: List[int] = []
        for slot in range(self.max_slots):
            req = self.slot_req[slot]
            if self.active[slot] and req is not None and expired(req):
                # Its last tokens first, then the finish.
                self.deliver_parked()
                self._dev_blocks = None
                self.active[slot] = False
                self.slot_req[slot] = None
                self._on_slot_finished(slot, req)
                _finish_request(req, "deadline", now)
                freed.append(slot)
                n += 1
        self.deadline_expired += n
        return freed

    def _decode_span_attrs(self) -> dict:
        """Decode-span attrs, computed only when tracing is on: span()
        itself is a no-op when off, but eager kwargs would still charge
        the decode hot loop an array reduction per chunk."""
        if not record_enabled():
            return {}
        return {"active": int(self.active.sum()),
                "request_ids": [self.slot_req[i].request_id
                                for i in range(self.max_slots)
                                if self.active[i]]}

    def _take_chunk(self, pulled: np.ndarray) -> bool:
        """The slot half of one decoded chunk, from what the host pulled
        of it (pack_decode_fn: tokens a step, tokens a slot, alive after):
        every active slot takes its tokens, the rows that ended are freed,
        and what the outside will see of it is parked (`_parked`), a row a
        request. After it the engine's slot state is what chunk=1 stepping
        would have left, and _admit may reuse a freed slot. Returns whether
        the device's carry agrees with that state — the host took every
        token the device emitted and ended exactly the rows it ended — so
        that it may be the next chunk's operands.

        Grammar-constrained slots take only the chunk's FIRST token: the
        gmask is exact for step 0 only (it cannot advance inside the
        scan), so later steps may have sampled illegal tokens. Skipped
        steps don't advance `lengths` — their KV sits past the cursor and
        is rewritten by the next dispatch, the same stale-data invariant
        speculative rollback rides. chunk=1 (the CPU default) makes this
        a no-op; spec decode restores multi-token steps for constrained
        slots. The device can't see a grammar_complete finish either."""
        toks, counts, alive = pulled[:-2], pulled[-2], pulled[-1] != 0
        agreed = True
        for slot in np.nonzero(self.active)[0]:
            req = self.slot_req[slot]
            n = int(counts[slot])
            if req._grammar is not None and n > 1:
                n, agreed = 1, False
            new = toks[:n, slot].tolist()
            self.lengths[slot] += n
            self.last_token[slot] = new[-1]
            self._parked.append(
                (req, new, self._take_tokens(slot, req, new)))
        return agreed and np.array_equal(self.active, alive)

    def step(self) -> int:
        """Admit queued requests, then advance every active slot: one
        speculative verify forward when drafting is on and any slot has
        a draft (no-draft slots ride the same batch and advance one
        token), otherwise one decode chunk (`decode_chunk` forward steps
        in a single jit call). Returns the number of tokens generated
        across slots."""
        attrs = ({"active": int(self.active.sum()),
                  "queued": len(self.queue)} if fine_enabled() else {})
        with fine("tick", **attrs):
            self._maybe_inject_fault()
            with fine("tick.admit") as admit:
                before = self.prefix_lookups   # one per admitted request
                self._admit(exclude_slots=self._expire_deadlines())
                admit.set(admitted=self.prefix_lookups - before)
            if not self.active.any():
                # Nothing is dispatched to hide a delivery behind.
                self.deliver_parked()
                return 0
            generated: Optional[int] = None
            if self._spec_index is not None:
                drafts = self._collect_drafts()
                if drafts is not None:
                    generated = self._verify_step(drafts)
            if generated is None:
                generated = self._decode_chunk_step()
            self.steps += 1
            return generated

    # -- speculative decoding (docs/speculative-decoding.md) -----------

    def _draft_for(self, slot: int, max_tokens: int) -> List[int]:
        """Draft proposal for one slot (<= max_tokens tokens). The
        default source is the prompt-lookup n-gram index; overridable so
        benches/tests can substitute a controlled-accuracy oracle while
        exercising the REAL verify path."""
        return self._spec_index.draft(slot, max_tokens)

    def _collect_drafts(self) -> Optional[dict]:
        """Per-active-slot draft proposals, capped so a verify step can
        never overrun a request's token budget (emitting <= d+1 tokens
        must fit in max_tokens) or write past the context window (the
        verify forward writes positions L..L+d, which must stay below
        the trash slot). None when no slot proposes anything — the
        caller then runs the plain decode chunk, so draft-less traffic
        keeps its full chunk amortization."""
        K = self.draft_tokens
        drafts: dict = {}
        any_draft = False
        for slot in range(self.max_slots):
            if not self.active[slot]:
                continue
            req = self.slot_req[slot]
            cap = min(K,
                      self.max_seq_len - 1 - int(self.lengths[slot]),
                      req.max_tokens - len(req.output_tokens) - 1)
            d = self._draft_for(slot, cap) if cap >= 1 else []
            d = [int(t) for t in d[:max(cap, 0)]]
            if req._grammar is not None and d:
                # Cut the proposal at its first grammar-illegal token
                # (and at a terminal accept state) BEFORE dispatch, so
                # every drafted token has nonzero mass under its verify
                # position's mask and speculative_verify's exact
                # accept/reject math is untouched.
                legal = legal_draft_prefix(req._grammar, d)
                if len(legal) < len(d):
                    self.grammar_draft_truncations += 1
                    obs_metrics.REGISTRY.inc(
                        "serve_grammar_draft_truncations_total",
                        help_text="Speculative drafts truncated at a "
                                  "grammar-illegal token before verify "
                                  "dispatch.")
                d = legal
            drafts[slot] = d
            any_draft = any_draft or bool(drafts[slot])
        return drafts if any_draft else None

    def _verify_step(self, drafts: dict) -> int:
        """One batched draft-verify step: assemble the [B, K+1] operands
        (carry-in token + per-slot drafts), dispatch the verify program,
        and replay each slot's verdict on the host — accept the longest
        verified prefix, emit its correction/bonus token, and advance
        the KV cursor (`lengths`) only past what was accepted. Rejected
        tokens' KV stays as garbage beyond the cursor and is rewritten
        by the next dispatch before anything can attend it, so rollback
        is free (dense: scatter cursor; paged: in-page cursor — shared
        pages are structurally out of write range either way)."""
        B, K = self.max_slots, self.draft_tokens
        tokens = np.zeros((B, K + 1), np.int32)
        draft_len = np.zeros(B, np.int32)
        for slot, d in drafts.items():
            tokens[slot, 0] = self.last_token[slot]
            if d:
                tokens[slot, 1:1 + len(d)] = d
                draft_len[slot] = len(d)
        positions = np.where(self.active, self.lengths, 0).astype(np.int32)
        step_drafted = int(draft_len.sum())
        t_dispatch = time.perf_counter()
        accept, resid, full = self._verify_dispatch(
            tokens, positions, draft_len, self._slot_floats[ROW_TEMP],
            self._slot_ints[ROW_TOP_K], self._slot_floats[ROW_TOP_P],
            self._grammar_verify_kwargs(drafts))
        self._dev_blocks = None     # the host moves every cursor below
        wall = time.perf_counter() - t_dispatch
        generated = 0
        step_accepted = 0
        reg = obs_metrics.REGISTRY
        for slot, d in drafts.items():
            if not self.active[slot] or self.slot_req[slot] is None:
                continue
            nd = len(d)
            a = 0
            while a < nd and bool(accept[slot, a]):
                a += 1
            # Accepted drafts, then the model's own next token: the
            # residual correction at the first rejection, or the bonus
            # sample after a clean sweep (nd == 0 degenerates to a plain
            # one-token decode for this slot).
            emitted = d[:a] + [int(resid[slot, a]) if a < nd
                               else int(full[slot, nd])]
            if nd:
                self.spec_drafted += nd
                self.spec_accepted += a
                step_accepted += a
                reg.observe("serve_spec_accept_len", float(a),
                            buckets=_ACCEPT_LEN_BUCKETS,
                            help_text="Draft tokens accepted per slot "
                                      "per verify step.")
            for tok in emitted:
                if not self.active[slot]:
                    break  # EOS / budget / room finished mid-replay
                generated += 1
                self.lengths[slot] += 1
                self.last_token[slot] = tok
                self._record_token(slot, tok)
        self.spec_verify_steps += 1
        if step_drafted:
            rate = step_accepted / step_drafted
            idx = min(int(rate * 4), 3)
            bucket = self._spec_rate_buckets[_ACCEPT_RATE_BUCKETS[idx]]
            bucket[0] += generated
            bucket[1] += wall
        return generated

    # -- dispatch seams the paged engine overrides (serve/paging.py) ---

    def _view_key(self, max_pos: int) -> tuple:
        """(key of the decode/verify program, view label in tokens) of
        the smallest view covering every position a dispatch can reach."""
        view = self._view_for(max_pos)
        return view, view

    def _table_operands(self) -> tuple:
        """Host operands a decode/verify program takes before the token
        operands: none here, the page table in the paged engine."""
        return ()

    def _verify_dispatch(self, tokens, positions, draft_len, temps,
                         top_ks, top_ps, gkw=None):
        """Run the verify program at the smallest view covering every
        position this step can write (L + K), returning host verdict
        arrays. ``gkw`` is the grammar mask kwargs built by the caller
        against this step's drafts ({} when grammar is off)."""
        key, label = self._view_key(int(self.lengths[self.active].max())
                                    + self.draft_tokens + 1)
        t_dispatch = time.perf_counter()
        with span("verify", view=label, drafted=int(draft_len.sum()),
                  **self._decode_span_attrs()), self._mesh_ctx():
            accept, resid, full, self.cache, self.rng = \
                self._verify_for(key)(
                    self.params, self.cache,
                    *map(jnp.asarray, self._table_operands()),
                    jnp.asarray(tokens),
                    jnp.asarray(positions), jnp.asarray(draft_len),
                    self.rng, jnp.asarray(temps), jnp.asarray(top_ks),
                    jnp.asarray(top_ps), jnp.asarray(self.active),
                    **self._adapter_kwargs(), **(gkw or {}))
            # One sync a verify step, not a token.
            accept, resid, full = _pull("verify.sync", accept, resid, full)
        obs_metrics.REGISTRY.observe(
            "serve_verify_dispatch_seconds",
            time.perf_counter() - t_dispatch, view=str(label),
            help_text="Speculative verify dispatch+sync wall time, "
                      "labeled by cache view bucket.")
        return accept, resid, full

    def spec_stats(self) -> dict:
        """Speculation effectiveness snapshot (/debug/programs): draft
        volume, accept rate, and decode tok/s per accept-rate bucket —
        the host-side join that says whether drafting pays on THIS
        traffic (docs/speculative-decoding.md)."""
        out = {"mode": self.options.speculative}
        if self.options.speculative == "off":
            return out
        out.update({
            "draft_tokens": self.draft_tokens,
            "ngram_max": self.options.ngram_max,
            "ngram_min": self.options.ngram_min,
            "drafted_total": self.spec_drafted,
            "accepted_total": self.spec_accepted,
            "accept_rate": (round(self.spec_accepted / self.spec_drafted,
                                  4) if self.spec_drafted else None),
            "verify_steps": self.spec_verify_steps,
            "tokens_per_sec_by_accept_rate": {
                name: {"tokens": tok, "seconds": round(sec, 6),
                       "tokens_per_sec": (round(tok / sec, 1)
                                          if sec > 0 else None)}
                for name, (tok, sec) in self._spec_rate_buckets.items()},
        })
        return out

    def _decode_chunk_step(self) -> int:
        """One plain decode chunk over every active slot, dense or paged
        (the seams above are all that differs). Between the pull of a
        chunk and the next dispatch the host does only what that dispatch
        depends on (_take_chunk); handing the tokens over (deliver_parked)
        waits until the next program runs — here, or in _prefill_dispatch
        when the next tick admits — unless the host alone can see how this
        chunk's requests go on: a grammar cursor (one token a chunk,
        finishes the device cannot see) or the speculative draft index
        (current before _collect_drafts)."""
        key, label = self._view_key(int(self.lengths[self.active].max())
                                    + self.decode_chunk)
        inline = self._spec_index is not None or (
            self._grammar_cache is not None and any(
                self.slot_req[slot]._grammar is not None
                for slot in np.nonzero(self.active)[0]))
        with span("decode", view=label, **self._decode_span_attrs()), \
                self._mesh_ctx():
            with fine("decode.operands"):
                blocks = self._dev_blocks
                self.operand_places["carry" if blocks else "rebuilt"] += 1
                if blocks is None:
                    # A slot changed hands: the budget and who is alive
                    # join the rows the host keeps current anyway. Inactive
                    # rows' other values are inert (the device parks them).
                    left = self._slot_ints[ROW_LEFT]
                    left[:] = 0
                    for slot in np.nonzero(self.active)[0]:
                        req = self.slot_req[slot]
                        left[slot] = req.max_tokens - len(req.output_tokens)
                    self._slot_ints[ROW_ALIVE] = self.active
                t_dispatch = time.perf_counter()
                if blocks is None:
                    blocks = self._place_blocks(self._slot_ints,
                                                self._slot_floats)
                operands = (*map(jnp.asarray, self._table_operands()),
                            *blocks, self.rng)
                kwargs = {**self._decode_kwargs(),
                          **self._grammar_decode_kwargs()}
            with fine("decode.dispatch"):
                pulled, ints, self.cache, self.rng, *moe = \
                    self._decode_for(key)(
                        self.params, self.cache, *operands, **kwargs)
            # The chunk before this one, while the device works.
            self.deliver_parked(hidden=True)
            with fine("decode.sync"):
                # Tokens, counts and liveness in one [chunk + 2, slots]
                # array: one sync a chunk.
                (pulled,) = _pull("decode.sync", pulled)
                self._count_moe("decode", moe, self.max_slots,
                                steps=self.decode_chunk)
            obs_metrics.REGISTRY.observe(
                "serve_decode_dispatch_seconds",
                time.perf_counter() - t_dispatch, view=str(label),
                help_text="Decode-chunk dispatch+sync wall time, labeled "
                          "by cache view bucket.")
            before = self.lengths.copy()
            agreed = self._take_chunk(pulled)
            self._count_sparse_decode(label, before)
            self._dev_blocks = (ints, blocks[1]) if agreed else None
            generated = sum(len(toks) for _, toks, _ in self._parked)
            if inline or not self.has_work() or any(
                    reason and req.auto_prefix
                    for req, _, reason in self._parked):
                # No dispatch follows to hide behind, or the worker lifts
                # a finished auto_prefix request's prompt K/V out of its
                # slot before the next admission can recycle it.
                self.deliver_parked()
        return generated

    # ------------------------------------------------------------------
    # Convenience synchronous generation
    # ------------------------------------------------------------------

    def generate(self, requests: List[Request],
                 timeout_s: float = 600.0) -> List[Request]:
        for r in requests:
            self.submit(r)
        deadline = time.monotonic() + timeout_s
        while self.has_work() and time.monotonic() < deadline:
            self.step()
        self.deliver_parked()       # a timeout may leave a chunk parked
        return requests
