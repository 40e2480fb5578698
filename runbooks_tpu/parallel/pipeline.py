"""Pipeline parallelism over a "stage" mesh axis: GPipe forward + 1F1B.

TPU-native design (SURVEY §2a: the reference has no parallelism engine to
port): the transformer already stores its layers *stacked* and scans over
them, so pipelining is a resharding of that same structure — the stacked
leading dim shards over the ``stage`` mesh axis, and the forward becomes an
SPMD loop of ticks in which every stage runs its layer block on its current
microbatch and ``lax.ppermute``s activations between stages. No per-stage
programs: one jitted SPMD computation.

Two schedules:

- ``pipeline_apply`` (GPipe, forward-only): differentiable end-to-end (the
  transpose of ppermute is the reverse permute, so jax.grad yields the
  exact pipelined backward). Simple, but the autodiff tape keeps O(M)
  microbatch activations live per stage — a correctness oracle and the
  inference/eval path, not the way to train at scale.
- ``pipeline_1f1b_grads`` (1F1B, training): owns the backward explicitly.
  Each tick runs one microbatch-forward AND one microbatch-backward per
  stage; backward recomputes the stage block from a saved input (full
  rematerialization — the same trade cfg.remat_policy="nothing_saveable"
  makes) and ``jax.vjp``s it, accumulating layer grads in-loop. The only
  cross-tick activation storage is a residual ring of min(M, 2S-1) block
  INPUTS per stage — in-flight activations are bounded by O(S) no matter
  how many microbatches amortize the bubble, which is the point of 1F1B.
  The head/loss runs at the last stage mid-pipeline and full-batch logits
  are never materialized.

Bubble fraction: GPipe (S-1)/(S+M-1); the 1F1B loop runs M + 2(S-1)
double-pumped (fwd+bwd) ticks. During fill/drain, stages compute on
garbage rows — wasted FLOPs, bought for compiler simplicity (static
shapes, no data-dependent control flow: the XLA-friendly trade).

Used by models/transformer.forward when the active mesh has stage > 1 (the
no-cache path; decode pipelining is a serving-engine concern, not a
training one), and by train/step.py via transformer.loss_and_grads_1f1b.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _psum(x, axis):
    """psum that survives the CPU backend: XLA CPU's AllReducePromotion
    pass aborts on the bf16 all-reduces of the stage-manual region
    ("Invalid binary instruction opcode copy" CHECK) — upcast around the
    collective there. Still so on jaxlib 0.9.0: without the upcast
    tests/test_pipeline.py::test_1f1b_bf16_activations_compile_on_cpu
    aborts the process. On TPU the native bf16 all-reduce is kept (half
    the ICI bytes)."""
    if x.dtype == jnp.bfloat16 and jax.default_backend() == "cpu":
        return jax.lax.psum(x.astype(jnp.float32),
                            axis).astype(jnp.bfloat16)
    return jax.lax.psum(x, axis)


def _mb_index(tree, idx):
    """Select microbatch idx (traced ok) from arrays shaped [M, ...]."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, idx, axis=0,
                                               keepdims=False),
        tree)


def pipeline_apply(
    block_fn: Callable,                # (layer, x, consts_mb) -> (x, aux)
    layers: Any,                       # pytree, leaves [L, ...], L = S*Lps
    x: jax.Array,                      # [b, s, h] embedded activations
    consts: Any,                       # pytree of [b, ...] per-batch consts
    *,
    mesh,
    n_stages: int,
    n_microbatches: Optional[int] = None,
    axis: str = "stage",
):
    """Run the layer stack as a pipeline; returns (activations [b, s, h],
    aux-loss scalar — per-layer aux summed over layers, averaged over
    microbatches).

    block_fn runs ONE layer; each stage scans it over its L/S local layers.
    consts is a pytree of batch-leading arrays (positions, masks, ...)
    microbatched alongside x; None leaves pass through.
    """
    S = n_stages
    M = n_microbatches or S
    b = x.shape[0]
    L = jax.tree.leaves(layers)[0].shape[0]
    if L % S:
        raise ValueError(f"{L} layers not divisible by {S} pipeline stages")
    if b % M:
        raise ValueError(f"batch {b} not divisible by {M} microbatches")

    def to_mb(a):
        return a.reshape((M, b // M) + a.shape[1:])

    x_mb = to_mb(x)
    consts_mb = jax.tree.map(to_mb, consts)

    def stage_fn(layers_local, x_mb, consts_mb):
        stage = jax.lax.axis_index(axis)

        def run_block(x, mb_consts):
            def scan_body(carry, layer):
                y, aux_sum = carry
                y, aux = block_fn(layer, y, mb_consts)
                return (y, aux_sum + aux), None
            (y, aux), _ = jax.lax.scan(
                scan_body, (x, jnp.zeros((), jnp.float32)), layers_local)
            return y, aux

        recv = jnp.zeros_like(x_mb[0])
        out_buf = jnp.zeros_like(x_mb)
        aux_total = jnp.zeros((), jnp.float32)
        for t in range(S + M - 1):
            # Stage s works on microbatch t - s at tick t (when in range);
            # stage 0 feeds fresh microbatches, others consume upstream
            # activations from the previous tick's ppermute.
            feed_idx = min(t, M - 1)
            inp = jnp.where(stage == 0, x_mb[feed_idx], recv)
            mb_idx = jnp.clip(t - stage, 0, M - 1)
            out, aux = run_block(inp, _mb_index(consts_mb, mb_idx))
            # Fill/drain ticks compute on garbage rows; only in-range
            # microbatches contribute aux.
            valid = jnp.logical_and(t - stage >= 0,
                                    t - stage <= M - 1)
            aux_total = aux_total + jnp.where(valid, aux, 0.0)
            # Last stage banks its result. Clamped static index: before the
            # pipeline fills (t < S-1) this writes garbage to slot 0, which
            # the real microbatch-0 result overwrites at t = S-1.
            out_buf = jax.lax.dynamic_update_index_in_dim(
                out_buf, out, max(t - (S - 1), 0), axis=0)
            if t < S + M - 2:
                recv = jax.lax.ppermute(
                    out, axis, [(i, (i + 1) % S) for i in range(S)])
        # Everyone returns the last stage's buffer (masked psum broadcast),
        # so the head/loss runs replicated over the stage axis.
        is_last = (stage == S - 1).astype(out_buf.dtype)
        # aux: every stage saw every microbatch once -> psum over stages
        # sums over layers; divide by M for the per-batch mean.
        return (_psum(out_buf * is_last, axis),
                jax.lax.psum(aux_total, axis) / M)

    # Manual only over the stage axis: data/fsdp/sequence/tensor sharding
    # inside the stage body stays with the GSPMD partitioner.
    layer_specs = jax.tree.map(lambda _: P(axis), layers)
    const_specs = jax.tree.map(lambda _: P(), consts_mb)
    out, aux = jax.shard_map(
        stage_fn, mesh=mesh,
        in_specs=(layer_specs, P(), const_specs),
        out_specs=(P(), P()),
        axis_names={axis},
        check_vma=False,
    )(layers, x_mb, consts_mb)
    return out.reshape((b,) + x.shape[1:]), aux


def pipeline_1f1b_grads(
    block_fn: Callable,                # (layer, x, consts_mb) -> (x, aux)
    head_loss_fn: Callable,            # (head_params, y_mb, loss_consts_mb)
                                       #   -> scalar loss contribution
    layers: Any,                       # pytree, leaves [L, ...], L = S*Lps
    head_params: Any,                  # pytree used by head_loss_fn
    x: jax.Array,                      # [b, s, h] embedded activations
    consts: Any,                       # pytree of [b, ...] per-batch consts
    loss_consts: Any,                  # pytree of [b, ...] (targets, masks)
    *,
    mesh,
    n_stages: int,
    n_microbatches: Optional[int] = None,
    axis: str = "stage",
    aux_scale: float = 0.0,            # cotangent for block aux (MoE coef/M)
    head_specs: Any = None,            # per-leaf PartitionSpec for
                                       # head_params; any non-replicated
                                       # leaf selects the SHARDED head path
):
    """1F1B training pipeline: returns (loss_sum, layer_grads, head_grads,
    dx [b,s,h], aux_mean).

    Schedule (double-pumped SPMD ticks; every stage runs one F and one B
    sub-step per tick, masked outside its live range):

      F(i, s) at tick i + s               (same timing as GPipe)
      B(i, s) at tick i + 2(S-1) - s      (last stage: same tick as its F)

    so the backward of microbatch i leaves the last stage immediately after
    its forward and flows back one stage per tick. A stage's residual —
    just the block INPUT; the backward rematerializes the block and vjps it
    — lives 2(S-1) - 2s ticks, so a ring of min(M, 2S-1) slots suffices for
    ANY M: in-flight activation memory is O(S), not O(M). Total ticks:
    M + 2(S-1).

    head_loss_fn must return the microbatch's *contribution to the total
    scalar loss* (caller pre-scales by 1/total_weight); its grads w.r.t.
    head_params accumulate across microbatches and are psum'd, and its
    grad w.r.t. y seeds the backward.

    Head scheduling: the last stage's forward microbatch index t - (S-1)
    is STATIC per tick, so the head runs only in the tick window
    [S-1, S-2+M] — M head invocations per stage instead of one per tick
    (a Python-level if: uniform across stages, no GSPMD non-uniformity).
    Within the window two modes:

    - replicated head_params (default): every stage runs the head on its
      own y and masks to the last stage (the r3/r4 shape) — S x the
      oracle's head FLOPs, acceptable for small vocabularies and required
      for tied embeddings.
    - sharded head_specs (e.g. the [h, vocab] head split over the stage
      axis): the last stage's y broadcasts (one h-sized psum), every
      stage computes its vocab slice of the head fwd+bwd, and the dy
      partials psum back (second h-sized psum). head_loss_fn must be
      written vocab-parallel (global log-softmax via psum/pmax over the
      stage axis, returning a per-stage partial loss whose stage-psum is
      the true loss — models/transformer.loss_and_grads_1f1b provides
      this). Total head FLOPs = 1 x the oracle at the cost of two
      h-sized collectives per tick: the S x masked-head overhead
      (~(S-1) x 2*s*h*V/M FLOPs per tick, dominant at llama-3-size
      vocabularies) becomes ICI traffic that overlaps with compute.

    The microbatch feed is block-sharded over stages (in_spec P(axis)) and
    rotated toward stage 0 every M/S ticks — stage 0 consumes each block
    as it arrives, so no stage ever holds the full batch feed (requires
    M % S == 0; M defaults to S). dx is banked replicated (it feeds the
    embedding backward, which runs stage-replicated anyway).
    """
    S = n_stages
    M = n_microbatches or S
    b = x.shape[0]
    L = jax.tree.leaves(layers)[0].shape[0]
    if L % S:
        raise ValueError(f"{L} layers not divisible by {S} pipeline stages")
    if b % M:
        raise ValueError(f"batch {b} not divisible by {M} microbatches")
    if M % S:
        raise ValueError(
            f"1F1B feed sharding needs microbatches ({M}) divisible by "
            f"stages ({S}); set pipeline_microbatches to a multiple of "
            f"{S} (or use the gpipe schedule)")
    Q = M // S                 # microbatches per feed block
    R = min(M, 2 * S - 1)      # residual ring slots
    T = M + 2 * (S - 1)        # double-pumped ticks

    if head_specs is None:
        head_specs = jax.tree.map(lambda _: P(), head_params)
    # Leaves with a replicated spec hold identical values on every stage
    # and their grads psum at the end; sharded leaves (vocab-split head)
    # keep per-stage grad slices that the outer shard_map reassembles.
    head_psum_mask = jax.tree.map(
        lambda spec: all(a is None for a in spec), head_specs,
        is_leaf=lambda s: isinstance(s, P))
    sharded_head = not all(jax.tree.leaves(head_psum_mask))

    def to_mb(a):
        return a.reshape((M, b // M) + a.shape[1:])

    x_mb = to_mb(x)
    consts_mb = jax.tree.map(to_mb, consts)
    loss_consts_mb = jax.tree.map(to_mb, loss_consts)

    def stage_fn(layers_local, head_params, x_loc, consts_mb,
                 loss_consts_mb):
        stage = jax.lax.axis_index(axis)
        is_last = stage == S - 1

        def run_block(layers_loc, x, mb_consts):
            def scan_body(carry, layer):
                y, aux_sum = carry
                y, aux = block_fn(layer, y, mb_consts)
                return (y, aux_sum + aux), None
            (y, aux), _ = jax.lax.scan(
                scan_body, (x, jnp.zeros((), jnp.float32)), layers_loc)
            return y, aux

        mb_shape = x_loc[0]
        feed = x_loc                               # [Q, b/M, s, h]
        recv_f = jnp.zeros_like(mb_shape)
        recv_b = jnp.zeros_like(mb_shape)
        # R live slots + one trash slot (index R): fill/drain ticks write
        # their garbage input there — a drain tick's clipped index would
        # otherwise clobber microbatch M-1's residual before its backward
        # reads it (observed as garbage dx at stages <= S-2).
        ring = jnp.zeros((R + 1,) + mb_shape.shape, mb_shape.dtype)
        dx_buf = jnp.zeros((M,) + mb_shape.shape, mb_shape.dtype)
        gacc_layers = jax.tree.map(jnp.zeros_like, layers_local)
        gacc_head = jax.tree.map(jnp.zeros_like, head_params)
        loss_sum = jnp.zeros((), jnp.float32)
        aux_sum = jnp.zeros((), jnp.float32)

        head_vg = jax.value_and_grad(head_loss_fn, argnums=(0, 1))

        for t in range(T):
            # ---- forward sub-step: F(mb_f, stage) at tick mb_f + stage.
            mb_f = t - stage
            f_valid = jnp.logical_and(mb_f >= 0, mb_f <= M - 1)
            mb_f_c = jnp.clip(mb_f, 0, M - 1)
            # Stage 0 feeds from its current rotated block; feed blocks
            # arrive just-in-time (block k = microbatches [kQ, (k+1)Q),
            # held by stage 0 during exactly those ticks after k
            # rotations), so the local row is t % Q. Drain ticks (t >= M)
            # read a stale row that f_valid masks out.
            inp = jnp.where(stage == 0, feed[t % Q], recv_f)

            mb_b = t - 2 * (S - 1) + stage
            b_valid = jnp.logical_and(mb_b >= 0, mb_b <= M - 1)
            mb_b_c = jnp.clip(mb_b, 0, M - 1)

            y_f, aux_f = run_block(layers_local, inp,
                                   _mb_index(consts_mb, mb_f_c))
            aux_sum = aux_sum + jnp.where(f_valid, aux_f, 0.0)
            ring = jax.lax.dynamic_update_index_in_dim(
                ring, inp, jnp.where(f_valid, mb_f_c % R, R), axis=0)
            # Residual read AFTER this tick's write: at the last stage,
            # B(i, S-1) shares the tick with F(i, S-1), so the residual it
            # needs is the input just written. For s < S-1 the slots of a
            # valid same-tick write/read differ (slot distance
            # 2(S-1-s) mod R is nonzero: it is < M when both are valid,
            # and < 2S-1 always), so nothing is clobbered early.
            x_saved = jax.lax.dynamic_index_in_dim(
                ring, mb_b_c % R, axis=0, keepdims=False)

            # Head + loss + dy. The last stage's forward microbatch index
            # t - (S-1) is static, so the head runs only in the tick
            # window where it is in range — a Python if, uniform across
            # stages (GSPMD collectives inside a stage-non-uniform
            # lax.cond crash the partitioner: spmd_partitioner_util CHECK,
            # observed). Replicated mode masks to the last stage; sharded
            # mode broadcasts the last stage's y and computes vocab
            # slices everywhere (see docstring).
            if S - 1 <= t <= S - 2 + M:
                head_mb = t - (S - 1)
                lc = _mb_index(loss_consts_mb, head_mb)
                if sharded_head:
                    y_head = _psum(jnp.where(is_last, y_f, 0), axis)
                    loss_t, (ghead_t, dy_loc) = head_vg(head_params,
                                                        y_head, lc)
                    # Partial loss / local slice grads: real on every
                    # stage, no mask.
                    loss_sum = loss_sum + loss_t
                    gacc_head = jax.tree.map(lambda a, g: a + g,
                                             gacc_head, ghead_t)
                    dy_t = _psum(dy_loc, axis)
                else:
                    # Non-last stages run on their own (wrong-microbatch)
                    # y_f and are masked out — uniformity over FLOPs.
                    loss_t, (ghead_t, dy_t) = head_vg(head_params, y_f, lc)
                    loss_sum = loss_sum + jnp.where(is_last, loss_t, 0.0)
                    gacc_head = jax.tree.map(
                        lambda a, g: a + jnp.where(is_last, g, 0),
                        gacc_head, ghead_t)
            else:
                dy_t = jnp.zeros_like(mb_shape)

            # ---- backward sub-step: B(mb_b, stage) at tick
            # mb_b + 2(S-1) - stage. Rematerialize the block from the saved
            # input and vjp it; aux gets its loss-weight as cotangent.
            def blk(Ls, xx):
                return run_block(Ls, xx, _mb_index(consts_mb, mb_b_c))

            g_in = jnp.where(is_last, dy_t, recv_b)
            _, vjp_fn = jax.vjp(blk, layers_local, x_saved)
            dlayers, dx = vjp_fn(
                (g_in, jnp.asarray(aux_scale, jnp.float32)))
            gacc_layers = jax.tree.map(
                lambda a, g: a + jnp.where(b_valid, g, 0),
                gacc_layers, dlayers)
            # Bank dx (real data only at stage 0; garbage rows from
            # fill ticks land clipped at slot 0 and are overwritten by the
            # real slot-0 write later).
            dx_buf = jax.lax.dynamic_update_index_in_dim(
                dx_buf, dx, mb_b_c, axis=0)

            if t < T - 1:
                recv_f = jax.lax.ppermute(
                    y_f, axis, [(i, (i + 1) % S) for i in range(S)])
                recv_b = jax.lax.ppermute(
                    dx, axis, [(i, (i - 1) % S) for i in range(S)])
                if (t + 1) % Q == 0 and t + 1 < M:
                    # Next feed block drifts one stage toward stage 0.
                    feed = jax.lax.ppermute(
                        feed, axis, [(i, (i - 1) % S) for i in range(S)])

        is_first = (stage == 0).astype(dx_buf.dtype)
        dx_full = _psum(dx_buf * is_first, axis)
        loss_sum = jax.lax.psum(loss_sum, axis)
        # Replicated head leaves: every stage contributed a (masked or
        # partial) grad -> psum. Sharded leaves: each stage already holds
        # exactly its slice's grad; the outer shard_map reassembles.
        gacc_head = jax.tree.map(
            lambda g, do_psum: _psum(g, axis) if do_psum else g,
            gacc_head, head_psum_mask)
        aux_mean = jax.lax.psum(aux_sum, axis) / M
        return loss_sum, gacc_layers, gacc_head, dx_full, aux_mean

    layer_specs = jax.tree.map(lambda _: P(axis), layers)
    const_specs = jax.tree.map(lambda _: P(), consts_mb)
    lconst_specs = jax.tree.map(lambda _: P(), loss_consts_mb)
    loss_sum, layer_grads, head_grads, dx, aux_mean = jax.shard_map(
        stage_fn, mesh=mesh,
        in_specs=(layer_specs, head_specs, P(axis), const_specs,
                  lconst_specs),
        out_specs=(P(), layer_specs, head_specs, P(), P()),
        axis_names={axis},
        check_vma=False,
    )(layers, head_params, x_mb, consts_mb, loss_consts_mb)
    return (loss_sum, layer_grads, head_grads,
            dx.reshape((b,) + x.shape[1:]), aux_mean)
