"""Sharded train state + train step.

The whole step (fwd, bwd, optimizer) is one jit'ed function over the mesh;
XLA inserts all collectives (FSDP all-gathers, TP all-reduces, gradient
reduce-scatters) from the sharding annotations — there is no hand-written
communication here (SURVEY.md §2a: the reference has no distributed backend;
this is the TPU-native equivalent, XLA collectives over ICI/DCN).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from runbooks_tpu.models.config import ModelConfig
from runbooks_tpu.models.transformer import forward, init_params, param_logical_axes
from runbooks_tpu.parallel.sharding import spec_for_array

Params = Any
Batch = Dict[str, jax.Array]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    step: jax.Array
    params: Params
    opt_state: Any


def cross_entropy_loss(
    logits: jax.Array,        # [b, s, v] float32
    targets: jax.Array,       # [b, s] int32
    weights: Optional[jax.Array] = None,  # [b, s] float {0,1} loss mask
) -> Tuple[jax.Array, jax.Array]:
    """Returns (mean loss over weighted tokens, total weight).

    This is the reference (parity-oracle) loss: it consumes fully
    materialized [b, s, v] f32 logits. The training fast path uses
    ``chunked_cross_entropy`` below, which never builds that tensor; this
    function is what the chunked path is tested against (the same role
    gpipe plays for 1f1b).
    """
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if weights is None:
        weights = jnp.ones_like(nll)
    weights = weights.astype(jnp.float32)
    total = jnp.maximum(jnp.sum(weights), 1.0)
    return jnp.sum(nll * weights) / total, total


def chunked_cross_entropy(
    acts: jax.Array,          # [b, s, d] post-final-norm activations
    head: jax.Array,          # [d, v] head weights (embed.T when tied)
    targets: jax.Array,       # [b, s] int32
    weights: Optional[jax.Array] = None,  # [b, s] float {0,1} loss mask
    chunk_size: int = 256,
    compute_dtype: Any = jnp.bfloat16,
) -> Tuple[jax.Array, jax.Array]:
    """Fused chunked softmax cross-entropy: (mean loss, total weight).

    Numerically equivalent to ``cross_entropy_loss(acts @ head, ...)`` but
    the [b, s, v] f32 logits tensor is never materialized: the sequence is
    processed in chunks of ``chunk_size`` tokens by a ``lax.scan`` whose
    body computes [b, c, v] chunk logits (bf16 operands, f32 accumulation
    — same dtype contract as the head einsum in models/transformer.py),
    reduces them to a stabilized log-sum-exp plus the target logit, and
    accumulates the weighted NLL sum. The body is ``jax.checkpoint``-ed so
    the backward re-forms each chunk's logits instead of the scan stacking
    [n_chunks, b, c, v] residuals — peak logits memory is O(b * c * v) in
    both passes. At llama vocab (32k) and s=2048 this is the difference
    between a 256 MB-per-sample tensor held twice and a ~32x smaller
    rolling buffer, which is what lets the accumulation path below raise
    the global batch.
    """
    b, s, _ = acts.shape
    if weights is None:
        weights = jnp.ones((b, s), jnp.float32)
    weights = weights.astype(jnp.float32)
    total = jnp.maximum(jnp.sum(weights), 1.0)

    c = max(1, min(int(chunk_size), s))
    n = -(-s // c)
    pad = n * c - s
    if pad:
        # Zero-weight padding tokens: they contribute exactly 0 to the sum.
        acts = jnp.pad(acts, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        weights = jnp.pad(weights, ((0, 0), (0, pad)))

    # [b, n*c, ...] -> [n, b, c, ...] so scan walks sequence chunks.
    a_ch = acts.reshape(b, n, c, acts.shape[-1]).transpose(1, 0, 2, 3)
    t_ch = targets.reshape(b, n, c).transpose(1, 0, 2)
    w_ch = weights.reshape(b, n, c).transpose(1, 0, 2)

    def body(nll_sum, xs):
        a_c, t_c, w_c = xs
        logits = jnp.einsum(
            "bch,hv->bcv", a_c.astype(compute_dtype),
            head.astype(compute_dtype),
            preferred_element_type=jnp.float32)
        # Online (per-chunk) max/log-sum-exp; the max shift is pure
        # stabilization, so no gradient flows through it.
        m = jax.lax.stop_gradient(
            jnp.max(logits, axis=-1, keepdims=True))
        lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
        tgt = jnp.take_along_axis(logits, t_c[..., None], axis=-1)[..., 0]
        return nll_sum + jnp.sum((lse - tgt) * w_c), None

    nll_sum, _ = jax.lax.scan(
        jax.checkpoint(body), jnp.zeros((), jnp.float32),
        (a_ch, t_ch, w_ch))
    return nll_sum / total, total


def infer_state_shardings(axes: Any, state_shapes: TrainState,
                          mesh: Mesh, rules=None) -> TrainState:
    """Shardings for a full TrainState given the params' logical-axes tree.

    Optimizer moments (adam mu/nu) have the same tree *suffix* paths as the
    params they track, so each state leaf is matched to a param's logical axes
    by its longest dict-key suffix; unmatched leaves (counts, scalars)
    replicate.
    """
    flat_axes: Dict[Tuple[str, ...], tuple] = {}
    def record(path, leaf):
        keys = tuple(k.key for k in path
                     if isinstance(k, jax.tree_util.DictKey))
        flat_axes[keys] = leaf
        return leaf
    jax.tree_util.tree_map_with_path(
        record, axes,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))

    def assign(path, leaf):
        keys = tuple(k.key for k in path
                     if isinstance(k, jax.tree_util.DictKey))
        for i in range(len(keys) + 1):
            logical = flat_axes.get(keys[i:])
            if logical is not None and len(logical) <= len(leaf.shape):
                return NamedSharding(
                    mesh, spec_for_array(leaf.shape, logical, mesh, rules))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(assign, state_shapes)


def batch_shardings(mesh: Mesh, batch_shapes, rules=None) -> Any:
    def one(s):
        logical = ("batch", "seq") if len(s.shape) == 2 else ("batch",)
        return NamedSharding(mesh, spec_for_array(s.shape, logical, mesh, rules))
    return jax.tree.map(one, batch_shapes)


@contextlib.contextmanager
def layout_invariant_init():
    """Make sharded jitted init independent of the device layout.

    The non-partitionable threefry lowering (this jax version's default)
    generates different random bits when ``jax.random.normal`` runs under
    ``jit(..., out_shardings=...)`` on different mesh layouts — the
    carried ROADMAP bug where d2f2t2/d4t2 initial params diverged from
    dp8/fsdp8 by enough for a 0.75% step-1 loss delta
    (tests/test_train_step.py::test_mesh_layouts_agree_numerically).
    The partitionable threefry lowering computes each element's bits from
    its *global* index, so every layout materializes the same values
    while still initializing shard-local (no single-host OOM on large
    models). Scoped to the init call: the flag is part of jit's trace
    key, so the train step itself is untouched.

    The scope also marks its compiles as *expected* for the compile
    sentinel (obs/device.py): a sharded init is by definition an
    intentional startup compile, and must not page an operator when it
    runs in a process where another component (a colocated serve
    engine) already declared itself steady.

    The flag flip is THREAD-LOCAL (jax config State context manager)
    whenever this jax exposes it: a colocated engine decoding on its
    worker thread must not see its jit cache key change mid-request (a
    recompile = serve-time stall). The process-global update is only
    the fallback for jax builds without the context-manager API.
    """
    from runbooks_tpu.obs import device as obs_device

    try:
        from jax._src.config import threefry_partitionable as _tp_state

        ctx = _tp_state(True)
    except (ImportError, AttributeError, TypeError):
        ctx = None
    with obs_device.SENTINEL.expected():
        if ctx is not None:
            with ctx:
                yield
        else:
            prev = jax.config.jax_threefry_partitionable
            jax.config.update("jax_threefry_partitionable", True)
            try:
                yield
            finally:
                jax.config.update("jax_threefry_partitionable", prev)


def create_train_state(
    cfg: ModelConfig,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    rng: jax.Array,
    rules=None,
) -> Tuple[TrainState, TrainState]:
    """Initialize a sharded TrainState directly on the mesh.

    Returns (state, state_shardings). Init happens inside jit with
    out_shardings so large models materialize already sharded (no single-host
    OOM); the partitionable-threefry scope makes the values identical on
    every mesh layout (see layout_invariant_init).
    """

    def init_fn(rng):
        params = init_params(cfg, rng)
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=optimizer.init(params),
        )

    state_shapes = jax.eval_shape(init_fn, rng)
    shardings = infer_state_shardings(param_logical_axes(cfg), state_shapes,
                                      mesh, rules)
    with jax.set_mesh(mesh), layout_invariant_init():
        state = jax.jit(init_fn, out_shardings=shardings)(rng)
    return state, shardings


def make_ce_terms(cfg: ModelConfig, remat: bool, loss_chunk: int):
    """(params, batch) -> (mean CE loss, total weight, MoE aux).

    loss_chunk > 0 selects the fused chunked path: the forward returns
    [b, s, d] activations (return_activations=True) and
    ``chunked_cross_entropy`` consumes them with the head weights, so the
    [b, s, vocab] f32 logits tensor never exists. loss_chunk == 0 is the
    reference path (full logits + ``cross_entropy_loss``), kept as the
    parity oracle. Shared by the full and LoRA train steps.
    """

    def ce_terms(params, batch: Batch):
        if loss_chunk:
            acts, _, aux = forward(
                cfg, params, batch["tokens"],
                positions=batch.get("positions"),
                segment_ids=batch.get("segment_ids"),
                remat=remat, with_aux=True, return_activations=True)
            head = (params["embed"].T if cfg.tie_embeddings
                    else params["head"])
            with jax.named_scope("loss"):
                loss, total = chunked_cross_entropy(
                    acts, head, batch["targets"], batch.get("loss_mask"),
                    chunk_size=loss_chunk,
                    compute_dtype=cfg.activation_dtype)
        else:
            logits, _, aux = forward(
                cfg, params, batch["tokens"],
                positions=batch.get("positions"),
                segment_ids=batch.get("segment_ids"),
                remat=remat, with_aux=True)
            with jax.named_scope("loss"):
                loss, total = cross_entropy_loss(
                    logits, batch["targets"], batch.get("loss_mask"))
        return loss, total, aux

    return ce_terms


def accumulated_value_and_grad(cfg: ModelConfig, ce_terms, k: int):
    """(params, batch) -> ((loss, total_weight), grads) over k microbatches.

    The [b, s] batch is viewed as [k, b/k, s]; a ``lax.scan`` runs
    fwd+bwd per microbatch and accumulates gradients into an f32
    accumulator (cast back to the param dtype at the end — bf16 params
    still accumulate exactly). Peak activation memory is that of ONE
    microbatch, which is what lets a fixed memory budget run a k-times
    larger global batch.

    Exactness: the full-batch loss is sum(nll*w)/total_w over the whole
    batch, so each microbatch contributes its *unnormalized* NLL sum
    scaled by the global 1/total_w (total_w is a function of the batch
    only, computed outside the grad). The k partial losses and gradients
    then sum to exactly the single-large-batch values; the MoE aux term
    (a nonlinear per-batch statistic) is averaged over microbatches.
    """

    def value_and_grad(params, batch: Batch):
        b = batch["tokens"].shape[0]
        if b % k:
            raise ValueError(
                f"accumulate_steps={k} must divide batch size {b}")
        micro = jax.tree.map(
            lambda a: a.reshape((k, b // k) + a.shape[1:]), batch)
        lm = batch.get("loss_mask")
        full_w = (jnp.sum(lm.astype(jnp.float32)) if lm is not None
                  else jnp.asarray(
                      float(b * batch["tokens"].shape[1]), jnp.float32))
        total_weight = jnp.maximum(full_w, 1.0)

        def micro_loss(p, mb):
            loss, total, aux = ce_terms(p, mb)
            # mean -> sum/global-total: partial losses sum to the
            # full-batch loss (see docstring).
            out = loss * total / total_weight
            if cfg.moe_num_experts:
                out = out + cfg.moe_aux_coef * aux / k
            return out

        grad_fn = jax.value_and_grad(micro_loss)

        def acc_body(carry, mb):
            loss_acc, grads_acc = carry
            loss_i, g_i = grad_fn(params, mb)
            grads_acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), grads_acc, g_i)
            return (loss_acc + loss_i, grads_acc), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss, grads_f32), _ = jax.lax.scan(
            acc_body, (jnp.zeros((), jnp.float32), zeros), micro)
        grads = jax.tree.map(lambda p, g: g.astype(p.dtype),
                             params, grads_f32)
        return (loss, total_weight), grads

    return value_and_grad


def make_train_step(
    cfg: ModelConfig,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    state_shardings: TrainState,
    rules=None,
    remat: bool = True,
    accumulate_steps: int = 1,
    loss_chunk: int = 0,
):
    """Build the jit'ed train step: (state, batch) -> (state, metrics).

    Batch keys: tokens [b,s], targets [b,s], and optional loss_mask [b,s],
    segment_ids [b,s], positions [b,s].

    accumulate_steps=k splits the batch into k microbatches scanned with a
    donated f32 gradient accumulator (one optimizer step per call; peak
    activation memory of one microbatch). loss_chunk=c computes the loss
    via the chunked fused cross-entropy (never materializing [b, s, vocab]
    logits; see chunked_cross_entropy). Both are ignored on the 1f1b
    pipeline path, which already microbatches and never builds full-batch
    logits — accumulate_steps>1 there raises (use
    cfg.pipeline_microbatches instead).
    """

    n_stages = int(mesh.shape.get("stage", 1))
    use_1f1b = n_stages > 1 and cfg.pipeline_schedule == "1f1b"
    if cfg.pipeline_schedule not in ("1f1b", "gpipe"):
        raise ValueError(
            f"unknown pipeline_schedule {cfg.pipeline_schedule!r}; "
            "expected 1f1b|gpipe")
    k = int(accumulate_steps)
    if k < 1:
        raise ValueError(f"accumulate_steps must be >= 1, got {k}")
    if use_1f1b and k > 1:
        raise ValueError(
            "accumulate_steps > 1 is redundant under the 1f1b pipeline "
            "schedule (it already runs per-microbatch fwd/bwd); set "
            "cfg.pipeline_microbatches instead")

    ce_terms = make_ce_terms(cfg, remat, int(loss_chunk))
    acc_grad_fn = accumulated_value_and_grad(cfg, ce_terms, k) if k > 1 \
        else None

    def step_fn(state: TrainState, batch: Batch):
        if use_1f1b:
            # Explicit-backward pipeline: in-flight activations bounded by
            # O(stages), no full-batch logits (models/transformer.py:
            # loss_and_grads_1f1b). The gpipe schedule below is the
            # autodiff oracle it is tested against.
            from runbooks_tpu.models.transformer import loss_and_grads_1f1b

            loss, grads, total_weight = loss_and_grads_1f1b(
                cfg, state.params, batch["tokens"], batch["targets"],
                batch.get("loss_mask"),
                positions=batch.get("positions"),
                segment_ids=batch.get("segment_ids"))
        elif acc_grad_fn is not None:
            (loss, total_weight), grads = acc_grad_fn(state.params, batch)
        else:
            def loss_fn(params):
                loss, total, aux = ce_terms(params, batch)
                if cfg.moe_num_experts:
                    loss = loss + cfg.moe_aux_coef * aux
                return loss, total

            (loss, total_weight), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params)
        with jax.named_scope("optimizer"):
            updates, new_opt_state = optimizer.update(
                grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
            grad_norm = optax.global_norm(grads)
            # Non-finite guard (docs/fault-tolerance.md): a poisoned batch
            # or a numeric blow-up must not write NaN into the params — the
            # update is skipped wholesale (params AND optimizer state
            # bitwise unchanged, step counter still advances) and the step
            # is flagged in metrics so the trainer can count consecutive
            # bad steps and abort.
            ok = jnp.isfinite(loss) & jnp.isfinite(grad_norm)
            new_params, new_opt_state = jax.tree.map(
                lambda new, old: jnp.where(ok, new, old),
                (new_params, new_opt_state),
                (state.params, state.opt_state))
        metrics = {
            "loss": loss,
            "grad_norm": grad_norm,
            "weight_tokens": total_weight,
            "nonfinite": (~ok).astype(jnp.int32),
        }
        return TrainState(step=state.step + 1, params=new_params,
                          opt_state=new_opt_state), metrics

    replicated = NamedSharding(mesh, P())
    return jax.jit(
        step_fn,
        in_shardings=(state_shardings, None),
        out_shardings=(state_shardings, replicated),
        donate_argnums=(0,),
    )
