"""Mixture-of-Experts layer: dropless, and told which experts it holds.

One layer for every sparse model (docs/sparse-latent-models.md). A token
is scored over ALL ``moe_num_experts`` experts, ``moe_top_k`` of them are
chosen, and this layer computes

    sum over e in (chosen AND held here) of  g_e * Expert_e(x)

for the contiguous range of experts whose weights it was given: the
leading axis of ``p["wi_gate"]`` is the experts HELD, ``held`` the id of
the first. What the other experts would add is computed by whoever holds
them — here, by nobody — and adds nothing. A process that holds every
expert computes the whole layer; a chip that holds one share of them
(``ModelConfig.moe_experts_held``) computes its share; under an "expert"
mesh axis the same function runs a shard (tokens replicated over that
axis) and the parts are summed. The share and expert parallelism are one
mechanism.

Dropless: no capacity, no token is dropped and a token's output does not
depend on its batch-mates. The (token, expert) assignments are sorted by
expert, the experts run as one grouped (ragged) matrix product whose work
follows the group sizes (``grouped_matmul``: rows beyond the held groups
— the assignments routed elsewhere, sorted last — belong to no group and
cost nothing), and the gate weights are applied on the way back to token
order. Static shapes throughout: the sorted array always has tokens x
top_k rows.

Router kinds are config, not code paths by model name
(``ModelConfig.moe_router``):
  softmax  probabilities over all experts, the chosen k renormalised
  sigmoid  a sigmoid a score; the selection bias (``router_bias``) is
           added ONLY to choose; gate weights are the chosen scores over
           their sum (plus ``moe_router_eps`` where the model adds
           one), times ``moe_routed_scale``
The router runs in float32: near-ties decide which expert runs.

The shared expert is the dense gated MLP at width ``moe_intermediate_size
x moe_shared_experts``, added once, unscaled, by whoever calls with
``shared=True`` (one caller among the shares).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# Tokens whose assignments are sorted and run at once. The sorted copy of
# the layer's input has tokens x top_k rows whoever holds the experts; a
# [8, 2048] prefill at top-8 would gather a gigabyte a layer.
TOKEN_CHUNK = 2048


def route(cfg, p, xt: jax.Array):
    """xt [T, h] -> (scores [T, E] f32, chosen ids [T, k] int32, gate
    weights [T, k] f32, the weights already scaled)."""
    logits = jnp.einsum("th,he->te", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    if cfg.moe_router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choose = scores
        if "router_bias" in p:
            # The bias moves the CHOICE only (load balancing without an
            # auxiliary loss); the weights are the unbiased scores.
            choose = scores + p["router_bias"].astype(jnp.float32)
    else:
        scores = choose = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(choose, cfg.moe_top_k)
    gate = jnp.take_along_axis(scores, idx, axis=-1)
    total = gate.sum(-1, keepdims=True)
    gate = gate / (total + cfg.moe_router_eps if cfg.moe_router_eps
                   else jnp.maximum(total, 1e-9))
    return scores, idx.astype(jnp.int32), gate * cfg.moe_routed_scale


def _balance_loss(cfg, scores, idx):
    """Switch load-balance loss: E * sum_e mean_score_e * mean_first_e
    (first-choice assignment fraction), minimized by uniform routing."""
    E = cfg.moe_num_experts
    probs = scores / jnp.maximum(scores.sum(-1, keepdims=True), 1e-9)
    me = probs.mean(axis=0)
    ce = jax.nn.one_hot(idx[:, 0], E, dtype=probs.dtype).mean(axis=0)
    return (E * (me * ce).sum()).astype(jnp.float32)


def _gmm_tiling(m: int, k: int, n: int, itemsize: int = 2):
    """(tm, tk, tn) for the Pallas grouped matmul, or None where its tiles
    do not divide the problem (tm must divide the rows). Two buffers each
    of a [tm, tk] and a [tk, tn] operand tile and of a [tm, tn] result
    tile, and a float32 accumulator, stay under the 16 MiB of scoped VMEM:
    10 MiB in bfloat16, 8 in float32."""
    wide = itemsize > 2
    tm = next((t for t in (512, 256, 128, 64, 32, 16)
               if m % t == 0 and not (wide and t > 256)), None)
    if tm is None or k % 128 or n % 128:
        return None
    return tm, min(k, 512 if wide else 1024), min(n, 1024)


def grouped_matmul(lhs, w, sizes, layer=None):
    """lhs [m, k], rows sorted by group, times each group's matrix ->
    [m, n] in lhs's dtype, float32 accumulation. Rows past the groups'
    total belong to no group: nothing is computed for them and what they
    hold is unspecified.

    w [groups, k, n] with sizes [groups]: `jax.lax.ragged_dot` (portable,
    differentiable, partitionable). w [layers, groups, k, n] with `layer`
    (the cached, serving forward): the WHOLE stack is handed over and the
    layer's groups are named by their sizes among zeros — a layer's slice
    of the stack is never cut out. Cut out inside a layer scan, it is a
    copy of every held expert's weights a layer and step, whichever
    experts the step hits (measured: 20 of a 27 ms decode step, PERF.md
    section 6, PR 30). On a TPU that case runs the Pallas grouped matmul
    (megablox), which visits only the (group, row tile) pairs that hold
    rows and keeps the program's scope names, which the compiler's own
    ragged dot loses."""
    w = w.astype(lhs.dtype)
    if layer is not None:
        from runbooks_tpu.utils.hw import on_tpu

        n_layers, n_groups = w.shape[:2]
        w = w.reshape((n_layers * n_groups,) + w.shape[2:])
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros(n_layers * n_groups, jnp.int32), sizes,
            (layer * n_groups,))
        tiling = _gmm_tiling(lhs.shape[0], *w.shape[1:], lhs.dtype.itemsize)
        if on_tpu() and tiling is not None:
            from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

            return gmm(lhs, w, sizes, preferred_element_type=lhs.dtype,
                       tiling=tiling)
    return jax.lax.ragged_dot(
        lhs, w, sizes, preferred_element_type=jnp.float32).astype(lhs.dtype)


def _held_part(cfg, p, xt, idx, gate, first, layer=None):
    """The held experts' part of the sum for tokens xt [T, h] ->
    (y [T, h] in the activation dtype, counts [held + 1] int32: the
    assignments each held expert got, and last those routed elsewhere).
    `layer`: see grouped_matmul (the expert weights are then stacks)."""
    from runbooks_tpu.models.transformer import _activation

    ad = cfg.activation_dtype
    T, k = idx.shape
    n_held = p["wi_gate"].shape[0 if layer is None else 1]
    with jax.named_scope("moe.sort"):
        local = idx.reshape(-1) - first
        here = (local >= 0) & (local < n_held)
        # Routed elsewhere: one group past the held ones, so last.
        group = jnp.where(here, local, n_held)
        order = jnp.argsort(group, stable=True)
        counts = jnp.bincount(group, length=n_held + 1).astype(jnp.int32)
        sizes = counts[:n_held]
        token = (jnp.arange(T * k, dtype=jnp.int32) // k)[order]
        xs = xt.astype(ad)[token]
    with jax.named_scope("moe.experts"):
        def grouped(lhs, w):
            return grouped_matmul(lhs, w, sizes, layer)

        hidden = _activation(cfg, grouped(xs, p["wi_gate"])) \
            * grouped(xs, p["wi_up"])
        out = grouped(hidden, p["wo"])
    with jax.named_scope("moe.combine"):
        # Back to token order, then the gate-weighted sum over a token's k
        # assignments (float32 accumulation). Rows past the held groups
        # hold nothing anybody computed: they are dropped by selection,
        # never by a multiplication with 0.
        rows = out[jnp.argsort(order)].reshape(T, k, -1)
        held_here = here.reshape(T, k)
        y = jnp.einsum(
            "tkh,tk->th", jnp.where(held_here[..., None], rows, 0),
            jnp.where(held_here, gate, 0.0).astype(ad),
            preferred_element_type=jnp.float32).astype(ad)
    return y, counts


def _held_part_chunked(cfg, p, xt, idx, gate, first, layer=None):
    """_held_part, TOKEN_CHUNK tokens at a time (one chunk: as it is)."""
    T, k = idx.shape
    if T <= TOKEN_CHUNK:
        return _held_part(cfg, p, xt, idx, gate, first, layer)
    n = -(-T // TOKEN_CHUNK)
    pad = n * TOKEN_CHUNK - T

    def chunks(a, value=0):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                    constant_values=value)
        return a.reshape((n, TOKEN_CHUNK) + a.shape[1:])

    # Padding is routed to an expert nobody has: it reads as `elsewhere`,
    # and is taken off that count again.
    y, counts = jax.lax.map(
        lambda c: _held_part(cfg, p, *c, first, layer),
        (chunks(xt), chunks(idx, cfg.moe_num_experts), chunks(gate)))
    counts = counts.sum(axis=0).at[-1].add(-pad * k)
    return y.reshape(n * TOKEN_CHUNK, -1)[:T], counts


def _expert_axis_size() -> int:
    from runbooks_tpu.parallel.sharding import _current_mesh

    mesh = _current_mesh()
    return int(mesh.shape.get("expert", 1)) if mesh is not None else 1


def moe_block(cfg, p, x: jax.Array, held: Optional[int] = None,
              shared: bool = True, token_mask: Optional[jax.Array] = None,
              layer=None) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Sparse FFN over x [b, s, h] -> (out [b, s, h], aux loss scalar,
    counts [experts held + 1] int32: assignments by held expert, and last
    the assignments routed to experts held elsewhere).

    ``held``: id of the first expert whose weights ``p`` holds (default
    ``cfg.moe_experts_first``); how many it holds is the leading axis of
    the expert weights. ``token_mask`` [b, s] bool: tokens that are nobody's
    (a bucket's padding, a parked decode row) are routed to no expert,
    cost nothing in the grouped product and are in no count. ``layer``:
    the expert matrices in ``p`` are whole stacks [layers, held, …] and
    this is the layer's index in them (grouped_matmul)."""
    from runbooks_tpu.models.transformer import _mlp_block

    b, s, h = x.shape
    xt = x.reshape(b * s, h)
    first = cfg.moe_experts_first if held is None else held
    with jax.named_scope("moe.router"):
        scores, idx, gate = route(cfg, p, xt)
        aux = _balance_loss(cfg, scores, idx)
        unreal = 0
        if token_mask is not None:
            real = token_mask.reshape(b * s)
            # An expert nobody has: sorted last with `elsewhere`, and
            # taken off that count below.
            idx = jnp.where(real[:, None], idx, cfg.moe_num_experts)
            unreal = (real.size - real.sum(dtype=jnp.int32)) * cfg.moe_top_k

    ep = _expert_axis_size()
    n_held = p["wi_gate"].shape[0 if layer is None else 1]
    if layer is None and ep > 1 and n_held % ep == 0:
        # Expert parallelism: every shard of the "expert" axis holds
        # n_held / ep experts, sees every token, and computes its part.
        from jax.sharding import PartitionSpec as P

        from runbooks_tpu.parallel.sharding import _current_mesh

        experts = {k_: p[k_] for k_ in ("wi_gate", "wi_up", "wo")}

        def shard(w, mine, xt_, idx_, gate_):
            y_, counts_ = _held_part_chunked(cfg, w, xt_, idx_, gate_,
                                             mine[0])
            return jax.lax.psum(y_.astype(jnp.float32), "expert"), \
                counts_[:-1]

        # Each shard is TOLD the id of its first expert (an operand split
        # over the axis): lax.axis_index would have to be lowered over
        # every other mesh axis, one of which the pipeline already holds.
        starts = first + jnp.arange(ep, dtype=jnp.int32) * (n_held // ep)
        y, held_counts = jax.shard_map(
            shard, mesh=_current_mesh(), axis_names={"expert"},
            in_specs=(P("expert"), P("expert"), P(), P(), P()),
            out_specs=(P(), P("expert")), check_vma=False)(
                experts, starts, xt, idx, gate)
        y = y.astype(cfg.activation_dtype)
        counts = jnp.concatenate(
            [held_counts, (idx.size - held_counts.sum())[None]])
    else:
        y, counts = _held_part_chunked(cfg, p, xt, idx, gate, first, layer)
    if token_mask is not None:
        counts = counts.at[-1].add(-unreal)
    y = y.reshape(b, s, h)
    if shared and "shared" in p:
        with jax.named_scope("moe.shared"):
            y = y + _mlp_block(cfg, p["shared"], x)
    return y, aux, counts


def moe_logical_axes(cfg):
    """Logical axes for the stacked [L, ...] MoE params."""
    axes = {
        "router": ("layers", "embed", None),
        "wi_gate": ("layers", "experts", "embed", "mlp"),
        "wi_up": ("layers", "experts", "embed", "mlp"),
        "wo": ("layers", "experts", "mlp", "embed"),
    }
    if cfg.moe_router_bias:
        axes["router_bias"] = ("layers", None)
    if cfg.moe_shared_experts:
        axes["shared"] = {"wo": ("layers", "mlp", "embed"),
                          "wi_gate": ("layers", "embed", "mlp"),
                          "wi_up": ("layers", "embed", "mlp")}
    return axes
