"""Decoder-only transformer: functional JAX, one definition for every family.

Design (TPU-first, not a port — the reference contains no model code and
delegates compute to external containers, SURVEY.md §2a):

- Params are a plain pytree: {"embed": …, "layers": {…stacked [L, …] arrays…},
  "final_norm": …, "head": …}. Layers are *stacked* and the forward pass scans
  over them with ``lax.scan`` — one compiled block instead of L unrolled ones
  (faster compiles, natural remat boundary, later the unit of pipeline
  parallelism).
- The scan runs over PERIODS of the layer pattern (ModelConfig.layer_types;
  docs/hybrid-models.md). A homogeneous model is a period of one layer and
  its params["layers"] is what it always was. A hybrid keeps its
  full-attention layers (one a period) in params["layers"], stacked over
  those layers only, and its linear-attention layers in
  params["linear_layers"]: one stack [periods, …] for each position such
  a layer has in the period, so that every stack is scanned by the
  period's number alone, as a homogeneous model's is. Sliding-attention
  and short-convolution layers lie the same way, in
  params["window_layers"] and params["conv_layers"].
- Every major activation gets a logical sharding constraint
  (runbooks_tpu.parallel.sharding) so pjit can propagate DP/FSDP/SP/TP layouts
  from a rule table.
- fp32 softmax/norms/logits; bf16 everything else by default.
- One code path serves training (no cache) and inference (KVCache dataclass),
  including chunked prefill: attention masking is by *absolute position*, so
  sequence-parallel shards and cache decode use the same op.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from runbooks_tpu.models.config import ModelConfig
from runbooks_tpu.ops.attention import (
    alibi_slopes,
    dot_product_attention,
    make_attention_mask,
)
from runbooks_tpu.ops.norms import layer_norm, rms_norm
from runbooks_tpu.ops.quantization import (
    QuantizedArray,
    dequantize_kv,
    quantize_kv,
    quantized_matmul,
)
from runbooks_tpu.ops.rotary import apply_rope
from runbooks_tpu.parallel.sharding import with_logical_constraint
from runbooks_tpu.utils.hw import on_tpu

Params = Dict[str, Any]

# Flash cached-prefill only pays off once the query block is at least one
# sublane tile; below this the XLA path's mask build is noise anyway.
FLASH_CACHED_PREFILL_MIN_Q = 16


def _matmul(x: jax.Array, w, ad, ring: Optional[str] = None,
            ring_bidir: bool = True) -> jax.Array:
    """x[..., k] @ w[k, out] in the activation dtype, f32 accumulation.
    Weight-only-quantized layers (QuantizedArray) take the fused
    dequant-matmul: integer blocks enter the einsum directly and the
    per-block scales apply post-dot (ops/quantization.py), so the bf16
    weight is never materialized — the point of weight-only quantization
    on the bandwidth-bound decode path.

    ring ("ag" column-parallel | "rs" row-parallel, None = off) selects
    the overlapped collective matmul (ops/collective_matmul.py): the
    tensor-parallel collective decomposes into ppermute ring steps hidden
    behind per-shard partial dots instead of GSPMD's blocking
    all-gather/all-reduce. Falls back to the GSPMD path per-weight when
    the shapes don't divide the ring (ring_supported)."""
    if ring is not None:
        from runbooks_tpu.ops.collective_matmul import (
            matmul_reduce_scatter,
            ring_ag_matmul,
            ring_supported,
        )
        from runbooks_tpu.parallel.sharding import _current_mesh

        mesh = _current_mesh()
        if ring_supported(ring, x.shape, w, mesh):
            fn = ring_ag_matmul if ring == "ag" else matmul_reduce_scatter
            return fn(x, w, mesh=mesh, compute_dtype=ad,
                      bidirectional=ring_bidir).astype(ad)
    if isinstance(w, QuantizedArray):
        return quantized_matmul(x, w, compute_dtype=ad).astype(ad)
    return jnp.einsum("...k,ko->...o", x, w.astype(ad),
                      preferred_element_type=jnp.float32).astype(ad)


def resolve_collective_matmul(cfg: ModelConfig) -> bool:
    """Resolve cfg.collective_matmul ("off" | "ring" | "auto") against the
    active mesh: the ring path runs only when the mesh tensor-parallelizes
    ("auto" and "ring" are equivalent today — "ring" states intent, "auto"
    may later grow heuristics). The pipeline (stage > 1) path keeps GSPMD
    tensor parallelism: its blocks already run inside a stage-manual
    shard_map, and the ring's region is written manual over ALL mesh axes
    (ops/collective_matmul.py), which cannot nest there."""
    from runbooks_tpu.models.config import check_collective_matmul

    mode = check_collective_matmul(cfg.collective_matmul)
    if mode == "off":
        return False
    from runbooks_tpu.parallel.sharding import _current_mesh

    mesh = _current_mesh()
    if mesh is None or int(mesh.shape.get("tensor", 1)) <= 1:
        return False
    if int(mesh.shape.get("stage", 1)) > 1:
        return False
    return True


def _act_embed_rules(ring_on: bool):
    """Sharding rules for the residual stream. With the ring path on, the
    hidden axis of every [b, s, h] activation shards over tensor: the
    row-parallel matmul-reduce-scatter leaves it that way and the next
    column-parallel ring re-gathers it behind its dots — an exposed
    all-gather between them would give back exactly what the overlap
    bought. Norms on the sharded stream cost one [b, s] partial-sum
    all-reduce, inserted by GSPMD."""
    if not ring_on:
        return None
    from runbooks_tpu.parallel.sharding import DEFAULT_RULES

    return {**DEFAULT_RULES, "act_embed": "tensor"}


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------

def _dense_init(rng, shape, dtype, in_axis_size):
    scale = in_axis_size ** -0.5
    return (jax.random.normal(rng, shape) * scale).astype(dtype)


def _norm_params(cfg: ModelConfig, shape_prefix=()):
    h = cfg.hidden_size
    pd = cfg.parameter_dtype
    if cfg.norm_type == "rmsnorm":
        return {"scale": jnp.ones(shape_prefix + (h,), pd)}
    return {"scale": jnp.ones(shape_prefix + (h,), pd),
            "bias": jnp.zeros(shape_prefix + (h,), pd)}


def init_params(cfg: ModelConfig, rng: jax.Array) -> Params:
    """Random-init parameters (stacked layers). For real checkpoints use
    runbooks_tpu.models.convert (HF weight import)."""
    h, v = cfg.hidden_size, cfg.vocab_size
    # params["layers"] holds the attention layers the period scan runs: all
    # of the model's layers, but for a hybrid pattern (its linear layers)
    # and for leading layers (run before the scan, a stack of their own).
    L = cfg.num_periods
    pd = cfg.parameter_dtype
    keys = iter(jax.random.split(rng, 16))

    params: Params = {
        "embed": (jax.random.normal(next(keys), (v, h)) * h ** -0.5).astype(pd),
        "final_norm": _norm_params(cfg),
    }
    if cfg.position_type == "learned":
        params["pos_embed"] = (
            jax.random.normal(next(keys), (cfg.max_seq_len, h)) * 0.02
        ).astype(pd)
    if not cfg.tie_embeddings:
        params["head"] = _dense_init(next(keys), (h, v), pd, h)

    layers: Params = {"attn": _init_attention(cfg, keys, L),
                      "ln1": _norm_params(cfg, (L,))}
    ffn_key, ffn = _init_ffn(cfg, keys, L)
    layers[ffn_key] = ffn

    if not (cfg.parallel_block and cfg.shared_layer_norm):
        layers["ln2"] = _norm_params(cfg, (L,))

    params["layers"] = layers
    if cfg.has_linear_attention:
        params["linear_layers"] = _init_linear_layers(cfg, rng)
    if cfg.has_window:
        params["window_layers"] = _init_window_layers(cfg, rng)
    if cfg.has_short_conv:
        params["conv_layers"] = _init_conv_layers(cfg, rng)
    if cfg.leading_dense_layers:
        params["leading_layers"] = _init_leading_layers(cfg, rng)
    return params


def _init_ffn(cfg: ModelConfig, keys, L: int):
    """("moe" | "mlp", the FFN parameters of L layers, stacked): the
    model's sparse layer if it has experts, else its dense MLP; one key a
    matrix, in a fixed order."""
    h, pd = cfg.hidden_size, cfg.parameter_dtype
    if cfg.moe_num_experts:
        assert cfg.gated_mlp, "MoE experts are gated (mixtral-style)"
        # The router scores over ALL experts; the expert weights are those
        # of the experts held here (models/moe.py).
        E, held, m = cfg.moe_num_experts, cfg.moe_experts_here, cfg.moe_width
        moe = {
            "router": (jax.random.normal(next(keys), (L, h, E))
                       * h ** -0.5).astype(pd),
            "wi_gate": _dense_init(next(keys), (L, held, h, m), pd, h),
            "wi_up": _dense_init(next(keys), (L, held, h, m), pd, h),
            "wo": _dense_init(next(keys), (L, held, m, h), pd, m),
        }
        if cfg.moe_shared_experts:
            moe["shared"] = _init_gated_mlp(
                cfg, keys, L, m * cfg.moe_shared_experts)
        if cfg.moe_router_bias:
            # Not zero: a bias left out of the choice, or let into the
            # gate weights, must change the logits.
            moe["router_bias"] = (
                jax.random.normal(next(keys), (L, E))
                * cfg.moe_router_bias_std).astype(pd)
        return "moe", moe
    if cfg.gated_mlp:
        mlp: Params = _init_gated_mlp(cfg, keys, L, cfg.intermediate_size)
    else:
        mlp = {"wo": _dense_init(next(keys),
                                 (L, cfg.intermediate_size, h), pd,
                                 cfg.intermediate_size),
               "wi": _dense_init(next(keys),
                                 (L, h, cfg.intermediate_size), pd, h)}
    if cfg.mlp_bias:
        for k in ("wi_gate", "wi_up", "wi"):
            if k in mlp:
                mlp["b" + k[1:]] = jnp.zeros((L, cfg.intermediate_size), pd)
        mlp["bo"] = jnp.zeros((L, h), pd)
    return "mlp", mlp


def _init_gated_mlp(cfg: ModelConfig, keys, L: int, width: int) -> Params:
    h, pd = cfg.hidden_size, cfg.parameter_dtype
    return {"wo": _dense_init(next(keys), (L, width, h), pd, width),
            "wi_gate": _dense_init(next(keys), (L, h, width), pd, h),
            "wi_up": _dense_init(next(keys), (L, h, width), pd, h)}


def _init_attention(cfg: ModelConfig, keys, L: int,
                    kind: Optional[str] = None) -> Params:
    """The attention parameters of L layers of `kind` (None: the kind of
    params["layers"]), stacked; one key a matrix, in a fixed order."""
    kind = kind or cfg.attention_kind
    if kind == "latent_attention":
        return _init_latent_attention(cfg, keys, L)
    h, pd = cfg.hidden_size, cfg.parameter_dtype
    shape = cfg.attn_shape(kind)
    q_dim, o_dim = cfg.q_dim_of(kind), cfg.o_dim_of(kind)
    k_dim = shape.kv_heads * cfg.head_dim
    v_dim = shape.kv_heads * cfg.value_head_dim
    attn = {
        "wq": _dense_init(next(keys), (L, h, q_dim), pd, h),
        "wk": _dense_init(next(keys), (L, h, k_dim), pd, h),
        "wv": _dense_init(next(keys), (L, h, v_dim), pd, h),
        "wo": _dense_init(next(keys), (L, o_dim, h), pd, o_dim),
    }
    if shape.sink:
        # Not zero: a sink left out of the softmax must change the logits.
        attn["sink"] = jax.random.normal(
            next(keys), (L, shape.heads)).astype(pd)
    if shape.gate:
        # N(0, 1/fan_in) like a matrix: a gate left out (sigmoid = 1
        # instead of about a half) must change the logits.
        attn["wg"] = _dense_init(next(keys), (L, h, cfg.gate_dim_of(kind)),
                                 pd, h)
    if cfg.attn_bias:
        attn["bq"] = jnp.zeros((L, q_dim), pd)
        attn["bk"] = jnp.zeros((L, k_dim), pd)
        attn["bv"] = jnp.zeros((L, v_dim), pd)
        attn["bo"] = jnp.zeros((L, h), pd)
    if cfg.qk_norm:
        full = cfg.qk_norm_width == "full"
        attn["q_norm"] = jnp.ones(
            (L, q_dim if full else cfg.head_dim), pd)
        attn["k_norm"] = jnp.ones(
            (L, k_dim if full else cfg.head_dim), pd)
    return attn


def _init_latent_attention(cfg: ModelConfig, keys, L: int) -> Params:
    """Latent attention (MLA): the query projected directly to heads of
    qk_nope + qk_rope, the input projected DOWN to [c, k_r] (what is
    cached), and c projected UP to every head's k_nope and v."""
    h, pd, H = cfg.hidden_size, cfg.parameter_dtype, cfg.num_heads
    r = cfg.kv_lora_rank
    attn = {
        "wq": _dense_init(next(keys), (L, h, H * cfg.q_head_dim), pd, h),
        "w_kva": _dense_init(next(keys), (L, h, cfg.latent_width), pd, h),
        "w_kvb": _dense_init(
            next(keys), (L, r, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            pd, r),
        "wo": _dense_init(next(keys), (L, H * cfg.v_head_dim, h), pd,
                          H * cfg.v_head_dim),
        "kv_norm": jnp.ones((L, r), pd),
    }
    if cfg.qk_norm:
        attn["q_norm"] = jnp.ones((L, cfg.q_head_dim), pd)
    return attn


def _init_leading_layers(cfg: ModelConfig, rng: jax.Array) -> Params:
    """The layers before the period scan: a token mixer of
    cfg.leading_layer_kind ("attn": the period's attention kind, or
    "mixer": a short convolution or a KDA mixer) with a DENSE gated FFN of
    intermediate_size, stacked [leading, …]. Their keys come from a split
    of their own (fold_in 2), beside init_params' and the linear layers':
    no other leaf's key moves."""
    assert cfg.gated_mlp and not cfg.mlp_bias and \
        not (cfg.parallel_block and cfg.shared_layer_norm), \
        "leading layers (an attention or conv mixer before the period " \
        "scan) are written for the gated dense MLP, two norms"
    n = cfg.leading_dense_layers
    keys = iter(jax.random.split(jax.random.fold_in(rng, 2), 16))
    if cfg.leading_layer_kind == "conv":
        mixer = {"mixer": _init_short_conv(cfg, keys, n)}
    elif cfg.leading_layer_kind == "linear_attention":
        assert cfg.kda, "a leading linear-attention layer is a KDA layer"
        mixer = {"mixer": _init_kda_mixer(cfg, keys, n)}
    else:
        mixer = {"attn": _init_attention(cfg, keys, n)}
    return {**mixer,
            "mlp": _init_gated_mlp(cfg, keys, n, cfg.intermediate_size),
            "ln1": _norm_params(cfg, (n,)), "ln2": _norm_params(cfg, (n,))}


def _init_short_conv(cfg: ModelConfig, keys, L: int) -> Params:
    """The gated short convolution of L layers, stacked: W_in to the
    thirds [B | C | X], W_out, and the depthwise kernel [taps, channels]
    (its last tap weighs the current token); matrices N(0, 1/fan_in), the
    kernel N(0, 1/taps); one key a leaf, in a fixed order."""
    h, pd = cfg.hidden_size, cfg.parameter_dtype
    return {"w_in": _dense_init(next(keys), (L, h, 3 * h), pd, h),
            "w_out": _dense_init(next(keys), (L, h, h), pd, h),
            "conv": _dense_init(next(keys), (L, cfg.conv_kernel, h), pd,
                                cfg.conv_kernel)}


def _deal_to_positions(in_layer_order: Params, n: int) -> list:
    """Leaves drawn in layer order [L, …] -> one tree for each of the n
    positions such a layer has in the period, leaves [L / n, …]: layer l is
    period l // n, position l % n. Through a reshape, not a strided slice:
    of a[pos::n] the TPU compiler keeps every whole stack alive beside its
    dealt copies (6.2 GiB of temporaries at 27 window layers of 32 experts,
    more than a chip has left beside the weights; PERF.md section 6,
    PR 38)."""
    return [jax.tree.map(
        lambda a: a.reshape((a.shape[0] // n, n) + a.shape[1:])[:, pos],
        in_layer_order) for pos in range(n)]


def _init_window_layers(cfg: ModelConfig, rng: jax.Array) -> list:
    """The sliding-attention layers: a list with one tree for each position
    such a layer has in the period, its leaves stacked [periods, …], as
    params["linear_layers"] is. Every leaf is drawn once for all those
    layers in layer order [L_win, …] (one key a leaf, from a split of their
    own, fold_in 3) and dealt to the positions: window layer l is period
    l // n, position l % n. Each has the model's FFN (sparse if it has
    experts) and two norms."""
    assert not (cfg.parallel_block and cfg.shared_layer_norm), \
        "window layers are written for two norms a block"
    L = cfg.layers_of("sliding_attention")
    keys = iter(jax.random.split(jax.random.fold_in(rng, 3), 16))
    in_layer_order: Params = {
        "attn": _init_attention(cfg, keys, L, "sliding_attention"),
        "ln1": _norm_params(cfg, (L,)), "ln2": _norm_params(cfg, (L,))}
    ffn_key, ffn = _init_ffn(cfg, keys, L)
    in_layer_order[ffn_key] = ffn
    return _deal_to_positions(
        in_layer_order, cfg.layer_pattern.count("sliding_attention"))


def _init_conv_layers(cfg: ModelConfig, rng: jax.Array) -> list:
    """The short-convolution layers of the periods (the leading ones have
    their own stack): a list with one tree for each position such a layer
    has in the period, drawn in layer order and dealt as the window
    layers are, from a split of their own (fold_in 4). Each has the
    model's FFN (sparse if it has experts) and two norms."""
    assert not (cfg.parallel_block and cfg.shared_layer_norm), \
        "conv layers are written for two norms a block"
    n = cfg.layer_pattern.count("conv")
    L = cfg.num_periods * n
    keys = iter(jax.random.split(jax.random.fold_in(rng, 4), 16))
    in_layer_order: Params = {
        "mixer": _init_short_conv(cfg, keys, L),
        "ln1": _norm_params(cfg, (L,)), "ln2": _norm_params(cfg, (L,))}
    ffn_key, ffn = _init_ffn(cfg, keys, L)
    in_layer_order[ffn_key] = ffn
    return _deal_to_positions(in_layer_order, n)


def _init_linear_layers(cfg: ModelConfig, rng: jax.Array) -> list:
    """The linear-attention layers of a hybrid: a list with one tree for
    each position such a layer has in the period, its leaves stacked
    [periods, …]. Every leaf is DRAWN once for all those layers, in layer
    order [L_lin, …] (one key a leaf, as every other leaf of the model),
    and then dealt to the positions: linear layer l is period l // n,
    position l % n. The keys come from a split of their own BESIDE
    init_params' (fold_in 1), so every leaf a homogeneous preset draws
    keeps its key, and its seeded weights their values. Matrices N(0, 1/fan_in); conv N(0, 1/kernel);
    A_log = log U(1, 16) and dt_bias the inverse softplus of
    exp U(log 1e-3, log 1e-1) (the Gated DeltaNet layer's own recipe, so
    the decay is neither 0 nor 1 under random weights); norms 1. The FFN
    is the model's (sparse if it has experts: a KDA model's; the dense
    gated MLP draws wo, wi_gate, wi_up in that order). A KDA model's
    leaves outnumber sixteen keys: its split is of 32."""
    assert cfg.gated_mlp and not cfg.mlp_bias, \
        "linear-attention layers are written for the gated MLP"
    h, pd = cfg.hidden_size, cfg.parameter_dtype
    n = cfg.layer_pattern.count("linear_attention")
    L = cfg.num_periods * n
    kd, vd = cfg.linear_key_dim, cfg.linear_value_dim
    keys = iter(jax.random.split(jax.random.fold_in(rng, 1),
                                 32 if cfg.kda else 16))
    if cfg.kda:
        mixer = _init_kda_mixer(cfg, keys, L)
        ffn_key, ffn = _init_ffn(cfg, keys, L)
        return _deal_to_positions(
            {"mixer": mixer, ffn_key: ffn, "ln1": _norm_params(cfg, (L,)),
             "ln2": _norm_params(cfg, (L,))}, n)
    assert not cfg.moe_num_experts, \
        "gated-delta and lightning layers are written for the dense MLP"
    mixer = {
        "wq": _dense_init(next(keys), (L, h, kd), pd, h),
        "wk": _dense_init(next(keys), (L, h, kd), pd, h),
        "wv": _dense_init(next(keys), (L, h, vd), pd, h),
        "wg": _dense_init(next(keys), (L, h, vd), pd, h),
        "wo": _dense_init(next(keys), (L, vd, h), pd, vd),
    }
    if cfg.lightning:
        # No decay is drawn: it is the layer's and the head's number
        # (ops/lightning_attention.decay_rates). A QK norm a head, the
        # output norm over all the heads.
        mixer.update({
            "q_norm": jnp.ones((L, cfg.linear_key_head_dim), pd),
            "k_norm": jnp.ones((L, cfg.linear_key_head_dim), pd),
            "o_norm": jnp.ones((L, vd), pd)})
    else:
        mixer.update(_init_gated_delta_extras(cfg, keys, L))
    in_layer_order = {
        "mixer": mixer,
        "mlp": _init_gated_mlp(cfg, keys, L, cfg.intermediate_size),
        "ln1": _norm_params(cfg, (L,)),
        "ln2": _norm_params(cfg, (L,)),
    }
    return _deal_to_positions(in_layer_order, n)


def _init_kda_mixer(cfg: ModelConfig, keys, L: int) -> Params:
    """The KDA mixer of L layers, stacked, drawn in this order: wq, wk, wv,
    wo; the decay's low-rank pair (wf_down, wf_up) and the output gate's
    (wg_down, wg_up); wb; the conv; A_log (a head) and dt_bias (a channel
    of the key), by the gated delta rule's recipe; the output norm is 1."""
    h, pd, H = cfg.hidden_size, cfg.parameter_dtype, cfg.linear_num_heads
    kd, vd, r = cfg.linear_key_dim, cfg.linear_value_dim, cfg.linear_gate_rank
    mixer = {
        "wq": _dense_init(next(keys), (L, h, kd), pd, h),
        "wk": _dense_init(next(keys), (L, h, kd), pd, h),
        "wv": _dense_init(next(keys), (L, h, vd), pd, h),
        "wo": _dense_init(next(keys), (L, vd, h), pd, vd),
        "wf_down": _dense_init(next(keys), (L, h, r), pd, h),
        "wf_up": _dense_init(next(keys), (L, r, kd), pd, r),
        "wg_down": _dense_init(next(keys), (L, h, r), pd, h),
        "wg_up": _dense_init(next(keys), (L, r, vd), pd, r),
        "wb": _dense_init(next(keys), (L, h, H), pd, h),
        "conv": _dense_init(next(keys), (L, cfg.linear_conv_kernel,
                                         cfg.linear_conv_dim), pd,
                            cfg.linear_conv_kernel),
        "a_log": _draw_a_log(next(keys), (L, H), pd),
        "o_norm": jnp.ones((L, cfg.linear_value_head_dim), pd),
    }
    mixer["dt_bias"] = _draw_dt_bias(next(keys), (L, kd), pd)
    return mixer


def _draw_a_log(key, shape, pd):
    """A_log = log U(1, 16): the decay's rate, by the Gated DeltaNet
    layer's own recipe."""
    return jnp.log(jax.random.uniform(key, shape, minval=1.0,
                                      maxval=16.0)).astype(pd)


def _draw_dt_bias(key, shape, pd):
    """The inverse softplus of exp U(log 1e-3, log 1e-1)."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(pd)


def _init_gated_delta_extras(cfg: ModelConfig, keys, L: int) -> Params:
    """What the gated-delta mixer has beside its five matrices, drawn in
    this order: the a / b heads, the conv, A_log, the output norm and
    dt_bias."""
    h, pd, H = cfg.hidden_size, cfg.parameter_dtype, cfg.linear_num_heads
    mixer = {
        "wa": _dense_init(next(keys), (L, h, H), pd, h),
        "wb": _dense_init(next(keys), (L, h, H), pd, h),
        "conv": _dense_init(next(keys), (L, cfg.linear_conv_kernel,
                                         cfg.linear_conv_dim), pd,
                            cfg.linear_conv_kernel),
        "a_log": _draw_a_log(next(keys), (L, H), pd),
        "o_norm": jnp.ones((L, cfg.linear_value_head_dim), pd),
    }
    mixer["dt_bias"] = _draw_dt_bias(next(keys), (L, H), pd)
    return mixer


def param_logical_axes(cfg: ModelConfig) -> Params:
    """Pytree matching init_params, with logical axis names per dimension."""
    norm1 = lambda pre: {k: pre + ("norm",) for k in
                         (("scale", "bias") if cfg.norm_type == "layernorm"
                          else ("scale",))}
    axes: Params = {
        "embed": ("vocab", "embed"),
        "final_norm": norm1(()),
    }
    if cfg.position_type == "learned":
        axes["pos_embed"] = ("pos", "embed")
    if not cfg.tie_embeddings:
        axes["head"] = ("embed", "vocab")

    # The stacked-layer leading dim carries the "layers" logical axis: it
    # maps to the "stage" mesh axis for pipeline parallelism and drops to
    # replicated on meshes without one (parallel/sharding.py rules).
    attn = {
        "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "kv_heads"),
        "wv": ("layers", "embed", "kv_heads"),
        "wo": ("layers", "heads", "embed"),
    }
    if cfg.latent_cache:
        # The down-projection and the latent are whole on every device (no
        # head axis to split); the heads' projections split by head.
        attn = {"wq": ("layers", "embed", "heads"),
                "w_kva": ("layers", "embed", None),
                "w_kvb": ("layers", None, "heads"),
                "wo": ("layers", "heads", "embed"),
                "kv_norm": ("layers", None)}
        if cfg.qk_norm:
            attn["q_norm"] = ("layers", "head_dim")
    if cfg.attn_bias:
        attn.update({"bq": ("layers", "heads"),
                     "bk": ("layers", "kv_heads"),
                     "bv": ("layers", "kv_heads"),
                     "bo": ("layers", "norm")})
    if cfg.qk_norm and not cfg.latent_cache:
        full = cfg.qk_norm_width == "full"
        attn.update({"q_norm": ("layers", "heads" if full else "head_dim"),
                     "k_norm": ("layers",
                                "kv_heads" if full else "head_dim")})
    if cfg.attn_gate and not cfg.latent_cache:
        attn["wg"] = ("layers", "embed", "heads")

    if cfg.moe_num_experts:
        from runbooks_tpu.models.moe import moe_logical_axes
        ffn_key, ffn_axes = "moe", moe_logical_axes(cfg)
    else:
        mlp = {"wo": ("layers", "mlp", "embed")}
        if cfg.gated_mlp:
            mlp.update({"wi_gate": ("layers", "embed", "mlp"),
                        "wi_up": ("layers", "embed", "mlp")})
        else:
            mlp["wi"] = ("layers", "embed", "mlp")
        if cfg.mlp_bias:
            for k in list(mlp):
                if k.startswith("wi"):
                    mlp["b" + k[1:]] = ("layers", "mlp")
            mlp["bo"] = ("layers", "norm")
        ffn_key, ffn_axes = "mlp", mlp

    layers = {"attn": attn, ffn_key: ffn_axes, "ln1": norm1(("layers",))}
    if not (cfg.parallel_block and cfg.shared_layer_norm):
        layers["ln2"] = norm1(("layers",))
    axes["layers"] = layers
    if cfg.has_linear_attention:
        col, row = ("layers", "embed", "heads"), ("layers", "heads", "embed")
        extras = ({"q_norm": ("layers", "head_dim"),
                   "k_norm": ("layers", "head_dim"),
                   "o_norm": ("layers", "heads")} if cfg.lightning else
                  {"wa": ("layers", "embed", None),
                   "wb": ("layers", "embed", None),
                   "conv": ("layers", None, None),
                   "a_log": ("layers", None),
                   "dt_bias": ("layers", None),
                   "o_norm": ("layers", "head_dim")})
        linear_mixer = {"wq": col, "wk": col, "wv": col, "wg": col,
                        "wo": row, **extras}
        if cfg.kda:
            # The low-rank maps go down whole and come up by head.
            linear_mixer = {
                "wq": col, "wk": col, "wv": col, "wo": row,
                "wf_down": ("layers", "embed", None),
                "wf_up": ("layers", None, "heads"),
                "wg_down": ("layers", "embed", None),
                "wg_up": ("layers", None, "heads"),
                "wb": ("layers", "embed", None),
                "conv": ("layers", None, None),
                "a_log": ("layers", None), "dt_bias": ("layers", None),
                "o_norm": ("layers", "head_dim")}
        one_position = {
            "mixer": linear_mixer, ffn_key: ffn_axes,
            "ln1": norm1(("layers",)), "ln2": norm1(("layers",))}
        axes["linear_layers"] = [
            one_position] * cfg.layer_pattern.count("linear_attention")
    if cfg.has_window:
        one_position = {"attn": dict(attn), ffn_key: ffn_axes,
                        "ln1": norm1(("layers",)), "ln2": norm1(("layers",))}
        if cfg.sliding_sink:
            one_position["attn"]["sink"] = ("layers", "heads")
        axes["window_layers"] = [
            one_position] * cfg.layer_pattern.count("sliding_attention")
    # The thirds of w_in lie side by side, so its output is split by no
    # mesh axis: the mixer is whole on every device of a tensor mesh.
    short_conv = {"w_in": ("layers", "embed", None),
                  "w_out": ("layers", None, "embed"),
                  "conv": ("layers", None, None)}
    if cfg.has_short_conv:
        one_position = {"mixer": short_conv, ffn_key: ffn_axes,
                        "ln1": norm1(("layers",)), "ln2": norm1(("layers",))}
        axes["conv_layers"] = [
            one_position] * cfg.layer_pattern.count("conv")
    if cfg.leading_dense_layers:
        axes["leading_layers"] = {
            **({"mixer": short_conv} if cfg.leading_layer_kind == "conv"
               else {"mixer": linear_mixer}
               if cfg.leading_layer_kind == "linear_attention"
               else {"attn": attn}),
            "mlp": {"wo": ("layers", "mlp", "embed"),
                    "wi_gate": ("layers", "embed", "mlp"),
                    "wi_up": ("layers", "embed", "mlp")},
            "ln1": norm1(("layers",)), "ln2": norm1(("layers",))}
    return axes


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    """Per-model cache, layers stacked on the leading axis: keys and
    values for the full-attention layers and, for a hybrid, the recurrent
    state of the linear-attention layers beside them.

    k: [full layers, batch, cache_len, num_kv_heads, head_dim]
    v: [full layers, batch, cache_len, num_kv_heads, value_head_dim] (the
    value width is the key width unless the model says otherwise)
    index: [] int32 — number of tokens already written (same for the whole
    batch). Two write modes in ``forward``:

    - scalar-index mode (positions omitted): tokens append at ``index``;
      every row advances together.
    - position-scatter mode (positions given): token j of row b writes to
      slot ``positions[b, j]`` (clipped to cache_len-1). Rows advance
      independently — this is what slot-based continuous batching uses.
      Allocate with ``trash_slot=True`` (cache_len = max_len+1) and point
      padding at slot max_len so pad tokens land in a slot no real query
      ever attends (slot s is visible only to queries with position >= s).
      The cache's last slot IS the trash slot in this mode: a token whose
      position is clipped to it is nobody's query, and the flash prefill
      computes nothing for it (its attention output is 0).

    quantize_kv=True stores k/v as int8 with one f32 scale per
    (layer, row, slot, kv-head) in k_scale/v_scale
    ([num_layers, batch, cache_len, num_kv_heads]) — halving the HBM the
    bandwidth-bound decode step streams, which doubles max_slots x
    max_seq_len at fixed memory. forward() detects the int8 dtype and
    quantizes on write / dequantizes on read transparently.

    state, conv (present when the layer pattern has linear-attention
    layers, as k_scale is for int8): what such a layer keeps of a row's
    past, whatever its length.
      state [linear layers, batch, heads, d_k, d_v] float32 (never
            quantized): the delta rule's S;
      conv  [linear layers, batch, kernel-1, conv channels]: the inputs of
            the short convolution at the row's last kernel-1 tokens.
    Neither has a slot axis, so neither has a trash slot: a token that
    must not count is named by forward(token_mask=...) and leaves both
    exactly as they were. A row starts from zeros.

    conv WITHOUT state (the layer pattern has short-convolution layers,
    kind "conv"): the tail is all such a layer keeps,
      conv  [conv layers, batch, conv_kernel-1, hidden]: B * X at the
            row's last conv_kernel-1 tokens,
    under the same rules. Every leaf holds exactly the layers of its kind,
    in layer order: where the leading layers are conv layers they are the
    conv leaf's first, and k / v hold the periods' attention layers alone.

    latent (present instead of k / v content when the attention layers are
    latent_attention; k and v then hold no layer):
      latent [latent layers, batch, cache_len, kv_lora_rank +
             qk_rope_head_dim], activation dtype: a token's compressed
             [c, k_r], with NO head axis. Slots, the trash slot and both
             write modes are those of k / v.

    ring_k, ring_v (present when the layer pattern has sliding_attention
    layers): those layers' keys and values, a RING a row and not cache_len
    slots:
      ring_k [window layers, batch, ring, sliding kv heads, head_dim]
      ring_v [window layers, batch, ring, sliding kv heads, value_head_dim]
    with ring = sliding_window + RING_MARGIN (models/config.py). The token
    at position p lies at slot p mod ring. Nothing is stored about a slot:
    a query at position t takes slot j to hold position t - a, a = (t - j)
    mod ring its age, and sees it iff a < sliding_window and a <= t. That
    is true of every slot it sees as long as (1) every token of the row
    from max(0, t - window + 1) to t has been written by the row's
    present occupant, and (2) nothing younger than t has overwritten one
    of them: a dispatch that writes n tokens of a row before its first
    query reads overwrites, for that query, the slots of ages ring - 1
    down to ring - (n - 1), so n <= RING_MARGIN + 1 (a decode step writes
    one; forward() sends a longer call another way, below). A slot the
    rule hides may hold anything: a previous occupant's tokens (age > t),
    a rejected or parked write (none is made: a token that is nobody's —
    a bucket's padding, a parked decode row — is DROPPED from the write,
    the ring has no trash slot). So a freed row's ring needs no clearing.
    A call of more than RING_MARGIN + 1 tokens a row is taken as a row's
    FIRST tokens (a prompt prefilled whole): a window layer then attends
    the call's own keys and values, not the ring, and writes only the
    row's last `ring` real tokens. Nothing that puts earlier tokens under
    such a call (a spliced prefix, a second prefill chunk) is sound, and
    the serving engine refuses it (docs/window-full-models.md).

    ckeys (present when the full-attention layers read sparsely,
    ModelConfig.sparse_topk; ops/block_sparse_attention.py): the row's
    compressed keys, what a query scores to choose its blocks,
      ckeys [full layers, batch, (cache_len - kernel) // stride + 1,
             num_kv_heads, head_dim] float32: entry j the mean of the keys
             at positions stride j .. stride j + kernel - 1 AS STORED.
    Slots-like at a stride-th of the length, with no trash slot: entry j
    is read only by a query at a position >= stride j + kernel - 1, which
    the row's present occupant wrote before it (a call of one token writes
    the entry that token completes, a longer call every entry of the row
    from the row's keys), so a freed row's entries need no clearing and a
    parked token writes none.

    forward() carries every leaf whole through its layer scan (the carry,
    not xs/ys): a layer writes this call's tokens at [layer, row, slot],
    reads [layer, :, :view], and a linear-attention layer reads and writes
    state/conv at its number in layer order. A jitted caller that donates
    the cache (the engine's programs do) gets the same buffers back,
    updated in place; nothing pool-sized is copied inside a loop.
    """

    k: jax.Array
    v: jax.Array
    index: jax.Array
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None
    state: Optional[jax.Array] = None
    conv: Optional[jax.Array] = None
    latent: Optional[jax.Array] = None
    ring_k: Optional[jax.Array] = None
    ring_v: Optional[jax.Array] = None
    ckeys: Optional[jax.Array] = None

    @classmethod
    def create(cls, cfg: ModelConfig, batch: int, max_len: int,
               trash_slot: bool = False,
               quantize_kv: bool = False) -> "KVCache":
        cache_len = max_len + 1 if trash_slot else max_len
        return cls(index=jnp.zeros((), jnp.int32), **{
            leaf.name: jnp.zeros(leaf.shape(cfg, batch, cache_len),
                                 leaf.dtype)
            for leaf in cache_leaves(cfg, quantize_kv)})

    @property
    def quantized(self) -> bool:
        return self.k.dtype == jnp.int8


class LeafTraits(NamedTuple):
    """What a KVCache field is, whatever the configuration."""
    # Its axis behind [layers, batch]: "slots" (cache_len of them: the
    # trash slot, cache_view and token-sized writes apply), "ring" (a row's
    # own ring of tokens, written a token at a time), "compressed" (an
    # entry a stride of slots: written one at a time in decode, a layer at
    # a time in prefill) or None (a row's own state or tail, rewritten a
    # layer at a time).
    tokens: Optional[str]
    axes: tuple        # logical sharding axes (parallel/sharding.py)
    group: str         # its bytes are reported as <group>_bytes


_BY_KV_HEAD = (None, "batch", None, "act_heads", None)
_WHOLE = (None, "batch", None, None)
# The recurrent state shards by head; the conv tail (q | k | v channels side
# by side) does not split on a head boundary and stays whole, as a latent
# does, which has no head axis.
LEAF_TRAITS = {        # KVCache's leaves, in its field order
    "k": LeafTraits("slots", _BY_KV_HEAD, "kv_pool"),
    "v": LeafTraits("slots", _BY_KV_HEAD, "kv_pool"),
    "k_scale": LeafTraits("slots", _BY_KV_HEAD[:4], "kv_pool"),
    "v_scale": LeafTraits("slots", _BY_KV_HEAD[:4], "kv_pool"),
    "state": LeafTraits(None, (None, "batch", "act_heads", None, None),
                        "recurrent_state"),
    "conv": LeafTraits(None, _WHOLE, "recurrent_state"),
    "latent": LeafTraits("slots", _WHOLE, "latent_cache"),
    "ring_k": LeafTraits("ring", _BY_KV_HEAD, "kv_ring"),
    "ring_v": LeafTraits("ring", _BY_KV_HEAD, "kv_ring"),
    "ckeys": LeafTraits("compressed", _BY_KV_HEAD, "kv_compressed"),
}
# The groups whose leaves have no int8 form, and why.
_NO_INT8 = {
    "kv_ring": "quantize_kv has no form for a window layer's ring cache "
               "yet (docs/window-full-models.md)",
    "latent_cache": "quantize_kv stores one scale a KV head; a latent "
                    "cache has no head axis and no int8 form yet "
                    "(docs/sparse-latent-models.md)",
    "kv_compressed": "quantize_kv has no form for a sparse read yet: the "
                     "compressed keys are means of the keys as stored, and "
                     "no test holds the choice against int8 keys "
                     "(docs/hybrid-models.md)",
}


class CacheLeaf(NamedTuple):
    """One leaf a configuration's KVCache has (cache_leaves)."""
    name: str          # the KVCache field
    kind: str          # the layer kind whose layers it holds, in layer
    #                    order: cfg.layers_of(kind) on its leading axis
    tail: tuple        # its shape behind [layers, batch (, cache_len)]
    dtype: Any
    tokens: Optional[str]    # LeafTraits, by name
    axes: tuple
    group: str

    def shape(self, cfg: ModelConfig, batch: int, cache_len: int) -> tuple:
        slots = ()
        if self.tokens == "slots":
            slots = (cache_len,)
        elif self.tokens == "compressed":
            slots = (cfg.compressed_len(cache_len),)
        return (cfg.layers_of(self.kind), batch) + slots + self.tail


class LayerCache(NamedTuple):
    """What one layer's token mixer is handed of a cache: the WHOLE leaves
    of its kind as forward's layer scan carries them (it writes and reads
    its part by index, so the loop updates them in place), and where."""
    leaves: dict       # {KVCache field: the leaf} of the layer's kind
    layer: Any         # the layer's number in them (int, or traced int32)
    index: Any         # cache.index; None in position-scatter mode
    view: Optional[int]      # forward's cache_view
    parked: Any        # [b, s] bool, a window layer's: the tokens that are
    #                    nobody's (None: there is none)
    bound: Optional[int] = None     # forward's row_len_bound: no row holds
    #                    a real token at or behind this slot


def _leaf_index(cfg: ModelConfig, kind: str, period, j: int):
    """Where a layer lies in the leaves of its kind, which hold the kind's
    layers in layer order, the leading ones first: the kind's j-th layer of
    the scanned period number `period`."""
    return (cfg.leading_layers_of(kind)
            + period * cfg.layer_pattern.count(kind) + j)


def cache_leaves(cfg: ModelConfig, quantize_kv: bool = False) -> tuple:
    """The leaves a KVCache of this configuration has, in KVCache's field
    order: THE declaration of what per-slot state each layer kind keeps
    (KVCache's docstring has each kind's invariants). Whoever enumerates the
    leaves reads it: KVCache.create, forward's layer scan, the serving
    engine's splice, placement and gauges, analysis/loop_copies. A latent
    configuration has k and v too, with no layer in them."""
    ad, f32 = cfg.activation_dtype, jnp.float32
    kv_dtype, heads = (jnp.int8 if quantize_kv else ad), cfg.num_kv_heads
    found = [("k", "full_attention", (heads, cfg.head_dim), kv_dtype),
             ("v", "full_attention", (heads, cfg.value_head_dim), kv_dtype)]
    if quantize_kv:
        found += [("k_scale", "full_attention", (heads,), f32),
                  ("v_scale", "full_attention", (heads,), f32)]
    if cfg.has_linear_attention:
        found += [
            ("state", "linear_attention",
             (cfg.linear_num_heads, cfg.linear_key_head_dim,
              cfg.linear_value_head_dim), f32)]
        if not cfg.lightning:
            found.append(
                ("conv", "linear_attention",
                 (cfg.linear_conv_kernel - 1, cfg.linear_conv_dim), ad))
    if cfg.has_short_conv:
        found.append(("conv", "conv",
                      (cfg.conv_kernel - 1, cfg.hidden_size), ad))
    if cfg.latent_cache:
        found.append(("latent", "latent_attention", (cfg.latent_width,), ad))
    if cfg.has_window:
        ring = (cfg.ring_len, cfg.attn_shape("sliding_attention").kv_heads)
        found += [
            ("ring_k", "sliding_attention", ring + (cfg.head_dim,), ad),
            ("ring_v", "sliding_attention", ring + (cfg.value_head_dim,),
             ad)]
    if cfg.sparse_read is not None:
        found.append(("ckeys", "full_attention", (heads, cfg.head_dim), f32))
    leaves = tuple(CacheLeaf(*f, *LEAF_TRAITS[f[0]]) for f in found)
    no_int8 = [_NO_INT8[leaf.group] for leaf in leaves
               if leaf.group in _NO_INT8]
    if quantize_kv and no_int8:
        raise NotImplementedError(no_int8[0])
    return leaves


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _norm(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    if cfg.norm_type == "rmsnorm":
        return rms_norm(x, p["scale"], cfg.norm_eps)
    return layer_norm(x, p["scale"], p["bias"], cfg.norm_eps)


def _activation(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.activation == "silu":
        return jax.nn.silu(x)
    if cfg.activation == "gelu":
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.relu(x)


def _auto_embed_one_hot(cfg: ModelConfig, has_cache: bool) -> bool:
    """One-hot-vs-gather auto rule, shared by forward() and the 1F1B
    embed (they must not drift). One-hot when the mesh tensor-shards the
    vocab (the gather forces a full-remat reshard), or — training only —
    when the sequence axis is sharded (the gather's scatter-add TRANSPOSE
    hits the same involuntary-remat path; a cached/serving forward has no
    backward, and the one-hot there would materialize a [b, s, vocab]
    tensor for nothing)."""
    if cfg.embed_one_hot is not None:
        return cfg.embed_one_hot
    from runbooks_tpu.parallel.sharding import _current_mesh

    m0 = _current_mesh()
    if m0 is None:
        return False
    if int(m0.shape.get("tensor", 1)) > 1:
        return True
    return not has_cache and int(m0.shape.get("sequence", 1)) > 1


def resolve_attention_impl(cfg: ModelConfig) -> str:
    """Resolve cfg.attention_impl ("auto" included) to a concrete impl for
    the no-cache (training) path: ring when the active mesh is
    sequence-parallel, flash on TPU, else xla. ALiBi bias and logit softcap
    force xla (not yet in the kernels). Single source of truth — used both
    for dispatch and for skipping the O(s^2) mask build."""
    impl = cfg.attention_impl
    if impl not in ("auto", "xla", "flash", "ring"):
        raise ValueError(
            f"unknown attention_impl {impl!r}; expected auto|xla|flash|ring")
    if impl == "auto":
        from runbooks_tpu.parallel.sharding import _current_mesh

        mesh = _current_mesh()
        if mesh is not None and mesh.shape.get("sequence", 1) > 1:
            impl = "ring"
        elif on_tpu():
            impl = "flash"
        else:
            impl = "xla"
    if cfg.position_type == "alibi" or cfg.logit_softcap is not None:
        impl = "xla"
    return impl


def use_flash_cached_prefill(cfg: ModelConfig, q_len: int) -> bool:
    """Route a prefill-with-cache through the flash kernel instead of the
    XLA O(s*kv) path? True when the query block is at least one flash tile
    and the kernel covers the config (ALiBi bias and logit softcap are
    XLA-only, as in resolve_attention_impl). Decode (q_len=1) always stays
    XLA. forward() skips the mask build entirely on this path — the kernel
    masks from absolute positions, which for a cache (slot i == position i)
    is exactly the XLA mask."""
    if q_len < FLASH_CACHED_PREFILL_MIN_Q:
        return False
    if cfg.position_type == "alibi" or cfg.logit_softcap is not None:
        return False
    impl = cfg.attention_impl
    if impl == "flash":
        return True
    if impl != "auto":
        return False
    return on_tpu()


class FlashCall(NamedTuple):
    """The static shapes of one flash call: what ops/flash_attention.
    block_shape and head_block are asked about while it is traced."""
    sq: int
    sk: int
    d: int
    dv: int
    n_rep: int                 # query heads a KV head
    window: int = 0
    sink: bool = False


def flash_call_shapes(cfg: ModelConfig, q_len: int, kv_len: int,
                      tensor_parallel: int = 1) -> dict:
    """{layer kind: FlashCall}: the flash call a forward of ``q_len``
    queries on ``kv_len`` keys makes in each kind of attention layer, on
    the shard one device of a tensor mesh launches. Window layers attend
    the call's own keys; latent attention is expanded to as many key heads
    as query heads (a group of one)."""
    from runbooks_tpu.ops.flash_attention import heads_per_shard

    def call(q_heads, kv_heads, keys, d, dv, window=0, sink=False):
        h, kv_h = heads_per_shard(q_heads, kv_heads, tensor_parallel)
        return FlashCall(q_len, keys, d, dv, h // kv_h, window, sink)

    if cfg.latent_cache:
        calls = {"latent_attention": call(cfg.num_heads, cfg.num_heads,
                                          kv_len, cfg.q_head_dim,
                                          cfg.v_head_dim)}
    else:
        shape = cfg.attn_shape("full_attention")
        calls = {"full_attention": call(
            shape.heads, shape.kv_heads, kv_len, cfg.head_dim,
            cfg.value_head_dim)}
    if cfg.has_window:
        shape = cfg.attn_shape("sliding_attention")
        calls["sliding_attention"] = call(
            shape.heads, shape.kv_heads, q_len, cfg.head_dim,
            cfg.value_head_dim, shape.window, shape.sink)
    return calls


def _flash_block_shape(cfg: ModelConfig, kernel: str, c: FlashCall) -> tuple:
    """block_shape of a call: cfg.flash_block_q / _k where a test set them,
    else what the call's shapes say."""
    from runbooks_tpu.ops.flash_attention import block_shape

    return block_shape(kernel, c.sq, c.sk, c.n_rep, c.window,
                       cfg.flash_block_q, cfg.flash_block_k)


def flash_blocks(cfg: ModelConfig, q_len: int, kv_len: int,
                 tensor_parallel: int = 1, backward: bool = False) -> dict:
    """{layer kind: {"fwd": [block_q, block_k][, "bwd": ...]}}: the block
    shape each flash kernel of such a forward (and its backward) compiles
    with. Static per compiled program, so the engine and the trainer
    publish it at start-up, and the engine counts visited blocks at it."""
    from runbooks_tpu.ops.flash_attention import KERNELS

    return {kind: {kernel: list(_flash_block_shape(cfg, kernel, call))
                   for kernel in KERNELS if backward or kernel == "fwd"}
            for kind, call in flash_call_shapes(
                cfg, q_len, kv_len, tensor_parallel).items()}


def flash_heads_per_step(cfg: ModelConfig, q_len: int, kv_len: int,
                         tensor_parallel: int = 1) -> dict:
    """{layer kind: G}: how many query heads one grid step of the flash
    forward holds (ops/flash_attention.head_block at the call's block
    shape). Static per compiled program, as flash_blocks."""
    from runbooks_tpu.ops.flash_attention import head_block

    return {kind: head_block(c.n_rep, *_flash_block_shape(cfg, "fwd", c),
                             c.d, c.dv, c.sink, c.window)
            for kind, c in flash_call_shapes(
                cfg, q_len, kv_len, tensor_parallel).items()}


def _dispatch_attention(cfg: ModelConfig, q, k, v, positions, segment_ids,
                        mask, bias, scale=None):
    """Pick the attention implementation for the no-cache (training) path.
    k/v stay at kv_heads width on every path (GQA-native kernels). scale:
    None = head_dim ** -0.5."""
    impl = resolve_attention_impl(cfg)  # forces xla for alibi/softcap

    if impl == "flash":
        from runbooks_tpu.ops.flash_attention import flash_attention

        return flash_attention(
            q, k, v, positions, positions, segment_ids, segment_ids,
            True, scale, cfg.flash_block_q, cfg.flash_block_k)

    if impl == "ring":
        if scale is not None:
            raise NotImplementedError(
                "ring attention takes no softmax scale of the caller's: "
                "latent attention has no sequence-parallel path")
        from runbooks_tpu.parallel.ring_attention import (
            ring_attention,
            ring_flash_attention_sharded,
            use_flash_inner_default,
        )
        from runbooks_tpu.parallel.sharding import (
            _current_mesh, spec_for_array)

        mesh = _current_mesh()
        if mesh is None or mesh.shape.get("sequence", 1) == 1:
            # No ring to run; single-shard blockwise math is plain attention.
            return dot_product_attention(
                q, k, v, mask=mask, logit_softcap=cfg.logit_softcap)
        qspec = spec_for_array(q.shape, ("batch", "seq", "act_heads", None),
                               mesh)
        kspec = spec_for_array(k.shape, ("batch", "seq", "act_heads", None),
                               mesh)
        rspec = spec_for_array(positions.shape, ("batch", "seq"), mesh)
        seg = (segment_ids if segment_ids is not None
               else jnp.ones_like(positions))

        use_flash = cfg.ring_flash_inner
        if use_flash is None:
            use_flash = use_flash_inner_default()
        if use_flash:
            lse_spec = spec_for_array(
                (q.shape[0], q.shape[2], q.shape[1]),
                ("batch", "act_heads", "seq"), mesh)
            return ring_flash_attention_sharded(
                q, k, v, positions, seg, mesh, qspec, kspec, rspec,
                lse_spec, block_q=cfg.flash_block_q,
                block_k=cfg.flash_block_k)

        def local(ql, kl, vl, pl_, sl):
            return ring_attention(ql, kl, vl, pl_, pl_, sl, sl,
                                  axis_name="sequence")

        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(qspec, kspec, kspec, rspec, rspec),
            out_specs=qspec,
            # The scan carry starts unvarying (zeros) and becomes varying
            # after the first ppermute; skip the VMA check rather than
            # pcast-annotating for every possible mesh shape.
            check_vma=False,
        )(q, k, v, positions, seg)

    return dot_product_attention(q, k, v, mask=mask, bias=bias, scale=scale,
                                 logit_softcap=cfg.logit_softcap)


def _adapter_delta(adapter, name: str, x_in: jax.Array, y: jax.Array,
                   ad) -> jax.Array:
    """Add the grouped per-row LoRA delta for one target to the base
    projection's output (docs/multi-tenant-lora.md). ``adapter`` is
    None (off) or (pool_layer, lane_idx): pool_layer a nested
    {"attn"/"mlp": {target: {"a", "b"}}} slice for THIS layer, lane_idx
    the per-row int32 lane indices (already trash-mapped). Targets
    absent from the pool pass through untouched, so a pool configured
    for attention-only injection costs the MLP nothing."""
    if adapter is None:
        return y
    sub, idx = adapter
    ab = sub.get(name)
    if ab is None:
        return y
    from runbooks_tpu.ops.lora import grouped_lora_delta

    return y + grouped_lora_delta(x_in, ab, idx, ad)


def _attention_block(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,                      # [b, s, h] activation dtype
    positions: jax.Array,              # [b, s]
    segment_ids: Optional[jax.Array],
    mask: Optional[jax.Array],
    bias: Optional[jax.Array],
    layer_cache: Optional[LayerCache],
    adapter=None,
    kind: str = "full_attention",
    long_rows: bool = False,
):
    """Per-head attention of one layer of `kind` (full_attention or
    sliding_attention: ModelConfig.attn_shape gives what they differ in).
    Keys are head_dim wide and values value_head_dim; the kind's shape says
    how many query heads there are, how many of a head's dimensions rotate
    and how, and whether a gate multiplies the core's output. A
    full_attention layer of a configuration with a sparse read
    (ModelConfig.sparse_read) keeps the row's compressed keys and, where
    ``long_rows`` says a row of this call may be sparse_dense_len long,
    reads through ops/block_sparse_attention."""
    b, s, _ = x.shape
    ad = cfg.activation_dtype
    shape = cfg.attn_shape(kind)
    sparse = cfg.sparse_read if kind == "full_attention" else None
    # Scope names: a window layer's parts are swa.*, a sparse read's
    # bsa.*, inside `attn` like every token mixer's
    # (docs/observability.md).
    sc = "swa" if shape.window else "attn"
    core = "attn.core" if sparse is None else "bsa.core"
    ring_on = resolve_collective_matmul(cfg)
    ring_col = "ag" if ring_on else None
    ring_row = "rs" if ring_on else None
    bidir = cfg.collective_matmul_bidirectional

    def proj(w, bname, aname):
        y = _matmul(x, w, ad, ring=ring_col, ring_bidir=bidir)
        y = _adapter_delta(adapter, aname, x, y, ad)
        if bname in p:
            y = y + p[bname].astype(ad)
        return y

    def rope(t):
        return apply_rope(t, positions, shape.rope_theta, shape.rope_yarn,
                          shape.rotary_dim, shape.rope_factor)

    # The named scopes are metadata only (op names in the HLO and in a
    # profiler capture; docs/observability.md): the compiled program and
    # its compile-cache key do not change.
    with jax.named_scope(sc + ".qkv"):
        full_norm = cfg.qk_norm and cfg.qk_norm_width == "full"

        def heads(y, scale, n, d=cfg.head_dim):
            # The "full" QK norm runs over the whole projection, before
            # the heads are split; the per-head one below, after.
            if full_norm and scale is not None:
                y = rms_norm(y, scale, cfg.norm_eps)
            return y.reshape(b, s, n, d)

        q = heads(proj(p["wq"], "bq", "wq"), p.get("q_norm"), shape.heads)
        k = heads(proj(p["wk"], "bk", "wk"), p.get("k_norm"),
                  shape.kv_heads)
        v = heads(proj(p["wv"], "bv", "wv"), None, shape.kv_heads,
                  cfg.value_head_dim)
        if cfg.attn_value_scale != 1.0:
            v = v * jnp.asarray(cfg.attn_value_scale, ad)
        q = with_logical_constraint(q, ("batch", "seq", "act_heads", None))
        k = with_logical_constraint(k, ("batch", "seq", "act_heads", None))
        v = with_logical_constraint(v, ("batch", "seq", "act_heads", None))
        if cfg.qk_norm and cfg.qk_norm_width == "head":
            q = rms_norm(q, p["q_norm"], cfg.norm_eps)
            k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.position_type == "rope":
        with jax.named_scope("swa.qkv" if shape.window else "attn.rope"):
            q, k = rope(q), rope(k)

    new_layer_cache = None
    if shape.window:
        out, new_layer_cache = _window_attention(
            cfg, q, k, v, p.get("sink"), positions, segment_ids,
            mask is None, layer_cache)
    elif layer_cache is not None:
        with jax.named_scope("attn.kv_write"):
            k, v, new_layer_cache = _write_layer_cache(k, v, positions,
                                                       layer_cache, ad)
        ckeys = q_pos = None
        if sparse is not None:
            q_pos = (positions if layer_cache.parked is None
                     else jnp.where(layer_cache.parked, -1, positions))
            with jax.named_scope("bsa.compress"):
                ckeys, leaf = _write_compressed_keys(cfg, k, q_pos,
                                                     layer_cache)
                new_layer_cache["ckeys"] = leaf
        if sparse is not None and long_rows:
            out = _sparse_read(cfg, q, k, v, ckeys, q_pos)
        else:
            with jax.named_scope(core):
                # No index means position-scatter mode, whose last slot is
                # the trash slot.
                trash_pos = (layer_cache.leaves["k"].shape[2] - 1
                             if layer_cache.index is None else None)
                out = _cached_attention(cfg, q, k, v, positions, mask, bias,
                                        trash_pos)
    elif sparse is not None and long_rows:
        from runbooks_tpu.ops.block_sparse_attention import compress_keys

        # Without a cache the call's own keys are the row's: key i lies at
        # position i (forward's default positions).
        with jax.named_scope("bsa.compress"):
            ckeys = compress_keys(k, sparse)
        out = _sparse_read(cfg, q, k, v, ckeys, positions)
    else:
        with jax.named_scope(core):
            out = _dispatch_attention(cfg, q, k, v, positions, segment_ids,
                                      mask, bias)
    if shape.gate:
        with jax.named_scope("bsa.gate" if sparse else sc + ".gate"):
            # From the layer's normed input: one number a head and token,
            # or one an element of the core's output.
            g = jax.nn.sigmoid(
                _matmul(x, p["wg"], ad).astype(jnp.float32)).astype(ad)
            out = out * (g.reshape(out.shape)
                         if cfg.attn_gate_width == "element"
                         else g[..., None])
    with jax.named_scope(sc + ".out"):
        out = out.reshape(b, s, shape.heads * cfg.value_head_dim)
        attn_ctx = out
        out = _matmul(out, p["wo"], ad, ring=ring_row, ring_bidir=bidir)
        out = _adapter_delta(adapter, "wo", attn_ctx, out, ad)
        if "bo" in p:
            out = out + p["bo"].astype(ad)
    return out, new_layer_cache


def _write_compressed_keys(cfg: ModelConfig, k, q_pos, layer_cache):
    """Keep the compressed-key leaf (KVCache.ckeys) with this call's
    tokens, whose keys are written: k [b, view, kv heads, d] is the layer's
    view of the pool WITH them, q_pos [b, s] their positions (below 0:
    nobody's). A call of one token a row writes the entry that token
    completes, if it completes one; a longer call every entry the view
    holds, from the rows' keys as they lie (an entry that is not whole yet
    holds what it holds: nobody reads it before the token that completes it
    rewrites it). Returns (this layer's entries [b, n, kv heads, d] float32,
    the updated leaf)."""
    from runbooks_tpu.ops.block_sparse_attention import (
        compress_keys,
        compressed_key_at,
    )

    leaf, layer = layer_cache.leaves["ckeys"], layer_cache.layer
    b, s = q_pos.shape
    if s == 1:
        c, j = compressed_key_at(k, q_pos[:, 0], cfg.sparse_read)
        leaf = leaf.at[layer, jnp.arange(b, dtype=jnp.int32), j].set(
            c, mode="drop")
    else:
        whole = compress_keys(k, cfg.sparse_read)[:, :leaf.shape[2]]
        leaf = jax.lax.dynamic_update_slice(leaf, whole[None],
                                            (layer, 0, 0, 0, 0))
    return jax.lax.dynamic_index_in_dim(leaf, layer, 0, False), leaf


def _sparse_read(cfg: ModelConfig, q, k, v, ckeys, q_pos):
    """The sparse core of a full-attention layer
    (ops/block_sparse_attention.py). q [b, s, heads, d]; k, v [b, keys, kv
    heads, *] the row's keys by position; ckeys [b, n, kv heads, d] float32;
    q_pos [b, s] (below 0: nobody's token). A row is read sparsely when it
    is sparse_dense_len long with this call's tokens, else whole. One query
    a row goes through the masked read of the view, more through the flash
    forward under the tokens' choices."""
    from runbooks_tpu.ops.block_sparse_attention import (
        n_blocks,
        select_blocks,
        sparse_decode,
        sparse_prefill,
    )

    sp, scale = cfg.sparse_read, cfg.head_dim ** -0.5
    sparse_row = jnp.max(q_pos, axis=-1) + 1 >= sp.dense_len
    if q.shape[1] == 1:
        with jax.named_scope("bsa.select"):
            chosen = select_blocks(q, ckeys, q_pos, sp,
                                   n_blocks(k.shape[1], sp), scale,
                                   cfg.sparse_exclude_window)
        with jax.named_scope("bsa.core"):
            return sparse_decode(q, k, v, chosen, q_pos, sparse_row, sp,
                                 scale)
    # Names its own scopes: the choice, then the one kernel call.
    return sparse_prefill(q, k, v, ckeys, q_pos, sparse_row, sp, scale,
                          cfg.sparse_exclude_window, cfg.flash_block_q,
                          cfg.flash_block_k)


def _window_attention(cfg: ModelConfig, q, k, v, sink, positions,
                      segment_ids, flash: bool, ring_cache):
    """The core of a sliding-attention layer: a query at position t sees
    the keys j of its row (and document) with 0 <= t - j < sliding_window,
    and the sink. Three forms of it, chosen by what the call is:

    no cache            the call's own keys under a causal window mask
                        (flash forward, or XLA);
    cache, a call of    write the tokens into the ring, then read the ring
    <= RING_MARGIN + 1  whole at KV-head width under the age mask (decode:
    tokens a row        one token a row);
    cache, a longer     a row's first tokens (KVCache): the call's own keys
    call                as without a cache, and the row's last `ring` real
                        tokens written to the ring, the rest to nowhere.

    ``ring_cache``: None or the LayerCache of the ring leaves.
    ``flash``: the caller built no mask because the flash forward needs
    none. Returns (out [b, s, heads, value_head_dim], None or the updated
    leaves)."""
    from runbooks_tpu.models.config import RING_MARGIN

    b, s = positions.shape
    W, ring = cfg.sliding_window, cfg.ring_len
    parked = None if ring_cache is None else ring_cache.parked
    leaves = None
    through_ring = ring_cache is not None and s <= RING_MARGIN + 1
    if ring_cache is not None:
        with jax.named_scope("swa.ring_write"):
            ring_k, ring_v = (ring_cache.leaves[n]
                              for n in ("ring_k", "ring_v"))
            layer = ring_cache.layer
            keep = jnp.ones((b, s), bool) if parked is None else ~parked
            if not through_ring:
                # Of a long call only the row's last `ring` real tokens:
                # earlier ones would share their slots.
                last = jnp.max(jnp.where(keep, positions, -1), axis=-1,
                               keepdims=True)
                keep &= positions > last - ring
            # A token that is not kept is written nowhere: slot `ring` is
            # out of bounds, and the scatter drops it.
            slot = jnp.where(keep, positions % ring, ring)
            b_idx = jnp.arange(b, dtype=jnp.int32)[:, None]
            ring_k = ring_k.at[layer, b_idx, slot].set(k, mode="drop")
            ring_v = ring_v.at[layer, b_idx, slot].set(v, mode="drop")
            leaves = {"ring_k": ring_k, "ring_v": ring_v}
    with jax.named_scope("swa.core"):
        if through_ring:
            def row(leaf):
                return jax.lax.dynamic_slice(
                    leaf, (layer, 0, 0, 0, 0), (1,) + leaf.shape[1:])[0]

            age = (positions[:, :, None]
                   - jnp.arange(ring, dtype=jnp.int32)) % ring  # [b, s, ring]
            seen = (age < W) & (age <= positions[:, :, None])
            if parked is not None:
                seen &= ~parked[:, :, None]
            out = dot_product_attention(q, row(ring_k), row(ring_v),
                                        mask=seen[:, None], sink=sink)
        else:
            q_pos = kv_pos = positions
            if parked is not None:
                from runbooks_tpu.ops.flash_attention import PAD_POS

                # Nobody's tokens see no key and are no key.
                q_pos = jnp.where(parked, -1, positions)
                kv_pos = jnp.where(parked, PAD_POS, positions)
            if flash:
                from runbooks_tpu.ops.flash_attention import flash_attention

                # The block shape follows the window (ops/flash_attention.
                # block_shape): a query block of 256 on key blocks of 512
                # under windows up to 256, today's 512 x 1024 under longer
                # ones — on the chip 1.97 against 2.13 ms a call at window
                # 128 with a group of 8 a step, and nothing to gain at
                # window 512 (PERF.md section 6, PR 39; with ONE head a
                # step a key block of 128 was slower, PR 32).
                out = flash_attention(
                    q, k, v, q_pos, kv_pos, segment_ids, segment_ids, True,
                    None, cfg.flash_block_q, cfg.flash_block_k, window=W,
                    sink=sink)
            else:
                seen = make_attention_mask(q_pos, kv_pos, segment_ids,
                                           segment_ids, causal=True)
                seen &= (q_pos[:, None, :, None]
                         - kv_pos[:, None, None, :]) < W
                out = dot_product_attention(q, k, v, mask=seen, sink=sink)
    return out, leaves


def _write_layer_cache(k, v, positions, layer_cache, ad):
    """Write this call's K/V into the pool at this layer and return what
    attention reads: (k, v, the pool's leaves after the write).

    ``layer_cache`` is the LayerCache of the pool leaves [full layers,
    batch, cache_len, …]. Only the tokens of this call are written and only
    ``[layer, :, :view]`` is read, both by index into the carried buffer,
    so the loop updates the pool in place: no layer is sliced out, none is
    written back."""
    b = k.shape[0]
    leaves, layer = layer_cache.leaves, layer_cache.layer
    index, view = layer_cache.index, layer_cache.view
    ck, cv = leaves["k"], leaves["v"]
    ck_s, cv_s = leaves.get("k_scale"), leaves.get("v_scale")
    quantized = ck.dtype == jnp.int8
    if quantized:
        # int8 KV: one f32 scale per (row, slot, kv-head) rides next to
        # the int8 values; both scatter with the same indices.
        k_w, k_s = quantize_kv(k)
        v_w, v_s = quantize_kv(v)
    else:
        k_w, v_w, k_s, v_s = k, v, None, None
    if index is None:
        # Position-scatter mode: row b token j -> slot positions[b, j].
        cache_len = ck.shape[2]
        slot = jnp.clip(positions, 0, cache_len - 1)
        b_idx = jnp.arange(b, dtype=jnp.int32)[:, None]
        ck = ck.at[layer, b_idx, slot].set(k_w)
        cv = cv.at[layer, b_idx, slot].set(v_w)
        if quantized:
            ck_s = ck_s.at[layer, b_idx, slot].set(k_s)
            cv_s = cv_s.at[layer, b_idx, slot].set(v_s)
    else:
        at = (layer, 0, index, 0, 0)
        ck = jax.lax.dynamic_update_slice(ck, k_w[None], at)
        cv = jax.lax.dynamic_update_slice(cv, v_w[None], at)
        if quantized:
            ck_s = jax.lax.dynamic_update_slice(ck_s, k_s[None], at[:-1])
            cv_s = jax.lax.dynamic_update_slice(cv_s, v_s[None], at[:-1])
    # Writes go to the FULL cache; attention READS only [0, view).
    # Exact for any view > max query position: slot s is attended only
    # by queries at positions >= s, so slots beyond the view hold
    # nothing a masked-in query could see. Serving uses this to stop
    # decode from streaming the whole max-length cache through HBM
    # when occupancy is low (the decode step is bandwidth-bound). The
    # read is of the UPDATED leaves: a read of the old ones would keep
    # them alive across the write and cost a copy of the pool.

    def read(leaf):
        if leaf is None:
            return None
        size = (1, b, leaf.shape[2] if view is None else view)
        return jax.lax.dynamic_slice(
            leaf, (layer,) + (0,) * (leaf.ndim - 1),
            size + leaf.shape[3:])[0]

    k, v = read(ck), read(cv)
    if quantized:
        # Dequantize at the read: the scale multiply fuses into the
        # attention contraction, so HBM streams int8 + one scale per
        # row — half the bytes of the bf16 cache the decode step is
        # bound on.
        k = dequantize_kv(k, read(ck_s), ad)
        v = dequantize_kv(v, read(cv_s), ad)
        return k, v, {"k": ck, "v": cv, "k_scale": ck_s, "v_scale": cv_s}
    return k, v, {"k": ck, "v": cv}


def _cached_attention(cfg: ModelConfig, q, k, v, positions, mask, bias,
                      trash_pos=None, scale=None):
    """Attention of q against one layer's cache view. trash_pos: in
    position-scatter mode the cache's last slot, where callers park a
    bucket's padding (KVCache); None in append-at-index mode. scale: None
    = head_dim ** -0.5."""
    b = q.shape[0]
    if mask is None:
        # Flash cached-prefill (forward() skipped the O(s*kv) mask
        # build): cache slot i holds absolute position i by
        # construction, so the kernel's causal-by-absolute-position
        # masking reproduces the XLA path's mask exactly — unwritten
        # or future slots are never attended, and the kernel works out
        # from these positions which kv blocks to visit at all (query
        # rows start at cache.index or at a prefix length, not at 0).
        from runbooks_tpu.ops.flash_attention import flash_attention

        kv_pos = jnp.broadcast_to(
            jnp.arange(k.shape[1], dtype=jnp.int32)[None, :],
            (b, k.shape[1]))
        if trash_pos is not None:
            # A token parked at the trash slot is nobody's query: nothing
            # reads its output. At its parked position it would see every
            # key, and its block would visit every kv block for it; at -1
            # it sees none (the row comes out exactly 0), so a bucket's
            # padded tail costs nothing.
            positions = jnp.where(positions >= trash_pos, -1, positions)
        out = flash_attention(
            q, k, v, positions, kv_pos, None, None, True, scale,
            cfg.flash_block_q, cfg.flash_block_k)
    else:
        # Decode (s=1) keeps the XLA path: a one-row query block has no
        # O(s^2) term and the step is bandwidth-bound anyway.
        out = dot_product_attention(
            q, k, v, mask=mask, bias=bias, scale=scale,
            logit_softcap=cfg.logit_softcap)
    return out


def _write_layer_latent(lat, positions, layer_cache):
    """_write_layer_cache for the latent leaf: write this call's [c, k_r]
    (lat [b, s, width]) at [layer, row, slot], token-sized and in place in
    the carried leaf [latent layers, batch, cache_len, width]; return
    (this layer's view [b, view, width] of the UPDATED leaf, the leaf).
    ``layer_cache`` is the LayerCache of the latent leaf."""
    b = lat.shape[0]
    leaf, layer = layer_cache.leaves["latent"], layer_cache.layer
    index, view = layer_cache.index, layer_cache.view
    if index is None:
        slot = jnp.clip(positions, 0, leaf.shape[2] - 1)
        if lat.shape[1] == 1:
            b_idx = jnp.arange(b, dtype=jnp.int32)[:, None]
            leaf = leaf.at[layer, b_idx, slot].set(lat)
        else:
            # A piece of a row at a time, in a loop: the TPU compiler takes
            # 50-60 s over ONE scatter of 1024 or more tokens into this
            # leaf (a slot is one sublane of a tile: no head axis lies
            # between it and the width) and half a second over a loop of
            # scatters of 512 (AOT, PR 48: at 16k slots a row a cold
            # warm-up of 21 prefill programs did not end inside the
            # benchmark's deadline; the keys' and values' leaves, whose
            # slot is a major axis, are not so).
            s, width = lat.shape[1:]
            piece = math.gcd(s, 512)
            pieces = s // piece

            def write(i, leaf):
                r, at = i // pieces, i % pieces * piece
                return leaf.at[layer, r, jax.lax.dynamic_slice(
                    slot, (r, at), (1, piece))[0]].set(jax.lax.dynamic_slice(
                        lat, (r, at, 0), (1, piece, width))[0])

            leaf = jax.lax.fori_loop(0, b * pieces, write, leaf)
    else:
        leaf = jax.lax.dynamic_update_slice(leaf, lat[None],
                                            (layer, 0, index, 0))
    seen = jax.lax.dynamic_slice(
        leaf, (layer, 0, 0, 0),
        (1, b, leaf.shape[2] if view is None else view, leaf.shape[3]))[0]
    return seen, leaf


def _latent_attention_block(cfg: ModelConfig, p: Params, x: jax.Array,
                            positions, segment_ids, mask, layer_cache):
    """Latent attention (MLA; docs/sparse-latent-models.md). What a token
    caches is lat = [RMSNorm(c), rotated k_r]: kv_lora_rank +
    qk_rope_head_dim numbers with no head axis (position_type "none":
    q_rope and k_r are NOT rotated, and pass as they are projected). Two
    forms of the same attention, chosen by what the call is:

    expanded  (no cache, or a cached prefill on the flash path, mask None)
              every head's k_nope and v are made from c by w_kvb, the one
              k_r is shared by the heads, and attention runs at
              (q_head_dim, q_head_dim, v_head_dim);
    absorbed  (a cache read through a mask: decode, the chunk loop) the
              query is taken into the latent space, q_lat = q_nope W_uk,
              scores are q_lat . c + q_rope . k_r against the leaf as it
              lies, the weighted sum of c comes back through W_uv: the
              cache is never expanded to per-head keys or values.

    ``layer_cache``: None or the LayerCache of the latent leaf, see
    _write_layer_latent. Returns (out [b, s, h], None or the updated
    leaf, by name as _attention_block gives its own)."""
    b, s, _ = x.shape
    ad = cfg.activation_dtype
    H, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    scale = cfg.q_head_dim ** -0.5 * cfg.yarn_attn_factor ** 2

    def rope(t):
        if cfg.position_type != "rope":
            return t    # no rotary: the recurrent layers carry order
        return apply_rope(t, positions, cfg.rope_theta, cfg.rope_yarn,
                          factor=cfg.yarn_rotary_factor)

    with jax.named_scope("mla.q"):
        q = _matmul(x, p["wq"], ad).reshape(b, s, H, dn + dr)
        q = with_logical_constraint(q, ("batch", "seq", "act_heads", None))
        if "q_norm" in p:
            q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        q_nope, q_rope = q[..., :dn], rope(q[..., dn:])
    with jax.named_scope("mla.kv_down"):
        down = _matmul(x, p["w_kva"], ad)
        lat = jnp.concatenate(
            [rms_norm(down[..., :r], p["kv_norm"], cfg.norm_eps),
             rope(down[..., None, r:])[:, :, 0]], axis=-1)
    w_kvb = p["w_kvb"].astype(ad).reshape(r, H, dn + dv)

    leaf = None
    if layer_cache is not None:
        with jax.named_scope("attn.kv_write"):
            lat, leaf = _write_layer_latent(lat, positions, layer_cache)
    if layer_cache is None or mask is None:
        if layer_cache is not None and layer_cache.bound is not None:
            # A prefill's bucket: no real query sees a slot behind it (the
            # parked ones are nobody's), so only that much of the row is
            # expanded to keys and values a head: at 8 rows of 16k slots
            # the whole rows' would not fit beside the weights.
            lat = lat[:, :layer_cache.bound]
        with jax.named_scope("mla.kv_up"):
            up = jnp.einsum("bkr,rhd->bkhd", lat[..., :r], w_kvb,
                            preferred_element_type=jnp.float32).astype(ad)
            k = jnp.concatenate(
                [up[..., :dn], jnp.broadcast_to(
                    lat[:, :, None, r:], (*up.shape[:3], dr))], axis=-1)
            v = up[..., dn:]
            k = with_logical_constraint(
                k, ("batch", "seq", "act_heads", None))
            v = with_logical_constraint(
                v, ("batch", "seq", "act_heads", None))
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
        with jax.named_scope("mla.core"):
            if layer_cache is None:
                out = _dispatch_attention(cfg, q, k, v, positions,
                                          segment_ids, mask, None, scale)
            else:
                trash_pos = (leaf.shape[2] - 1
                             if layer_cache.index is None else None)
                out = _cached_attention(cfg, q, k, v, positions, None, None,
                                        trash_pos, scale)
    else:
        with jax.named_scope("mla.absorb"):
            q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w_kvb[..., :dn],
                               preferred_element_type=jnp.float32).astype(ad)
        with jax.named_scope("mla.core"):
            # One "KV head" (the latent) under all H query heads: each row
            # is one matrix product with H * s rows (ops/attention.py).
            o_lat = dot_product_attention(
                jnp.concatenate([q_lat, q_rope], axis=-1),
                lat[:, :, None, :], lat[:, :, None, :r], mask=mask,
                scale=scale)
        with jax.named_scope("mla.absorb"):
            out = jnp.einsum("bshr,rhd->bshd", o_lat, w_kvb[..., dn:],
                             preferred_element_type=jnp.float32).astype(ad)
    with jax.named_scope("mla.out"):
        out = _matmul(out.reshape(b, s, H * dv), p["wo"], ad)
    return out, None if leaf is None else {"latent": leaf}


def _linear_attention_block(cfg: ModelConfig, p: Params, x: jax.Array,
                            token_mask: Optional[jax.Array], layer_state):
    """The gated-delta token mixer (ops/gated_delta.py) of one layer.
    x [b, s, h]; token_mask [b, s] bool or None (all valid; a row's valid
    tokens are a prefix of it); layer_state None (no cache: start from
    zeros, keep nothing) or the LayerCache of the recurrent leaves (state
    [linear layers, b, H, d_k, d_v] f32 and conv [linear layers, b,
    kernel-1, channels]). The layer reads its own state and conv tail there
    and writes the new ones back at the same index. Returns (out [b, s, h],
    None or the updated leaves)."""
    from runbooks_tpu.ops.gated_delta import (
        causal_conv,
        gated_delta_chunked,
        gated_delta_step,
        l2_normalize,
    )

    b, s, _ = x.shape
    ad = cfg.activation_dtype
    f32 = jnp.float32
    H, dk, dv = (cfg.linear_num_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim)
    kd = cfg.linear_key_dim
    state = tail = None
    if layer_state is not None:
        all_state, all_tail = (layer_state.leaves[n]
                               for n in ("state", "conv"))
        layer = layer_state.layer
        state = jax.lax.dynamic_index_in_dim(all_state, layer, 0, False)
        tail = jax.lax.dynamic_index_in_dim(all_tail, layer, 0, False)

    def small(w):   # [h, H] heads of the decay and the write strength
        return jnp.einsum("...k,ko->...o", x, w.astype(ad),
                          preferred_element_type=f32)

    with jax.named_scope("linattn.proj"):
        qkv = jnp.concatenate([_matmul(x, p["wq"], ad),
                               _matmul(x, p["wk"], ad),
                               _matmul(x, p["wv"], ad)], axis=-1)
        gate = _matmul(x, p["wg"], ad)
        g = -jnp.exp(p["a_log"].astype(f32)) * jax.nn.softplus(
            small(p["wa"]) + p["dt_bias"].astype(f32))
        beta = jax.nn.sigmoid(small(p["wb"]))
        if cfg.linear_allow_neg_eigval:
            beta = 2.0 * beta
    with jax.named_scope("linattn.conv"):
        n_valid = (None if token_mask is None
                   else jnp.sum(token_mask, axis=-1, dtype=jnp.int32))
        qkv, tail = causal_conv(qkv, p["conv"], tail, n_valid)
    with jax.named_scope("linattn.core"):
        q = qkv[..., :kd].reshape(b, s, H, dk)
        k = qkv[..., kd:2 * kd].reshape(b, s, H, dk)
        v = qkv[..., 2 * kd:].reshape(b, s, H, dv)
        q = with_logical_constraint(q, ("batch", "seq", "act_heads", None))
        k = with_logical_constraint(k, ("batch", "seq", "act_heads", None))
        v = with_logical_constraint(v, ("batch", "seq", "act_heads", None))
        q = (l2_normalize(q) * dk ** -0.5).astype(ad)
        k = l2_normalize(k).astype(ad)
        if layer_state is not None and s == 1:
            # Decode: one recurrent step a row.
            o, state = gated_delta_step(
                q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state,
                None if token_mask is None else token_mask[:, 0])
            o = o[:, None].astype(ad)
        else:
            o, state = gated_delta_chunked(q, k, v, g, beta, state,
                                           token_mask)
    with jax.named_scope("linattn.out"):
        # One norm weight of d_v, shared by the heads; then the output gate.
        o = rms_norm(o, p["o_norm"], cfg.norm_eps)
        o = o * jax.nn.silu(gate.reshape(b, s, H, dv))
        out = _matmul(o.reshape(b, s, H * dv), p["wo"], ad)
    if layer_state is None:
        return out, None
    return out, {
        "state": jax.lax.dynamic_update_index_in_dim(
            all_state, state, layer, 0),
        "conv": jax.lax.dynamic_update_index_in_dim(all_tail, tail, layer, 0)}


def _kda_block(cfg: ModelConfig, p: Params, x: jax.Array,
               token_mask: Optional[jax.Array], layer_state):
    """The KDA token mixer (ops/kda.py) of one layer: q, k, v through the
    short convolution and SiLU, q and k to unit length a head (q further
    times d_k^-1/2); the decay a channel g = -exp(A_log_h) softplus((x
    W_f_down) W_f_up + dt_bias), beta = sigmoid(x W_b); the delta rule; an
    RMSNorm a head (one [d_v] weight) under the low-rank sigmoid gate
    sigmoid((x W_g_down) W_g_up); the output projection. x, token_mask,
    layer_state and what is returned as _linear_attention_block's: a row
    keeps the state and the conv tail."""
    from runbooks_tpu.ops.gated_delta import causal_conv
    from runbooks_tpu.ops.kda import kda_chunked, kda_decay, kda_step, unit_qk

    b, s, _ = x.shape
    ad = cfg.activation_dtype
    f32 = jnp.float32
    H, dk, dv = (cfg.linear_num_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim)
    kd = cfg.linear_key_dim
    state = tail = None
    if layer_state is not None:
        all_state, all_tail = (layer_state.leaves[n]
                               for n in ("state", "conv"))
        layer = layer_state.layer
        state = jax.lax.dynamic_index_in_dim(all_state, layer, 0, False)
        tail = jax.lax.dynamic_index_in_dim(all_tail, layer, 0, False)
    decode = layer_state is not None and s == 1

    def to_f32(y, w):   # the last map before a float32 nonlinearity
        return jnp.einsum("...k,ko->...o", y, w.astype(ad),
                          preferred_element_type=f32)

    with jax.named_scope("kda.proj"):
        qkv = jnp.concatenate([_matmul(x, p["wq"], ad),
                               _matmul(x, p["wk"], ad),
                               _matmul(x, p["wv"], ad)], axis=-1)
    with jax.named_scope("kda.gates"):
        # The decay's operands: the prefill kernel makes g a chunk in
        # VMEM (no [b, s, H, d_k] float32 array exists); the step is fed it.
        decay = (_matmul(x, p["wf_down"], ad), p["wf_up"], p["dt_bias"],
                 p["a_log"])
        if decode:
            g = kda_decay(*decay)
        beta = jax.nn.sigmoid(to_f32(x, p["wb"]))
    with jax.named_scope("kda.conv"):
        n_valid = (None if token_mask is None
                   else jnp.sum(token_mask, axis=-1, dtype=jnp.int32))
        qkv, tail = causal_conv(qkv, p["conv"], tail, n_valid)
    with jax.named_scope("kda.core"):
        if decode:
            # One recurrent step a row, its operands made in plain XLA.
            q = qkv[..., :kd].reshape(b, s, H, dk)
            k = qkv[..., kd:2 * kd].reshape(b, s, H, dk)
            v = qkv[..., 2 * kd:].reshape(b, s, H, dv)
            q = with_logical_constraint(q, ("batch", "seq", "act_heads", None))
            k = with_logical_constraint(k, ("batch", "seq", "act_heads", None))
            v = with_logical_constraint(v, ("batch", "seq", "act_heads", None))
            q, k = unit_qk(q, k)
            o, state = kda_step(
                q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state,
                None if token_mask is None else token_mask[:, 0])
            o = o[:, None].astype(ad)
        else:
            # q and k go in as the convolution left them: the kernel
            # brings them to unit length a head.
            o, state = kda_chunked(qkv, *decay, beta, state, token_mask)
    with jax.named_scope("kda.gates"):
        # The output gate, made where it is used: a [tokens, H d_v] array
        # that need not live through the core.
        gate = jax.nn.sigmoid(
            to_f32(_matmul(x, p["wg_down"], ad), p["wg_up"]))
    with jax.named_scope("kda.out"):
        # One norm weight of d_v, shared by the heads; then the gate.
        o = rms_norm(o, p["o_norm"], cfg.norm_eps).astype(jnp.float32)
        o = (o * gate.reshape(b, s, H, dv)).astype(ad)
        out = _matmul(o.reshape(b, s, H * dv), p["wo"], ad)
    if layer_state is None:
        return out, None
    return out, {
        "state": jax.lax.dynamic_update_index_in_dim(
            all_state, state, layer, 0),
        "conv": jax.lax.dynamic_update_index_in_dim(all_tail, tail, layer, 0)}


def _lightning_block(cfg: ModelConfig, p: Params, x: jax.Array, positions,
                     token_mask: Optional[jax.Array], layer_state,
                     layer_index):
    """The lightning token mixer (ops/lightning_attention.py) of one layer:
    q, k, v of linear_num_heads heads, an RMSNorm a head on q and k, a
    rotary over the whole head where the linear layers have one, the
    decay-only recurrence at the decay of the layer's index as run
    (``layer_index``) scaled by d_k^-1/2, an RMSNorm over all the heads'
    outputs, an elementwise sigmoid gate from x, the output projection.
    token_mask and layer_state as _linear_attention_block's, but a row
    keeps the state alone. Returns (out [b, s, h], None or the updated
    leaf, by name)."""
    from runbooks_tpu.ops.lightning_attention import (
        decay_rates,
        lightning_chunked,
        lightning_step,
    )

    b, s, _ = x.shape
    ad = cfg.activation_dtype
    H, dk, dv = (cfg.linear_num_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim)
    state = None
    if layer_state is not None:
        all_state, layer = layer_state.leaves["state"], layer_state.layer
        state = jax.lax.dynamic_index_in_dim(all_state, layer, 0, False)
    with jax.named_scope("lightning.proj"):
        q = _matmul(x, p["wq"], ad).reshape(b, s, H, dk)
        k = _matmul(x, p["wk"], ad).reshape(b, s, H, dk)
        v = _matmul(x, p["wv"], ad).reshape(b, s, H, dv)
        gate = _matmul(x, p["wg"], ad)
        q = with_logical_constraint(q, ("batch", "seq", "act_heads", None))
        k = with_logical_constraint(k, ("batch", "seq", "act_heads", None))
        v = with_logical_constraint(v, ("batch", "seq", "act_heads", None))
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
        if cfg.linear_rope_theta:
            q = apply_rope(q, positions, cfg.linear_rope_theta)
            k = apply_rope(k, positions, cfg.linear_rope_theta)
    with jax.named_scope("lightning.core"):
        rate = decay_rates(H, layer_index, cfg.lightning_decay_layers)
        scale = dk ** -0.5
        if layer_state is not None and s == 1:
            # Decode: one recurrent step a row.
            o, state = lightning_step(
                q[:, 0], k[:, 0], v[:, 0], rate, state, scale,
                None if token_mask is None else token_mask[:, 0])
            o = o[:, None].astype(ad)
        else:
            o, state = lightning_chunked(q, k, v, rate, scale, state,
                                         token_mask)
    with jax.named_scope("lightning.out"):
        o = rms_norm(o.reshape(b, s, H * dv), p["o_norm"], cfg.norm_eps)
        o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(ad)
        out = _matmul(o, p["wo"], ad)
    if layer_state is None:
        return out, None
    return out, {"state": jax.lax.dynamic_update_index_in_dim(
        all_state, state, layer, 0)}


def _short_conv_block(cfg: ModelConfig, p: Params, x: jax.Array,
                      token_mask: Optional[jax.Array], layer_tail):
    """The gated short convolution of one layer. x [b, s, h] (the layer's
    normed input); [B | C | X] = x W_in, z = B * X, c = the depthwise
    causal convolution of z over conv_kernel tokens (no bias, no
    activation; before a row's first token z is 0), out = (C * c) W_out.
    token_mask as _linear_attention_block's; layer_tail None (no cache:
    start from zeros, keep nothing) or the LayerCache of the conv leaf
    [conv layers, b, conv_kernel-1, h]. The layer reads its tail there (z
    at the row's last conv_kernel-1 valid tokens) and writes the new one
    back at the same index. Returns (out [b, s, h], None or the updated
    leaf, by name)."""
    from runbooks_tpu.ops.gated_delta import causal_conv

    ad = cfg.activation_dtype
    h = cfg.hidden_size
    tail = None
    if layer_tail is not None:
        all_tail, layer = layer_tail.leaves["conv"], layer_tail.layer
        tail = jax.lax.dynamic_index_in_dim(all_tail, layer, 0, False)
    with jax.named_scope("shortconv.in"):
        bcx = _matmul(x, p["w_in"], ad)
        z = bcx[..., :h] * bcx[..., 2 * h:]
    with jax.named_scope("shortconv.core"):
        n_valid = (None if token_mask is None
                   else jnp.sum(token_mask, axis=-1, dtype=jnp.int32))
        c, tail = causal_conv(z, p["conv"], tail, n_valid, activation=None)
    with jax.named_scope("shortconv.out"):
        out = _matmul(bcx[..., h:2 * h] * c, p["w_out"], ad)
    if layer_tail is None:
        return out, None
    return out, {"conv": jax.lax.dynamic_update_index_in_dim(
        all_tail, tail, layer, 0)}


def _mlp_block(cfg: ModelConfig, p: Params, x: jax.Array,
               adapter=None) -> jax.Array:
    ad = cfg.activation_dtype
    ring_on = resolve_collective_matmul(cfg)
    bidir = cfg.collective_matmul_bidirectional

    def mm(y, w, ring=None):
        return _matmul(y, w, ad, ring=ring, ring_bidir=bidir)

    ring_col = "ag" if ring_on else None
    ring_row = "rs" if ring_on else None
    if cfg.gated_mlp:
        gate = _adapter_delta(adapter, "wi_gate", x,
                              mm(x, p["wi_gate"], ring_col), ad)
        up = _adapter_delta(adapter, "wi_up", x,
                            mm(x, p["wi_up"], ring_col), ad)
        if "bi_gate" in p:
            gate = gate + p["bi_gate"].astype(ad)
            up = up + p["bi_up"].astype(ad)
        hidden = _activation(cfg, gate) * up
    else:
        hidden = _adapter_delta(adapter, "wi", x,
                                mm(x, p["wi"], ring_col), ad)
        if "bi" in p:
            hidden = hidden + p["bi"].astype(ad)
        hidden = _activation(cfg, hidden)
    hidden = with_logical_constraint(hidden, ("batch", "seq", "act_mlp"))
    out = _adapter_delta(adapter, "wo", hidden,
                         mm(hidden, p["wo"], ring_row), ad)
    if "bo" in p:
        out = out + p["bo"].astype(ad)
    return out


def _ffn_block(cfg: ModelConfig, layer: Params, x: jax.Array,
               adapter=None, token_mask=None):
    """Dense MLP or MoE, by what the layer holds (a sparse model's leading
    layers are dense), returning (out, aux-loss scalar, None or the sparse
    layer's assignment counts, models/moe.py)."""
    if "moe" in layer:
        from runbooks_tpu.models.moe import moe_block

        return moe_block(cfg, layer["moe"], x, token_mask=token_mask,
                         layer=layer.get("moe_layer"))
    return (_mlp_block(cfg, layer["mlp"], x, adapter=adapter),
            jnp.zeros((), jnp.float32), None)


def _adapter_group(adapter, group: str):
    """(group_pool, idx) for one block sub-module, or None when the pool
    has no targets there."""
    if adapter is None:
        return None
    pool_layer, idx = adapter
    sub = pool_layer.get(group)
    return None if sub is None else (sub, idx)


# Where params keeps the layers of a kind that the period scan runs: the
# one stack of the attention kind, or a list with a stack for each position
# the kind has in the period. The attention kind comes first.
_STACK_OF = {"full_attention": "layers", "latent_attention": "layers",
             "linear_attention": "linear_layers",
             "sliding_attention": "window_layers", "conv": "conv_layers"}


def _block(cfg: ModelConfig, layer: Params, x, positions, segment_ids, mask,
           bias, layer_cache, adapter=None, token_mask=None,
           layer_index=None, kind: str = "full_attention",
           long_rows: bool = False):
    """One transformer block. x: [b, s, h]. Returns (x, cache, aux,
    counts): counts is None, or a sparse FFN's assignment counts.
    ``adapter``: None or (per-layer adapter-pool slice, lane indices) —
    the grouped LoRA injection (docs/multi-tenant-lora.md). ``kind`` names
    the token mixer (ModelConfig.layer_types). ``layer_cache`` is None or
    the LayerCache of the layer's kind; the updated leaves come back, by
    name. ``token_mask`` says which tokens may change a
    linear-attention layer's state or a conv layer's tail, and which a
    sparse FFN routes at all. ``layer_index`` is the layer's index as run
    (a lightning layer's decay depends on it), ``long_rows`` whether a row
    of this call may be long enough for a sparse read."""
    scaled = cfg.residual_scale != 1.0

    def joins(out):
        """A sub-layer's output as it joins the residual stream: times
        residual_scale in float32 (in the activation type the scale itself
        would round, every layer the same way), rounded once."""
        if not scaled:
            return out
        return (out.astype(jnp.float32) * cfg.residual_scale).astype(
            out.dtype)

    def mixer(h_in):
        # The linear and conv mixers run inside the `attn` scope too, under
        # inner linattn.* / shortconv.* scopes: `attn` means "the token
        # mixer" to every reader of a capture (docs/observability.md).
        with jax.named_scope("attn"):
            if kind == "linear_attention" and cfg.lightning:
                return _lightning_block(
                    cfg, layer["mixer"], h_in, positions, token_mask,
                    layer_cache, layer_index)
            if kind == "linear_attention" and cfg.kda:
                return _kda_block(
                    cfg, layer["mixer"], h_in, token_mask, layer_cache)
            if kind == "linear_attention":
                return _linear_attention_block(
                    cfg, layer["mixer"], h_in, token_mask, layer_cache)
            if kind == "conv":
                return _short_conv_block(
                    cfg, layer["mixer"], h_in, token_mask, layer_cache)
            if kind == "latent_attention":
                return _latent_attention_block(
                    cfg, layer["attn"], h_in, positions, segment_ids, mask,
                    layer_cache)
            return _attention_block(
                cfg, layer["attn"], h_in, positions, segment_ids, mask, bias,
                layer_cache, adapter=_adapter_group(adapter, "attn"),
                kind=kind, long_rows=long_rows)

    # Scopes: everything a layer does is under `block`; inside it `norm`,
    # `attn` (with its attn.* parts) and `ffn`; what is left directly
    # under `block` is the residual adds.
    with jax.named_scope("block"):
        act_rules = _act_embed_rules(resolve_collective_matmul(cfg))
        x = with_logical_constraint(x, ("batch", "seq", "act_embed"),
                                    rules=act_rules)
        mlp_adapter = _adapter_group(adapter, "mlp")
        if cfg.norm_position == "post":
            # Reordered norm: x + norm(f(x)), for both sub-layers.
            attn_out, new_cache = mixer(x)
            attn_out = checkpoint_name(attn_out, "attn_out")
            with jax.named_scope("norm"):
                attn_out = _norm(cfg, layer["ln1"], attn_out)
            x = x + joins(attn_out)
            with jax.named_scope("ffn"):
                ffn_out, aux, counts = _ffn_block(
                    cfg, layer, x, adapter=mlp_adapter,
                    token_mask=token_mask)
            with jax.named_scope("norm"):
                ffn_out = _norm(cfg, layer["ln2"], ffn_out)
            x = x + joins(ffn_out)
            x = with_logical_constraint(x, ("batch", "seq", "act_embed"),
                                        rules=act_rules)
            return x, new_cache, aux, counts
        with jax.named_scope("norm"):
            h1 = _norm(cfg, layer["ln1"], x)
        attn_out, new_cache = mixer(h1)
        # Named checkpoint for selective remat: remat_policy=
        # "save_attn_out" saves this [b, s, h] tensor (plus the flash
        # kernel's hoisted "attn_context"/"attn_lse" residuals — see
        # ops/flash_attention.py) so the backward never re-runs the
        # O(s^2) flash fwd kernel, while activations stay
        # O(layers * b * s * h) instead of the dots_saveable blow-up.
        attn_out = checkpoint_name(attn_out, "attn_out")
        if cfg.parallel_block:
            if cfg.shared_layer_norm:
                h2 = h1
            else:
                with jax.named_scope("norm"):
                    h2 = _norm(cfg, layer["ln2"], x)
            with jax.named_scope("ffn"):
                mlp_out, aux, counts = _ffn_block(
                    cfg, layer, h2, adapter=mlp_adapter,
                    token_mask=token_mask)
            x = x + joins(attn_out) + joins(mlp_out)
        else:
            x = x + joins(attn_out)
            with jax.named_scope("norm"):
                h2 = _norm(cfg, layer["ln2"], x)
            with jax.named_scope("ffn"):
                ffn_out, aux, counts = _ffn_block(
                    cfg, layer, h2, adapter=mlp_adapter,
                    token_mask=token_mask)
            x = x + joins(ffn_out)
        x = with_logical_constraint(x, ("batch", "seq", "act_embed"),
                                    rules=act_rules)
    return x, new_cache, aux, counts


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _read_in_place(w: jax.Array, stack_layout) -> jax.Array:
    """One layer's slice `w` of a ``[layers, ...]`` stack that lies in
    `stack_layout`, held to that layout without its leading axis. As it is
    (no constraint) where the layout is unknown, where the layers are not
    the stack's major-most axis, for a type under a byte, and for a slice
    under two dimensions (a norm's scale: its tiles are not a matrix's)."""
    order = getattr(stack_layout, "major_to_minor", None)
    if (not order or order[0] != 0 or w.ndim < 2 or len(order) != w.ndim + 1
            or getattr(stack_layout, "_sub_byte_element_size_in_bits", 0)):
        return w
    from jax.experimental.layout import Layout, with_layout_constraint

    return with_layout_constraint(
        w, Layout(tuple(d - 1 for d in order[1:]), stack_layout.tiling))


def forward(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,                      # [b, s] int32
    *,
    positions: Optional[jax.Array] = None,  # [b, s] absolute positions
    segment_ids: Optional[jax.Array] = None,  # [b, s] packed-seq ids (0 = pad)
    cache: Optional[KVCache] = None,
    cache_view: Optional[int] = None,
    remat: bool = False,
    with_aux: bool = False,
    return_activations: bool = False,
    adapters=None,
    token_mask: Optional[jax.Array] = None,  # [b, s] bool
    with_moe_counts: bool = False,
    weight_layouts=None,
    row_len_bound: Optional[int] = None,
) -> Tuple[jax.Array, Optional[KVCache]]:
    """Returns (logits [b, s, vocab] float32, updated cache or None) — or,
    with_aux=True, (logits, cache, aux) where aux is the summed per-layer
    auxiliary loss (MoE load balance; 0.0 for dense models).

    with_moe_counts=True (sparse models) appends int32 counts [sparse
    layers, experts held + 1] to what is returned: the (token, expert)
    assignments each held expert of each layer got in this call, and last
    those routed to experts held elsewhere (models/moe.py).

    weight_layouts (a serving decode program's; ``jax.tree.leaves(params)``
    order, a ``jax.experimental.layout.Layout`` or None a leaf): the layout
    each weight lies in on the device. Each layer's slice of a scanned
    stack is then read in that layout (``_read_in_place``): a program that
    runs this forward inside a loop of its own otherwise picks the layers'
    layouts for itself and copies whole stacks to them once a call
    (serve/weight_layout.py).

    return_activations=True skips the head matmul and returns the
    post-final-norm activations [b, s, hidden] in place of logits — the
    input to the chunked fused cross-entropy (train/step.py
    chunked_cross_entropy), which consumes activations + head weights in
    sequence chunks so the [b, s, vocab] f32 logits tensor is never
    materialized.

    Without cache: standard training/eval forward, causal + segment masking.
    With cache: tokens are appended at cache.index (prefill chunks or single-
    token decode); positions default to index + arange(s).

    cache_view (static): attention reads only cache slots [0, cache_view) —
    writes still land in the full cache. Exact whenever every query position
    is < cache_view; the serving engine picks the smallest bucketed view
    covering current occupancy so decode doesn't stream the whole
    max-length cache through HBM each step.

    adapters: None or (pool, lane_idx) — the multi-tenant batched LoRA
    injection (ops/lora.py, docs/multi-tenant-lora.md). ``pool`` is the
    stacked adapter pytree ({"attn"/"mlp": {target: {"a": [L, lanes,
    d_in, r], "b": [L, lanes, r, d_out]}}}); ``lane_idx`` [b] int32
    selects each row's adapter lane (-1 = base-only, mapped to the
    all-zero trash lane). The pool scans with the layers, and every
    targeted projection adds its row's ``(x @ A) @ B`` delta — one
    program for any tenant mix. Not supported on the pipeline (stage >
    1) path.

    row_len_bound (static; models whose full-attention layers read
    sparsely, and latent attention's expanded prefill): no row is longer
    than this once the call's tokens are written (a serving prefill's
    bucket). Beside it forward knows the keys a query can see (the call's,
    the cache's or the view's); where neither reaches sparse_dense_len the
    call compiles no sparse core. A latent layer's cached prefill expands
    only that much of a row to per-head keys and values.

    token_mask (models with linear-attention layers or sparse FFNs;
    ignored by the others): which tokens are real. A masked-out token
    leaves a row's recurrent state and conv tail exactly as they were (a
    bucket's padding behind a prompt, a parked row of a decode batch), and
    a sparse FFN routes it to no expert (its output there is the shared
    expert's alone; nobody reads it). For a recurrent layer a row's real
    tokens must be a prefix of it. None = all real. The full-attention layers
    keep their own rule: padding is parked by *position* (the trash slot).
    """
    b, s = tokens.shape
    ad = cfg.activation_dtype
    pattern = cfg.layer_pattern
    _check_support(cfg, segment_ids, adapters)

    if cache is not None and segment_ids is not None:
        raise NotImplementedError(
            "packed sequences (segment_ids) are not supported together with a "
            "KV cache: the cache mask is positional-only. Prefill packed "
            "batches without a cache, or one sequence per batch row with one."
        )

    # With a cache, explicitly-passed positions select position-scatter
    # writes (per-row slots); omitted positions select append-at-index.
    scatter_mode = cache is not None and positions is not None

    if positions is None:
        if cache is not None:
            positions = cache.index + jnp.arange(s, dtype=jnp.int32)[None, :]
            positions = jnp.broadcast_to(positions, (b, s))
        else:
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :],
                                         (b, s))

    use_one_hot = _auto_embed_one_hot(cfg, has_cache=cache is not None)
    with jax.named_scope("embed"):
        if use_one_hot:
            one_hot = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=ad)
            x = jnp.einsum("bsv,vh->bsh", one_hot,
                           params["embed"].astype(ad),
                           preferred_element_type=jnp.float32).astype(ad)
        else:
            x = params["embed"].astype(ad)[tokens]
        if cfg.embed_scale:
            x = x * (cfg.hidden_size ** 0.5)
        if cfg.embed_multiplier:
            x = (x.astype(jnp.float32) * cfg.embed_multiplier).astype(ad)
        if cfg.position_type == "learned":
            x = x + params["pos_embed"].astype(ad)[positions]
    # Deliberately the DEFAULT (replicated-h) constraint even when the
    # ring path tensor-shards the residual stream: constraining the
    # one-hot embed einsum's output tensor-sharded while its vocab
    # contraction is also tensor-sharded makes the SPMD partitioner
    # produce wrong VALUES in the train step. Still so on jaxlib 0.9.0:
    # with the ring rules here, tests/test_collective_matmul.py::
    # test_train_step_matches_gspmd[plain] fails (loss off in the first
    # digit). The first block's constraint shards the stream one op
    # later, which the partitioner handles correctly.
    x = with_logical_constraint(x, ("batch", "seq", "act_embed"))

    # Mask & bias over the full kv extent (or the static read view).
    with jax.named_scope("attn.mask"):
        if cache is not None:
            max_kv = (cache_view if cache_view is not None
                      else cache.k.shape[2])
            kv_positions = jnp.broadcast_to(
                jnp.arange(max_kv, dtype=jnp.int32)[None, :], (b, max_kv))
            if use_flash_cached_prefill(cfg, s):
                # Flash cached-prefill: the kernel masks causally from
                # absolute positions; no O(s*kv) mask tensor (see
                # _attention_block).
                mask = None
            else:
                # Slots at arange > q position are either future or
                # unwritten: the causal comparison masks both, so no
                # separate validity mask needed.
                mask = make_attention_mask(positions, kv_positions,
                                           causal=True)
        else:
            kv_positions = positions
            if resolve_attention_impl(cfg) == "flash":
                mask = None  # the kernel masks from positions/segments
            else:
                mask = make_attention_mask(
                    positions, kv_positions, segment_ids, segment_ids,
                    causal=True)

        bias = None
        if cfg.position_type == "alibi":
            slopes = alibi_slopes(cfg.num_heads)  # [h]
            rel = (kv_positions[:, None, :]
                   - positions[:, :, None]).astype(jnp.float32)
            bias = slopes[None, :, None, None] * rel[:, None, :, :]

    # Whether a row of this call may be long enough for a sparse read.
    long_rows = False
    if cfg.sparse_read is not None:
        seen = s if cache is None else (
            cache_view if cache_view is not None else cache.k.shape[2])
        long_rows = min(seen, row_len_bound or seen) \
            >= cfg.sparse_read.dense_len
    blocks = {kind: (_block if kind == "full_attention" and not long_rows
                     else functools.partial(_block, kind=kind,
                                            long_rows=long_rows))
              for kind in set(pattern)}
    if remat and cfg.remat_policy != "none":
        blocks = {kind: jax.checkpoint(
            fn, policy=_remat_policy(cfg.remat_policy), static_argnums=(0,))
            for kind, fn in blocks.items()}
    block = blocks[cfg.attention_kind]

    apool = aidx = None
    if adapters is not None:
        from runbooks_tpu.ops.lora import map_lane_indices, pool_lanes

        apool, aidx = adapters
        aidx = map_lane_indices(jnp.asarray(aidx), pool_lanes(apool))

    # One scan step is one PERIOD of the layer pattern: its one
    # full-attention layer (params["layers"], scanned as it lies, so a
    # homogeneous model's program is what it was before patterns) and its
    # other layers, each position's stack scanned the same way. A cache's
    # leaves ride the scan's CARRY, whole: a scanned output is a new buffer
    # by construction, so leaves scanned as xs/ys cost a copy of a whole
    # layer in and out every layer, and a copy of the pool for whoever
    # carries the cache through a loop of its own (make_decode_fn). Each
    # layer writes its part by index (`run_layer`) and the loop updates the
    # buffers in place.
    kinds = [kind for kind in _STACK_OF if kind in pattern]
    # The leaves that hold a layer (a latent cache's k and v hold none), in
    # KVCache's field order: what the carry flattens to.
    carried = [] if cache is None else [
        leaf for leaf in cache_leaves(cfg, cache.quantized)
        if cfg.layers_of(leaf.kind)]
    # Tokens a window layer's ring must not take: in position-scatter mode
    # padding is parked at the K/V leaves' last slot, by position.
    parked = (positions >= cache.k.shape[2] - 1
              if scatter_mode and (cfg.has_window or cfg.sparse_read)
              else None)

    def run_layer(kind, layer_params, x, leaves, layer, adapter=None,
                  index=None):
        """One layer of `kind`, number `layer` among the layers of its kind
        (where its part of that kind's leaves lies) and `index` among all
        the layers as run. `leaves`: the carried leaves by name, given back
        with the layer's own updated."""
        layer_cache = None
        if cache is not None:
            layer_cache = LayerCache(
                {leaf.name: leaves[leaf.name] for leaf in carried
                 if leaf.kind == kind}, layer,
                None if scatter_mode else cache.index, cache_view, parked,
                row_len_bound)
        x, new, aux, counts = blocks[kind](
            cfg, layer_params, x, positions, segment_ids, mask, bias,
            layer_cache, adapter, token_mask, index)
        return x, leaves | (new or {}), aux, counts

    names = ("wi_gate", "wi_up", "wo")
    whole_stacks = cache is not None and not _expert_mesh()

    def without_stacks(tree):
        """Serving: a sparse layer gets its expert matrices as the WHOLE
        stacks beside its number in them, not as the scan's slice of them
        (models/moe.grouped_matmul says why): (the tree without them,
        them)."""
        if not whole_stacks or "moe" not in tree:
            return tree, None
        moe = tree["moe"]
        return ({**tree, "moe": {k: v for k, v in moe.items()
                                 if k not in names}},
                {k: moe[k] for k in names})

    # Each kind's stacks, one a position it has in the period (the
    # attention kind has one), and the expert stacks taken out of them.
    scanned, expert_stacks = [], {}
    for kind in kinds:
        stacks = params[_STACK_OF[kind]]
        trees, expert_stacks[kind] = zip(*map(
            without_stacks, [stacks] if isinstance(stacks, dict) else stacks))
        scanned.append(trees)
    # The scanned stacks' layouts, in the order the scan body flattens
    # its slices of them (the leaves are the parameters' own objects).
    lain = None
    if weight_layouts is not None:
        by_leaf = dict(zip(map(id, jax.tree.leaves(params)), weight_layouts))
        lain = [by_leaf.get(id(w)) for w in jax.tree.leaves(scanned)]

    def scan_body(carry, xs):
        x, aux_sum, leaves = carry
        scanned, pool_layer, rel = xs
        if lain is not None:
            sliced, tree = jax.tree.flatten(scanned)
            scanned = tree.unflatten(list(map(_read_in_place, sliced, lain)))
        stacks = dict(zip(kinds, scanned))
        adapter = None if apool is None else (pool_layer, aidx)
        counts = []
        for at, kind in enumerate(pattern):
            j = pattern[:at].count(kind)
            layer_params = stacks[kind][j]
            if expert_stacks[kind][j] is not None:
                # `rel` is the period's number among the scanned ones: the
                # sparse layer's number in its whole stacks.
                layer_params = {
                    **layer_params, "moe_layer": rel,
                    "moe": {**layer_params["moe"], **expert_stacks[kind][j]}}
            # (No cache: no number, and nothing reads one.)
            layer = None if cache is None else _leaf_index(cfg, kind, rel, j)
            x, leaves, aux, c = run_layer(
                kind, layer_params, x, leaves, layer,
                adapter if kind == cfg.attention_kind else None,
                None if rel is None else
                cfg.leading_dense_layers + rel * len(pattern) + at)
            counts.append(c)
            aux_sum = aux_sum + aux
        # A sparse model's assignment counts, a row a layer in the
        # period's order (one row: as the one layer gave them).
        counts = (None if counts[0] is None
                  else counts[0] if len(counts) == 1 else jnp.stack(counts))
        return (x, aux_sum, leaves), counts

    aux_total = jnp.zeros((), jnp.float32)
    # An OrderedDict flattens in its own order, KVCache's field order here
    # (a dict in the scan's carry would flatten by sorted name).
    leaves = collections.OrderedDict(
        (leaf.name, getattr(cache, leaf.name)) for leaf in carried)
    if cfg.leading_dense_layers:
        # Leading layers (dense FFN, parameter shapes of their own) run
        # unrolled before the scan, under the same block, at indices
        # 0 .. n_lead - 1 of their kind's leaves.
        with jax.named_scope("leading_layers"):
            for i in range(cfg.leading_dense_layers):
                one = jax.tree.map(lambda a: a[i], params["leading_layers"])
                x, leaves, aux, _ = run_layer(
                    cfg.leading_layer_kind, one, x, leaves, i, index=i)
                aux_total = aux_total + aux
    moe_counts = None
    n_stages = 1
    if cache is None:
        from runbooks_tpu.parallel.sharding import _current_mesh

        mesh = _current_mesh()
        n_stages = int(mesh.shape.get("stage", 1)) if mesh is not None \
            else 1
    if n_stages > 1:
        if apool is not None:
            raise NotImplementedError(
                "adapter pools are not supported on the pipeline "
                "(stage > 1) path; serve adapters with tensor/data "
                "parallelism (docs/multi-tenant-lora.md)")
        if len(pattern) > 1:
            raise NotImplementedError(
                "a layer pattern is not supported on the pipeline "
                "(stage > 1) path: its stages split one homogeneous "
                "stack (parallel/pipeline.py)")
        # Pipeline-parallel path: same block, stacked layers sharded
        # over the stage axis, activations ppermuted between stages
        # (parallel/pipeline.py).
        from runbooks_tpu.parallel.pipeline import pipeline_apply

        def pipe_block(layer, xx, mb_consts):
            pos, seg, mk, bs = mb_consts
            y, _, aux, _ = block(cfg, layer, xx, pos, seg, mk, bs, None)
            return y, aux

        with jax.named_scope("layers"):
            x, aux_total = pipeline_apply(
                pipe_block, params["layers"], x,
                (positions, segment_ids, mask, bias),
                mesh=mesh, n_stages=n_stages,
                n_microbatches=cfg.pipeline_microbatches or None)
    else:
        # The adapter pool (leading L axis) rides the scan as xs when
        # given, and the period's number with a cache or where a layer's
        # mathematics depends on its index (lightning's decay).
        xs = (scanned, apool, None if cache is None and not cfg.lightning
              else jnp.arange(cfg.num_periods, dtype=jnp.int32))
        # `layers`: the scan itself (slices of the stacked weights, what
        # the compiler hoists out of the loop); each layer is a `block`.
        with jax.named_scope("layers"):
            (x, aux_total, leaves), moe_counts = jax.lax.scan(
                scan_body, (x, aux_total, leaves), xs)
    new_cache = None if cache is None else dataclasses.replace(
        cache, index=cache.index if scatter_mode else cache.index + s,
        **leaves)

    with jax.named_scope("head"):
        x = _norm(cfg, params["final_norm"], x)
        if cfg.logit_divisor != 1.0:
            x = (x.astype(jnp.float32) / cfg.logit_divisor).astype(x.dtype)
    extra = (aux_total,) if with_aux else ()
    if with_moe_counts:
        if moe_counts is None:
            raise ValueError(
                "with_moe_counts: this model has no sparse layer in its "
                "period scan (or runs the pipeline path)")
        # [periods, layers a period, held + 1] -> a row a sparse layer.
        extra += (moe_counts.reshape(-1, moe_counts.shape[-1]),)
    if return_activations:
        act_rules = _act_embed_rules(resolve_collective_matmul(cfg))
        x = with_logical_constraint(x, ("batch", "seq", "act_embed"),
                                    rules=act_rules)
        return (x, new_cache, *extra)
    logits = project_logits(cfg, params, x)
    return (logits, new_cache, *extra)


def project_logits(cfg: ModelConfig, params: Params,
                   x: jax.Array) -> jax.Array:
    """Float32 logits [..., vocab] of post-final-norm activations
    x [b, s, h] — or [b, h]: the serving prefill projects only each row's
    last prompt position (forward(return_activations=True), one gather,
    this), so the [rows, bucket, vocab] tensor is never made."""
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    # bf16 operands + f32 accumulation: the MXU accumulates in f32 either
    # way, but f32 operands run at 1/4 the bf16 MXU rate on v5e/v5p.
    with jax.named_scope("head"):
        logits = jnp.einsum("...h,hv->...v", x.astype(cfg.activation_dtype),
                            head.astype(cfg.activation_dtype),
                            preferred_element_type=jnp.float32)
        seq = ("seq",) * (x.ndim - 2)
        logits = with_logical_constraint(logits, ("batch", *seq, None))
    return logits


def _expert_mesh() -> bool:
    """The active mesh shards experts (models/moe.py runs a shard of them
    a device then)."""
    from runbooks_tpu.parallel.sharding import _current_mesh

    mesh = _current_mesh()
    return mesh is not None and int(mesh.shape.get("expert", 1)) > 1


# What forward cannot do yet for a model that keeps a group of cache leaves
# (LeafTraits.group) beside K/V, by name: {group: (its layers as a message
# names them, why no adapter pool, ((mesh axis, why not), ...))}
# (docs/hybrid-models.md, docs/sparse-latent-models.md,
# docs/window-full-models.md; ROADMAP.md M5 / M7).
_UNSUPPORTED = {
    "recurrent_state": (
        "recurrent",
        "adapter pools target the attention projections of a "
        "homogeneous stack; a layer pattern has no pooled path "
        "(docs/hybrid-models.md)", ()),
    "latent_cache": (
        "latent-attention",
        "adapter pools target the wq / wk / wv / wo of per-head "
        "attention; latent attention has no pooled path",
        (("tensor", "the latent cache has no head axis to shard, and the "
                    "absorbed decode's head split is not written"),
         ("sequence", "ring attention takes no softmax scale or value "
                      "width of the caller's"),
         ("stage", "the pipeline's stages split one homogeneous stack, "
                   "and leading layers are not part of it"))),
    "kv_compressed": (
        "sparse-read attention",
        "adapter pools target the attention projections of a "
        "homogeneous stack; no test holds a pooled lane through the "
        "choice of blocks (docs/hybrid-models.md)",
        (("tensor", "the choice of blocks and the walk by query blocks are "
                    "not written per shard of KV heads"),
         ("sequence", "ring attention knows no per-token choice of blocks"),
         ("stage", "the pipeline's stages split one homogeneous stack"))),
    "kv_ring": (
        "sliding-attention",
        "adapter pools target the attention projections of a "
        "homogeneous stack; window layers have stacks of their own and "
        "no pooled path",
        (("tensor", "the flash forward with a window or a sink is not "
                    "launched per shard, and the ring leaves' layout by "
                    "KV head is not held by a test"),
         ("sequence", "ring attention knows no window and no sink"),
         ("stage", "the pipeline's stages split one homogeneous stack"))),
}


def _check_support(cfg: ModelConfig, segment_ids, adapters):
    """Refuse what _UNSUPPORTED names, for each group of leaves the
    configuration keeps, in KVCache's field order."""
    from runbooks_tpu.parallel.sharding import _current_mesh

    mesh = _current_mesh()

    def size(axis):
        return int(mesh.shape.get(axis, 1)) if mesh is not None else 1

    for group in dict.fromkeys(leaf.group for leaf in cache_leaves(cfg)):
        if group not in _UNSUPPORTED:
            continue
        layers, no_adapters, no_axes = _UNSUPPORTED[group]
        if group == "kv_compressed" and segment_ids is not None:
            raise NotImplementedError(
                "packed sequences (segment_ids) with a sparse read need "
                "compressed keys and a choice of blocks a document; that "
                "is not written (ops/block_sparse_attention.py, "
                "docs/hybrid-models.md)")
        if group == "recurrent_state" and segment_ids is not None:
            if cfg.has_short_conv:
                raise NotImplementedError(
                    "packed sequences (segment_ids) with short-convolution "
                    "layers need the tail reset at document boundaries; "
                    "that is not written (ops/gated_delta.causal_conv, "
                    "docs/hybrid-models.md)")
            raise NotImplementedError(
                "packed sequences (segment_ids) with linear-attention "
                "layers need the recurrent state reset at document "
                "boundaries, and training on them the chunked scan's "
                "backward; neither is written (ops/gated_delta.py, "
                "ROADMAP.md M7)")
        if adapters is not None:
            raise NotImplementedError(no_adapters)
        for axis, why in no_axes:
            if size(axis) > 1:
                raise NotImplementedError(
                    f"a {axis} mesh axis > 1 is not supported with "
                    f"{layers} layers: {why}")
        if group == "recurrent_state" and cfg.linear_num_heads % size(
                "tensor"):
            raise NotImplementedError(
                f"a tensor mesh of {size('tensor')} does not divide the "
                f"{cfg.linear_num_heads} linear-attention heads: the "
                "recurrent state shards by head (docs/hybrid-models.md)")


def loss_and_grads_1f1b(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,                      # [b, s] int32
    targets: jax.Array,                     # [b, s] int32
    loss_mask: Optional[jax.Array] = None,  # [b, s] float {0,1}
    positions: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Params, jax.Array]:
    """Masked-mean CE loss + grads via the 1F1B pipeline schedule.

    Numerically equivalent to
    ``jax.value_and_grad(ce(forward(...)))`` on a stage>1 mesh (the GPipe
    autodiff path is the test oracle), but the backward is explicit: the
    pipeline interleaves per-microbatch vjp ticks so in-flight activations
    are O(stages) and full-batch logits never materialize (see
    parallel/pipeline.pipeline_1f1b_grads). Embedding fwd/bwd runs outside
    the pipeline via jax.vjp; head grads (incl. tied-embedding head) come
    back from the last stage and are tree-added.

    Returns (loss, grads, total_weight) with grads matching params'
    structure — a drop-in for the value_and_grad call in train/step.py.
    """
    from runbooks_tpu.parallel.pipeline import pipeline_1f1b_grads
    from runbooks_tpu.parallel.sharding import _current_mesh

    mesh = _current_mesh()
    n_stages = int(mesh.shape.get("stage", 1)) if mesh is not None else 1
    if n_stages <= 1:
        raise ValueError("loss_and_grads_1f1b needs a mesh with stage > 1")
    if cfg.embed_multiplier or cfg.residual_scale != 1.0 \
            or cfg.logit_divisor != 1.0:
        raise NotImplementedError(
            "embed_multiplier, residual_scale and logit_divisor are not "
            "written on the 1F1B pipeline path (its embedding and head are "
            "its own)")
    if len(cfg.layer_pattern) > 1:
        raise NotImplementedError(
            "a layer pattern is not supported on the pipeline (stage > 1) "
            "path: its stages split one homogeneous stack")
    b, s = tokens.shape
    ad = cfg.activation_dtype
    M = cfg.pipeline_microbatches or n_stages

    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))

    weights = (jnp.ones((b, s), jnp.float32) if loss_mask is None
               else loss_mask.astype(jnp.float32))
    total_weight = jnp.maximum(jnp.sum(weights), 1.0)
    inv_total = 1.0 / total_weight

    nl_params = {k: v for k, v in params.items() if k != "layers"}

    def embed_fn(nl):
        use_one_hot = _auto_embed_one_hot(cfg, has_cache=False)
        if use_one_hot:
            one_hot = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=ad)
            x = jnp.einsum("bsv,vh->bsh", one_hot, nl["embed"].astype(ad),
                           preferred_element_type=jnp.float32).astype(ad)
        else:
            x = nl["embed"].astype(ad)[tokens]
        if cfg.embed_scale:
            x = x * (cfg.hidden_size ** 0.5)
        if cfg.position_type == "learned":
            x = x + nl["pos_embed"].astype(ad)[positions]
        return with_logical_constraint(x, ("batch", "seq", "act_embed"))

    x, embed_vjp = jax.vjp(embed_fn, nl_params)

    # Mask/bias exactly as the no-cache forward builds them.
    if resolve_attention_impl(cfg) == "flash":
        mask = None
    else:
        mask = make_attention_mask(positions, positions, segment_ids,
                                   segment_ids, causal=True)
    bias = None
    if cfg.position_type == "alibi":
        slopes = alibi_slopes(cfg.num_heads)
        rel = (positions[:, None, :]
               - positions[:, :, None]).astype(jnp.float32)
        bias = slopes[None, :, None, None] * rel[:, None, :, :]

    def blk_fn(layer, xx, mb_consts):
        pos, seg, mk, bs = mb_consts
        y, _, aux, _ = _block(cfg, layer, xx, pos, seg, mk, bs, None)
        return y, aux

    def head_loss_fn(nl, y, lc):
        tgt, w = lc
        h = _norm(cfg, nl["final_norm"], y)
        head = nl["embed"].T if cfg.tie_embeddings else nl["head"]
        logits = jnp.einsum("bsh,hv->bsv", h.astype(ad), head.astype(ad),
                            preferred_element_type=jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        # One-hot select, NOT take_along_axis: the gather's transpose is a
        # scatter-add into the tensor-sharded logits, which crashes the
        # GSPMD partitioner inside the stage-manual shard_map
        # (spmd_partitioner_util.cc CHECK, reduced and verified); the
        # masked-sum transpose is a broadcast-multiply and partitions
        # cleanly (and is exactly how embed_one_hot sidesteps the same
        # class of problem on the embedding side).
        onehot = (jnp.arange(logits.shape[-1], dtype=tgt.dtype)[None, None]
                  == tgt[..., None])
        nll = -jnp.sum(jnp.where(onehot, logp, 0.0), axis=-1)
        return jnp.sum(nll * w) * inv_total

    # Vocab-parallel head for untied models: the [h, vocab] head shards
    # over the stage axis (head FLOPs drop S x back to the oracle's —
    # see pipeline_1f1b_grads docstring) and the loss becomes a global
    # log-softmax over the stage-sharded vocab: a stop-gradient'ed pmax
    # for stability, a psum'd sum-exp, and a per-stage PARTIAL loss
    # (lse/S - local target logit) whose stage-psum is the true loss —
    # autodiff through the psums yields exactly w*(softmax - onehot) on
    # each slice. Tied embeddings keep the replicated path (the embedding
    # must stay whole for the embedding fwd/bwd outside the pipeline).
    Vs = cfg.vocab_size // n_stages
    use_sharded_head = (not cfg.tie_embeddings
                        and cfg.vocab_size % n_stages == 0)

    def head_loss_fn_sharded(nl, y, lc):
        tgt, w = lc
        h = _norm(cfg, nl["final_norm"], y)
        z = jnp.einsum("bsh,hv->bsv", h.astype(ad), nl["head"].astype(ad),
                       preferred_element_type=jnp.float32)  # [b, s, V/S]
        # stop_gradient BEFORE pmax: pmax has no differentiation rule,
        # and the max is only a stabilization shift anyway.
        m = jax.lax.pmax(
            jax.lax.stop_gradient(jnp.max(z, axis=-1)), "stage")
        sumexp = jax.lax.psum(
            jnp.sum(jnp.exp(z - m[..., None]), axis=-1), "stage")
        lse = m + jnp.log(sumexp)
        lo = jax.lax.axis_index("stage").astype(tgt.dtype) * Vs
        onehot = (jnp.arange(Vs, dtype=tgt.dtype)[None, None]
                  == (tgt[..., None] - lo))
        z_t_local = jnp.sum(jnp.where(onehot, z, 0.0), axis=-1)
        partial_nll = lse / n_stages - z_t_local
        return jnp.sum(partial_nll * w) * inv_total

    head_specs = None
    active_head_loss = head_loss_fn
    if use_sharded_head:
        from jax.sharding import PartitionSpec as P

        head_specs = jax.tree.map(lambda _: P(), nl_params)
        head_specs["head"] = P(None, "stage")
        active_head_loss = head_loss_fn_sharded

    aux_scale = (cfg.moe_aux_coef / M) if cfg.moe_num_experts else 0.0
    loss_sum, layer_grads, head_grads, dx, aux_mean = pipeline_1f1b_grads(
        blk_fn, active_head_loss, params["layers"], nl_params, x,
        (positions, segment_ids, mask, bias), (targets, weights),
        mesh=mesh, n_stages=n_stages, n_microbatches=M,
        aux_scale=aux_scale, head_specs=head_specs)

    (embed_grads,) = embed_vjp(dx)
    nl_grads = jax.tree.map(lambda a, g: a + g, embed_grads, head_grads)
    grads = dict(nl_grads)
    grads["layers"] = layer_grads
    loss = loss_sum
    if cfg.moe_num_experts:
        loss = loss + cfg.moe_aux_coef * aux_mean
    return loss, grads, total_weight


def _remat_policy(name: str):
    # "none" never reaches here: it disables the jax.checkpoint wrapper
    # entirely at the call site (remat off, all activations saved).
    policies = {
        "nothing_saveable": jax.checkpoint_policies.nothing_saveable,
        "dots_saveable": jax.checkpoint_policies.dots_saveable,
        "dots_with_no_batch_dims_saveable":
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        # Selective: save the per-layer attention outputs — the post-wo
        # "attn_out" tagged in _block plus the flash kernel's hoisted
        # residuals "attn_context"/"attn_lse" (ops/flash_attention.py) —
        # and remat everything else. On the flash path the backward then
        # feeds the dq/dkv kernels from saved residuals instead of
        # re-running the O(s^2) fwd kernel (verified: the recompute pallas
        # call disappears from the grad jaxpr); on the xla path the s^2
        # einsum residuals are not nameable at O(s) memory, so this is
        # ~nothing_saveable plus a saved wo output there.
        "save_attn_out":
            jax.checkpoint_policies.save_only_these_names(
                "attn_out", "attn_context", "attn_lse"),
    }
    if name not in policies:
        raise ValueError(
            f"unknown remat_policy {name!r}; expected none|{'|'.join(policies)}")
    return policies[name]
