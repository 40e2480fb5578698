"""LFM2 (24B-A2B), plain: the reference the benchmark compares the system with.

Straightforward float32 `jax.numpy` under
`jax.default_matmul_precision("highest")`: one sequence at a time, every
layer over the whole sequence. A conv layer is three shifted products (no
convolution routine, no tail, no cache); an attention layer attends ALL the
sequence's keys under a causal mask, one softmax a query; leading dense
layers, then sparse layers whose experts run ONE AT A TIME over every token
(a dense product an expert, masked by the routing). No kernel, no batching,
no sorting: the program's tail cache, its flash forward and its grouped
product are checked against different mathematics.

One layer on the residual x [s, h]; u = RMSNorm_op(x) (every norm is an
RMSNorm with a learned weight, eps norm_eps):

  conv       [B | C | X] = u W_in (h -> 3h, no bias; the thirds in the
             order `in_proj_order`, "BCX" as published); z = B * X;
             c_t = sum_{j=0..K-1} w_j * z_{t-(K-1)+j}, depthwise, causal,
             K = conv_L_cache taps, z before the first token 0, no bias
             (conv_bias false), NO activation; a = (C * c) W_out
  attention  q = u W_q -> H x d, k = u W_k, v = u W_v -> n x d, d = h / H,
             no bias; q and k each through an RMSNorm over the head's d
             (one weight of d for all query heads, one for all key heads)
             BEFORE the rotary; rotary over the whole head, halves
             convention (pairs (x_i, x_{i + d/2})), angle position *
             theta^(-2i/d); s_tj = q_t . k_j / sqrt(d) for 0 <= j <= t,
             float32 softmax; query head i reads KV head i // (H / n);
             a = concat_heads(o_i) W_o
  block      h' = x + a; u' = RMSNorm_ffn(h'); out = h' + FFN(u')
  dense FFN  W_2 (SiLU(W_1 u') * W_3 u'), width intermediate_size: the
             layers below num_dense_layers
  sparse FFN s = sigmoid(u' W_r) over all num_experts (float32); S = the
             num_experts_per_tok largest of s + b (b the expert bias: it
             moves the CHOICE only, use_expert_bias); w_e = s_e /
             (sum_{e' in S} s_e' + router_eps) (norm_topk_prob, the
             published 1e-6), times routed_scaling_factor; y = sum_{e in S}
             w_e SwiGLU_e(u') at moe_intermediate_size. No shared expert.
  model      embedding, blocks in the order of layer_types, one RMSNorm
             (the source's embedding_norm), head = the embedding transposed
             (tie_word_embeddings)

It imports nothing of the program and takes nothing the program made. The
weights are part of the seeded input: `init_weights` draws them from the
seed with the recipe the configuration states (matrices N(0, 1/fan_in), the
kernel N(0, 1/taps), the expert bias N(0, router_bias_std^2), norm scales
1; drawn in float32, stored in bfloat16; one threefry key a leaf in a fixed
order: the attention layers' and the model's from split(key), the leading
layers' from fold_in(key, 2), the periods' conv layers' from fold_in(key,
4)), which is the recipe the program's random init follows. A leaf is drawn
in a jitted call of its own, so that one leaf's float32 draw (4.5 GiB for
the conv layers' expert stacks) is all that lies beside the weights.

Departures from the published model, noted: the config.json gives sizes and
switches, not equations; the equations above are the lfm2_moe modelling
code's as the configuration's `assumed` lists them (the order B, C, X; a
tail of conv_L_cache - 1; b selection-only and started at zero there, seeded
here so that a program which drops it fails; the 1e-6; the tied head; head
width hidden / heads). Weights are seeded random. Depth (one leading layer
and two whole periods, 9 of 40 layers) is the configuration's cut; every
expert and the whole vocabulary are here.

Readings the configuration fixes and `as_run` may override, for the tests
that show each is seen: `in_proj_order` ("BCX"), `router_eps` (1e-6),
`expert_bias_in_weights` (false), `qk_norm` (true), `conv_gate` (true: the
C third multiplies the convolution's output).

`low=True` is the control: the same mathematics with every product with a
weight matrix (the float32 router apart) computed in int8 (per-row
activation scales, per-column weight scales), the nearest precision below
the bfloat16 the configuration states.

`logits_at` also prints, for the sequence it was given, the share of
(token, expert) assignments on which routing from the float32 input and
routing from the same input rounded to bfloat16 (what a bfloat16 program's
router sees) choose the same expert.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

Q_BLOCK = 512
FULL, CONV = "full_attention", "conv"


def dims(as_run: dict) -> dict:
    kinds = list(as_run["layer_types"])
    layers, lead = as_run["num_hidden_layers"], as_run["num_dense_layers"]
    assert len(kinds) == layers and 0 < lead < layers
    assert set(kinds) <= {FULL, CONV}
    # The leading layers are conv layers with a dense FFN; every layer
    # behind them is sparse.
    assert all(k == CONV for k in kinds[:lead])
    assert not as_run.get("conv_bias", False)
    h, H = as_run["hidden_size"], as_run["num_attention_heads"]
    order = as_run.get("in_proj_order", "BCX")
    assert sorted(order) == ["B", "C", "X"]
    rope = as_run["rope_parameters"]
    assert rope.get("rope_type", "default") == "default"
    return {"h": h, "f": as_run["intermediate_size"],
            "v": as_run["vocab_size"], "eps": as_run["norm_eps"],
            "H": H, "kv": as_run["num_key_value_heads"], "d": h // H,
            "theta": float(rope["rope_theta"]),
            "K": as_run["conv_L_cache"], "order": order,
            "kinds": kinds, "lead": lead,
            "full": kinds[lead:].count(FULL),
            "conv": kinds[lead:].count(CONV),
            "E": as_run["num_experts"], "k": as_run["num_experts_per_tok"],
            "fe": as_run["moe_intermediate_size"],
            "bias": bool(as_run["use_expert_bias"]),
            "bias_std": as_run.get("router_bias_std", 0.0),
            "bias_in_weights": bool(as_run.get("expert_bias_in_weights")),
            "norm_topk": bool(as_run["norm_topk_prob"]),
            "router_eps": as_run.get("router_eps", 1e-6),
            "scale": as_run["routed_scaling_factor"] or 1.0,
            "qk_norm": bool(as_run.get("qk_norm", True)),
            "conv_gate": bool(as_run.get("conv_gate", True))}


# --------------------------------------------------------------------------
# Seeded inputs: weights
# --------------------------------------------------------------------------

def weight_recipe(dm: dict) -> tuple:
    """Three ordered lists of (name, shape, scale): the leaves whose keys
    come from split(key, 16), from split(fold_in(key, 2), 16) and from
    split(fold_in(key, 4), 16), in the order the keys are dealt; every
    leaf is normal x scale."""
    h, f, v, d = dm["h"], dm["f"], dm["v"], dm["d"]
    H, n, K, E, fe = dm["H"], dm["kv"], dm["K"], dm["E"], dm["fe"]
    root = lambda fan_in: fan_in ** -0.5  # noqa: E731

    def short_conv(pre, L):
        return [(pre + "w_in", (L, h, 3 * h), root(h)),
                (pre + "w_out", (L, h, h), root(h)),
                (pre + "kernel", (L, K, h), root(K))]

    def sparse(pre, L):
        leaves = [(pre + "router", (L, h, E), root(h)),
                  (pre + "exp_gate", (L, E, h, fe), root(h)),
                  (pre + "exp_up", (L, E, h, fe), root(h)),
                  (pre + "exp_down", (L, E, fe, h), root(fe))]
        if dm["bias"]:
            leaves.append((pre + "router_bias", (L, E), dm["bias_std"]))
        return leaves

    L = dm["full"]
    first = [("embed", (v, h), root(h)),
             ("wq", (L, h, H * d), root(h)), ("wk", (L, h, n * d), root(h)),
             ("wv", (L, h, n * d), root(h)), ("wo", (L, H * d, h),
                                              root(H * d))] + sparse("", L)
    m = dm["lead"]
    second = short_conv("lead_", m) + [
        ("lead_mlp_down", (m, f, h), root(f)),
        ("lead_mlp_gate", (m, h, f), root(h)),
        ("lead_mlp_up", (m, h, f), root(h))]
    third = short_conv("conv_", dm["conv"]) + sparse("conv_", dm["conv"])
    return first, second, third


@functools.partial(jax.jit, static_argnames=("shape", "scale", "sharding"))
def _draw(key, shape, scale, sharding=None):
    leaf = (jax.random.normal(key, shape) * scale).astype(jnp.bfloat16)
    if sharding is not None:
        leaf = jax.lax.with_sharding_constraint(leaf, sharding)
    return leaf


def init_weights(as_run: dict, seed: int, shard=None) -> dict:
    """Weights from the seed, bfloat16, made on the device(s), a jitted
    call a leaf. `shard(shape)` gives a sharding for a leaf (four chips)."""
    dm = dims(as_run)
    key = jax.random.key(seed)
    w = {}
    with jax.threefry_partitionable(True):   # values independent of layout
        for leaves, k in zip(weight_recipe(dm),
                             (key, jax.random.fold_in(key, 2),
                              jax.random.fold_in(key, 4))):
            for sub, (name, shape, scale) in zip(jax.random.split(k, 16),
                                                 leaves):
                w[name] = _draw(sub, shape, float(scale),
                                None if shard is None else shard(shape))
    ones = lambda *shape: jnp.ones(shape, jnp.float32)  # noqa: E731
    for pre, L in (("", dm["full"]), ("lead_", dm["lead"]),
                   ("conv_", dm["conv"])):
        w.update({pre + "ln1": ones(L, dm["h"]),
                  pre + "ln2": ones(L, dm["h"])})
    w.update(q_norm=ones(dm["full"], dm["d"]),
             k_norm=ones(dm["full"], dm["d"]), final_norm=ones(dm["h"]))
    return w


# --------------------------------------------------------------------------
# The forward pass
# --------------------------------------------------------------------------

def matmul(x, w):
    return jnp.matmul(x, w, precision="highest")


def matmul_int8(x, w):
    """The control's product: int8 x int8 with per-row / per-column scales."""
    sx = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0 + 1e-30
    sw = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0 + 1e-30
    return matmul(jnp.round(x / sx), jnp.round(w / sw)) * sx * sw


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def silu(x):
    return x * jax.nn.sigmoid(x)


def swiglu(u, gate, up, down, mm):
    return mm(silu(mm(u, gate)) * mm(u, up), down)


def short_conv(dm, u, lw, mm):
    """The gated short convolution over one sequence u [s, h]."""
    s, h, K = u.shape[0], dm["h"], dm["K"]
    thirds = mm(u, lw["w_in"])
    part = {name: thirds[:, i * h:(i + 1) * h]
            for i, name in enumerate(dm["order"])}
    z = part["B"] * part["X"]
    # Tap j weighs the token K - 1 - j back; before the first token, 0.
    c = sum(lw["kernel"][j] * jnp.pad(z, ((K - 1 - j, 0), (0, 0)))[:s]
            for j in range(K))
    return mm(part["C"] * c if dm["conv_gate"] else c, lw["w_out"])


def rotate(x, positions, theta: float):
    """x [s, n, d], positions [s]: the whole head rotates as pairs (x_i,
    x_{i + d/2}) by position * theta^(-2i/d)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None, None] * freq
    sin, cos = jnp.sin(angle), jnp.cos(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(dm, u, lw, mm):
    s, H, n, d = u.shape[0], dm["H"], dm["kv"], dm["d"]
    pos = jnp.arange(s)
    q = mm(u, lw["wq"]).reshape(s, H, d)
    k = mm(u, lw["wk"]).reshape(s, n, d)
    v = mm(u, lw["wv"]).reshape(s, n, d)
    if dm["qk_norm"]:
        q = rms_norm(q, lw["q_norm"], dm["eps"])
        k = rms_norm(k, lw["k_norm"], dm["eps"])
    q, k = rotate(q, pos, dm["theta"]), rotate(k, pos, dm["theta"])
    # Query head i reads KV head i // (H / n).
    k, v = (jnp.repeat(t, H // n, axis=1) for t in (k, v))

    def attend(first_row):
        rows = first_row + jnp.arange(block)
        scores = jnp.einsum(
            "qhd,khd->hqk", jax.lax.dynamic_slice_in_dim(q, first_row, block),
            k, precision="highest") * d ** -0.5
        seen = rows[:, None] >= pos[None, :]
        p = jax.nn.softmax(jnp.where(seen[None], scores, -1e30), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision="highest")

    # Blocks of query rows, one after the other, so that the [heads, rows,
    # keys] scores fit beside the weights; the mathematics is unchanged.
    block = Q_BLOCK if s % Q_BLOCK == 0 else s
    ctx = jax.lax.map(attend, jnp.arange(0, s, block)).reshape(s, H * d)
    return mm(ctx, lw["wo"])


def choose(dm, u, lw):
    """(chosen [s, k] int, weights [s, k]) of the float32 router."""
    score = jax.nn.sigmoid(matmul(u, lw["router"]))
    biased = score + lw["router_bias"] if dm["bias"] else score
    picked, chosen = jax.lax.top_k(biased, dm["k"])
    g = picked if dm["bias_in_weights"] else jnp.take_along_axis(
        score, chosen, axis=-1)
    if dm["norm_topk"]:
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + dm["router_eps"])
    return chosen, dm["scale"] * g


def sparse_ffn(dm, u, lw, mm):
    """-> (y [s, h], assignments on which bfloat16-input routing agrees
    with float32-input routing)."""
    chosen, g = choose(dm, u, lw)
    rounded, _ = choose(dm, u.astype(jnp.bfloat16).astype(jnp.float32), lw)
    agree = jnp.sum(jnp.any(chosen[:, :, None] == rounded[:, None, :],
                            axis=-1))

    def one_expert(acc, scanned):
        e, gate, up, down = scanned
        w_e = jnp.sum(jnp.where(chosen == e, g, 0.0), axis=-1)   # [s]
        y_e = swiglu(u, gate.astype(jnp.float32), up.astype(jnp.float32),
                     down.astype(jnp.float32), mm)
        return acc + w_e[:, None] * y_e, None

    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u),
        (jnp.arange(dm["E"]), lw["exp_gate"], lw["exp_up"], lw["exp_down"]))
    return y, agree


@functools.partial(jax.jit,
                   static_argnames=("as_run_json", "kind", "lead", "low"))
def _layer(x, lw, as_run_json, kind, lead, low):
    """One block on the residual stream x [s, h] -> (x, assignments on
    which rounded routing agrees). A jitted call a layer: the float32
    copies of one layer's weights and its scores are freed before the
    next layer's are made (the weights are stored in bfloat16)."""
    dm = dims(json.loads(as_run_json))
    mm = matmul_int8 if low else matmul
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    u = rms_norm(x, f32(lw["ln1"]), dm["eps"])
    if kind == CONV:
        a = short_conv(dm, u, {n: f32(lw[n])
                               for n in ("w_in", "w_out", "kernel")}, mm)
    else:
        a = attention(dm, u, {n: f32(lw[n]) for n in (
            "wq", "wk", "wv", "wo", "q_norm", "k_norm")}, mm)
    h = x + a
    u = rms_norm(h, f32(lw["ln2"]), dm["eps"])
    if lead:
        return h + swiglu(u, *(f32(lw["mlp_" + n])
                               for n in ("gate", "up", "down")), mm), 0
    ffn = {**{n: f32(lw[n]) for n in ("router", "router_bias") if n in lw},
           **{n: lw[n] for n in ("exp_gate", "exp_up", "exp_down")}}
    y, same = sparse_ffn(dm, u, ffn, mm)
    return h + y, same


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _head(x, norm, embed, eps, low):
    mm = matmul_int8 if low else matmul
    return mm(rms_norm(x, norm, eps), embed.astype(jnp.float32).T)


def hidden_states(as_run: dict, w: dict, tokens, low: bool = False):
    """(the residual stream [s, h] of one sequence after the last block,
    the share of assignments on which rounded routing agrees)."""
    dm = dims(as_run)
    as_run_json = json.dumps(as_run, sort_keys=True)
    x = w["embed"][tokens].astype(jnp.float32)
    agree = 0
    taken = {"": 0, "lead_": 0, "conv_": 0}
    for number, kind in enumerate(dm["kinds"]):
        lead = number < dm["lead"]
        pre = "lead_" if lead else ("conv_" if kind == CONV else "")
        i = taken[pre]
        taken[pre] += 1
        names = ("ln1", "ln2") + (
            ("w_in", "w_out", "kernel") if kind == CONV else
            ("wq", "wk", "wv", "wo")) + (
            ("mlp_gate", "mlp_up", "mlp_down") if lead else
            ("router", "exp_gate", "exp_up", "exp_down")
            + (("router_bias",) if dm["bias"] else ()))
        lw = {n: w[pre + n][i] for n in names}
        if kind == FULL:
            lw.update(q_norm=w["q_norm"][i], k_norm=w["k_norm"][i])
        x, same = _layer(x, lw, as_run_json, kind, lead, low)
        agree = agree + same
    total = (len(dm["kinds"]) - dm["lead"]) * tokens.shape[0] * dm["k"]
    return x, agree / total


def logits_at(as_run: dict, w: dict, tokens, rows, low: bool = False):
    """Logits [len(rows), vocab] of one causal sequence at `rows`."""
    with jax.default_matmul_precision("highest"):
        x, agree = hidden_states(as_run, w, jnp.asarray(tokens, jnp.int32),
                                 low)
        logits = _head(x[jnp.asarray(rows, jnp.int32)], w["final_norm"],
                       w["embed"], as_run["norm_eps"], low)
    if not low:
        print(f"check: routing from the bfloat16-rounded input chooses the "
              f"float32 router's expert on {float(agree):.5f} of this "
              "sequence's assignments", flush=True)
    return logits
