"""Laguna (XS.2), plain: the reference the benchmark compares the system with.

Straightforward float32 `jax.numpy` under
`jax.default_matmul_precision("highest")`: every layer attends ALL the
sequence's keys under a mask (causal for a full layer, causal and a window
for a sliding one), one softmax a query over the visible keys; a leading
dense layer, then sparse layers whose held experts run ONE AT A TIME over
every token (a dense product an expert, masked by the routing) beside the
shared expert. No cache, no ring, no kernel, no batching, no block
skipping, no sorting: the program's ring cache, its flash forward with
ranges and its grouped product are checked against different mathematics.

One layer of kind c in {full_attention, sliding_attention} on the residual
x [s, h]; u = RMSNorm(x). H_c query heads (num_attention_heads_per_layer:
48 full, 64 sliding) on n KV heads of width d; query head i reads KV head
i // (H_c / n):

  attention  q = u W_q -> H_c x d;  k = u W_k, v = u W_v -> n x d; no bias;
             the first r_c = partial_rotary_factor_c x d dimensions of
             every q and k head rotate, halves convention (pairs (x_i,
             x_{i + r_c/2})), the other d - r_c pass:
               full     YaRN: frequencies blended over the width r_c
                        (theta, factor, original_max_position_embeddings,
                        beta_fast, beta_slow), and sin and cos BOTH times
                        attention_factor, so the rotated part of a score
                        carries its square and the part that passes 1;
               sliding  plain, angle position * theta^(-2i/r_c);
             s_tj = q_t . k_j / sqrt(d) for the keys j the query at t sees:
             full 0 <= j <= t; sliding 0 <= t - j < W; float32 softmax, no
             sink; o = sum_j p_j v_j -> H_c x d
  gate       g = sigmoid(u W_g), W_g [h, H_c]: one number a head and
             token, from the layer's normed input; a = concat_heads(g_i
             o_i) W_o; h = x + a
  dense FFN  W_down (SiLU(W_gate u') * W_up u'), width intermediate_size
             (the layers mlp_layer_types calls "dense"); u' = RMSNorm(h)
  sparse FFN s = softmax(u' W_r) over all experts (float32; `router`:
             "softmax", or "sigmoid" for the other reading of the missing
             scoring key: s = sigmoid(u' W_r), no selection bias), S =
             top-k(s), w_e = moe_routed_scaling_factor * s_e / sum_{e' in
             S} s_e' (norm_topk_prob); y = sum_{e in S, e held} w_e
             SwiGLU_e(u') + SwiGLU_shared(u'). The router scores over all
             `num_experts_routed`; the experts HELD are [first_expert_held,
             first_expert_held + num_experts): what the others would add
             is left out, here as in the program. The shared expert is
             added whole, ungated and unscaled (`shared=False` leaves it
             out: of eight shares one counts it).
  block      out = h + FFN(RMSNorm(h)); eps rms_norm_eps
  model      embedding, blocks in the order of layer_types, final RMSNorm,
             head (not tied); logits over the vocabulary slice held

It imports nothing of the program and takes nothing the program made. The
weights are part of the seeded input: `init_weights` draws them from the
seed with the recipe the configuration states (matrices, the gate among
them, N(0, 1/fan_in), norm scales 1; drawn in float32, stored in bfloat16;
one threefry key a leaf in a fixed order: the full layers' and the model's
from split(key), the leading layer's from fold_in(key, 2), the sliding
layers' from fold_in(key, 3)), which is the recipe the program's random
init follows.

Departures from the published model, noted: the config.json gives sizes
and switches, not equations. `gating: true` is read as the sibling
Laguna-S-2.1's "per-head" (one sigmoid a head from the layer's normed
input, on the core's output before W_o); norm_topk_prob true by the same
sibling; the router's scores a softmax over all experts (no scoring key,
no selection bias); the shared expert ungated; no QK norm;
attention_factor on sin and cos, not on the softmax scale;
moe_apply_router_weight_on_input false = weights on the experts' outputs:
the configuration's `assumed`. Weights are seeded random. Depth (the
leading layer and nine whole periods, 37 of 40 layers: the last three
sliding layers are a period's remainder), experts held and vocabulary rows
are the configuration's cut.

`low=True` is the control: the same mathematics with every product with a
weight matrix (the float32 router apart) computed in int8 (per-row
activation scales, per-column weight scales), the nearest precision below
the bfloat16 the configuration states.

`logits_at` also prints, for the sequence it was given, the share of
(token, expert) assignments on which routing from the float32 input and
routing from the same input rounded to bfloat16 (what a bfloat16 program's
router sees) choose the same expert: near-ties flipped by rounding are the
expected source of the widest gaps (the configuration's `limits_why`).
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512
FULL, SLIDING = "full_attention", "sliding_attention"


def dims(as_run: dict) -> dict:
    kinds = list(as_run["layer_types"])
    ffn = list(as_run["mlp_layer_types"])
    heads = list(as_run["num_attention_heads_per_layer"])
    layers, lead = as_run["num_hidden_layers"], as_run["first_k_dense_replace"]
    assert len(kinds) == len(ffn) == len(heads) == layers and 0 < lead < layers
    # The leading layers are full attention with a dense FFN; every layer
    # behind them is sparse.
    assert all(k == FULL for k in kinds[:lead]) \
        and all(f == "dense" for f in ffn[:lead]) \
        and all(f == "sparse" for f in ffn[lead:])
    H = {}
    for kind, n in zip(kinds, heads):
        assert H.setdefault(kind, n) == n, "one head count a layer kind"
    d = as_run["head_dim"]
    rope = as_run["rope_parameters"]
    rot = {c: int(round(rope[c]["partial_rotary_factor"] * d))
           for c in (FULL, SLIDING)}
    return {"h": as_run["hidden_size"], "f": as_run["intermediate_size"],
            "v": as_run["vocab_size"], "eps": as_run["rms_norm_eps"],
            "H": H, "kv": as_run["num_key_value_heads"], "d": d,
            "rot": rot, "rope": rope, "window": as_run["sliding_window"],
            "gate": bool(as_run["gating"]),
            "kinds": kinds, "lead": lead,
            "full": kinds[lead:].count(FULL),
            "win": kinds[lead:].count(SLIDING),
            "E": as_run["num_experts_routed"], "held": as_run["num_experts"],
            "first": as_run["first_expert_held"],
            "k": as_run["num_experts_per_tok"],
            "fe": as_run["moe_intermediate_size"],
            "fs": as_run["shared_expert_intermediate_size"],
            "router": as_run.get("router", "softmax"),
            "scale": as_run["moe_routed_scaling_factor"] or 1.0}


# --------------------------------------------------------------------------
# Seeded inputs: weights
# --------------------------------------------------------------------------

def weight_recipe(dm: dict) -> tuple:
    """Three ordered lists of (name, shape, fan-in): the leaves whose keys
    come from split(key, 16), from split(fold_in(key, 2), 16) and from
    split(fold_in(key, 3), 16), in the order the keys are dealt; every
    leaf is normal / sqrt(fan-in)."""
    h, f, v, n, d = dm["h"], dm["f"], dm["v"], dm["kv"], dm["d"]
    E, held, fe, fs = dm["E"], dm["held"], dm["fe"], dm["fs"]

    def attention(pre, L, kind):
        H = dm["H"].get(kind, 0)
        leaves = [(pre + "wq", (L, h, H * d), h),
                  (pre + "wk", (L, h, n * d), h),
                  (pre + "wv", (L, h, n * d), h),
                  (pre + "wo", (L, H * d, h), H * d)]
        if dm["gate"]:
            leaves.append((pre + "wg", (L, h, H), h))
        return leaves

    def sparse(pre, L):
        return [(pre + "router", (L, h, E), h),
                (pre + "exp_gate", (L, held, h, fe), h),
                (pre + "exp_up", (L, held, h, fe), h),
                (pre + "exp_down", (L, held, fe, h), fe),
                (pre + "shared_down", (L, fs, h), fs),
                (pre + "shared_gate", (L, h, fs), h),
                (pre + "shared_up", (L, h, fs), h)]

    first = ([("embed", (v, h), h), ("head", (h, v), h)]
             + attention("", dm["full"], FULL) + sparse("", dm["full"]))
    m = dm["lead"]
    second = attention("lead_", m, FULL) + [
        ("lead_mlp_down", (m, f, h), f), ("lead_mlp_gate", (m, h, f), h),
        ("lead_mlp_up", (m, h, f), h)]
    third = attention("win_", dm["win"], SLIDING) + sparse("win_", dm["win"])
    return first, second, third


def init_weights(as_run: dict, seed: int, shard=None) -> dict:
    """Weights from the seed, bfloat16, made on the device(s) in one jitted
    call. `shard(shape)` gives a sharding for a leaf (four chips)."""
    dm = dims(as_run)
    first, second, third = weight_recipe(dm)

    def make(key):
        out = {}
        for leaves, k in ((first, key), (second, jax.random.fold_in(key, 2)),
                          (third, jax.random.fold_in(key, 3))):
            for sub, (name, shape, fan_in) in zip(jax.random.split(k, 16),
                                                  leaves):
                out[name] = (jax.random.normal(sub, shape)
                             * fan_in ** -0.5).astype(jnp.bfloat16)
        return out

    out_sh = None if shard is None else {
        name: shard(shape) for name, shape, _ in first + second + third}
    with jax.threefry_partitionable(True):   # values independent of layout
        w = jax.jit(make, out_shardings=out_sh)(jax.random.key(seed))
    ones = lambda *shape: jnp.ones(shape, jnp.float32)  # noqa: E731
    for pre, L in (("", dm["full"]), ("lead_", dm["lead"]),
                   ("win_", dm["win"])):
        w.update({pre + "ln1": ones(L, dm["h"]),
                  pre + "ln2": ones(L, dm["h"])})
    w["final_norm"] = ones(dm["h"])
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


def frequencies(rope: dict, r: int) -> np.ndarray:
    """Inverse frequencies [r/2] of a rotary over a width of r. "default":
    theta^(-2i/r). "yarn": pairs that turn more than beta_fast times inside
    the original context keep that frequency, pairs that turn fewer than
    beta_slow times take it divided by factor, a linear ramp over the pair
    index between (the published rope_init of the type, at dim = r)."""
    half = r // 2
    theta = float(rope["rope_theta"])
    plain = theta ** (-np.arange(half, dtype=np.float64) / half)
    if rope.get("rope_type", "default") == "default":
        return plain.astype(np.float32)
    assert rope["rope_type"] == "yarn", rope["rope_type"]
    original = rope["original_max_position_embeddings"]

    def pair_that_turns(times):
        return (r * math.log(original / (times * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_that_turns(rope["beta_fast"])), 0)
    high = min(math.ceil(pair_that_turns(rope["beta_slow"])), r - 1)
    if low == high:
        high += 0.001
    slowed = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    return (plain / rope["factor"] * slowed
            + plain * (1.0 - slowed)).astype(np.float32)


def rotate(x, positions, rope: dict, r: int):
    """x [s, n, d], positions [s]: the first r dimensions of each head
    rotate as pairs (x_i, x_{i + r/2}) by position * frequency_i, sin and
    cos times the rope's attention_factor; dimensions r .. d-1 pass."""
    half = r // 2
    angle = positions.astype(jnp.float32)[:, None, None] \
        * jnp.asarray(frequencies(rope, r))
    m = float(rope.get("attention_factor", 1.0))
    sin, cos = m * jnp.sin(angle), m * jnp.cos(angle)
    a, b = x[..., :half], x[..., half:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., r:]], axis=-1)


def attention(dm, kind, u, lw, mm):
    s, H, n, d = u.shape[0], dm["H"][kind], dm["kv"], dm["d"]
    rope, r = dm["rope"][kind], dm["rot"][kind]
    pos = jnp.arange(s)
    q = rotate(mm(u, lw["wq"]).reshape(s, H, d), pos, rope, r)
    k = rotate(mm(u, lw["wk"]).reshape(s, n, d), pos, rope, r)
    v = mm(u, lw["wv"]).reshape(s, n, d)
    # Query head i reads KV head i // (H / n).
    k, v = (jnp.repeat(t, H // n, axis=1) for t in (k, v))

    def attend(first_row):
        rows = first_row + jnp.arange(block)
        scores = jnp.einsum(
            "qhd,khd->hqk", jax.lax.dynamic_slice_in_dim(q, first_row, block),
            k, precision="highest") * d ** -0.5
        age = rows[:, None] - pos[None, :]
        seen = age >= 0
        if kind == SLIDING:
            seen &= age < dm["window"]
        p = jax.nn.softmax(jnp.where(seen[None], scores, -1e30), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision="highest")

    # Blocks of query rows, one after the other, so that the [heads, rows,
    # keys] scores fit beside the weights; the mathematics is unchanged.
    block = Q_BLOCK if s % Q_BLOCK == 0 else s
    ctx = jax.lax.map(attend, jnp.arange(0, s, block)).reshape(s, H, d)
    if dm["gate"]:
        ctx = ctx * jax.nn.sigmoid(mm(u, lw["wg"]))[:, :, None]
    return mm(ctx.reshape(s, H * d), lw["wo"])


def choose(dm, u, lw):
    """(chosen [s, k] int, weights [s, k]) of the float32 router."""
    logits = matmul(u, lw["router"])
    score = (jax.nn.softmax(logits, axis=-1) if dm["router"] == "softmax"
             else jax.nn.sigmoid(logits))
    g, chosen = jax.lax.top_k(score, dm["k"])
    return chosen, dm["scale"] * g / jnp.sum(g, axis=-1, keepdims=True)


def sparse_ffn(dm, u, lw, mm, shared=True):
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
        (dm["first"] + jnp.arange(dm["held"]), lw["exp_gate"], lw["exp_up"],
         lw["exp_down"]))
    if shared:
        y = y + swiglu(u, lw["shared_gate"], lw["shared_up"],
                       lw["shared_down"], mm)
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
    attn = {n: f32(lw[n]) for n in ("wq", "wk", "wv", "wo", "wg") if n in lw}
    h = x + attention(dm, kind, rms_norm(x, f32(lw["ln1"]), dm["eps"]),
                      attn, mm)
    u = rms_norm(h, f32(lw["ln2"]), dm["eps"])
    if lead:
        return h + swiglu(u, *(f32(lw["mlp_" + n])
                               for n in ("gate", "up", "down")), mm), 0
    ffn = {**{n: f32(lw[n]) for n in ("router", "shared_gate", "shared_up",
                                      "shared_down")},
           **{n: lw[n] for n in ("exp_gate", "exp_up", "exp_down")}}
    y, same = sparse_ffn(dm, u, ffn, mm)
    return h + y, same


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _head(x, norm, head, eps, low):
    mm = matmul_int8 if low else matmul
    return mm(rms_norm(x, norm, eps), head.astype(jnp.float32))


def hidden_states(as_run: dict, w: dict, tokens, low: bool = False):
    """(the residual stream [s, h] of one sequence after the last block,
    the share of assignments on which rounded routing agrees)."""
    dm = dims(as_run)
    as_run_json = json.dumps(as_run, sort_keys=True)
    x = w["embed"][tokens].astype(jnp.float32)
    agree = 0
    taken = {"": 0, "lead_": 0, "win_": 0}
    for number, kind in enumerate(dm["kinds"]):
        pre = "lead_" if number < dm["lead"] else (
            "win_" if kind == SLIDING else "")
        i = taken[pre]
        taken[pre] += 1
        names = ("wq", "wk", "wv", "wo", "ln1", "ln2") + (
            ("wg",) if dm["gate"] else ()) + (
            ("mlp_gate", "mlp_up", "mlp_down") if pre == "lead_" else
            ("router", "exp_gate", "exp_up", "exp_down", "shared_gate",
             "shared_up", "shared_down"))
        lw = {n: w[pre + n][i] for n in names}
        x, same = _layer(x, lw, as_run_json, kind, pre == "lead_", low)
        agree = agree + same
    total = (len(dm["kinds"]) - dm["lead"]) * tokens.shape[0] * dm["k"]
    return x, agree / total


def logits_at(as_run: dict, w: dict, tokens, rows, low: bool = False):
    """Logits [len(rows), vocab] of one causal sequence at `rows`."""
    with jax.default_matmul_precision("highest"):
        x, agree = hidden_states(as_run, w, jnp.asarray(tokens, jnp.int32),
                                 low)
        logits = _head(x[jnp.asarray(rows, jnp.int32)], w["final_norm"],
                       w["head"], as_run["rms_norm_eps"], low)
    if not low:
        print(f"check: routing from the bfloat16-rounded input chooses the "
              f"float32 router's expert on {float(agree):.5f} of this "
              "sequence's assignments", flush=True)
    return logits
