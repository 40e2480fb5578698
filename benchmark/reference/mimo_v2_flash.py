"""MiMo-V2-Flash, plain: the reference the benchmark compares the system with.

Straightforward float32 `jax.numpy` under
`jax.default_matmul_precision("highest")`: every layer attends ALL the
sequence's keys under a mask (causal for a full layer, causal and a window
for a window layer), one softmax a query over the visible keys and, in a
window layer, the sink; a leading dense layer, then sparse layers whose
held experts run ONE AT A TIME over every token (a dense product an
expert, masked by the routing). No cache, no ring, no kernel, no batching,
no block skipping, no sorting: the program's ring cache, its flash forward
with ranges and its grouped product are checked against different
mathematics.

Per token x in R^h (u = the sub-layer's normed input), H query heads, a
layer of kind c in {full, window} with n_c KV heads (query head i reads KV
head i // (H / n_c)):

  attention  q = W_q u -> H x d;  k = W_k u -> n_c x d;
             v = value_scale * W_v u -> n_c x d_v;
             the first r of the d dimensions of every q and k head rotate
             (pairs (x_i, x_{i + r/2}), angle position * theta_c^(-2i/r)),
             the other d - r pass;
             s_tj = q_t . k_j / sqrt(d) for the keys j the query at t sees:
             full 0 <= j <= t; window 0 <= t - j < W;
             p = softmax([s_t., b_head]) with the sink b_head one more
             logit (window layers only) whose column is then dropped: it
             takes weight and gives no value; o = sum_j p_j v_j -> H x d_v;
             out = W_o concat_heads(o)
  dense FFN  W_down (SiLU(W_gate u) * W_up u), width intermediate_size
             (the layers moe_layer_freq marks 0)
  sparse FFN s = sigmoid(W_r u) (float32), S = top-k(s + bias) with the
             bias used ONLY to choose, g_e = s_e / sum_{e' in S} s_e';
             y = routed_scaling_factor * sum_{e in S, e held} g_e
             SwiGLU_e(u). The router scores over all `num_experts_routed`;
             the experts HELD are [first_expert_held, first_expert_held +
             num_experts): what the others would add is left out, here as
             in the program. No shared expert.
  block      h = x + Attn(RMSNorm(x)); out = h + FFN(RMSNorm(h))
  model      embedding, blocks in the order of hybrid_layer_pattern (0 =
             full, 1 = window), final RMSNorm, head (not tied); logits
             over the vocabulary slice held

It imports nothing of the program and takes nothing the program made. The
weights are part of the seeded input: `init_weights` draws them from the
seed with the recipe the configuration states (matrices N(0, 1/fan_in),
sinks N(0, 1), the selection bias N(0, router_bias_std^2), norm scales 1;
drawn in float32, stored in bfloat16; one threefry key a leaf in a fixed
order: the full layers' and the model's from split(key), the leading
layer's from fold_in(key, 2), the window layers' from fold_in(key, 3)),
which is the recipe the program's random init follows.

Departures from the published model, noted: the config.json gives sizes
and switches, not equations; where the value scale applies, the sink's
convention, which dimensions rotate and in which pairing, the window's
edge, no QK norm and pre-norm blocks are the configuration's `assumed`.
The 3 multi-token-prediction layers of the model card have no key in the
config.json and are not run. Weights are seeded random. Depth, experts
held and vocabulary rows are the configuration's cut; the published first
period is one window layer short (F W W W W F ...), the cut takes the
leading layer and one WHOLE later period.

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

import jax
import jax.numpy as jnp

Q_BLOCK = 512


def dims(as_run: dict) -> dict:
    kinds = list(as_run["hybrid_layer_pattern"])
    sparse = list(as_run["moe_layer_freq"])
    layers, lead = as_run["num_hidden_layers"], as_run["first_k_dense_replace"]
    assert len(kinds) == len(sparse) == layers and 0 < lead < layers
    # The leading layers are full attention with a dense FFN; every layer
    # behind them is sparse.
    assert not any(kinds[:lead]) and not any(sparse[:lead]) \
        and all(sparse[lead:])
    return {"h": as_run["hidden_size"], "f": as_run["intermediate_size"],
            "v": as_run["vocab_size"], "eps": as_run["layernorm_epsilon"],
            "H": as_run["num_attention_heads"], "d": as_run["head_dim"],
            "dv": as_run["v_head_dim"], "rot": as_run["rotary_dim"],
            "kv": {0: as_run["num_key_value_heads"],
                   1: as_run["swa_num_key_value_heads"]},
            "theta": {0: float(as_run["rope_theta"]),
                      1: float(as_run["swa_rope_theta"])},
            "sink": {0: bool(as_run["add_full_attention_sink_bias"]),
                     1: bool(as_run["add_swa_attention_sink_bias"])},
            "window": as_run["sliding_window"],
            "value_scale": as_run["attention_value_scale"],
            "kinds": kinds, "lead": lead,
            "full": kinds[lead:].count(0), "win": kinds[lead:].count(1),
            "E": as_run["num_experts_routed"], "held": as_run["num_experts"],
            "first": as_run["first_expert_held"],
            "k": as_run["num_experts_per_tok"],
            "fe": as_run["moe_intermediate_size"],
            "bias_std": as_run["router_bias_std"],
            "scale": as_run["routed_scaling_factor"] or 1.0}


# --------------------------------------------------------------------------
# Seeded inputs: weights
# --------------------------------------------------------------------------

def weight_recipe(dm: dict) -> tuple:
    """Three ordered lists of (name, shape, how): the leaves whose keys
    come from split(key, 16), from split(fold_in(key, 2), 16) and from
    split(fold_in(key, 3), 16), in the order the keys are dealt. `how` is a
    fan-in (normal / sqrt(fan-in)), "sink" (normal) or "bias" (normal x
    the selection bias's standard deviation)."""
    h, f, v, H, d, dv = dm["h"], dm["f"], dm["v"], dm["H"], dm["d"], dm["dv"]
    E, held, fe = dm["E"], dm["held"], dm["fe"]

    def attention(pre, L, kind):
        n = dm["kv"][kind]
        leaves = [(pre + "wq", (L, h, H * d), h),
                  (pre + "wk", (L, h, n * d), h),
                  (pre + "wv", (L, h, n * dv), h),
                  (pre + "wo", (L, H * dv, h), H * dv)]
        if dm["sink"][kind]:
            leaves.append((pre + "sink", (L, H), "sink"))
        return leaves

    def sparse(pre, L):
        return [(pre + "router", (L, h, E), h),
                (pre + "exp_gate", (L, held, h, fe), h),
                (pre + "exp_up", (L, held, h, fe), h),
                (pre + "exp_down", (L, held, fe, h), fe),
                (pre + "router_bias", (L, E), "bias")]

    first = ([("embed", (v, h), h), ("head", (h, v), h)]
             + attention("", dm["full"], 0) + sparse("", dm["full"]))
    n = dm["lead"]
    second = attention("lead_", n, 0) + [
        ("lead_mlp_down", (n, f, h), f), ("lead_mlp_gate", (n, h, f), h),
        ("lead_mlp_up", (n, h, f), h)]
    third = attention("win_", dm["win"], 1) + sparse("win_", dm["win"])
    return first, second, third


def init_weights(as_run: dict, seed: int, shard=None) -> dict:
    """Weights from the seed, bfloat16, made on the device(s) in one jitted
    call. `shard(shape)` gives a sharding for a leaf (four chips)."""
    dm = dims(as_run)
    first, second, third = weight_recipe(dm)

    def draw(key, shape, how):
        if how == "sink":
            return jax.random.normal(key, shape)
        if how == "bias":
            return jax.random.normal(key, shape) * dm["bias_std"]
        return jax.random.normal(key, shape) * how ** -0.5

    def make(key):
        out = {}
        for leaves, k in ((first, key), (second, jax.random.fold_in(key, 2)),
                          (third, jax.random.fold_in(key, 3))):
            for sub, (name, shape, how) in zip(jax.random.split(k, 16),
                                               leaves):
                out[name] = draw(sub, shape, how).astype(jnp.bfloat16)
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


def rotate(x, positions, theta, r):
    """Rotary embedding over the first r dimensions of each head, halves
    convention: x [s, n, d], positions [s]; pair i is (x_i, x_{i + r/2}),
    angle position * theta^(-2i/r); dimensions r .. d-1 pass."""
    half = r // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None, None] * freqs
    sin, cos = jnp.sin(angle), jnp.cos(angle)
    a, b = x[..., :half], x[..., half:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., r:]], axis=-1)


def attention(dm, kind, u, lw, mm):
    s, H, d, dv = u.shape[0], dm["H"], dm["d"], dm["dv"]
    n = dm["kv"][kind]
    pos = jnp.arange(s)
    q = rotate(mm(u, lw["wq"]).reshape(s, H, d), pos, dm["theta"][kind],
               dm["rot"])
    k = rotate(mm(u, lw["wk"]).reshape(s, n, d), pos, dm["theta"][kind],
               dm["rot"])
    v = dm["value_scale"] * mm(u, lw["wv"]).reshape(s, n, dv)
    # Query head i reads KV head i // (H / n).
    k, v = (jnp.repeat(t, H // n, axis=1) for t in (k, v))

    def attend(first_row):
        rows = first_row + jnp.arange(block)
        scores = jnp.einsum(
            "qhd,khd->hqk", jax.lax.dynamic_slice_in_dim(q, first_row, block),
            k, precision="highest") * d ** -0.5
        age = rows[:, None] - pos[None, :]
        seen = age >= 0
        if kind == 1:
            seen &= age < dm["window"]
        scores = jnp.where(seen[None], scores, -1e30)
        if dm["sink"][kind]:
            sink = jnp.broadcast_to(lw["sink"][:, None, None], (H, block, 1))
            p = jax.nn.softmax(jnp.concatenate([scores, sink], axis=-1),
                               axis=-1)[..., :-1]
        else:
            p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision="highest")

    # Blocks of query rows, one after the other, so that the [heads, rows,
    # keys] scores fit beside the weights; the mathematics is unchanged.
    block = Q_BLOCK if s % Q_BLOCK == 0 else s
    ctx = jax.lax.map(attend, jnp.arange(0, s, block))
    return mm(ctx.reshape(s, H * dv), lw["wo"])


def choose(dm, u, lw):
    """(chosen [s, k] int, gate weights [s, k]) of the float32 router."""
    score = jax.nn.sigmoid(matmul(u, lw["router"]))
    _, chosen = jax.lax.top_k(score + lw["router_bias"], dm["k"])
    g = jnp.take_along_axis(score, chosen, axis=-1)
    return chosen, g / jnp.sum(g, axis=-1, keepdims=True)


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

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u),
        (dm["first"] + jnp.arange(dm["held"]), lw["exp_gate"], lw["exp_up"],
         lw["exp_down"]))
    return dm["scale"] * routed, agree


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
    attn = {n: f32(lw[n]) for n in ("wq", "wk", "wv", "wo", "sink")
            if n in lw}
    h = x + attention(dm, kind, rms_norm(x, f32(lw["ln1"]), dm["eps"]),
                      attn, mm)
    u = rms_norm(h, f32(lw["ln2"]), dm["eps"])
    if lead:
        return h + swiglu(u, *(f32(lw["mlp_" + n])
                               for n in ("gate", "up", "down")), mm), 0
    ffn = {"router": f32(lw["router"]),
           "router_bias": f32(lw["router_bias"]),
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
        pre = "lead_" if number < dm["lead"] else ("win_" if kind else "")
        i = taken[pre]
        taken[pre] += 1
        names = ("wq", "wk", "wv", "wo", "ln1", "ln2") + (
            ("mlp_gate", "mlp_up", "mlp_down") if pre == "lead_" else
            ("router", "router_bias", "exp_gate", "exp_up", "exp_down")) + (
            ("sink",) if dm["sink"][kind] else ())
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
                       w["head"], as_run["layernorm_epsilon"], low)
    if not low:
        print(f"check: routing from the bfloat16-rounded input chooses the "
              f"float32 router's expert on {float(agree):.5f} of this "
              "sequence's assignments", flush=True)
    return logits
