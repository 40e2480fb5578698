"""Sarvam-MLA, plain: the reference the benchmark compares the system with.

Straightforward float32 `jax.numpy` under
`jax.default_matmul_precision("highest")`: latent attention in its
EXPANDED form only (every head's keys and values made from the latent, one
causal softmax over all positions), a leading dense layer, then sparse
layers whose held experts run ONE AT A TIME over every token (a dense
product an expert, masked by the routing). No cache, no kernel, no
batching, no absorbed product, no sorting: the program's decode and its
grouped product are checked against different mathematics.

Per token x in R^h (u = the sub-layer's normed input), H heads:

  attention  q = W_q u -> H x (d_n + d_r); RMSNorm over each head's
             d_n + d_r with one scale; split (q_n, q_r); [c, k_r] = W_kva u
             -> r + d_r; c <- RMSNorm_r(c); q_r, k_r rotated (yarn-blended
             frequencies; ONE k_r shared by the heads); [k_n, v] = W_kvb c
             -> H x (d_n + d_v); scores = (q_n . k_n + q_r . k_r)
             (d_n + d_r)^-1/2 m^2, m = 0.1 mscale_all_dim ln(factor) + 1;
             causal softmax; o = W_o concat_heads(P v)
  dense FFN  W_down (SiLU(W_gate u) * W_up u), width intermediate_size
             (layer 0 .. first_k_dense_replace - 1)
  sparse FFN s = sigmoid(W_r u) (float32), S = top-k(s + b) with the bias
             b used ONLY to choose, g_e = s_e / sum_{e' in S} s_e';
             y = SwiGLU_shared(u) + routed_scaling_factor
                 * sum_{e in S, e held} g_e SwiGLU_e(u)
             The router scores over all `num_experts_routed`; the experts
             HELD are [first_expert_held, first_expert_held + num_experts):
             what the others would add is left out, here as in the program.
  block      h = x + Attn(RMSNorm(x)); out = h + FFN(RMSNorm(h))
  model      embedding, blocks, final RMSNorm, head (not tied); logits over
             the vocabulary slice held

It imports nothing of the program and takes nothing the program made. The
weights are part of the seeded input: `init_weights` draws them from the
seed with the recipe the configuration states (matrices N(0, 1/fan_in),
the selection bias N(0, 0.05^2), norm scales 1; drawn in float32, stored
in bfloat16; one threefry key a leaf in a fixed order, the leading layers'
keys from fold_in(key, 2)), which is the recipe the program's random init
follows.

Departures from the published model, noted: the config.json gives sizes
and switches, not equations; sigmoid scoring, weights normalised over the
chosen k, no expert groups, the QK norm's placement, the unscaled shared
expert and pre-norm blocks are the configuration's `assumed`. Weights are
seeded random. Depth, experts held and vocabulary rows are the
configuration's cut.

`low=True` is the control: the same mathematics with every product with a
weight matrix (the float32 router apart) computed in int8 (per-row
activation scales, per-column weight scales), the nearest precision below
the bfloat16 the configuration states.

`logits_at` also prints, for the sequence it was given, the share of
(token, expert) assignments on which routing from the float32 input and
routing from the same input rounded to bfloat16 (what a bfloat16 program's
router sees) choose the same expert: near-ties flipped by rounding are
the expected source of the widest gaps (the configuration's `limits_why`).
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 512


def dims(as_run: dict) -> dict:
    scaling = as_run["rope_scaling"]
    assert scaling["type"] == "deepseek_yarn"
    lead = as_run["first_k_dense_replace"]
    layers = as_run["num_hidden_layers"]
    assert 0 < lead < layers
    return {"h": as_run["hidden_size"], "f": as_run["intermediate_size"],
            "v": as_run["vocab_size"], "eps": as_run["rms_norm_eps"],
            "H": as_run["num_attention_heads"],
            "dn": as_run["qk_nope_head_dim"],
            "dr": as_run["qk_rope_head_dim"], "dv": as_run["v_head_dim"],
            "r": as_run["kv_lora_rank"],
            "qk_norm": bool(as_run["use_qk_norm"]),
            "lead": lead, "sparse": layers - lead,
            "E": as_run["num_experts_routed"],
            "held": as_run["num_experts"],
            "first": as_run["first_expert_held"],
            "k": as_run["num_experts_per_tok"],
            "fe": as_run["moe_intermediate_size"],
            "shared": as_run["num_shared_experts"],
            "bias": bool(as_run["moe_router_enable_expert_bias"]),
            "bias_std": as_run["router_bias_std"],
            "scale": as_run["routed_scaling_factor"],
            "theta": float(as_run["rope_theta"]), "yarn": scaling}


# --------------------------------------------------------------------------
# Seeded inputs: weights
# --------------------------------------------------------------------------

def weight_recipe(dm: dict) -> tuple:
    """Two ordered lists of (name, shape, how): the leaves whose keys come
    from split(key, 16) and those from split(fold_in(key, 2), 16), in the
    order the keys are dealt. `how` is a fan-in (normal / sqrt(fan-in)) or
    "bias" (normal x the selection bias's standard deviation)."""
    h, f, v, H, r = dm["h"], dm["f"], dm["v"], dm["H"], dm["r"]
    dq, dkv = dm["dn"] + dm["dr"], dm["dn"] + dm["dv"]
    S, n, E, held, fe = dm["sparse"], dm["lead"], dm["E"], dm["held"], dm["fe"]
    fs = fe * dm["shared"]

    def attention(pre, L):
        return [(pre + "wq", (L, h, H * dq), h),
                (pre + "w_kva", (L, h, r + dm["dr"]), h),
                (pre + "w_kvb", (L, r, H * dkv), r),
                (pre + "wo", (L, H * dm["dv"], h), H * dm["dv"])]

    first = [("embed", (v, h), h), ("head", (h, v), h)] + attention("", S) + [
        ("router", (S, h, E), h),
        ("exp_gate", (S, held, h, fe), h), ("exp_up", (S, held, h, fe), h),
        ("exp_down", (S, held, fe, h), fe),
        ("shared_down", (S, fs, h), fs), ("shared_gate", (S, h, fs), h),
        ("shared_up", (S, h, fs), h)]
    if dm["bias"]:
        first.append(("router_bias", (S, E), "bias"))
    second = attention("lead_", n) + [
        ("lead_mlp_down", (n, f, h), f), ("lead_mlp_gate", (n, h, f), h),
        ("lead_mlp_up", (n, h, f), h)]
    return first, second


def init_weights(as_run: dict, seed: int, shard=None) -> dict:
    """Weights from the seed, bfloat16, made on the device(s) in one jitted
    call. `shard(shape)` gives a sharding for a leaf (four chips)."""
    dm = dims(as_run)
    first, second = weight_recipe(dm)

    def draw(key, shape, how):
        if how == "bias":
            return jax.random.normal(key, shape) * dm["bias_std"]
        return jax.random.normal(key, shape) * how ** -0.5

    def make(key):
        out = {}
        for leaves, k in ((first, key), (second, jax.random.fold_in(key, 2))):
            for sub, (name, shape, how) in zip(jax.random.split(k, 16),
                                               leaves):
                out[name] = draw(sub, shape, how).astype(jnp.bfloat16)
        return out

    out_sh = None if shard is None else {
        name: shard(shape) for name, shape, _ in first + second}
    with jax.threefry_partitionable(True):   # values independent of layout
        w = jax.jit(make, out_shardings=out_sh)(jax.random.key(seed))
    ones = lambda *shape: jnp.ones(shape, jnp.float32)  # noqa: E731
    for pre, L in (("", dm["sparse"]), ("lead_", dm["lead"])):
        w.update({pre + "q_norm": ones(L, dm["dn"] + dm["dr"]),
                  pre + "kv_norm": ones(L, dm["r"]),
                  pre + "ln1": ones(L, dm["h"]),
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


def yarn(dm: dict) -> tuple:
    """(inverse frequencies [d_r / 2] as a list, m). A pair of the rotary
    dimensions that turns more than beta_fast times within the original
    context keeps its frequency; one that turns fewer than beta_slow times
    has it divided by factor; between them, a linear ramp over the pair's
    index."""
    y, d, theta = dm["yarn"], dm["dr"], dm["theta"]
    factor, original = y["factor"], y["original_max_position_embeddings"]

    def index_of(turns):
        return d * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(index_of(y["beta_fast"])), 0)
    high = min(math.ceil(index_of(y["beta_slow"])), d - 1)
    freqs = []
    for i in range(d // 2):
        plain = theta ** (-2.0 * i / d)
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        freqs.append(plain / factor * ramp + plain * (1.0 - ramp))
    m = 1.0
    if factor > 1 and y["mscale_all_dim"]:
        m = 0.1 * y["mscale_all_dim"] * math.log(factor) + 1.0
    return freqs, m


def rotate(x, positions, freqs):
    """Rotary embedding, halves convention: x [..., s, n, d_r] (or
    [s, d_r]), positions [s]; pair i is (x_i, x_{i + d_r/2})."""
    half = x.shape[-1] // 2
    angle = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(freqs, jnp.float32)
    if x.ndim == 3:
        angle = angle[:, None, :]
    sin, cos = jnp.sin(angle), jnp.cos(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(dm, u, lw, mm):
    s, H, dn, dr, dv, r = (u.shape[0], dm["H"], dm["dn"], dm["dr"],
                           dm["dv"], dm["r"])
    freqs, m = yarn(dm)
    pos = jnp.arange(s)
    q = mm(u, lw["wq"]).reshape(s, H, dn + dr)
    if dm["qk_norm"]:
        q = rms_norm(q, lw["q_norm"], dm["eps"])
    q_n, q_r = q[..., :dn], rotate(q[..., dn:], pos, freqs)
    down = mm(u, lw["w_kva"])
    c = rms_norm(down[:, :r], lw["kv_norm"], dm["eps"])
    k_r = rotate(down[:, r:], pos, freqs)
    kv = mm(c, lw["w_kvb"]).reshape(s, H, dn + dv)
    k_n, v = kv[..., :dn], kv[..., dn:]
    scale = (dn + dr) ** -0.5 * m * m

    def attend(first_row, n_rows):
        rows = first_row + jnp.arange(n_rows)
        scores = (jnp.einsum("qhd,khd->hqk", q_n[first_row:first_row + n_rows],
                             k_n, precision="highest")
                  + jnp.einsum("qhd,kd->hqk",
                               q_r[first_row:first_row + n_rows], k_r,
                               precision="highest")) * scale
        scores = jnp.where((pos[None, :] <= rows[:, None])[None], scores,
                           -1e30)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1),
                          v, precision="highest")

    # Blocks of query rows, so that the [heads, rows, keys] scores fit
    # beside the weights; the mathematics is unchanged.
    ctx = jnp.concatenate([attend(i, min(Q_BLOCK, s - i))
                           for i in range(0, s, Q_BLOCK)])
    return mm(ctx.reshape(s, H * dv), lw["wo"])


def choose(dm, u, lw):
    """(chosen [s, k] int, gate weights [s, k]) of the float32 router."""
    score = jax.nn.sigmoid(matmul(u, lw["router"]))
    pick = score + lw["router_bias"] if dm["bias"] else score
    _, chosen = jax.lax.top_k(pick, dm["k"])
    g = jnp.take_along_axis(score, chosen, axis=-1)
    return chosen, g / jnp.sum(g, axis=-1, keepdims=True)


def sparse_ffn(dm, u, lw, mm):
    """-> (y [s, h], assignments on which bfloat16-input routing agrees
    with float32-input routing, assignments)."""
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
    y = dm["scale"] * routed
    if dm["shared"]:
        y = y + swiglu(u, lw["shared_gate"].astype(jnp.float32),
                       lw["shared_up"].astype(jnp.float32),
                       lw["shared_down"].astype(jnp.float32), mm)
    return y, agree, chosen.size


def hidden_states(dm, w, tokens, mm):
    """(final-norm activations [s, h] of one sequence, routing agreement
    counts). Layer weights are read in float32 one layer (one expert) at a
    time: they are stored in bfloat16."""
    x = w["embed"][tokens].astype(jnp.float32)
    small = ("wq", "w_kva", "w_kvb", "wo", "q_norm", "kv_norm", "ln1", "ln2")

    def attn_part(x, lw):
        lw = {n: a.astype(jnp.float32) for n, a in lw.items()}
        h = x + attention(dm, rms_norm(x, lw["ln1"], dm["eps"]), lw, mm)
        return h, rms_norm(h, lw["ln2"], dm["eps"])

    for i in range(dm["lead"]):
        h, u = attn_part(x, {n: w["lead_" + n][i] for n in small})
        x = h + swiglu(u, *(w["lead_mlp_" + n][i].astype(jnp.float32)
                            for n in ("gate", "up", "down")), mm)

    def body(carry, lw):
        x, agree = carry
        h, u = attn_part(x, {n: lw[n] for n in small})
        ffn = {n: lw[n] for n in lw if n not in small}
        ffn["router"] = ffn["router"].astype(jnp.float32)
        if dm["bias"]:
            ffn["router_bias"] = ffn["router_bias"].astype(jnp.float32)
        y, same, _ = sparse_ffn(dm, u, ffn, mm)
        return (h + y, agree + same), None

    layer_names = [n for n in w if not n.startswith("lead_")
                   and n not in ("embed", "head", "final_norm")]
    (x, agree), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.int32)),
                                 {n: w[n] for n in layer_names})
    total = dm["sparse"] * tokens.shape[0] * dm["k"]
    return rms_norm(x, w["final_norm"], dm["eps"]), agree / total


@functools.partial(jax.jit, static_argnames=("as_run_json", "low"))
def _logits_at(w, tokens, rows, as_run_json, low):
    dm = dims(json.loads(as_run_json))
    mm = matmul_int8 if low else matmul
    x, agree = hidden_states(dm, w, tokens, mm)
    return mm(x[rows], w["head"].astype(jnp.float32)), agree


def logits_at(as_run: dict, w: dict, tokens, rows, low: bool = False):
    """Logits [len(rows), vocab] of one causal sequence at `rows`."""
    with jax.default_matmul_precision("highest"):
        logits, agree = _logits_at(w, jnp.asarray(tokens, jnp.int32),
                                   jnp.asarray(rows, jnp.int32),
                                   json.dumps(as_run, sort_keys=True), low)
    if not low:
        print(f"check: routing from the bfloat16-rounded input chooses the "
              f"float32 router's expert on {float(agree):.5f} of this "
              "sequence's assignments", flush=True)
    return logits
