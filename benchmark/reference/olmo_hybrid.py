"""Olmo-Hybrid, plain: the reference the benchmark compares the system with.

Straightforward float32 `jax.numpy` under
`jax.default_matmul_precision("highest")`: a period of three
linear-attention layers (the gated delta rule, Gated DeltaNet) and one
full-attention layer, repeated; reordered-norm blocks; an untied output
head. The delta rule is the plain recurrence, one token at a time
(`lax.scan` over positions): no chunks, no cache, no batching, no kernel.

Per token x in R^h, per head of H, key size d_k, value size d_v:

  linear mixer   q~ = W_q x, k~ = W_k x, v~ = W_v x; each through a causal
                 depthwise convolution (kernel 4, own weights, no bias)
                 and SiLU; q = q/|q| d_k^-1/2, k = k/|k| (per head; the
                 length is sqrt(sum x^2 + 1e-6)); beta = 2 sigmoid(W_b x);
                 g = -exp(A_log) softplus(W_a x + dt_bias), alpha = exp g;
                 S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1}
                       + beta_t k_t v_t^T,   o_t = S_t^T q_t;
                 y = W_o [RMSNorm_{d_v}(o) * SiLU(W_g x)]
  full mixer     H heads of head_dim, causal softmax at head_dim^-1/2, no
                 bias, NO rotary embedding, RMSNorm over the whole q and
                 the whole k before the heads are split
  block          h = x + RMSNorm(Mixer(x)); out = h + RMSNorm(MLP(h));
                 MLP(h) = W_down (SiLU(W_gate h) * W_up h)
  model          embedding, blocks, final RMSNorm, head (not tied)

It imports nothing of the program and takes nothing the program made. The
weights are part of the seeded input: `init_weights` draws them from the
seed with the recipe the configuration states (matrices N(0, 1/fan_in),
conv N(0, 1/4), A_log = log U(1, 16), dt_bias the inverse softplus of
exp U(log 1e-3, log 1e-1), norm weights 1; drawn in float32, stored in
bfloat16; one threefry key per leaf in a fixed order, the linear layers'
keys from fold_in(key, 1)), which is the recipe the program's random init
follows.

Departures from the published model, noted: the published config.json
gives sizes and the layer pattern, not the equations; the block's norm
placement, the QK norm's width, the absence of rotary embedding (its
`rope_theta` is null) and the Gated DeltaNet layer's details are the
configuration's `assumed`. Weights are seeded random. Depth is the
configuration's `num_hidden_layers`.

`low=True` is the control: the same mathematics with every product with a
weight matrix computed in int8 (per-row activation scales, per-column
weight scales), the nearest precision below the bfloat16 the configuration
states.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

Q_BLOCK = 512
L2_EPS = 1e-6
KINDS = ("linear_attention", "full_attention")


def dims(as_run: dict) -> dict:
    period = tuple(as_run["layer_period"])
    layers = as_run["num_hidden_layers"]
    assert layers % len(period) == 0 and set(period) <= set(KINDS)
    heads = as_run["linear_num_value_heads"]
    assert heads == as_run["linear_num_key_heads"]
    return {"h": as_run["hidden_size"], "f": as_run["intermediate_size"],
            "v": as_run["vocab_size"], "eps": as_run["rms_norm_eps"],
            "nq": as_run["num_attention_heads"], "d": as_run["head_dim"],
            "H": heads, "dk": as_run["linear_key_head_dim"],
            "dv": as_run["linear_value_head_dim"],
            "K": as_run["linear_conv_kernel_dim"],
            "neg": as_run["linear_allow_neg_eigval"],
            "period": period, "periods": layers // len(period),
            "n_full": layers // len(period) * period.count("full_attention"),
            "n_lin": layers // len(period)
            * period.count("linear_attention")}


# --------------------------------------------------------------------------
# Seeded inputs: weights
# --------------------------------------------------------------------------

def weight_recipe(dm: dict) -> tuple:
    """Two ordered lists of (name, shape, how): the leaves whose keys come
    from split(key, 16) and those from split(fold_in(key, 1), 16), in the
    order the keys are dealt. `how` is a fan-in (normal / sqrt(fan-in)) or
    the name of a special draw."""
    h, f, v = dm["h"], dm["f"], dm["v"]
    q = dm["nq"] * dm["d"]
    F, L = dm["n_full"], dm["n_lin"]
    kd, vd, H = dm["H"] * dm["dk"], dm["H"] * dm["dv"], dm["H"]
    first = [("embed", (v, h), h), ("head", (h, v), h),
             ("wq", (F, h, q), h), ("wk", (F, h, q), h),
             ("wv", (F, h, q), h), ("wo", (F, q, h), q),
             ("mlp_down", (F, f, h), f), ("mlp_gate", (F, h, f), h),
             ("mlp_up", (F, h, f), h)]
    second = [("lin_wq", (L, h, kd), h), ("lin_wk", (L, h, kd), h),
              ("lin_wv", (L, h, vd), h), ("lin_wg", (L, h, vd), h),
              ("lin_wo", (L, vd, h), vd), ("lin_wa", (L, h, H), h),
              ("lin_wb", (L, h, H), h),
              ("lin_conv", (L, dm["K"], 2 * kd + vd), dm["K"]),
              ("lin_a_log", (L, H), "a_log"),
              ("lin_dt_bias", (L, H), "dt_bias"),
              ("lin_mlp_down", (L, f, h), f), ("lin_mlp_gate", (L, h, f), h),
              ("lin_mlp_up", (L, h, f), h)]
    return first, second


def init_weights(as_run: dict, seed: int, shard=None) -> dict:
    """Weights from the seed, bfloat16, made on the device(s) in one jitted
    call. `shard(shape)` gives a sharding for a leaf (four chips)."""
    dm = dims(as_run)
    first, second = weight_recipe(dm)

    def draw(key, shape, how):
        if how == "a_log":
            return jnp.log(jax.random.uniform(key, shape, minval=1.0,
                                              maxval=16.0))
        if how == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                key, shape, minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        return jax.random.normal(key, shape) * how ** -0.5

    def make(key):
        out = {}
        for leaves, k in ((first, key), (second, jax.random.fold_in(key, 1))):
            for sub, (name, shape, how) in zip(jax.random.split(k, 16),
                                               leaves):
                out[name] = draw(sub, shape, how).astype(jnp.bfloat16)
        return out

    out_sh = None if shard is None else {
        name: shard(shape) for name, shape, _ in first + second}
    with jax.threefry_partitionable(True):   # values independent of layout
        w = jax.jit(make, out_shardings=out_sh)(jax.random.key(seed))
    h, q = dm["h"], dm["nq"] * dm["d"]
    ones = lambda *shape: jnp.ones(shape, jnp.float32)  # noqa: E731
    w.update({"q_norm": ones(dm["n_full"], q), "k_norm": ones(dm["n_full"], q),
              "ln1": ones(dm["n_full"], h), "ln2": ones(dm["n_full"], h),
              "lin_o_norm": ones(dm["n_lin"], dm["dv"]),
              "lin_ln1": ones(dm["n_lin"], h), "lin_ln2": ones(dm["n_lin"], h),
              "final_norm": ones(h)})
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


def l2_normalize(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + L2_EPS)


def short_conv(x, w):
    """x [s, c], w [K, c]: y_t = sum_j w[j] x[t - (K-1) + j], zeros before
    the sequence; then SiLU."""
    k, s = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return silu(sum(padded[j:j + s] * w[j] for j in range(k)))


def delta_rule(q, k, v, alpha, beta):
    """The recurrence, token by token. q, k [s, H, d_k], v [s, H, d_v],
    alpha, beta [s, H]. Returns o [s, H, d_v]."""
    heads, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(state, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        # S <- alpha (I - beta k k^T) S + beta k v^T
        state = a_t[:, None, None] * state
        erased = jnp.einsum("hk,hkv->hv", k_t, state, precision="highest")
        state = state + k_t[:, :, None] * (
            b_t[:, None] * (v_t - erased))[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", q_t, state,
                                 precision="highest")

    _, o = jax.lax.scan(step, jnp.zeros((heads, dk, dv), jnp.float32),
                        (q, k, v, alpha, beta))
    return o


def linear_mixer(dm, x, lw, mm):
    s = x.shape[0]
    heads, dk, dv = dm["H"], dm["dk"], dm["dv"]
    kd = heads * dk
    qkv = jnp.concatenate([mm(x, lw["lin_wq"]), mm(x, lw["lin_wk"]),
                           mm(x, lw["lin_wv"])], axis=-1)
    qkv = short_conv(qkv, lw["lin_conv"])
    q = l2_normalize(qkv[:, :kd].reshape(s, heads, dk)) * dk ** -0.5
    k = l2_normalize(qkv[:, kd:2 * kd].reshape(s, heads, dk))
    v = qkv[:, 2 * kd:].reshape(s, heads, dv)
    beta = jax.nn.sigmoid(mm(x, lw["lin_wb"])) * (2.0 if dm["neg"] else 1.0)
    g = -jnp.exp(lw["lin_a_log"]) * jax.nn.softplus(
        mm(x, lw["lin_wa"]) + lw["lin_dt_bias"])
    o = delta_rule(q, k, v, jnp.exp(g), beta)
    o = rms_norm(o, lw["lin_o_norm"], dm["eps"])
    o = o * silu(mm(x, lw["lin_wg"])).reshape(s, heads, dv)
    return mm(o.reshape(s, heads * dv), lw["lin_wo"])


def full_mixer(dm, x, lw, mm):
    s, nq, d = x.shape[0], dm["nq"], dm["d"]
    q = rms_norm(mm(x, lw["wq"]), lw["q_norm"], dm["eps"]).reshape(s, nq, d)
    k = rms_norm(mm(x, lw["wk"]), lw["k_norm"], dm["eps"]).reshape(s, nq, d)
    v = mm(x, lw["wv"]).reshape(s, nq, d)
    idx = jnp.arange(s)

    def attend(q_rows, first_row):
        scores = jnp.einsum("qhd,khd->hqk", q_rows, k,
                            precision="highest") * d ** -0.5
        rows = first_row + jnp.arange(q_rows.shape[0])
        scores = jnp.where((idx[None, :] <= rows[:, None])[None], scores,
                           -1e30)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1),
                          v, precision="highest")

    # Blocks of query rows, so that the [heads, rows, keys] scores fit
    # beside the weights at 4096 keys; the mathematics is unchanged.
    ctx = jnp.concatenate([attend(q[i:i + Q_BLOCK], i)
                           for i in range(0, s, Q_BLOCK)])
    return mm(ctx.reshape(s, nq * d), lw["wo"])


def block(dm, x, lw, kind, mm):
    pre = "lin_" if kind == "linear_attention" else ""
    mixer = linear_mixer if kind == "linear_attention" else full_mixer
    h = x + rms_norm(mixer(dm, x, lw, mm), lw[pre + "ln1"], dm["eps"])
    mlp = mm(silu(mm(h, lw[pre + "mlp_gate"])) * mm(h, lw[pre + "mlp_up"]),
             lw[pre + "mlp_down"])
    return h + rms_norm(mlp, lw[pre + "ln2"], dm["eps"])


def hidden_states(dm, w, tokens, mm):
    """Final-norm activations [s, h] of one sequence. Layer weights are
    read in float32 one period at a time (they are stored in bfloat16)."""
    x = w["embed"][tokens].astype(jnp.float32)
    period = dm["period"]
    per = {kind: period.count(kind) for kind in KINDS}

    def stacked(kind):
        lin = kind == "linear_attention"
        names = [n for n in w if n.startswith("lin_") == lin
                 and n not in ("embed", "head", "final_norm")]
        n = per[kind]
        return {name: w[name].reshape((dm["periods"], n) + w[name].shape[1:])
                for name in names} if n else {}

    def body(x, scanned):
        seen = {kind: 0 for kind in KINDS}
        for kind in period:
            i = seen[kind]
            seen[kind] += 1
            lw = {n: a[i].astype(jnp.float32)
                  for n, a in scanned[kind].items()}
            x = block(dm, x, lw, kind, mm)
        return x, None

    x, _ = jax.lax.scan(body, x, {kind: stacked(kind) for kind in KINDS})
    return rms_norm(x, w["final_norm"], dm["eps"])


@functools.partial(jax.jit, static_argnames=("as_run_json", "low"))
def _logits_at(w, tokens, rows, as_run_json, low):
    dm = dims(json.loads(as_run_json))
    mm = matmul_int8 if low else matmul
    x = hidden_states(dm, w, tokens, mm)
    return mm(x[rows], w["head"].astype(jnp.float32))


def logits_at(as_run: dict, w: dict, tokens, rows, low: bool = False):
    """Logits [len(rows), vocab] of one causal sequence at `rows`."""
    with jax.default_matmul_precision("highest"):
        return _logits_at(w, jnp.asarray(tokens, jnp.int32),
                          jnp.asarray(rows, jnp.int32),
                          json.dumps(as_run, sort_keys=True), low)
