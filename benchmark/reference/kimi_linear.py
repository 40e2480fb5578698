"""Kimi-Linear, plain: the reference the benchmark compares the system with.

Straightforward float32 `jax.numpy` under
`jax.default_matmul_precision("highest")`: Kimi Delta Attention layers
(the delta rule with a decay a channel of the key) beside latent-attention
layers WITHOUT a rotary, in the published order (`linear_attn_config`'s two
lists); a leading layer with a dense FFN, then sparse layers whose held
experts run ONE AT A TIME over every token (a dense product an expert,
masked by the routing). The delta rule is the plain recurrence, one token
at a time (`lax.scan` over positions); latent attention is in its EXPANDED
form only, a block of query rows at a time so that 16k keys fit. No chunks, no
cache, no kernel, no batching, no absorbed product, no sorting.

Every norm is RMSNorm with a learned weight, eps `rms_norm_eps`. Per token
(u = the sub-layer's normed input), H heads:

  KDA mixer     q~, k~, v~ = u W_q, u W_k, u W_v; each through a causal
                depthwise convolution (kernel 4, own weights, no bias) and
                SiLU; as H heads of d: q = q/|q| d^-1/2, k = k/|k| (the
                length is sqrt(sum x^2 + 1e-6)); the decay a channel
                g = -exp(A_log_h) softplus((u W_f_down) W_f_up + dt_bias)
                in R^{H x d}, alpha = exp g; beta = sigmoid(u W_b) in R^H;
                S' = Diag(alpha_t) S_{t-1}
                S_t = S' - beta_t k_t (k_t^T S') + beta_t k_t v_t^T
                o_t = S_t^T q_t,      S_0 = 0, S in R^{d x d} a head;
                a = (RMSNorm_d(o) * sigmoid((u W_g_down) W_g_up)) W_o, the
                norm a head with one [d] weight
  latent mixer  q = u W_q -> H x (d_n + d_r), no norm, NO rotary; [c, k_r]
                = u W_kva -> r + d_r; c <- RMSNorm_r(c); [k_n, v] = c W_kvb
                -> H x (d_n + d_v); scores = (q_n . k_n + q_r . k_r)
                (d_n + d_r)^-1/2 with the ONE k_r shared by the heads;
                causal softmax; a = concat_heads(P v) W_o
  dense FFN     W_down (SiLU(W_gate u) * W_up u), width intermediate_size
                (the first `first_k_dense_replace` layers)
  sparse FFN    s = sigmoid(u W_r) (float32), S = top-k(s + b) with the bias
                b used ONLY to choose, g_e = s_e / sum_{e' in S} s_e';
                y = SwiGLU_shared(u) + routed_scaling_factor
                    * sum_{e in S, e held} g_e SwiGLU_e(u)
                The router scores over all `num_experts_routed`; the
                experts HELD are [first_expert_held, first_expert_held +
                num_experts): what the others would add is left out, here
                as in the program.
  block         h = x + Mixer(RMSNorm(x)); out = h + FFN(RMSNorm(h))
  model         embedding, blocks, final RMSNorm, head (not tied); logits
                over the vocabulary slice held

It imports nothing of the program and takes nothing the program made. The
weights are part of the seeded input: `init_weights` draws them from the
seed with the recipe the configuration states (matrices N(0, 1/fan_in),
conv N(0, 1/4), A_log = log U(1, 16) a head, dt_bias the inverse softplus
of exp U(log 1e-3, log 1e-1) a channel, the selection bias N(0, 0.01^2),
norm weights 1; drawn in float32, stored in bfloat16; one threefry key a
leaf in a fixed order: the latent layers' from split(key, 16), the KDA
layers of the periods from split(fold_in(key, 1), 32), the leading layer's
from split(fold_in(key, 2), 16)), which is the recipe the program's random
init follows. The layers after the leading ones must be whole periods of
one pattern (the program scans a period): layer l's weights are row l //
(KDA layers a period) of the KDA stacks, or its period's row of the latent
stacks.

Departures from the published model, noted: the config.json gives sizes,
the two layer lists and switches, not equations; the equations are Kimi
Linear's (arXiv:2510.26692) and the open KimiDeltaAttention layer's; what
the row does not say is the configuration's `assumed` (the low-rank width
of the two gates, no bias on the gate's second map, no QK norm in the
latent layers, the unscaled shared expert, the seeded recipes). Weights
are seeded random. Depth, experts held and vocabulary rows are the
configuration's cut.

`low=True` is the control: the same mathematics with every product with a
weight matrix (the float32 router apart) computed in int8 (per-row
activation scales, per-column weight scales), the nearest precision below
the bfloat16 the configuration states.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

Q_BLOCK = 512
L2_EPS = 1e-6
KDA_LEAVES = ("wq", "wk", "wv", "wo", "wf_down", "wf_up", "wg_down", "wg_up",
              "wb", "conv", "a_log", "dt_bias")
MLA_LEAVES = ("wq", "w_kva", "w_kvb", "wo")


def dims(as_run: dict) -> dict:
    lin = as_run["linear_attn_config"]
    layers, lead = as_run["num_hidden_layers"], as_run["first_k_dense_replace"]
    kda, full = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    kinds = tuple("kda" if l in kda else "full"
                  for l in range(1, layers + 1))      # 1-based, as published
    assert kda | full == set(range(1, layers + 1)) and not kda & full
    assert 0 < lead < layers and set(kinds[:lead]) == {"kda"}
    period = tuple(as_run["layer_period"])
    assert (layers - lead) % len(period) == 0 and period.count("full") == 1
    periods = (layers - lead) // len(period)
    assert kinds[lead:] == period * periods, \
        "the layers behind the leading ones are whole periods of one pattern"
    assert as_run["mla_use_nope"] and as_run["q_lora_rank"] is None
    assert as_run["num_expert_group"] == 1 and as_run["moe_renormalize"]
    assert as_run["moe_router_activation_func"] == "sigmoid"
    return {"h": as_run["hidden_size"], "f": as_run["intermediate_size"],
            "v": as_run["vocab_size"], "eps": as_run["rms_norm_eps"],
            "H": as_run["num_attention_heads"],
            "dn": as_run["qk_nope_head_dim"],
            "dr": as_run["qk_rope_head_dim"], "dv": as_run["v_head_dim"],
            "r": as_run["kv_lora_rank"],
            "lH": lin["num_heads"], "ld": lin["head_dim"],
            "K": lin["short_conv_kernel_size"],
            "rank": as_run["gate_low_rank"],
            "lead": lead, "period": period, "periods": periods,
            "n_kda": periods * period.count("kda"),
            "E": as_run["num_experts_routed"],
            "held": as_run["num_experts"],
            "first": as_run["first_expert_held"],
            "k": as_run["num_experts_per_token"],
            "fe": as_run["moe_intermediate_size"],
            "shared": as_run["num_shared_experts"],
            "bias_std": as_run["router_bias_std"],
            "scale": as_run["routed_scaling_factor"]}


# --------------------------------------------------------------------------
# Seeded inputs: weights
# --------------------------------------------------------------------------

def weight_recipe(dm: dict) -> tuple:
    """Three ordered lists of (name, shape, how): the leaves whose keys
    come from split(key, 16), from split(fold_in(key, 1), 32) and from
    split(fold_in(key, 2), 16), in the order the keys are dealt. `how` is
    a fan-in (normal / sqrt(fan-in)) or the name of a special draw."""
    h, f, v, H, r = dm["h"], dm["f"], dm["v"], dm["H"], dm["r"]
    dq, dkv = dm["dn"] + dm["dr"], dm["dn"] + dm["dv"]
    kd, rank, lH = dm["lH"] * dm["ld"], dm["rank"], dm["lH"]
    E, held, fe = dm["E"], dm["held"], dm["fe"]
    fs = fe * dm["shared"]

    def kda(pre, L):
        return [(pre + "wq", (L, h, kd), h), (pre + "wk", (L, h, kd), h),
                (pre + "wv", (L, h, kd), h), (pre + "wo", (L, kd, h), kd),
                (pre + "wf_down", (L, h, rank), h),
                (pre + "wf_up", (L, rank, kd), rank),
                (pre + "wg_down", (L, h, rank), h),
                (pre + "wg_up", (L, rank, kd), rank),
                (pre + "wb", (L, h, lH), h),
                (pre + "conv", (L, dm["K"], 3 * kd), dm["K"]),
                (pre + "a_log", (L, lH), "a_log"),
                (pre + "dt_bias", (L, kd), "dt_bias")]

    def sparse(pre, L):
        return [(pre + "router", (L, h, E), h),
                (pre + "exp_gate", (L, held, h, fe), h),
                (pre + "exp_up", (L, held, h, fe), h),
                (pre + "exp_down", (L, held, fe, h), fe),
                (pre + "shared_down", (L, fs, h), fs),
                (pre + "shared_gate", (L, h, fs), h),
                (pre + "shared_up", (L, h, fs), h),
                (pre + "router_bias", (L, E), "bias")]

    P, L, n = dm["periods"], dm["n_kda"], dm["lead"]
    first = [("embed", (v, h), h), ("head", (h, v), h),
             ("mla_wq", (P, h, H * dq), h),
             ("mla_w_kva", (P, h, r + dm["dr"]), h),
             ("mla_w_kvb", (P, r, H * dkv), r),
             ("mla_wo", (P, H * dm["dv"], h), H * dm["dv"])] \
        + sparse("mla_", P)
    second = kda("kda_", L) + sparse("kda_", L)
    third = kda("lead_", n) + [
        ("lead_mlp_down", (n, f, h), f), ("lead_mlp_gate", (n, h, f), h),
        ("lead_mlp_up", (n, h, f), h)]
    return first, second, third


def init_weights(as_run: dict, seed: int, shard=None) -> dict:
    """Weights from the seed, bfloat16, made on the device(s) a leaf a
    jitted call (a leaf's float32 draw is the largest temporary).
    `shard(shape)` gives a sharding for a leaf (four chips)."""
    dm = dims(as_run)
    first, second, third = weight_recipe(dm)
    key = jax.random.key(seed)

    def draw(sub, shape, how):
        if how == "a_log":
            return jnp.log(jax.random.uniform(sub, shape, minval=1.0,
                                              maxval=16.0))
        if how == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                sub, shape, minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        if how == "bias":
            return jax.random.normal(sub, shape) * dm["bias_std"]
        return jax.random.normal(sub, shape) * how ** -0.5

    w = {}
    with jax.threefry_partitionable(True):   # values independent of layout
        for leaves, k, n_keys in (
                (first, key, 16), (second, jax.random.fold_in(key, 1), 32),
                (third, jax.random.fold_in(key, 2), 16)):
            for i, (name, shape, how) in enumerate(leaves):
                def make(k, i=i, shape=shape, how=how, n_keys=n_keys):
                    return draw(jax.random.split(k, n_keys)[i], shape,
                                how).astype(jnp.bfloat16)

                out_sh = None if shard is None else shard(shape)
                w[name] = jax.jit(make, out_shardings=out_sh)(k)
    h = dm["h"]
    ones = lambda *shape: jnp.ones(shape, jnp.float32)  # noqa: E731
    for pre, L in (("mla_", dm["periods"]), ("kda_", dm["n_kda"]),
                   ("lead_", dm["lead"])):
        w.update({pre + "ln1": ones(L, h), pre + "ln2": ones(L, h)})
        if pre == "mla_":
            w["mla_kv_norm"] = ones(L, dm["r"])
        else:
            w[pre + "o_norm"] = ones(L, dm["ld"])
    w["final_norm"] = ones(h)
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
    """The recurrence, token by token. q, k, alpha [s, H, d_k], v [s, H,
    d_v], beta [s, H]. Returns o [s, H, d_v]."""
    heads, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(state, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        state = a_t[:, :, None] * state              # S' = Diag(alpha) S
        erased = jnp.einsum("hk,hkv->hv", k_t, state, precision="highest")
        state = state + k_t[:, :, None] * (
            b_t[:, None] * (v_t - erased))[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", q_t, state,
                                 precision="highest")

    _, o = jax.lax.scan(step, jnp.zeros((heads, dk, dv), jnp.float32),
                        (q, k, v, alpha, beta))
    return o


def kda_mixer(dm, u, lw, mm):
    s, H, d = u.shape[0], dm["lH"], dm["ld"]
    kd = H * d
    qkv = short_conv(jnp.concatenate(
        [mm(u, lw["wq"]), mm(u, lw["wk"]), mm(u, lw["wv"])], axis=-1),
        lw["conv"])
    q = l2_normalize(qkv[:, :kd].reshape(s, H, d)) * d ** -0.5
    k = l2_normalize(qkv[:, kd:2 * kd].reshape(s, H, d))
    v = qkv[:, 2 * kd:].reshape(s, H, d)
    g = -jnp.exp(lw["a_log"])[:, None] * jax.nn.softplus(
        mm(mm(u, lw["wf_down"]), lw["wf_up"]) + lw["dt_bias"]
    ).reshape(s, H, d)
    beta = jax.nn.sigmoid(mm(u, lw["wb"]))
    o = delta_rule(q, k, v, jnp.exp(g), beta)
    gate = jax.nn.sigmoid(mm(mm(u, lw["wg_down"]), lw["wg_up"]))
    o = rms_norm(o, lw["o_norm"], dm["eps"]) * gate.reshape(s, H, d)
    return mm(o.reshape(s, kd), lw["wo"])


def latent_mixer(dm, u, lw, mm, rotated: bool = False):
    """`rotated` is for the tests alone: a rotary (theta 10000, halves) on
    q_r and k_r, what the model does NOT do (mla_use_nope)."""
    s, H, dn, dr, dv, r = (u.shape[0], dm["H"], dm["dn"], dm["dr"],
                           dm["dv"], dm["r"])
    pos = jnp.arange(s)
    q = mm(u, lw["wq"]).reshape(s, H, dn + dr)
    q_n, q_r = q[..., :dn], q[..., dn:]
    down = mm(u, lw["w_kva"])
    c = rms_norm(down[:, :r], lw["kv_norm"], dm["eps"])
    k_r = down[:, r:]
    if rotated:
        angle = pos.astype(jnp.float32)[:, None] * 10000.0 ** (
            -jnp.arange(dr // 2, dtype=jnp.float32) * 2 / dr)

        def rotate(x, a):
            lo, hi = x[..., :dr // 2], x[..., dr // 2:]
            return jnp.concatenate([lo * jnp.cos(a) - hi * jnp.sin(a),
                                    hi * jnp.cos(a) + lo * jnp.sin(a)], -1)

        q_r, k_r = rotate(q_r, angle[:, None, :]), rotate(k_r, angle)
    kv = mm(c, lw["w_kvb"]).reshape(s, H, dn + dv)
    k_n, v = kv[..., :dn], kv[..., dn:]
    scale = (dn + dr) ** -0.5

    def attend(first_row):
        rows = first_row + jnp.arange(Q_BLOCK)
        q_nb = jax.lax.dynamic_slice_in_dim(q_n, first_row, Q_BLOCK)
        q_rb = jax.lax.dynamic_slice_in_dim(q_r, first_row, Q_BLOCK)
        scores = (jnp.einsum("qhd,khd->hqk", q_nb, k_n, precision="highest")
                  + jnp.einsum("qhd,kd->hqk", q_rb, k_r,
                               precision="highest")) * scale
        scores = jnp.where((pos[None, :] <= rows[:, None])[None], scores,
                           -1e30)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1),
                          v, precision="highest")

    # Blocks of query rows, one at a time, so that the [heads, rows, keys]
    # scores fit beside the weights at 16k keys; the mathematics is
    # unchanged. (Queries are padded to whole blocks with rows nobody
    # reads.)
    blocks = -(-s // Q_BLOCK)
    pad = ((0, blocks * Q_BLOCK - s), (0, 0), (0, 0))
    q_n, q_r = jnp.pad(q_n, pad), jnp.pad(q_r, pad)
    ctx = jax.lax.map(attend, jnp.arange(blocks) * Q_BLOCK)
    ctx = ctx.reshape(blocks * Q_BLOCK, H, dv)[:s]
    return mm(ctx.reshape(s, H * dv), lw["wo"])


def choose(dm, u, lw):
    """(chosen [s, k] int, gate weights [s, k]) of the float32 router."""
    score = jax.nn.sigmoid(matmul(u, lw["router"]))
    _, chosen = jax.lax.top_k(score + lw["router_bias"], dm["k"])
    g = jnp.take_along_axis(score, chosen, axis=-1)
    return chosen, g / jnp.sum(g, axis=-1, keepdims=True)


def sparse_ffn(dm, u, lw, mm, first=None, held=None, shared=True):
    """The held experts' part of the layer, one expert at a time. `first`,
    `held` and `shared` are for the share test: the experts [first, first +
    held) of a stack that holds them at rows 0 ..."""
    chosen, g = choose(dm, u, lw)
    first = dm["first"] if first is None else first
    held = dm["held"] if held is None else held

    def one_expert(acc, scanned):
        e, gate, up, down = scanned
        w_e = jnp.sum(jnp.where(chosen == e, g, 0.0), axis=-1)   # [s]
        y_e = swiglu(u, gate.astype(jnp.float32), up.astype(jnp.float32),
                     down.astype(jnp.float32), mm)
        return acc + w_e[:, None] * y_e, None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u),
        (first + jnp.arange(held), lw["exp_gate"], lw["exp_up"],
         lw["exp_down"]))
    y = dm["scale"] * routed
    if shared and dm["shared"]:
        y = y + swiglu(u, lw["shared_gate"].astype(jnp.float32),
                       lw["shared_up"].astype(jnp.float32),
                       lw["shared_down"].astype(jnp.float32), mm)
    return y


def _f32(lw, names):
    return {n: lw[n].astype(jnp.float32) for n in names}


def hidden_states(dm, w, tokens, mm):
    """Final-norm activations [s, h] of one sequence. Layer weights are
    read in float32 one layer (one expert) at a time: they are stored in
    bfloat16."""
    x = w["embed"][tokens].astype(jnp.float32)
    kda_small = KDA_LEAVES + ("o_norm", "ln1", "ln2")
    mla_small = MLA_LEAVES + ("kv_norm", "ln1", "ln2")
    ffn_names = ("router", "router_bias", "exp_gate", "exp_up", "exp_down",
                 "shared_gate", "shared_up", "shared_down")

    def mixed(x, lw, mixer):
        h = x + mixer(dm, rms_norm(x, lw["ln1"], dm["eps"]), lw, mm)
        return h, rms_norm(h, lw["ln2"], dm["eps"])

    for i in range(dm["lead"]):
        lw = _f32({n: w["lead_" + n][i] for n in kda_small}, kda_small)
        h, u = mixed(x, lw, kda_mixer)
        x = h + swiglu(u, *(w["lead_mlp_" + n][i].astype(jnp.float32)
                            for n in ("gate", "up", "down")), mm)

    def sparse_layer(x, lw, small, mixer):
        h, u = mixed(x, _f32(lw, small), mixer)
        ffn = {n: lw[n] for n in ffn_names}
        ffn.update(_f32(ffn, ("router", "router_bias")))
        return h + sparse_ffn(dm, u, ffn, mm)

    per = dm["period"].count("kda")

    def body(x, scanned):
        kda, mla = scanned
        j = 0
        for kind in dm["period"]:
            if kind == "kda":
                x = sparse_layer(x, {n: a[j] for n, a in kda.items()},
                                 kda_small, kda_mixer)
                j += 1
            else:
                x = sparse_layer(x, mla, mla_small, latent_mixer)
        return x, None

    def stacks(pre, group=None):
        out = {n[len(pre):]: a for n, a in w.items() if n.startswith(pre)}
        if group:       # [layers, ...] -> [periods, layers a period, ...]
            out = {n: a.reshape((dm["periods"], group) + a.shape[1:])
                   for n, a in out.items()}
        return out

    x, _ = jax.lax.scan(body, x, (stacks("kda_", per), stacks("mla_")))
    return rms_norm(x, w["final_norm"], dm["eps"])


@functools.partial(jax.jit, static_argnames=("as_run_json", "low"))
def _hidden(w, tokens, as_run_json, low):
    dm = dims(json.loads(as_run_json))
    return hidden_states(dm, w, tokens, matmul_int8 if low else matmul)


@functools.partial(jax.jit, static_argnames=("low",))
def _head(x, head, rows, low):
    return (matmul_int8 if low else matmul)(x[rows],
                                            head.astype(jnp.float32))


def logits_at(as_run: dict, w: dict, tokens, rows, low: bool = False):
    """Logits [len(rows), vocab] of one causal sequence at `rows`. Two
    compiled programs, so that a check compiles the model once a length
    and not once a (length, rows) pair: the final-norm activations by the
    sequence's length (beyond 4096 tokens padded with zeros to the next
    power of two: padding lies behind every row and no row sees it), the
    head by the rows'."""
    tokens = jnp.asarray(tokens, jnp.int32)
    n = tokens.shape[0]
    if n > 4096:
        tokens = jnp.pad(tokens, (0, (1 << (n - 1).bit_length()) - n))
    with jax.default_matmul_precision("highest"):
        x = _hidden(w, tokens, json.dumps(as_run, sort_keys=True), low)
        return _head(x, w["head"], jnp.asarray(rows, jnp.int32), low)
