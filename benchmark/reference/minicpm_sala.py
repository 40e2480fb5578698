"""MiniCPM-SALA, plain: the reference the benchmark compares the system with.

Straightforward float32 `jax.numpy` under
`jax.default_matmul_precision("highest")`: a period of one `minicpm4` layer
(grouped-query attention whose queries, on a long row, read a window, the
initial block and the blocks they choose by scores against compressed
keys: InfLLM-v2) and three `lightning-attn` layers (decay-only linear
attention), repeated; pre-norm blocks with MiniCPM's depth-scaled
residuals; an untied output head. The recurrence is the plain one, a token
at a time (`lax.scan` over positions); the choice of blocks is made a token
at a time, in blocks of `Q_BLOCK` queries so that the scores fit: no
chunks, no cache, no batching, no kernel.

Every norm is RMSNorm with a learned weight, eps `rms_norm_eps`.
alpha = scale_depth / sqrt(PUBLISHED layers).

  model          x_0 = scale_emb E[token]; blocks; logits = W_head
                 (RMSNorm(x) / (hidden / dim_model_base))
  block          u = RMSNorm(x); h = x + alpha Mixer(u); u' = RMSNorm(h);
                 out = h + alpha W_2 (silu(W_1 u') * W_3 u')
  lightning-attn q, k, v = u W_q, u W_k, u W_v as H heads of d; q, k each
                 through an RMSNorm over the head's d, then a rotary over
                 the whole head (theta, halves) at the token's position;
                 S_t = lambda_h S_{t-1} + k_t v_t^T, o_t = S_t^T q_t /
                 sqrt(d), S_0 = 0; lambda_h = exp(-s_h c_l), s_h =
                 2^(-8 (h + 1) / H), c_l = 1 - l / (published layers - 1) +
                 1e-5 with l the layer's index as run;
                 a = (RMSNorm_{H d}(concat_h o_h) * sigmoid(u W_g)) W_o
  minicpm4       q = u W_q as n_q heads of d; k, v as n_kv heads; an
                 RMSNorm a head on q and k; NO rotary; query head h reads
                 KV head h // (n_q / n_kv); scale 1 / sqrt(d); float32
                 softmax. A token of a row shorter than dense_len attends
                 causally to every key; else, for the query at position t
                 and each KV head g:
                 1. c_j = mean(k[stride j .. stride j + kernel - 1]) for
                    every j with stride j + kernel <= t + 1;
                 2. p_h = softmax_j(q_h . c_j / sqrt(d)) over those j; P =
                    the sum of p_h over the group's heads;
                 3. block b = keys [block b, block b + block) scores B_b =
                    max P_j over the kernels that overlap it (those not yet
                    whole count 0);
                 4. the window is the keys t - window + 1 .. t; blocks
                    wholly inside it are no candidates; the initial blocks
                    are always chosen; of the other candidates b < t //
                    block the topk - init largest B_b are chosen (ties to
                    the lower index);
                 5. every head of the group attends, causally, to the
                    chosen blocks and the window, one softmax over both.
                 a = (concat_h o_h * sigmoid(u W_g)) W_o.
                 "The row's length" of a token: the prompt's for a token of
                 the prompt (it is prefilled whole), t + 1 for a token
                 generated behind it (decoded one at a time). `logits_at`
                 takes the prompt's length from its rows (checker.py).

It imports nothing of the program and takes nothing the program made. The
weights are part of the seeded input: `init_weights` draws them from the
seed with the recipe the configuration states (matrices N(0, 1/fan_in),
norm weights 1; drawn in float32, stored in bfloat16; one threefry key per
leaf in a fixed order, the lightning layers' keys from fold_in(key, 1)),
which is the recipe the program's random init follows.

Departures from the published model, noted: the published config.json
gives sizes, not equations; everything the configuration lists under
`assumed` is the family's published convention, not this row's key. Where
two readings are plausible `as_run["readings"]` picks one: `gate`
("elementwise" | "head"), `decay_layer_factor` (true | false),
`window_blocks_are_candidates` (false | true). Weights are seeded random.
Depth and the layer order are the configuration's.

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

Q_BLOCK = 128
KINDS = ("minicpm4", "lightning-attn")
READINGS = {"gate": "elementwise", "decay_layer_factor": True,
            "window_blocks_are_candidates": False}


def dims(as_run: dict) -> dict:
    period = tuple(as_run["layer_period"])
    layers = as_run["num_hidden_layers"]
    assert layers % len(period) == 0 and set(period) <= set(KINDS)
    assert tuple(as_run["mixer_types"]) == period * (layers // len(period))
    assert as_run["lightning_nh"] == as_run["lightning_nkv"]
    sp = as_run["sparse_config"]
    assert sp["block_size"] % sp["kernel_stride"] == 0 \
        and sp["kernel_size"] % sp["kernel_stride"] == 0
    periods = layers // len(period)
    return {"h": as_run["hidden_size"], "f": as_run["intermediate_size"],
            "v": as_run["vocab_size"], "eps": as_run["rms_norm_eps"],
            "nq": as_run["num_attention_heads"],
            "nkv": as_run["num_key_value_heads"], "d": as_run["head_dim"],
            "H": as_run["lightning_nh"], "dl": as_run["lightning_head_dim"],
            "theta": float(as_run["rope_theta"]),
            "scale_emb": float(as_run["scale_emb"]),
            "alpha": as_run["scale_depth"]
            / as_run["published_num_hidden_layers"] ** 0.5,
            "decay_layers": as_run["published_num_hidden_layers"] - 1,
            "logit_div": as_run["hidden_size"] / as_run["dim_model_base"],
            "sp": {k: int(v) for k, v in sp.items()},
            "readings": {**READINGS, **as_run.get("readings", {})},
            "period": period, "periods": periods,
            "n_full": periods * period.count("minicpm4"),
            "n_lin": periods * period.count("lightning-attn")}


# --------------------------------------------------------------------------
# Seeded inputs: weights
# --------------------------------------------------------------------------

def weight_recipe(dm: dict) -> tuple:
    """Two ordered lists of (name, shape, fan-in): the leaves whose keys
    come from split(key, 16) and those from split(fold_in(key, 1), 16), in
    the order the keys are dealt; each is normal / sqrt(fan-in)."""
    h, f, v = dm["h"], dm["f"], dm["v"]
    q, kv = dm["nq"] * dm["d"], dm["nkv"] * dm["d"]
    gate = q if dm["readings"]["gate"] == "elementwise" else dm["nq"]
    F, L, ld = dm["n_full"], dm["n_lin"], dm["H"] * dm["dl"]
    first = [("embed", (v, h), h), ("head", (h, v), h),
             ("wq", (F, h, q), h), ("wk", (F, h, kv), h),
             ("wv", (F, h, kv), h), ("wo", (F, q, h), q),
             ("wg", (F, h, gate), h),
             ("mlp_down", (F, f, h), f), ("mlp_gate", (F, h, f), h),
             ("mlp_up", (F, h, f), h)]
    second = [("lin_wq", (L, h, ld), h), ("lin_wk", (L, h, ld), h),
              ("lin_wv", (L, h, ld), h), ("lin_wg", (L, h, ld), h),
              ("lin_wo", (L, ld, h), ld),
              ("lin_mlp_down", (L, f, h), f), ("lin_mlp_gate", (L, h, f), h),
              ("lin_mlp_up", (L, h, f), h)]
    return first, second


def init_weights(as_run: dict, seed: int, shard=None) -> dict:
    """Weights from the seed, bfloat16, made on the device(s) a leaf a
    jitted call (a leaf's float32 draw is the largest temporary).
    `shard(shape)` gives a sharding for a leaf (four chips)."""
    dm = dims(as_run)
    first, second = weight_recipe(dm)
    key = jax.random.key(seed)
    w = {}
    with jax.threefry_partitionable(True):   # values independent of layout
        for leaves, k in ((first, key), (second, jax.random.fold_in(key, 1))):
            for i, (name, shape, fan_in) in enumerate(leaves):
                def make(k, i=i, shape=shape, fan_in=fan_in):
                    sub = jax.random.split(k, 16)[i]
                    return (jax.random.normal(sub, shape)
                            * fan_in ** -0.5).astype(jnp.bfloat16)

                out_sh = None if shard is None else shard(shape)
                w[name] = jax.jit(make, out_shardings=out_sh)(k)
    h, F, L = dm["h"], dm["n_full"], dm["n_lin"]
    ones = lambda *shape: jnp.ones(shape, jnp.float32)  # noqa: E731
    w.update({"q_norm": ones(F, dm["d"]), "k_norm": ones(F, dm["d"]),
              "ln1": ones(F, h), "ln2": ones(F, h),
              "lin_q_norm": ones(L, dm["dl"]), "lin_k_norm": ones(L, dm["dl"]),
              "lin_o_norm": ones(L, dm["H"] * dm["dl"]),
              "lin_ln1": ones(L, h), "lin_ln2": ones(L, h),
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


def rotary(x, theta):
    """x [s, heads, d] at positions 0 .. s-1: the whole head rotates, pairs
    (x_i, x_{i + d/2}), frequencies theta^(-i / (d/2))."""
    s, half = x.shape[0], x.shape[-1] // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq
    sin, cos = jnp.sin(angle)[:, None, :], jnp.cos(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def lightning_recurrence(q, k, v, lam):
    """The recurrence, token by token. q, k, v [s, H, d], lam [H].
    Returns o [s, H, d] (before the 1 / sqrt(d))."""
    heads, d = q.shape[1], q.shape[2]

    def step(state, xs):
        q_t, k_t, v_t = xs
        state = lam[:, None, None] * state \
            + k_t[:, :, None] * v_t[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", q_t, state,
                                 precision="highest")

    _, o = jax.lax.scan(step, jnp.zeros((heads, d, d), jnp.float32),
                        (q, k, v))
    return o


def lightning_mixer(dm, u, lw, layer, mm):
    s, H, d = u.shape[0], dm["H"], dm["dl"]
    q = rms_norm(mm(u, lw["lin_wq"]).reshape(s, H, d), lw["lin_q_norm"],
                 dm["eps"])
    k = rms_norm(mm(u, lw["lin_wk"]).reshape(s, H, d), lw["lin_k_norm"],
                 dm["eps"])
    v = mm(u, lw["lin_wv"]).reshape(s, H, d)
    q, k = rotary(q, dm["theta"]), rotary(k, dm["theta"])
    slope = 2.0 ** (-8.0 * jnp.arange(1, H + 1, dtype=jnp.float32) / H)
    c = (1.0 - layer.astype(jnp.float32) / dm["decay_layers"] + 1e-5
         if dm["readings"]["decay_layer_factor"] else 1.0)
    o = lightning_recurrence(q, k, v, jnp.exp(-slope * c)) / d ** 0.5
    o = rms_norm(o.reshape(s, H * d), lw["lin_o_norm"], dm["eps"])
    return mm(o * jax.nn.sigmoid(mm(u, lw["lin_wg"])), lw["lin_wo"])


def chosen_blocks(dm, q_rows, rows, ck, nb):
    """Steps 2 to 4 for the queries q_rows [n, n_q, d] at positions rows
    [n], against the compressed keys ck [n_c, n_kv, d]. Returns [n, n_kv,
    nb] bool: the blocks a token reads beside its window."""
    sp, d, g = dm["sp"], dm["d"], dm["nkv"]
    n, n_c = q_rows.shape[0], ck.shape[0]
    block, stride, kernel = (sp["block_size"], sp["kernel_stride"],
                             sp["kernel_size"])
    qg = q_rows.reshape(n, g, dm["nq"] // g, d)
    scores = jnp.einsum("ngrd,jgd->ngrj", qg, ck,
                        precision="highest") * d ** -0.5
    whole = (jnp.arange(n_c) * stride + kernel
             <= rows[:, None] + 1)[:, None, None, :]
    p = jax.nn.softmax(jnp.where(whole, scores, -1e30), axis=-1)
    group_p = jnp.where(whole, p, 0.0).sum(axis=2)          # [n, g, n_c]
    # Block b and kernel j overlap iff stride j + kernel - 1 >= block b
    # and stride j <= block b + block - 1.
    b_idx, j_idx = jnp.arange(nb)[:, None], jnp.arange(n_c)[None, :]
    overlap = (stride * j_idx + kernel - 1 >= block * b_idx) \
        & (stride * j_idx <= block * b_idx + block - 1)     # [nb, n_c]
    score = jnp.max(jnp.where(overlap, group_p[:, :, None, :], 0.0),
                    axis=-1)                                # [n, g, nb]
    t = rows[:, None]
    blocks = jnp.arange(nb)[None, :]
    candidate = (blocks >= sp["init_blocks"]) & (blocks < t // block)
    if not dm["readings"]["window_blocks_are_candidates"]:
        # Wholly inside the window: its first key is.
        candidate &= blocks * block < t - sp["window_size"] + 1
    candidate = jnp.broadcast_to(candidate[:, None, :], score.shape)
    # The largest first, ties to the lower index: a stable sort.
    order = jnp.argsort(jnp.where(candidate, -score, jnp.inf), axis=-1,
                        stable=True)
    take = order[..., :sp["topk"] - sp["init_blocks"]]
    taken = jnp.take_along_axis(candidate, take, axis=-1)
    chosen = jnp.zeros(score.shape, bool)
    chosen = jax.vmap(jax.vmap(lambda c, i, ok: c.at[i].set(ok)))(
        chosen, take, taken)
    return chosen | (blocks < sp["init_blocks"])[:, None, :]


def sparse_mixer(dm, u, lw, prompt_len, mm):
    s, nq, nkv, d = u.shape[0], dm["nq"], dm["nkv"], dm["d"]
    sp = dm["sp"]
    block, stride, kernel = (sp["block_size"], sp["kernel_stride"],
                             sp["kernel_size"])
    q = rms_norm(mm(u, lw["wq"]).reshape(s, nq, d), lw["q_norm"], dm["eps"])
    k = rms_norm(mm(u, lw["wk"]).reshape(s, nkv, d), lw["k_norm"],
                 dm["eps"])
    v = mm(u, lw["wv"]).reshape(s, nkv, d)
    n_c = max((s - kernel) // stride + 1, 0)
    # c_j: the mean of the keys stride j .. stride j + kernel - 1.
    ck = k[stride * jnp.arange(n_c)[:, None]
           + jnp.arange(kernel)[None, :]].mean(axis=1)     # [n_c, nkv, d]
    nb = -(-s // block)
    idx = jnp.arange(s)
    pad = -s % Q_BLOCK
    q_p = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))

    def attend(first_row):
        q_rows = jax.lax.dynamic_slice_in_dim(q_p, first_row, Q_BLOCK, 0)
        rows = first_row + jnp.arange(Q_BLOCK)
        # The row's length, as the token that computed this query saw it.
        length = jnp.where(rows < prompt_len, prompt_len, rows + 1)
        sparse = length >= sp["dense_len"]
        chosen = chosen_blocks(dm, q_rows, rows, ck, nb)   # [n, nkv, nb]
        near = idx[None, :] > rows[:, None] - sp["window_size"]
        of_block = jnp.take(chosen, idx // block, axis=-1)  # [n, nkv, s]
        seen = (idx[None, :] <= rows[:, None])[:, None, :] & (
            near[:, None, :] | of_block | ~sparse[:, None, None])
        scores = jnp.einsum("ngrd,kgd->ngrk",
                            q_rows.reshape(Q_BLOCK, nkv, nq // nkv, d), k,
                            precision="highest") * d ** -0.5
        probs = jax.nn.softmax(jnp.where(seen[:, :, None, :], scores,
                                         -1e30), axis=-1)
        return jnp.einsum("ngrk,kgd->ngrd", probs, v,
                          precision="highest").reshape(Q_BLOCK, nq * d)

    ctx = jax.lax.map(attend, jnp.arange(0, s + pad, Q_BLOCK))
    ctx = ctx.reshape(s + pad, nq * d)[:s]
    gate = jax.nn.sigmoid(mm(u, lw["wg"]))
    if dm["readings"]["gate"] != "elementwise":
        gate = jnp.repeat(gate, d, axis=-1)
    return mm(ctx * gate, lw["wo"])


def block(dm, x, lw, kind, layer, prompt_len, mm):
    lin = kind == "lightning-attn"
    pre = "lin_" if lin else ""
    u = rms_norm(x, lw[pre + "ln1"], dm["eps"])
    mixed = (lightning_mixer(dm, u, lw, layer, mm) if lin
             else sparse_mixer(dm, u, lw, prompt_len, mm))
    h = x + dm["alpha"] * mixed
    u2 = rms_norm(h, lw[pre + "ln2"], dm["eps"])
    mlp = mm(silu(mm(u2, lw[pre + "mlp_gate"])) * mm(u2, lw[pre + "mlp_up"]),
             lw[pre + "mlp_down"])
    return h + dm["alpha"] * mlp


def hidden_states(dm, w, tokens, prompt_len, mm):
    """Final-norm activations [s, h] of one sequence whose first
    `prompt_len` tokens were a prompt. Layer weights are read in float32
    one period at a time (they are stored in bfloat16)."""
    x = dm["scale_emb"] * w["embed"][tokens].astype(jnp.float32)
    period = dm["period"]
    per = {kind: period.count(kind) for kind in KINDS}

    def stacked(kind):
        lin = kind == "lightning-attn"
        names = [n for n in w if n.startswith("lin_") == lin
                 and n not in ("embed", "head", "final_norm")]
        n = per[kind]
        return {name: w[name].reshape((dm["periods"], n) + w[name].shape[1:])
                for name in names} if n else {}

    def body(x, scanned):
        number, stacks = scanned
        seen = {kind: 0 for kind in KINDS}
        for at, kind in enumerate(period):
            i = seen[kind]
            seen[kind] += 1
            lw = {n: a[i].astype(jnp.float32)
                  for n, a in stacks[kind].items()}
            x = block(dm, x, lw, kind, number * len(period) + at,
                      prompt_len, mm)
        return x, None

    x, _ = jax.lax.scan(body, x, (jnp.arange(dm["periods"]),
                                  {kind: stacked(kind) for kind in KINDS}))
    return rms_norm(x, w["final_norm"], dm["eps"])


@functools.partial(jax.jit, static_argnames=("as_run_json", "low"))
def _logits_at(w, tokens, rows, as_run_json, low):
    dm = dims(json.loads(as_run_json))
    mm = matmul_int8 if low else matmul
    x = hidden_states(dm, w, tokens, rows[0] + 1, mm)
    return mm(x[rows] / dm["logit_div"], w["head"].astype(jnp.float32))


def logits_at(as_run: dict, w: dict, tokens, rows, low: bool = False):
    """Logits [len(rows), vocab] of one causal sequence at `rows`, whose
    first rows[0] + 1 tokens were the prompt (checker.serve_gaps)."""
    with jax.default_matmul_precision("highest"):
        return _logits_at(w, jnp.asarray(tokens, jnp.int32),
                          jnp.asarray(rows, jnp.int32),
                          json.dumps(as_run, sort_keys=True), low)
