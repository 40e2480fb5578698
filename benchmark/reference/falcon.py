"""Falcon, plain: the reference the benchmark compares the system with.

Straightforward float32 `jax.numpy` under
`jax.default_matmul_precision("highest")`: rotary embeddings (split-halves),
multi-query / grouped attention, the parallel attention+MLP block under one
norm (Falcon-7B) or two (Falcon-40B), a tied output head; for training the
masked next-token loss of a LoRA-adapted model, its adapter gradients, and
AdamW with global-norm clipping. No kernel, no cache, no batching.

It imports nothing of the program and takes nothing the program made. The
weights are part of the seeded input: `init_weights` draws them from the
seed with the recipe the cell's configuration states (N(0, 1/fan_in) per
matrix, one threefry key per matrix in a fixed order, drawn in float32 and
stored in bfloat16), which is the recipe the program's random init follows.

Departures from the published model, noted: GELU is the tanh approximation
(as the program computes it); the head is tied to the embedding for both
sizes; weights are seeded random.

`lower` is the control: the same mathematics with every matrix product
computed in int8 (per-row activation scales, per-column weight scales),
the nearest precision below the bfloat16 the configurations state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LORA_TARGETS = ("wq", "wk", "wv", "wo")   # attention projections, in order
Q_BLOCK = 512


def dims(as_run: dict) -> dict:
    h = as_run["hidden_size"]
    nq, nkv, d = (as_run["num_attention_heads"], as_run["num_kv_heads"],
                  as_run["head_dim"])
    return {"h": h, "nq": nq, "nkv": nkv, "d": d, "q": nq * d, "kv": nkv * d,
            "f": as_run["ffn_hidden_size"], "v": as_run["vocab_size"],
            "L": as_run["num_hidden_layers"],
            "two_norms": as_run["layer_norms_per_block"] == 2,
            "eps": as_run["layer_norm_epsilon"],
            "theta": as_run["rope_theta"]}


# --------------------------------------------------------------------------
# Seeded inputs: weights and adapters
# --------------------------------------------------------------------------

def weight_shapes(dm: dict) -> dict:
    """name -> (shape, fan_in), in the order the keys are dealt."""
    L, h = dm["L"], dm["h"]
    return {"embed": ((dm["v"], h), h),
            "wq": ((L, h, dm["q"]), h), "wk": ((L, h, dm["kv"]), h),
            "wv": ((L, h, dm["kv"]), h), "wo": ((L, dm["q"], h), dm["q"]),
            "mlp_out": ((L, dm["f"], h), dm["f"]),
            "mlp_in": ((L, h, dm["f"]), h)}


def init_weights(as_run: dict, seed: int, shard=None) -> dict:
    """Weights from the seed, bfloat16, made on the device(s) in one jitted
    call. `shard(shape)` gives a sharding for a matrix (four chips)."""
    dm = dims(as_run)
    shapes = weight_shapes(dm)

    def make(key):
        keys = jax.random.split(key, 16)
        out = {}
        for k, (name, (shape, fan_in)) in zip(keys, shapes.items()):
            out[name] = (jax.random.normal(k, shape) * fan_in ** -0.5
                         ).astype(jnp.bfloat16)
        return out

    out_sh = None if shard is None else {
        name: shard(shape) for name, (shape, _) in shapes.items()}
    with jax.threefry_partitionable(True):   # values independent of layout
        w = jax.jit(make, out_shardings=out_sh)(jax.random.key(seed))
    L, h = dm["L"], dm["h"]
    w["ln1_scale"] = jnp.ones((L, h), jnp.float32)
    w["ln1_bias"] = jnp.zeros((L, h), jnp.float32)
    if dm["two_norms"]:
        w["ln2_scale"] = jnp.ones((L, h), jnp.float32)
        w["ln2_bias"] = jnp.zeros((L, h), jnp.float32)
    w["lnf_scale"] = jnp.ones((h,), jnp.float32)
    w["lnf_bias"] = jnp.zeros((h,), jnp.float32)
    return w


def init_lora(as_run: dict, seed: int, rank: int) -> dict:
    """Adapters from the seed: A ~ N(0, 1/fan_in) stored in bfloat16 then
    read as float32, B = 0; one key per target, in LORA_TARGETS order."""
    dm = dims(as_run)
    shapes = weight_shapes(dm)

    def make(key):
        keys = jax.random.split(key, len(LORA_TARGETS))
        out = {}
        for k, name in zip(keys, LORA_TARGETS):
            (L, d_in, d_out), _ = shapes[name]
            a = (jax.random.normal(k, (L, d_in, rank)) * d_in ** -0.5
                 ).astype(jnp.bfloat16)
            out[name] = {"a": a.astype(jnp.float32),
                         "b": jnp.zeros((L, rank, d_out), jnp.float32)}
        return out

    with jax.threefry_partitionable(True):
        return jax.jit(make)(jax.random.key(seed))


# --------------------------------------------------------------------------
# The forward pass
# --------------------------------------------------------------------------

def matmul(x, w):
    return jnp.matmul(x, w, precision="highest")


def matmul_int8(x, w):
    """The control's product: int8 x int8 with per-row / per-column scales.
    Straight-through for gradients (the training control needs them)."""
    sx = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0 + 1e-30
    sw = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0 + 1e-30
    low = matmul(jnp.round(x / sx), jnp.round(w / sw)) * sx * sw
    exact = matmul(x, w)
    return exact + jax.lax.stop_gradient(low - exact)


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def rope(x, positions, theta):
    """x [s, heads, d]; rotate the two halves of d as (real, imag) pairs."""
    half = x.shape[-1] // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * freq
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def block(dm, x, lw, positions, segments, mm):
    """One parallel block on one sequence x [s, h]."""
    s = x.shape[0]
    h1 = layer_norm(x, lw["ln1_scale"], lw["ln1_bias"], dm["eps"])
    q = mm(h1, lw["wq"]).reshape(s, dm["nq"], dm["d"])
    k = mm(h1, lw["wk"]).reshape(s, dm["nkv"], dm["d"])
    v = mm(h1, lw["wv"]).reshape(s, dm["nkv"], dm["d"])
    q, k = rope(q, positions, dm["theta"]), rope(k, positions, dm["theta"])
    group = dm["nq"] // dm["nkv"]      # query head i reads kv head i // group
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    idx = jnp.arange(s)
    seen = (idx[None, :] <= idx[:, None]) \
        & (segments[None, :] == segments[:, None])

    def attend(q_rows, seen_rows):
        scores = jnp.einsum("qhd,khd->hqk", q_rows, k,
                            precision="highest") * dm["d"] ** -0.5
        scores = jnp.where(seen_rows[None], scores, -1e30)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1),
                          v, precision="highest")

    # Blocks of query rows, so that the [heads, rows, keys] scores fit
    # beside the weights at 2048 keys; the mathematics is unchanged.
    ctx = jnp.concatenate(
        [jax.checkpoint(attend)(q[i:i + Q_BLOCK], seen[i:i + Q_BLOCK])
         for i in range(0, s, Q_BLOCK)]).reshape(s, dm["q"])
    attn = mm(ctx, lw["wo"])
    h2 = h1 if not dm["two_norms"] else layer_norm(
        x, lw["ln2_scale"], lw["ln2_bias"], dm["eps"])
    mlp = mm(gelu_tanh(mm(h2, lw["mlp_in"])), lw["mlp_out"])
    return x + attn + mlp


def hidden_states(dm, w, tokens, positions, segments, mm, lora=None,
                  lora_scale=0.0, remat=False):
    """Final-norm activations [s, h] of one sequence. Layer weights are
    read in float32 one layer at a time (they are stored in bfloat16)."""
    x = w["embed"][tokens].astype(jnp.float32)
    names = [n for n in w if n not in ("embed", "lnf_scale", "lnf_bias")]

    def body(x, scanned):
        lw = {n: scanned[n].astype(jnp.float32) for n in names}
        if lora is not None:
            for t in LORA_TARGETS:
                ab = matmul(scanned["lora_" + t + "_a"],
                            scanned["lora_" + t + "_b"])
                lw[t] = lw[t] + lora_scale * ab
        return block(dm, x, lw, positions, segments, mm), None

    if remat:
        body = jax.checkpoint(body)
    xs = {n: w[n] for n in names}
    if lora is not None:
        for t in LORA_TARGETS:
            xs["lora_" + t + "_a"] = lora[t]["a"]
            xs["lora_" + t + "_b"] = lora[t]["b"]
    x, _ = jax.lax.scan(body, x, xs)
    return layer_norm(x, w["lnf_scale"], w["lnf_bias"], dm["eps"])


@functools.partial(jax.jit, static_argnames=("as_run_items", "low"))
def _logits_at(w, tokens, rows, as_run_items, low):
    dm = dims(dict(as_run_items))
    mm = matmul_int8 if low else matmul
    s = tokens.shape[0]
    x = hidden_states(dm, w, tokens, jnp.arange(s), jnp.ones(s, jnp.int32),
                      mm)
    return mm(x[rows], w["embed"].astype(jnp.float32).T)


def logits_at(as_run: dict, w: dict, tokens, rows, low: bool = False):
    """Logits [len(rows), vocab] of one causal sequence at `rows`."""
    with jax.default_matmul_precision("highest"):
        return _logits_at(w, jnp.asarray(tokens, jnp.int32),
                          jnp.asarray(rows, jnp.int32),
                          tuple(sorted(as_run.items())), low)


# --------------------------------------------------------------------------
# Training: loss, adapter gradients, AdamW
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("as_run_items", "low", "scale"))
def _row_loss_grads(w, lora, row, as_run_items, low, scale):
    dm = dims(dict(as_run_items))
    mm = matmul_int8 if low else matmul

    def nll_sum(lora):
        x = hidden_states(dm, w, row["tokens"], row["positions"],
                          row["segment_ids"], mm, lora, scale, remat=True)
        logits = mm(x, w["embed"].astype(jnp.float32).T)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, row["targets"][:, None], 1)[:, 0]
        return jnp.sum(nll * row["loss_mask"])

    return jax.value_and_grad(nll_sum)(lora)


def loss_and_grads(as_run, w, lora, rows, scale, low=False):
    """Mean masked next-token loss of a batch of packed rows and its
    gradients with respect to the adapters, one row at a time."""
    total = sum(float(np.sum(r["loss_mask"])) for r in rows)
    total = max(total, 1.0)
    loss, grads = 0.0, None
    with jax.default_matmul_precision("highest"):
        for r in rows:
            row = {k: jnp.asarray(v) for k, v in r.items()}
            val, g = _row_loss_grads(w, lora, row,
                                     tuple(sorted(as_run.items())), low,
                                     float(scale))
            loss += float(val)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    return loss / total, jax.tree.map(lambda g: g / total, grads)


def learning_rate(job: dict, count: int) -> float:
    """Linear warm-up then cosine decay to min_lr_ratio, by step count."""
    lr, warm = job["learning_rate"], int(job["warmup_steps"])
    if count < warm:
        return lr * count / max(warm, 1)
    if job["schedule"] != "cosine":
        raise ValueError("the reference follows the cosine schedule only")
    span = max(int(job["total_steps"]) - warm, 1)
    frac = min(max(count - warm, 0), span) / span
    cos = 0.5 * (1.0 + np.cos(np.pi * frac))
    alpha = job["min_lr_ratio"]
    return lr * ((1 - alpha) * cos + alpha)


def clip(grads, max_norm):
    norm = float(jnp.sqrt(sum(jnp.sum(jnp.square(g))
                              for g in jax.tree.leaves(grads))))
    factor = 1.0 if max_norm is None else min(1.0, max_norm / max(norm, 1e-30))
    return jax.tree.map(lambda g: g * factor, grads)


def adamw_step(job, params, grads, mu, nu, count):
    """One AdamW update (bias-corrected, decoupled weight decay) on the
    already clipped gradients; `count` is the number of updates so far."""
    b1, b2, eps = job["b1"], job["b2"], job["eps"]
    lr = learning_rate(job, count)
    t = count + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda n, g: b2 * n + (1 - b2) * g * g, nu, grads)
    params = jax.tree.map(
        lambda p, m, n: p - lr * ((m / (1 - b1 ** t))
                                  / (jnp.sqrt(n / (1 - b2 ** t)) + eps)
                                  + job["weight_decay"] * p),
        params, mu, nu)
    return params, mu, nu
