"""Block-sparse attention whose blocks a query chooses by scores against
compressed keys (InfLLM-v2), the read of a ``sparse_topk`` full-attention
layer (models/config.SparseRead `sp`; docs/hybrid-models.md).

For the query at position t of a row at least ``sp.dense_len`` long, and
each KV head g with its group of query heads:

 1. compressed keys c_j = mean(k[stride j .. stride j + kernel - 1]) for
    every j whose kernel is whole: stride j + kernel <= t + 1;
 2. p_h = softmax_j(q_h . c_j * scale) over those j, float32; P = the sum
    of p_h over the group's heads;
 3. block b = keys [block b, block b + block) scores B_b = max P_j over the
    kernels that overlap it (those not yet whole count 0);
 4. the window is the keys t - window + 1 .. t; blocks that lie wholly
    inside it are no candidates (``exclude_window``); the first ``init``
    blocks are always chosen; of the other candidates b < t // block the
    ``topk - init`` largest B_b are chosen, ties to the lower index;
 5. every head of the group attends, causally, to the keys of the chosen
    blocks and the window, one softmax over both.

A shorter row reads every key (causally). While the candidates are no more
than ``topk - init`` the read is the dense one too.

The scores that choose are float32 products at the highest precision, of
the query as the layer made it with compressed keys that are float32 means
of the keys AS STORED: prefill and decode of one program then rank the same
numbers (as models/moe.route's are, for the same reason).

Two cores over one choice (``select_blocks``, plain `jax.numpy`):

- ``sparse_prefill``: a call of many queries is ONE call of the flash
  forward (ops/flash_attention.py) under each token's own read: the window,
  and the token's choice of key blocks as one more term of the mask, an
  int8 ``[b, KV heads, queries, blocks]`` operand that a grid step widens
  to its key block's columns once for the KV head's whole group. Scores,
  the running softmax and the accumulator stay in VMEM. Every causal
  (query block, key block) tile is computed: with seeded weights the union
  of a query block's choices is nearly every block, and gathering a
  token's own keys would move topk x block + window keys a token and KV
  head. ``prefill_counts`` says how many score pairs the kernel's steps
  are beside those the choices need (PERF.md section 6, PR 46).
- ``sparse_decode``: a call of one query a row reads the row's keys under
  the token's mask (one product over the view, as every decode step's
  attention is); also the tests' one-masked-softmax oracle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_EXACT = jax.lax.Precision.HIGHEST
NEG_INF = -1e30
# A prefill chooses for this many of a dispatch's tokens at a time: the
# rank compares tokens x KV heads x blocks x blocks scores a step.
SELECT_TOKENS = 512


def n_blocks(keys: int, sp) -> int:
    return -(-keys // sp.block)


def compress_keys(k: jax.Array, sp) -> jax.Array:
    """k [b, L, g, d] as stored -> [b, (L - kernel) // stride + 1, g, d]
    float32: c_j = mean(k[stride j : stride j + kernel])."""
    b, L, g, d = k.shape
    n = (L - sp.kernel) // sp.stride + 1
    if n <= 0:
        return jnp.zeros((b, 0, g, d), jnp.float32)
    per = sp.kernel // sp.stride
    parts = k[:, :(n + per - 1) * sp.stride].astype(jnp.float32).reshape(
        b, n + per - 1, sp.stride, g, d).sum(axis=2)
    return sum(parts[:, i:i + n] for i in range(per)) / sp.kernel


def compressed_key_at(k: jax.Array, positions: jax.Array, sp):
    """The compressed key a row's token at `positions` [b] completes, from
    k [b, L, g, d] as stored WITH that token: (c [b, g, d] float32, j [b]
    int32, its index; an index out of every leaf's range where the token
    completes none)."""
    done = positions + 1 - sp.kernel
    whole = (done >= 0) & (done % sp.stride == 0)
    start = jnp.clip(done, 0, k.shape[1] - sp.kernel)
    rows = jax.vmap(lambda row, s: jax.lax.dynamic_slice_in_dim(
        row, s, sp.kernel, axis=0))(k, start)
    c = rows.astype(jnp.float32).mean(axis=1)
    return c, jnp.where(whole, done // sp.stride, np.iinfo(np.int32).max)


def select_blocks(q, ckeys, positions, sp, nb: int, scale: float,
                  exclude_window: bool = True):
    """Steps 2 to 4. q [b, s, H, d], ckeys [b, n_c, g, d] float32,
    positions [b, s] (below 0: nobody's token, which chooses nothing).
    Returns chosen [b, s, g, nb] bool: the blocks a token reads beside its
    window (the initial ones among them)."""
    b, s, H, d = q.shape
    n_c, g = ckeys.shape[1], ckeys.shape[2]
    t = positions[:, :, None]                                  # [b, s, 1]
    per = sp.block // sp.stride
    back = (sp.kernel - 1) // sp.stride
    if n_c:
        scores = jnp.einsum(
            "bsgrd,bjgd->bsgrj",
            q.astype(jnp.float32).reshape(b, s, g, H // g, d), ckeys,
            precision=_EXACT) * scale
        whole = (jnp.arange(n_c) * sp.stride + sp.kernel <= t + 1)
        whole = whole[:, :, None, None, :]                 # [b, s, 1, 1, j]
        scores = jnp.where(whole, scores, NEG_INF)
        e = jnp.where(whole, jnp.exp(
            scores - jnp.max(scores, axis=-1, keepdims=True)), 0.0)
        p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
        group_p = p.sum(axis=3)                            # [b, s, g, j]
    else:
        group_p = jnp.zeros((b, s, g, 0), jnp.float32)
    # Kernel j overlaps block b iff per b - back <= j <= per b + per - 1.
    want = per * nb + back
    padded = jnp.pad(group_p[..., :max(want - back, 0)],
                     ((0, 0),) * 3 + ((back, max(want - back - n_c, 0)),))
    block_score = jax.lax.reduce_window(
        padded, -jnp.inf, jax.lax.max, (1, 1, 1, per + back),
        (1, 1, 1, per), "VALID")                           # [b, s, g, nb]
    blocks = jnp.arange(nb)
    candidate = (blocks >= sp.init) & (blocks < t // sp.block)
    if exclude_window:
        candidate &= blocks * sp.block < t - sp.window + 1
    candidate = candidate[:, :, None, :]                   # [b, s, 1, nb]
    sc = jnp.where(candidate, block_score, -jnp.inf)
    ahead = (sc[..., None, :] > sc[..., :, None]) | (
        (sc[..., None, :] == sc[..., :, None])
        & (blocks[None, :] < blocks[:, None]))
    rank = jnp.sum(ahead & candidate[..., None, :], axis=-1)
    chosen = candidate & (rank < sp.topk - sp.init)
    return chosen | ((blocks < sp.init) & (t >= 0))[:, :, None, :]


def read_mask(chosen, positions, key_pos, sparse_row, sp):
    """Step 5 as a mask [b, s, g, keys]: which of the keys at `key_pos`
    [keys] a token reads. chosen [b, s, g, nb]; positions [b, s];
    sparse_row [b] bool (False: the row reads every key, causally)."""
    t = positions[:, :, None, None]
    causal = key_pos <= t                                  # [b, s, 1, k]
    near = key_pos > t - sp.window
    of_block = jnp.take(chosen, key_pos // sp.block, axis=-1, mode="clip")
    return causal & (near | of_block | ~sparse_row[:, None, None, None])


def sparse_decode(q, k, v, chosen, positions, sparse_row, sp, scale: float):
    """A few queries a row against the row's keys under each token's mask.
    q [b, s, H, d], k [b, L, g, d], v [b, L, g, dv]. Returns [b, s, H, dv]
    in q's dtype."""
    b, s, H, d = q.shape
    L, g = k.shape[1], k.shape[2]
    mask = read_mask(chosen, positions, jnp.arange(L), sparse_row, sp)
    logits = jnp.einsum("bsgrd,bkgd->bsgrk", q.reshape(b, s, g, H // g, d),
                        k, preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask[:, :, :, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(jnp.any(mask, axis=-1)[..., None, None], probs, 0.0)
    out = jnp.einsum("bsgrk,bkgd->bsgrd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, H, v.shape[-1]).astype(q.dtype)


def sparse_prefill(q, k, v, ckeys, positions, sparse_row, sp, scale: float,
                   exclude_window: bool = True, block_q=None, block_k=None):
    """Many queries a row. q [b, s, H, d]; k [b, L, g, d], v [b, L, g, dv]:
    the row's keys by position (slot p holds position p); ckeys [b, n_c, g,
    d] float32; positions [b, s] (below 0: nobody's token, whose output is
    0); sparse_row [b] bool. Returns [b, s, H, dv] in q's dtype.

    The choice is made SELECT_TOKENS tokens at a time (the rank's
    [tokens, g, blocks, blocks] comparisons stay bounded) and kept as int8
    [b, g, s, blocks]; a short row chooses every block. The core is one
    call of the flash forward under that choice and the window
    (block_q / block_k: None = flash_attention.block_shape's answer for
    the call)."""
    from runbooks_tpu.ops.flash_attention import flash_attention

    b, s, H, d = q.shape
    L = k.shape[1]
    nb = n_blocks(L, sp)
    step = min(max(SELECT_TOKENS // b, 1), s)
    pad = -s % step

    def steps(x, fill):                  # [b, s, ...] -> [n, b, step, ...]
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2),
                    constant_values=fill)
        return jnp.moveaxis(x.reshape((b, -1, step) + x.shape[2:]), 1, 0)

    def select(xs):
        return select_blocks(xs[0], ckeys, xs[1], sp, nb, scale,
                             exclude_window).astype(jnp.int8)

    with jax.named_scope("bsa.select"):
        if step == s:
            chosen = select((q, positions))                   # [b, s, g, nb]
        else:
            chosen = jax.lax.map(select, (steps(q, 0), steps(positions, -1)))
            chosen = jnp.moveaxis(chosen, 0, 1).reshape(
                b, -1, *chosen.shape[3:])[:, :s]
        chosen = jnp.swapaxes(chosen, 1, 2)                   # [b, g, s, nb]
        chosen = jnp.maximum(
            chosen, (~sparse_row).astype(jnp.int8)[:, None, None, None])
    kv_pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None], (b, L))
    with jax.named_scope("bsa.core"):
        return flash_attention(
            q, k, v, positions, kv_pos, None, None, True, scale, block_q,
            block_k, window=sp.window, choice=chosen, choice_block=sp.block)


def block_sparse_attention_reference(q, k, v, positions, sp, scale: float,
                                     dense_rows=None,
                                     exclude_window: bool = True):
    """The whole read in one piece, for the tests: compressed keys from
    the call's own keys, the choice, one masked softmax over all keys.
    q [b, s, H, d], k, v [b, s, g, *] (key i at position i of its row),
    positions [b, s]; dense_rows [b] bool (None: by the row's length, its
    last position + 1, against sp.dense_len)."""
    b, s = positions.shape
    if dense_rows is None:
        dense_rows = jnp.max(positions, axis=-1) + 1 < sp.dense_len
    chosen = select_blocks(q, compress_keys(k, sp), positions, sp,
                           n_blocks(k.shape[1], sp), scale, exclude_window)
    return sparse_decode(q, k, v, chosen, positions, ~dense_rows, sp, scale)


# --------------------------------------------------------------------------
# Counted on the host, from positions alone
# --------------------------------------------------------------------------

def read_counts(positions: np.ndarray, sparse: np.ndarray, sp) -> tuple:
    """(needed, chosen) of the tokens at `positions`, `sparse` saying which
    are of a long row. needed: score pairs their reads need, a query head:
    t + 1 for a token of a short row; else its window, the initial blocks'
    keys before it and `block` keys a block chosen beside them (the one
    block that may straddle the window's start is counted whole: at most
    block - 1 keys a token too many), never more than t + 1. chosen: the
    blocks they read beside their windows, a KV head (the initial ones
    among them; none for a token of a short row). How many blocks a token
    chooses does not depend on WHICH, so both are from positions alone."""
    t = np.asarray(positions, np.int64)
    lo = np.maximum(t - sp.window + 1, 0)
    beside = np.minimum(np.maximum(-(-lo // sp.block) - sp.init, 0),
                        sp.topk - sp.init)
    read = (np.minimum(t + 1, sp.window) + np.minimum(sp.init * sp.block, lo)
            + sp.block * beside)
    blocks = np.minimum(sp.init, -(-(t + 1) // sp.block)) + beside
    return (int(np.where(sparse, np.minimum(read, t + 1), t + 1).sum()),
            int(np.where(sparse, blocks, 0).sum()))


def prefill_counts(positions: np.ndarray, parked: np.ndarray, sp,
                   keys: int, group: int, block_q=None,
                   block_k=None) -> tuple:
    """(needed, visited, chosen) of one prefill dispatch through
    ``sparse_prefill``, a query head (a KV head) and layer: positions
    [rows, s] as the dispatch was given them, parked [rows, s] bool
    (nobody's tokens), `keys` the slots of a row, `group` the query heads
    a KV head (block_q / block_k as the call was given them). Visited:
    block_q x block_k score pairs for every grid step of the core whose
    body runs, counted by the function the kernel takes its ranges from
    (flash_attention.block_counts over the causal ranges, at the call's
    block_shape)."""
    from runbooks_tpu.ops.flash_attention import block_counts, block_shape

    rows, s = positions.shape
    pos = np.where(parked, -1, positions).astype(np.int64)
    sparse = np.broadcast_to(
        (pos.max(axis=-1) + 1 >= sp.dense_len)[:, None], pos.shape)
    real = pos >= 0
    needed, chosen = read_counts(pos[real], sparse[real], sp)
    block_q, block_k = block_shape("fwd", s, keys, group, sp.window,
                                   block_q, block_k)
    steps, _ = block_counts(
        pos.astype(np.int32),
        np.broadcast_to(np.arange(keys, dtype=np.int32), (rows, keys)),
        None, None, block_q, block_k, True)
    return needed, steps * block_q * block_k, chosen


def decode_counts(positions: np.ndarray, sp, view: int) -> tuple:
    """The same for the tokens decode steps wrote at `positions`, each a
    row of its own length with it, through ``sparse_decode`` over a view of
    `view` keys."""
    at = np.asarray(positions, np.int64)
    needed, chosen = read_counts(at, at + 1 >= sp.dense_len, sp)
    return needed, len(at) * view, chosen
