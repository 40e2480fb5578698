"""The gated delta rule (Gated DeltaNet), the token mixer of a
linear-attention layer, and the short causal convolution in front of it.

A head keeps a fixed-size state S [d_k, d_v] instead of keys and values a
token. Per token, with k and q of unit length (q further scaled by
d_k^-1/2), a decay alpha = exp(g) in (0, 1] and a write strength beta:

    S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

Two forms of the same recurrence:

- ``gated_delta_step``: one token a row, for decode. Elementwise products
  and sums in float32 (a matrix-vector product has nothing for the MXU).
- ``gated_delta_chunked``: a whole sequence in chunks of 64 tokens, for
  prefill and the no-cache forward. Inside a chunk the 64 rank-one updates
  collapse into the WY / UT-transform form of the paper (Yang, Kautz,
  Hatamizadeh 2024, "Gated Delta Networks", section 3.3): with the
  cumulated log-decay c_i of the chunk and
  A[i, j] = beta_i (k_i . k_j) exp(c_i - c_j) for j < i,
  T = (I + A)^-1, U = T (beta v), W = T (beta k exp(c)), a chunk that
  starts from S does
      V' = U - W S                      (what each token really writes)
      O  = (q exp(c)) S + tril(q k^T exp(c_i - c_j)) V'
      S' = exp(c_last) S + (k exp(c_last - c))^T V'
  so the sequential part is one walk over chunks carrying S, and
  everything else is matrix products. Operands of those products are in
  the activation dtype with float32 accumulation; S, the decays and T stay
  float32.

Both take a per-token validity mask: an invalid token has alpha = 1 and
beta = 0, which leaves S exactly as it was (its output row is garbage
nobody reads). That is what lets one program hold prompts of unequal
length in one bucket, and parked rows in a decode batch.

The chunked form is ONE Pallas kernel (``_chunk_kernel``: Mosaic on a TPU,
the Pallas interpreter elsewhere), launched on a grid of (row, block of
heads, group of chunks) with q, k, v, o heads-major ``[b, H, s, d]``:

- a grid step holds `hb` heads side by side and walks `cs` chunks in
  order. Per chunk, all in VMEM: the decay matrix from c; A from k k^T; T
  by the blocked scheme of ``_inv_unit_lower`` (forward substitution in
  the four 16-wide diagonal blocks, which sit side by side on the lanes
  and are inverted together, then the two levels of merges as products of
  [64, 64] whose zero blocks cost nothing extra); U and W; the four
  products with the state. The Neumann series is still not used: the
  powers of a nilpotent matrix grow before they vanish.
- S ``[hb, d_k, d_v]`` float32 is the kernel's second result, whose block
  does not move along the grid's last (sequential) axis: read from
  `initial_state` at a row's first chunk, it stays in VMEM until the row's
  last, one pass over the state a sequence.
- the products that the plain form asks at ``Precision.HIGHEST`` (T with
  beta v and beta k exp(c), the merges) are taken on the MXU's bfloat16
  passes with the float32 operand split into three bfloat16 terms
  (``_exact``): the same six pairs of terms HIGHEST sums, but the terms
  that meet one right-hand term are stacked on the rows and share its
  weights, and a bfloat16 v or k is exact as it stands and needs one pass
  of three. No product is taken at a lower precision than before.
- d_k = 96 and d_v = 192 are not multiples of 128 lanes: a block spans the
  whole last axis and Mosaic pads it in VMEM. c and beta come as rows
  ``[b, H, chunks, 1, 64]``; their columns are made in the kernel by a
  masked sum, so no array with a last axis of 1 exists in HBM.
- the launch shape is a pure function of the call's shapes
  (``kernel_shape``): `hb` = the largest divisor of the heads up to 10,
  then `cs` = 40 / hb chunks a step for 2-byte activations (half as many
  for float32: a step's blocks of q, k, v, o, double-buffered, stay under
  half of the 16 MiB a kernel gets unasked), fewer for a shorter sequence.
  The heads of a step are the independent chains that fill one another's
  waits: the compiler keeps to the order of the source, so one head alone
  runs its substitution with the MXU idle and its products with the
  vector unit idle. Sweep on the chip, the kernel alone at [1, 2048], ms
  (hb x cs): 1 x 8 1.450, 2 x 8 0.927, 3 x 8 0.731, 5 x 8 0.642, 6 x 8
  0.644, 10 x 4 0.555; cs moves nothing (5 x 4 0.644, 3 x 16 0.730, 2 x
  16 0.924); [8, 1024] 5.83 / 3.66 / 2.88 / 2.51 / 2.52 / 2.17 in the
  same order (chip run, PR 47). The compiler's schedule of one chunk
  agrees: 890 bundles a head at hb = 2, 819 at 3, 750 at 5, 698 at 10.
  No option, no environment variable.
- under a multi-device mesh the call is a ``shard_map``: the batch over the
  data axes, the heads over `tensor` where it divides them
  (``_chunked_kernel``). ``models/transformer`` refuses a tensor mesh that does
  not divide the linear heads before it gets here.
- ``jax.grad`` goes through ``_chunked_plain``, the same equations in plain
  `jax.numpy` (what this module's chunked form was before the kernel): it
  is the kernel's differentiation rule and its oracle in the tests, and
  nothing else calls it.

At olmo-hybrid-7b's widths (30 heads of 96 x 192, bfloat16) a [1, 2048]
prefill's core took 3.09 ms a layer in plain `jax.numpy`, 2.0 ms of it the
sixty row writes of the forward substitution, and takes 0.68 ms with the
kernel, 0.56 in the kernel itself and the rest in the heads-major copies
of q, k, v and o around it (chip runs, PR 47; `PERF.md` section 6).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from runbooks_tpu.utils import hw

CHUNK = 64
_BASE = 16      # blocks of (I + A) inverted row by row; larger ones merge
_EXACT = jax.lax.Precision.HIGHEST
_ONE_PASS = jax.lax.Precision.DEFAULT


def l2_normalize(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """x / sqrt(sum x^2 + eps) over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def causal_conv(x: jax.Array, w: jax.Array,
                tail: Optional[jax.Array] = None,
                n_valid: Optional[jax.Array] = None,
                activation: Optional[Callable] = jax.nn.silu,
                ) -> Tuple[jax.Array, jax.Array]:
    """Causal depthwise convolution over the sequence, then `activation`
    on the float32 sums (SiLU, the gated-delta mixer's; None: none, the
    gated short convolution's).

    x [b, s, c] in the activation dtype, w [kernel, c] (w[-1] weighs the
    current token), tail [b, kernel-1, c]: the inputs of the tokens just
    before x[:, 0] (zeros for a fresh sequence). Returns (y [b, s, c],
    new tail): the inputs of the last kernel-1 VALID tokens, where row r's
    valid tokens are its first n_valid[r] (all s when n_valid is None) —
    so a bucket's padding, or a parked decode row, leaves the tail as the
    last real token left it."""
    b, s, c = x.shape
    kernel = w.shape[0]
    if tail is None:
        tail = jnp.zeros((b, kernel - 1, c), x.dtype)
    xc = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    wf = w.astype(jnp.float32)
    y = sum(xc[:, j:j + s].astype(jnp.float32) * wf[j]
            for j in range(kernel))
    if activation is not None:
        y = activation(y)
    y = y.astype(x.dtype)
    if n_valid is None:
        new_tail = xc[:, s:]
    else:
        new_tail = jax.vmap(lambda row, n: jax.lax.dynamic_slice_in_dim(
            row, n, kernel - 1, axis=0))(xc, n_valid.astype(jnp.int32))
    return y, new_tail


def gated_delta_step(q, k, v, g, beta, state, valid=None):
    """One token a row. q, k [b, H, d_k] (already normalized and scaled),
    v [b, H, d_v], g, beta [b, H] float32, state [b, H, d_k, d_v] float32,
    valid [b] bool or None. Returns (o [b, H, d_v] float32, new state)."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    if valid is not None:
        g = jnp.where(valid[:, None], g, 0.0)
        beta = jnp.where(valid[:, None], beta, 0.0)
    s = state * jnp.exp(g)[..., None, None]
    written = jnp.sum(k[..., :, None] * s, axis=-2)          # S^T k
    delta = (v - written) * beta[..., None]
    s = s + k[..., :, None] * delta[..., None, :]
    return jnp.sum(q[..., :, None] * s, axis=-2), s


def _inv_unit_lower(a: jax.Array) -> jax.Array:
    """(I + a)^-1 for strictly lower-triangular a [..., n, n], float32.
    Forward substitution on 16-wide diagonal blocks, then block merges
    ([[P, 0], [C, Q]]^-1 = [[P^-1, 0], [-Q^-1 C P^-1, Q^-1]]): stable where
    the Neumann series of a nilpotent matrix is not (its powers grow
    before they vanish)."""
    n = a.shape[-1]
    if n <= _BASE:
        eye = jnp.eye(n, dtype=a.dtype)
        t = jnp.broadcast_to(eye, a.shape)
        for i in range(1, n):
            # Rows < i of t are final, rows >= i still unit rows that
            # a[i, :] (zero from column i on) does not reach.
            row = eye[i] - jnp.einsum("...j,...jk->...k", a[..., i, :], t,
                                      precision=_EXACT)
            t = t.at[..., i, :].set(row)
        return t
    h = n // 2
    p = _inv_unit_lower(a[..., :h, :h])
    q = _inv_unit_lower(a[..., h:, h:])
    low = -jnp.einsum("...ij,...jk,...kl->...il", q, a[..., h:, :h], p,
                      precision=_EXACT)
    top = jnp.concatenate([p, jnp.zeros_like(low).swapaxes(-1, -2)], -1)
    return jnp.concatenate([top, jnp.concatenate([low, q], -1)], -2)


def _chunked_plain(q, k, v, g, beta, initial_state, chunk: int):
    """``gated_delta_chunked`` in plain `jax.numpy`, g and beta already
    masked: the kernel's differentiation rule and its shape-for-shape
    oracle in the tests. Nothing else calls it."""
    f32 = jnp.float32
    ad = v.dtype
    b, s, heads, dk = q.shape
    dv = v.shape[-1]
    pad = -s % chunk
    if pad:
        widen = lambda x: jnp.pad(  # noqa: E731
            x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        q, k, v, g, beta = map(widen, (q, k, v, g, beta))
    n = (s + pad) // chunk

    def chunks(x):      # [b, s, H, ...] -> [n, b, H, chunk, ...]
        x = x.reshape((b, n, chunk, heads) + x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    q, k, v = chunks(q), chunks(k), chunks(v)
    g, beta = chunks(g.astype(f32)), chunks(beta.astype(f32))
    c = jnp.cumsum(g, axis=-1)                            # [n, b, H, chunk]
    seen = jnp.tril(jnp.ones((chunk, chunk), bool))
    # exp(c_i - c_j) for j <= i, 0 above the diagonal (where the
    # difference is positive and must not be exponentiated).
    decay = jnp.exp(jnp.where(seen, c[..., :, None] - c[..., None, :],
                              -jnp.inf))

    def mm(spec, x, y):
        return jnp.einsum(spec, x.astype(ad), y.astype(ad),
                          preferred_element_type=f32)

    # beta scales rows of k k^T and, folded into T's columns, the
    # operands of U and W: no beta-scaled copy of k or v is made.
    a = mm("...ik,...jk->...ij", k, k) * beta[..., None] * decay
    t = _inv_unit_lower(jnp.where(jnp.tril(seen, -1), a, 0.0))
    t_beta = t * beta[..., None, :]
    u = jnp.einsum("...ij,...jv->...iv", t_beta, v.astype(f32),
                   precision=_EXACT)
    # What the scan only ever uses as a product's operand is kept in the
    # operands' dtype: the same numbers at half the memory.
    w = jnp.einsum("...ij,...jk->...ik", t_beta * jnp.exp(c)[..., None, :],
                   k.astype(f32), precision=_EXACT).astype(ad)
    qk = (mm("...ik,...jk->...ij", q, k) * decay).astype(ad)
    q_in = (q.astype(f32) * jnp.exp(c)[..., None]).astype(ad)
    c_last = c[..., -1:]
    k_out = (k.astype(f32) * jnp.exp(c_last - c)[..., None]).astype(ad)

    def body(state, xs):
        u_i, w_i, qk_i, q_i, k_i, last_i = xs
        v_new = u_i - mm("...ik,...kv->...iv", w_i, state)
        o_i = mm("...ik,...kv->...iv", q_i, state) \
            + mm("...ij,...jv->...iv", qk_i, v_new)
        state = state * jnp.exp(last_i)[..., None] \
            + mm("...ik,...iv->...kv", k_i, v_new)
        return state, o_i.astype(ad)

    state, o = jax.lax.scan(
        body, initial_state.astype(f32),
        (u, w, qk, q_in, k_out, c_last))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)   # [b, n, chunk, H, d_v]
    return o.reshape(b, n * chunk, heads, dv)[:, :s], state


# ---------------------------------------------------------------------------
# The chunked form as one Pallas kernel
# ---------------------------------------------------------------------------

_STEP_HEADS = 10        # heads a grid step holds side by side, at most
_STEP_CHUNK_HEADS = 40  # chunks x heads a step holds at 2 bytes an element


def _interpret() -> bool:
    # Mosaic on a TPU, the Pallas interpreter elsewhere: off the one probe
    # (utils/hw.on_tpu), as ops/flash_attention.py decides. Looked up on
    # the module, so that a rehearsal that forces the probe
    # (benchmark/rehearse.py) compiles the kernel itself.
    return not hw.on_tpu()


def kernel_shape(s: int, heads: int, chunk: int = CHUNK,
                 itemsize: int = 2) -> Tuple[int, int, int]:
    """(chunks a grid step, heads a grid step, padded length) of the
    kernel's launch for a sequence of s tokens whose q, k, v hold
    `itemsize` bytes an element: a pure function of the call's shapes
    (the module docstring has the sweep behind it). As many heads side by
    side as divide the heads, up to 10; then as many chunks as keep a
    step's blocks of q, k, v and o, twice for the pipeline, under half of
    the 16 MiB of VMEM a kernel may take without asking."""
    n = -(-s // chunk)
    hb = max(d for d in range(1, min(heads, _STEP_HEADS) + 1)
             if heads % d == 0)
    cs = min(n, max(1, _STEP_CHUNK_HEADS * 2 // itemsize // hb))
    return cs, hb, -(-n // cs) * cs * chunk


def _split3(x):
    """float32 x as three bfloat16 terms, largest first (8 + 8 + 8 bits of
    mantissa: hi + mid + lo = x to float32's last bit)."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    hi = x.astype(bf16)
    rest = x - hi.astype(f32)
    mid = rest.astype(bf16)
    return hi, mid, (rest - mid.astype(f32)).astype(bf16)


def _exact(lhs, rhs):
    """lhs [n, i, j] times rhs [n, j, x] at float32 accuracy on the MXU's
    bfloat16 passes (float32 accumulation). Either operand may come as an
    array (bfloat16: exact as it stands; float32: split here) or as the
    three terms `_split3` gave. Terms of lhs that meet the same term of
    rhs are stacked on the rows and share that pass's weights; of the
    nine pairs the six that reach float32's last bits are summed, smallest
    first: what `Precision.HIGHEST` computes."""
    f32 = jnp.float32

    def terms(x):
        if isinstance(x, tuple):
            return x
        return (x,) if x.dtype == jnp.bfloat16 else _split3(x.astype(f32))

    lhs, rhs = terms(lhs), terms(rhs)
    i = lhs[0].shape[1]
    order = max(len(lhs), len(rhs))
    out = None
    # Term l of lhs times term r of rhs matters while l + r < order; the
    # smallest pairs first.
    for r in reversed(range(len(rhs))):
        meets = lhs[:order - r]
        got = jnp.einsum("nij,njx->nix", jnp.concatenate(meets, 1), rhs[r],
                         precision=_ONE_PASS, preferred_element_type=f32)
        for l in reversed(range(len(meets))):
            part = got[:, l * i:(l + 1) * i]
            out = part if out is None else out + part
    return out


def _inv_unit_lower_vmem(a):
    """``_inv_unit_lower`` on values a kernel holds: (I + a)^-1 for
    strictly lower-triangular a [n, m, m] float32, by the same blocked
    scheme, laid out for the vector unit and the MXU.

    The diagonal blocks, 16 wide: all m / 16 of a chunk side by side on
    the lanes ([n, 16, m]: block b's columns are the lanes it has in a),
    inverted together by the forward substitution taken by columns: row
    j of a block is final after step j - 1, and step j takes a[:, j]
    times it off the rows below, a lane gather for the column and a
    sublane broadcast for the row. The merges, at full width: with X the
    block-diagonal of the inverses so far and C the blocks of a that
    couple the two halves of each block twice as wide,
    [[P, 0], [C, Q]]^-1 = [[P, 0], [-Q C P, Q]] is X - X (C X), of which
    only the lower halves' rows are computed."""
    n, m, _ = a.shape
    base = min(m, _BASE)
    lane = jax.lax.broadcasted_iota(jnp.int32, (base, m), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (base, m), 0)
    blocks = [jnp.where(lane // base == b,
                        a[:, b * base:(b + 1) * base, :], 0.0)
              for b in range(m // base)]
    side = sum(blocks[1:], blocks[0]).reshape(n * base, m)
    first = jnp.tile(lane // base * base, (n, 1))     # a block's lane 0
    t = jnp.broadcast_to(jnp.where(lane % base == sub, 1.0, 0.0),
                         (n, base, m))
    for j in range(base - 1):
        column = jnp.take_along_axis(side, first + j, axis=1)
        t = t - column.reshape(n, base, m) * t[:, j:j + 1, :]
    x = jnp.concatenate([jnp.where(lane // base == b, t, 0.0)
                         for b in range(m // base)], axis=1)
    half = base
    while half < m:
        # Rows of the lower halves, and within them the columns of the
        # upper half of the same block of 2 * half.
        lower = [slice(r, r + half) for r in range(half, m, 2 * half)]
        zeros = jnp.zeros((n, half, m), jnp.float32)

        def take(y):        # [n, m, m] -> the lower halves' rows
            return jnp.concatenate([y[:, rows] for rows in lower], 1)

        def spread(y):      # ... and back, the upper halves' rows zero
            return jnp.concatenate(
                [part for i in range(len(lower))
                 for part in (zeros, y[:, i * half:(i + 1) * half])], 1)

        col = jax.lax.broadcasted_iota(jnp.int32, (m // 2, m), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (m // 2, m), 0)
        couples = col // half == row // half * 2
        x3 = _split3(x)
        cx = _exact(jnp.where(couples, take(a), 0.0), x3)   # C X
        x = x - spread(_exact(tuple(take(term) for term in x3), spread(cx)))
        half *= 2
    return x


def _chunk_kernel(q_ref, k_ref, v_ref, c_ref, beta_ref, s0_ref,
                  o_ref, s_ref, *, chunk: int, cs: int):
    """One grid step: `cs` chunks of the block's heads of one row, the
    chunks in order and the heads side by side (their chains of
    dependent operations fill one another's waits). q_ref, k_ref [1, hb,
    cs * chunk, d_k], v_ref, o_ref [1, hb, cs * chunk, d_v], c_ref (the
    chunk's cumulated log-decay) and beta_ref [1, hb, cs, 1, chunk]
    float32, s0_ref and s_ref [1, hb, d_k, d_v] float32. s_ref's block does
    not move along the grid's last (sequential) axis: it is the state, in
    VMEM from a row's first chunk to its last."""
    f32 = jnp.float32
    ad = v_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _load_state():
        s_ref[...] = s0_ref[...]

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)

    def as_column(x):   # [hb, 1, chunk] -> [hb, chunk, 1], no transpose
        return jnp.sum(jnp.where(row == col, x, 0.0), axis=-1, keepdims=True)

    def mm(spec, x, y):
        # bfloat16 operands are one pass whatever precision the caller's
        # context asks of float32 ones (Mosaic refuses the combination).
        return jnp.einsum(spec, x.astype(ad), y.astype(ad),
                          precision=_ONE_PASS if ad == jnp.bfloat16 else None,
                          preferred_element_type=f32)

    def one_chunk(i, carry):
        at = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)
        q, k, v = q_ref[0, :, at, :], k_ref[0, :, at, :], v_ref[0, :, at, :]
        c_row, beta_row = c_ref[0, :, i], beta_ref[0, :, i]  # [hb, 1, chunk]
        c_col, beta_col = as_column(c_row), as_column(beta_row)
        decay = jnp.exp(jnp.where(row >= col, c_col - c_row, -jnp.inf))
        a = mm("nik,njk->nij", k, k) * beta_col * decay
        t_beta = _inv_unit_lower_vmem(
            jnp.where(row > col, a, 0.0)) * beta_row
        u = _exact(t_beta, v)
        w = _exact(t_beta * jnp.exp(c_row), k).astype(ad)
        qk = (mm("nik,njk->nij", q, k) * decay).astype(ad)
        q_in = (q.astype(f32) * jnp.exp(c_col)).astype(ad)
        c_last = c_row[:, :, chunk - 1:]                     # [hb, 1, 1]
        k_out = (k.astype(f32) * jnp.exp(c_last - c_col)).astype(ad)
        state = s_ref[0]
        # W and q exp(c) meet the state in one pass over it.
        from_state = mm("nik,nkv->niv", jnp.concatenate([w, q_in], 1),
                        state)
        v_new = u - from_state[:, :chunk]
        o = from_state[:, chunk:] + mm("nij,njv->niv", qk, v_new)
        o_ref[0, :, at, :] = o.astype(o_ref.dtype)
        s_ref[0] = state * jnp.exp(c_last) + mm("nik,niv->nkv", k_out,
                                                v_new)
        return carry

    jax.lax.fori_loop(0, cs, one_chunk, 0)


def _launch(q, k, v, g, beta, state, *, chunk: int):
    """The kernel on the operands one device holds: heads-major and padded
    to whole grid steps (padding is invalid tokens: g = 0, beta = 0)."""
    f32 = jnp.float32
    b, s, heads, dk = q.shape
    dv = v.shape[-1]
    cs, hb, padded = kernel_shape(s, heads, chunk, v.dtype.itemsize)
    n = padded // chunk

    def heads_major(x):     # [b, s, H, ...] -> [b, H, padded, ...]
        x = jnp.pad(x, ((0, 0), (0, padded - s)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x, 2, 1)

    def rows(x):            # [b, s, H] -> [b, H, n, 1, chunk]
        return heads_major(x).reshape(b, heads, n, 1, chunk)

    def tokens(d):
        return pl.BlockSpec((1, hb, cs * chunk, d),
                            lambda r, h, c: (r, h, c, 0))

    a_row = pl.BlockSpec((1, hb, cs, 1, chunk),
                         lambda r, h, c: (r, h, c, 0, 0))
    whole = pl.BlockSpec((1, hb, dk, dv), lambda r, h, c: (r, h, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_chunk_kernel, chunk=chunk, cs=cs),
        grid=(b, heads // hb, n // cs),
        in_specs=[tokens(dk), tokens(dk), tokens(dv), a_row, a_row, whole],
        out_specs=[tokens(dv), whole],
        out_shape=[jax.ShapeDtypeStruct((b, heads, padded, dv), v.dtype),
                   jax.ShapeDtypeStruct((b, heads, dk, dv), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="gated_delta_chunked",
    )(heads_major(q), heads_major(k), heads_major(v),
      jnp.cumsum(rows(g), axis=-1), rows(beta), state)
    return jnp.moveaxis(o, 1, 2)[:, :s], state


def _chunked_kernel(q, k, v, g, beta, initial_state, chunk: int):
    """``_launch`` on a single device; under a multi-device mesh a
    shard_map of it, the batch over the data axes and the heads over
    `tensor` where it divides them — ops/flash_attention._shard_plan's
    plan for heads that all have their own keys (the layout the
    projections already give: no resharding; Mosaic kernels cannot be
    partitioned by GSPMD)."""
    from runbooks_tpu.ops.flash_attention import _shard_plan

    fn = functools.partial(_launch, chunk=chunk)
    plan = _shard_plan(q, k)
    if plan is not None:
        token = P(plan.batch, None, plan.heads, None)
        scalar = P(plan.batch, None, plan.heads)
        state = P(plan.batch, plan.heads, None, None)
        fn = jax.shard_map(
            fn, mesh=plan.mesh,
            in_specs=(token, token, token, scalar, scalar, state),
            out_specs=(token, state),
            axis_names=(frozenset(plan.mesh.axis_names)
                        - frozenset(plan.mesh.manual_axes)),
            check_vma=False)
    return fn(q, k, v, g, beta, initial_state)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _chunked(q, k, v, g, beta, initial_state, chunk):
    return _chunked_kernel(q, k, v, g, beta, initial_state, chunk)


def _chunked_fwd(q, k, v, g, beta, initial_state, chunk):
    return (_chunked_kernel(q, k, v, g, beta, initial_state, chunk),
            (q, k, v, g, beta, initial_state))


def _chunked_bwd(chunk, operands, cotangents):
    # The kernel has no backward of its own: the plain form's is taken.
    _, vjp = jax.vjp(
        functools.partial(_chunked_plain, chunk=chunk), *operands)
    return vjp(cotangents)


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def gated_delta_chunked(q, k, v, g, beta, initial_state=None, mask=None,
                        chunk: int = CHUNK):
    """A sequence, chunk by chunk. q, k [b, s, H, d_k] (normalized and
    scaled), v [b, s, H, d_v], g, beta [b, s, H] float32, initial_state
    [b, H, d_k, d_v] float32 (zeros when None), mask [b, s] bool (all
    valid when None). Returns (o [b, s, H, d_v] in v's dtype, final state
    float32). Any s: the sequence is padded to whole chunks with invalid
    tokens."""
    f32 = jnp.float32
    b, _, heads, dk = q.shape
    g, beta = g.astype(f32), beta.astype(f32)
    if mask is not None:
        g = jnp.where(mask[..., None], g, 0.0)
        beta = jnp.where(mask[..., None], beta, 0.0)
    if initial_state is None:
        initial_state = jnp.zeros((b, heads, dk, v.shape[-1]), f32)
    return _chunked(q, k, v, g, beta, initial_state.astype(f32), chunk)


def gated_delta_reference(q, k, v, g, beta, initial_state=None, mask=None):
    """The recurrence token by token (a scan of ``gated_delta_step``): the
    oracle of the tests, same arguments and results as the chunked form."""
    b, s, heads, dk = q.shape
    if initial_state is None:
        initial_state = jnp.zeros((b, heads, dk, v.shape[-1]), jnp.float32)
    valid = (jnp.ones((s, b), bool) if mask is None else mask.T)

    def body(state, xs):
        q_t, k_t, v_t, g_t, b_t, ok = xs
        o, state = gated_delta_step(q_t, k_t, v_t, g_t, b_t, state, ok)
        return state, o

    t_first = lambda x: jnp.moveaxis(x, 1, 0)  # noqa: E731
    state, o = jax.lax.scan(
        body, initial_state,
        (t_first(q), t_first(k), t_first(v), t_first(g.astype(jnp.float32)),
         t_first(beta.astype(jnp.float32)), valid))
    return jnp.moveaxis(o, 0, 1).astype(v.dtype), state
