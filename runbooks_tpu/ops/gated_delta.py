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
  so the sequential part is one scan over chunks carrying S, and
  everything else is batched matrix products. Operands of those products
  are in the activation dtype with float32 accumulation; S, the decays and
  T stay float32.

Both take a per-token validity mask: an invalid token has alpha = 1 and
beta = 0, which leaves S exactly as it was (its output row is garbage
nobody reads). That is what lets one program hold prompts of unequal
length in one bucket, and parked rows in a decode batch.

Plain `jax.numpy`: no kernel here (ROADMAP.md M7 asks for one against the
`linattn_core_roofline` this form sets).
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

CHUNK = 64
_BASE = 16      # blocks of (I + A) inverted row by row; larger ones merge
_EXACT = jax.lax.Precision.HIGHEST


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


def gated_delta_chunked(q, k, v, g, beta, initial_state=None, mask=None,
                        chunk: int = CHUNK):
    """A sequence, chunk by chunk. q, k [b, s, H, d_k] (normalized and
    scaled), v [b, s, H, d_v], g, beta [b, s, H] float32, initial_state
    [b, H, d_k, d_v] float32 (zeros when None), mask [b, s] bool (all
    valid when None). Returns (o [b, s, H, d_v] in v's dtype, final state
    float32). Any s: the sequence is padded to whole chunks with invalid
    tokens."""
    f32 = jnp.float32
    ad = v.dtype
    b, s, heads, dk = q.shape
    dv = v.shape[-1]
    if mask is not None:
        g = jnp.where(mask[..., None], g, 0.0)
        beta = jnp.where(mask[..., None], beta, 0.0)
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

    if initial_state is None:
        initial_state = jnp.zeros((b, heads, dk, dv), f32)
    state, o = jax.lax.scan(
        body, initial_state.astype(f32),
        (u, w, qk, q_in, k_out, c_last))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)   # [b, n, chunk, H, d_v]
    return o.reshape(b, n * chunk, heads, dv)[:, :s], state


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
