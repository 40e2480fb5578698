"""Lightning attention: the decay-only linear recurrence, the token mixer of
a ``linear_mixer: lightning`` layer.

A head keeps a fixed-size state S [d_k, d_v] instead of keys and values a
token. Per token, with one decay lambda_h in (0, 1) a head and layer:

    S_t = lambda_h S_{t-1} + k_t v_t^T
    o_t = S_t^T q_t * scale

Nothing is erased before it is written (no (I - beta k k^T) term), so a
chunk needs no triangular solve: ``ops/gated_delta.gated_delta_chunked``'s
(I + A)^-1 has no counterpart here and none is paid. Two forms of the same
recurrence:

- ``lightning_step``: one token a row, for decode. Elementwise products and
  sums in float32 (a matrix-vector product has nothing for the MXU).
- ``lightning_chunked``: a whole sequence in chunks of 128 tokens, for
  prefill and the no-cache forward. With G_i the cumulated log-decay of a
  chunk's tokens (i = 0 .. C-1, G_i = (i + 1) log lambda where every token
  is valid) a chunk that starts from S does
      O  = ((Q K^T) * D) V + exp(G) * (Q S),   D_ij = exp(G_i - G_j), i >= j
      S' = exp(G_last) S + (K * exp(G_last - G))^T V
  The products inside a chunk are batched over all chunks at once; the
  sequential part is one scan over chunks carrying S. Operands of the
  products are in the activation dtype with float32 accumulation; S and the
  decays stay float32. No power of lambda is ever inverted: every exponent
  is <= 0.

Both take a per-token validity mask: an invalid token decays nothing and
writes nothing, which leaves S exactly as it was (its output row is garbage
nobody reads). That is what lets one program hold prompts of unequal length
in one bucket, and parked rows in a decode batch.

Plain `jax.numpy`: no kernel here.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

CHUNK = 128


def decay_rates(heads: int, layer, decay_layers: int) -> jax.Array:
    """-log lambda_h [heads] float32 of one layer: s_h c_l with s_h =
    2^(-8 (h + 1) / heads) (Lightning Attention-2's slopes) and c_l = 1 -
    l / decay_layers + 1e-5 (MiniMax-01's layer factor; 1 where
    decay_layers is 0). ``layer`` is the layer's index as run, an int or a
    traced int32."""
    slopes = jnp.exp2(-8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32)
                      / heads)
    if not decay_layers:
        return slopes
    factor = 1.0 - jnp.asarray(layer, jnp.float32) / decay_layers + 1e-5
    return slopes * factor


def lightning_step(q, k, v, rate, state, scale: float, valid=None):
    """One token a row. q, k [b, H, d_k], v [b, H, d_v], rate [H] float32
    (-log lambda), state [b, H, d_k, d_v] float32, valid [b] bool or None.
    Returns (o [b, H, d_v] float32, new state)."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    new = state * jnp.exp(-rate)[None, :, None, None] \
        + k[..., :, None] * v[..., None, :]
    if valid is not None:
        new = jnp.where(valid[:, None, None, None], new, state)
    return jnp.sum(q[..., :, None] * new, axis=-2) * scale, new


def lightning_chunked(q, k, v, rate, scale: float, initial_state=None,
                      mask=None, chunk: int = CHUNK):
    """A sequence, chunk by chunk. q, k [b, s, H, d_k], v [b, s, H, d_v],
    rate [H] float32 (-log lambda), initial_state [b, H, d_k, d_v] float32
    (zeros when None), mask [b, s] bool (all valid when None). Returns
    (o [b, s, H, d_v] in v's dtype, final state float32). Any s: the
    sequence is padded to whole chunks with invalid tokens."""
    f32 = jnp.float32
    ad = v.dtype
    b, s, heads, dk = q.shape
    dv = v.shape[-1]
    valid = jnp.ones((b, s), bool) if mask is None else mask
    pad = -s % chunk
    if pad:
        widen = lambda x: jnp.pad(  # noqa: E731
            x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        q, k, v, valid = map(widen, (q, k, v, valid))
    n = (s + pad) // chunk

    def chunks(x):      # [b, s, H, ...] -> [n, b, H, chunk, ...]
        x = x.reshape((b, n, chunk, heads) + x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    # An invalid token has log-decay 0 and a zero key: it moves nothing.
    k = jnp.where(valid[..., None, None], k, jnp.zeros((), k.dtype))
    q, k, v = chunks(q), chunks(k), chunks(v)
    g = -rate.astype(f32)[None, None, :] * valid[..., None]   # [b, s, H]
    c = jnp.cumsum(chunks(g), axis=-1)                    # [n, b, H, chunk]
    seen = jnp.tril(jnp.ones((chunk, chunk), bool))
    # exp(c_i - c_j) for j <= i, 0 above the diagonal (where the
    # difference is positive and must not be exponentiated).
    decay = jnp.exp(jnp.where(seen, c[..., :, None] - c[..., None, :],
                              -jnp.inf))

    def mm(spec, x, y):
        return jnp.einsum(spec, x.astype(ad), y.astype(ad),
                          preferred_element_type=f32)

    # Inside the chunks, all of them at once.
    qk = (mm("...ik,...jk->...ij", q, k) * decay).astype(ad)
    inner = mm("...ij,...jv->...iv", qk, v)
    q_in = (q.astype(f32) * jnp.exp(c)[..., None]).astype(ad)
    c_last = c[..., -1:]
    k_out = (k.astype(f32) * jnp.exp(c_last - c)[..., None]).astype(ad)

    def body(state, xs):
        inner_i, q_i, k_i, v_i, last_i = xs
        o_i = inner_i + mm("...ik,...kv->...iv", q_i, state)
        state = state * jnp.exp(last_i)[..., None] \
            + mm("...ik,...iv->...kv", k_i, v_i)
        return state, (o_i * scale).astype(ad)

    if initial_state is None:
        initial_state = jnp.zeros((b, heads, dk, dv), f32)
    state, o = jax.lax.scan(body, initial_state.astype(f32),
                            (inner, q_in, k_out, v, c_last))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)   # [b, n, chunk, H, d_v]
    return o.reshape(b, n * chunk, heads, dv)[:, :s], state


def lightning_reference(q, k, v, rate, scale: float, initial_state=None,
                        mask=None):
    """The recurrence token by token (a scan of ``lightning_step``): the
    oracle of the tests, same arguments and results as the chunked form."""
    b, s, heads, dk = q.shape
    if initial_state is None:
        initial_state = jnp.zeros((b, heads, dk, v.shape[-1]), jnp.float32)
    valid = jnp.ones((s, b), bool) if mask is None else mask.T

    def body(state, xs):
        q_t, k_t, v_t, ok = xs
        o, state = lightning_step(q_t, k_t, v_t, rate, state, scale, ok)
        return state, o

    t_first = lambda x: jnp.moveaxis(x, 1, 0)  # noqa: E731
    state, o = jax.lax.scan(body, initial_state,
                            (t_first(q), t_first(k), t_first(v), valid))
    return jnp.moveaxis(o, 0, 1).astype(v.dtype), state
