"""Rotary position embeddings (RoPE).

Split-halves convention (as used by Llama/NeoX): the head dim is split into
two halves which are rotated as (real, imag) pairs. Computed in float32 and
cast back; sin/cos are generated on the fly from integer positions so the op
is position-shift-friendly for KV-cache decoding and sequence-parallel shards
(each shard passes its own absolute positions).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def yarn_inv_freq(head_dim: int, theta: float, yarn: tuple) -> np.ndarray:
    """YaRN's blended inverse frequencies [head_dim//2] (float32, a
    constant of the program): pairs that turn more than beta_fast times
    inside the original context keep theta's frequency, pairs that turn
    fewer than beta_slow times take it divided by factor, and a linear
    ramp over the pair index joins the two. yarn = (factor, original max
    position, beta_fast, beta_slow, ...)."""
    factor, original, beta_fast, beta_slow = yarn[:4]
    half = head_dim // 2
    plain = 1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))

    def pair_index(turns):   # the pair that turns `turns` times
        return (head_dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_index(beta_fast)), 0)
    high = min(math.ceil(pair_index(beta_slow)), head_dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    return (plain / factor * ramp + plain * (1 - ramp)).astype(np.float32)


def rope_sin_cos(positions: jax.Array, head_dim: int, theta: float = 10000.0,
                 yarn: tuple = (), factor: float = 1.0):
    """positions [...,] int32 -> (sin, cos) each [..., head_dim//2] float32,
    both times ``factor`` (YaRN's factor on the rotated dimensions)."""
    half = head_dim // 2
    if yarn:
        freq = jnp.asarray(yarn_inv_freq(head_dim, theta, yarn))
    else:
        freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32)
                                / half))
    angles = positions.astype(jnp.float32)[..., None] * freq  # [..., half]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    if factor != 1.0:
        sin, cos = sin * factor, cos * factor
    return sin, cos


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0,
               yarn: tuple = (), rotary_dim: int = 0,
               factor: float = 1.0) -> jax.Array:
    """Apply RoPE. x: [batch, seq, heads, head_dim]; positions: [batch, seq].
    rotary_dim (0 = head_dim): only the first rotary_dim dimensions of a
    head rotate, as a head of that width would (pairs (x_i,
    x_{i + rotary_dim/2}), frequencies theta^(-2i/rotary_dim), YaRN's
    blend over that width); the rest pass unchanged. ``factor`` multiplies
    sin and cos, so the rotated dimensions alone: between a query and a key
    rotated alike, their part of a score carries its square and the part
    that passes carries 1."""
    if rotary_dim and rotary_dim < x.shape[-1]:
        return jnp.concatenate(
            [apply_rope(x[..., :rotary_dim], positions, theta, yarn,
                        factor=factor),
             x[..., rotary_dim:]], axis=-1)
    dtype = x.dtype
    half = x.shape[-1] // 2
    sin, cos = rope_sin_cos(positions, x.shape[-1], theta, yarn,
                            factor)                       # [b, s, half]
    sin = sin[:, :, None, :]  # broadcast over heads
    cos = cos[:, :, None, :]
    x = x.astype(jnp.float32)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dtype)
