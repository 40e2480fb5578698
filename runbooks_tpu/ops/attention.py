"""Multi-head attention with GQA/MQA, packed-sequence masking, and ALiBi.

Two execution paths:
  - ``dot_product_attention``: reference XLA einsum path. fp32 softmax. XLA
    fuses this well on TPU for moderate sequence lengths and it is the
    numerically-trusted oracle for kernel tests.
  - ``runbooks_tpu.ops.flash_attention``: Pallas blockwise kernel for long
    sequences (imported lazily by ``attention`` to keep CPU tests light).

Grouped heads: K and V are never repeated per query head. The query heads
are viewed as ``[kv_heads, group]`` and each KV head is contracted against
its whole group (``bqhgd,bkhd->bhgqk``), so every (row, KV head) is one
matrix product with ``group * q_len`` rows, also at ``q_len == 1``. The
repeat-then-contract form it replaced made every (row, query head) of a
decode step a vector x matrix product, which the TPU compiler lowers to a
float32 multiply + reduce on the vector unit and not to the MXU, and under
tensor parallelism it materialized the widened K/V in float32 (AOT for
v5e:2x2, one layer, falcon-40b decode: 567 MB accessed and 134.5 MB of
temporaries against 44 MB and none; falcon-7b: 0 against 2 MXU
convolutions; PERF.md section 6, PR 25).

Masking model: a query token q may attend to key token k iff
  positions[k] <= positions[q]   (causal, by absolute position — this makes
                                  the op correct under sequence-parallel
                                  sharding and KV-cache decode)
  and segment_ids match          (packed-sequence isolation)
  and k is not padding (segment_id != 0 when segment_ids given).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from runbooks_tpu.parallel.sharding import (
    _current_mesh,
    spec_for_array,
    with_logical_constraint,
)

NEG_INF = -1e30


def make_attention_mask(
    q_positions: jax.Array,        # [b, q_len] int32 absolute positions
    kv_positions: jax.Array,       # [b, kv_len]
    q_segment_ids: Optional[jax.Array] = None,   # [b, q_len]
    kv_segment_ids: Optional[jax.Array] = None,  # [b, kv_len]
    causal: bool = True,
) -> jax.Array:
    """Boolean mask [b, 1, q_len, kv_len]; True = may attend."""
    mask = jnp.ones(
        (q_positions.shape[0], q_positions.shape[1], kv_positions.shape[1]),
        dtype=bool,
    )
    if causal:
        mask &= kv_positions[:, None, :] <= q_positions[:, :, None]
    if q_segment_ids is not None and kv_segment_ids is not None:
        mask &= q_segment_ids[:, :, None] == kv_segment_ids[:, None, :]
        mask &= kv_segment_ids[:, None, :] != 0
    return mask[:, None, :, :]


def alibi_slopes(num_heads: int) -> jax.Array:
    """ALiBi per-head slopes (geometric sequence), [num_heads] float32."""
    import math

    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]

    if math.log2(num_heads).is_integer():
        vals = pow2_slopes(num_heads)
    else:
        closest = 2 ** math.floor(math.log2(num_heads))
        vals = pow2_slopes(closest)
        extra = pow2_slopes(2 * closest)[0::2]
        vals += extra[: num_heads - closest]
    return jnp.asarray(vals, dtype=jnp.float32)


def _grouped_heads_axes(num_kv_heads: int):
    """Logical axes of a ``[b, s, kv_heads, group, d]`` view of the query
    heads. The mesh axis of the heads lies on ``kv_heads`` when it divides
    them (each device keeps whole KV heads with their groups, as K/V are
    sharded: no communication), else on ``group`` (multi-query under tensor
    parallelism: K/V replicated, each device a slice of the group)."""
    mesh = _current_mesh()
    on_kv = mesh is None or spec_for_array(
        (num_kv_heads,), ("act_heads",), mesh)[0] is not None
    heads = ("act_heads", None) if on_kv else (None, "act_heads")
    return ("batch", "seq") + heads + (None,)


def dot_product_attention(
    q: jax.Array,                   # [b, q_len, num_heads, head_dim]
    k: jax.Array,                   # [b, kv_len, num_kv_heads, head_dim]
    v: jax.Array,                   # [b, kv_len, num_kv_heads, v_dim]
    mask: Optional[jax.Array] = None,       # [b, 1|h, q_len, kv_len] bool
    bias: Optional[jax.Array] = None,       # [b|1, h, q_len, kv_len] additive
    scale: Optional[float] = None,
    logit_softcap: Optional[float] = None,
    sink: Optional[jax.Array] = None,       # [num_heads] float
) -> jax.Array:
    """Reference attention. fp32 logits/softmax, output in q.dtype.

    Query head ``h * group + r`` attends KV head ``h``; K and V stay at
    their own width (module docstring). ``sink``: one more logit a query
    head beside the keys', which takes weight in the softmax and gives no
    value (its column is dropped after the softmax)."""
    b, q_len, num_heads, head_dim = q.shape
    num_kv_heads = k.shape[2]
    group = num_heads // num_kv_heads
    scale = scale if scale is not None else head_dim ** -0.5

    def grouped(x):  # [b|1, 1|h, q_len, kv_len] -> [b|1, 1|kvh, 1|g, ...]
        if x.shape[1] == 1:
            return x[:, :, None]
        return x.reshape(x.shape[0], num_kv_heads, group, *x.shape[2:])

    axes = _grouped_heads_axes(num_kv_heads)
    q = with_logical_constraint(
        q.reshape(b, q_len, num_kv_heads, group, head_dim), axes)
    logits = jnp.einsum(
        "bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32
    )
    logits = logits * scale
    if logit_softcap is not None:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)
    if bias is not None:
        logits = logits + grouped(bias).astype(jnp.float32)
    if mask is not None:
        mask = grouped(mask)
        logits = jnp.where(mask, logits, NEG_INF)

    if sink is not None:
        # The sink as one more key column: the softmax's denominator
        # holds it, the weighted sum of values does not. A fully-masked
        # row gives it all the weight, and comes out 0.
        col = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(1, num_kv_heads, group, 1, 1),
            logits.shape[:-1] + (1,))
        probs = jax.nn.softmax(
            jnp.concatenate([logits, col], axis=-1), axis=-1)[..., :-1]
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    # Fully-masked query rows (e.g. padding) softmax to uniform; zero them so
    # padding contributes nothing downstream.
    if mask is not None:
        any_valid = jnp.any(mask, axis=-1, keepdims=True)
        probs = jnp.where(any_valid, probs, 0.0)
    out = jnp.einsum(
        "bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    out = with_logical_constraint(out, axes)
    # At the VALUE width: keys and values may differ in it (latent
    # attention: 192-wide keys, 128-wide values).
    return out.reshape(b, q_len, num_heads, v.shape[-1]).astype(q.dtype)
