"""Pallas TPU flash attention (blockwise, O(seq) memory) with custom VJP.

Design (see /opt/skills/guides/pallas_guide.md):
- Grid (batch, heads, q_blocks, kv_blocks); TPU executes the grid sequentially
  with the last dimension innermost, so the kernel accumulates the softmax
  running state (m, l, acc) across kv-block iterations in VMEM scratch and
  finalizes on the last kv block it visits.
- fp32 accumulation throughout; inputs may be bf16.
- Masking is by absolute position (causal) + optional segment ids (packed
  sequences), matching runbooks_tpu.ops.attention semantics so the XLA path
  is a drop-in numerical oracle.
- The forward visits only the kv blocks a query block can see. Which those
  are is computed from the positions and segment ids of the call itself
  (``block_ranges``: one ``[lo, hi]`` a batch row and query block), not from
  grid indices and not from a caller's flag, and rides into the kernel as
  scalar-prefetch operands: the k / v index maps clamp the kv index into the
  range (a repeated block index issues no DMA) and the body runs only inside
  it. The element-wise mask is unchanged, so any range that covers the
  needed blocks is exact: a cached prefill whose queries start at a prefix
  length, a bucket's padded tail, ``sk != sq``, a rotated ring shard and a
  packed training batch all take the one path, and a block whose every
  score the mask would set to NEG_INF is never loaded or computed.
- Backward: standard flash backward from saved logsumexp — one kernel for dq
  (grid over q blocks) and one for dk/dv (grid over kv blocks), both
  recomputing p blockwise. They still skip by GRID index (``block_skip``:
  exact only where q storage index i and kv storage index i hold the same
  position, the training layout), see ``flash_attention``.
- GQA-native: k/v stay at kv_heads width; the BlockSpec index map routes
  q head hi to kv head hi // n_rep, so no repeated k/v is ever materialized.

On non-TPU backends the kernels run in interpreter mode (tests). The
default ``attention_impl="auto"`` picks this kernel on TPU and the XLA
reference path elsewhere.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from runbooks_tpu.utils.hw import on_tpu

NEG_INF = -1e30
PAD_POS = 2 ** 30  # kv-position sentinel for padding; always masked
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128

# Mosaic requires the last two dims of every block to be (multiples of the
# (8, 128) tile) or equal to the array dims. Row metadata (positions/segment
# ids) and per-row residuals (lse, delta) are therefore carried in
# tile-friendly layouts, the same convention as the reference TPU kernels in
# jax.experimental.pallas.ops.tpu.flash_attention: q-side rows broadcast
# across LANES ([b, sq, 128], block [1, bq, 128]), kv-side rows broadcast
# across SUBLANES ([b, 8, sk], block [1, 8, bk]), lse/delta stored
# lane-broadcast ([b, h, sq, 128]).
LANES = 128
SUBLANES = 8


def _bcast_lanes(x):  # [b, s] -> [b, s, LANES]
    return jax.lax.broadcast_in_dim(x, (*x.shape, LANES), (0, 1))


def _bcast_sublanes(x):  # [b, s] -> [b, SUBLANES, s]
    return jax.lax.broadcast_in_dim(x, (x.shape[0], SUBLANES, x.shape[1]),
                                    (0, 2))


def _interpret() -> bool:
    # Mosaic on a TPU, the Pallas interpreter everywhere else; a function
    # of the one probe (utils/hw.on_tpu), so nothing on a TPU interprets.
    return not on_tpu()


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def block_ranges(q_pos, kv_pos, q_seg, kv_seg, block_q: int, block_k: int,
                 causal: bool, window: int = 0):
    """Which kv blocks each query block has to visit: ``(lo, hi)``, int32
    ``[b, q_blocks]``, the hull of the needed kv blocks of every batch row
    and query block; an empty range is ``lo = 0, hi = -1``.

    kv block j is needed by query block i iff some valid key of j can be
    seen by some valid query of i. A key is valid unless it is padding
    (position PAD_POS, or segment 0 where segments are given); a query is
    valid unless its segment is 0. Segments: the two blocks' spans of
    segment ids overlap. Causal: the least valid key of j is not above the
    greatest valid query of i — by position alone without segments, and
    with them by (segment, position) in dictionary order, which a key and
    a query of one document keep (in a packed row a later block starts a
    document at position 0, below every query position, and is still not
    needed). Window (``window`` > 0: a query at position t sees the keys j
    with t - j < window, beside causality): the greatest valid key of j is
    less than a window below the least valid query of i, by position over
    all their valid entries whatever their segments (queries at negative
    positions apart, which see nothing), so ``lo`` rises with the queries
    as ``hi`` does. Every unmasked (query, key) pair satisfies these, whatever
    the layout (offset, non-monotone or repeated positions, sk != sq,
    lengths that are no multiple of a block), so the hull never drops a
    block that holds one, and the kernel's element-wise mask makes any
    superset exact.

    q_pos [b, sq], kv_pos [b, sk], q_seg / kv_seg the same shapes or None.
    NumPy arrays give NumPy results (the serving engine counts blocks on
    the host with this same function), anything else goes through jax.numpy.
    Blocks are clamped to the lengths as the kernel clamps them."""
    xp = np if isinstance(q_pos, np.ndarray) else jnp
    b, sq = q_pos.shape
    sk = kv_pos.shape[1]
    block_q, block_k = min(block_q, sq), min(block_k, sk)
    lowest, highest = np.iinfo(np.int32).min, np.iinfo(np.int32).max

    def blocks(x, block, fill):          # [b, s] -> [b, s_p // block, block]
        pad = -x.shape[1] % block
        x = xp.pad(x.astype(xp.int32), ((0, 0), (0, pad)),
                   constant_values=fill)
        return x.reshape(b, -1, block)

    def span(x, ok):                     # least and greatest valid entry
        return (xp.where(ok, x, highest).min(axis=-1),
                xp.where(ok, x, lowest).max(axis=-1))

    def pairs(of_keys, of_queries):      # [b, nk], [b, nq] -> [b, nq, nk]
        return of_keys[:, None, :], of_queries[:, :, None]

    qp, kp = blocks(q_pos, block_q, 0), blocks(kv_pos, block_k, PAD_POS)
    q_ok = blocks(xp.ones_like(q_pos), block_q, 0) != 0
    k_ok = kp < PAD_POS
    if q_seg is not None:
        qs, ks = blocks(q_seg, block_q, 0), blocks(kv_seg, block_k, 0)
        q_ok, k_ok = q_ok & (qs != 0), k_ok & (ks != 0)
        (q_first, q_last), (k_first, k_last) = span(qs, q_ok), span(ks, k_ok)
    need = q_ok.any(axis=-1)[:, :, None] & k_ok.any(axis=-1)[:, None, :]
    if window:
        # A query parked at a negative position sees no key (the callers'
        # convention for a token that is nobody's): it does not hold lo
        # down.
        k_max, q_min = pairs(span(kp, k_ok)[1],
                             span(qp, q_ok & (qp >= 0))[0])
        need &= k_max > q_min - window
    if q_seg is not None:
        k_lo, q_hi = pairs(k_first, q_last)
        k_hi, q_lo = pairs(k_last, q_first)
        need &= (k_lo <= q_hi) & (q_lo <= k_hi)
        # What is left of the causal test: the least position of a block's
        # first segment against the greatest of the other's last one.
        k_ok = k_ok & (ks == k_first[..., None])
        q_ok = q_ok & (qs == q_last[..., None])
    if causal:
        k_min, q_max = pairs(span(kp, k_ok)[0], span(qp, q_ok)[1])
        before = k_min <= q_max
        need &= before if q_seg is None else (k_lo < q_hi) | before
    j = xp.arange(need.shape[-1], dtype=xp.int32)
    hi = xp.where(need, j, -1).max(axis=-1)
    lo = xp.where(need, j, highest).min(axis=-1)
    return xp.where(hi < 0, 0, lo).astype(xp.int32), hi.astype(xp.int32)


def grid_ranges(q_pos, kv_pos, q_seg, kv_seg, block_q: int, block_k: int,
                causal: bool, window: int = 0):
    """``block_ranges`` as the forward's grid walks them: (lo, hi, steps),
    ``steps`` the length of the grid's kv axis. Without a window that is
    every kv block. A window bounds how many kv blocks a query block can
    see — those that can hold the block_q + window - 1 consecutive
    positions its queries see, whatever their alignment — so the grid
    walks that many FROM lo and not the whole kv extent: a grid step costs
    its overhead even where nothing is computed. Exact where a row's valid
    queries and keys each hold consecutive positions at consecutive
    indices (a prompt, a cache view, documents packed one behind the
    other): the hull is then no longer than this. The clamp of hi keeps
    any other layout finite, not exact."""
    xp = np if isinstance(q_pos, np.ndarray) else jnp
    sq, sk = q_pos.shape[1], kv_pos.shape[1]
    block_q, block_k = min(block_q, sq), min(block_k, sk)
    lo, hi = block_ranges(q_pos, kv_pos, q_seg, kv_seg, block_q, block_k,
                          causal, window)
    steps = -(-sk // block_k)
    if window:
        steps = min(steps, (block_q + window - 2) // block_k + 2)
        hi = xp.minimum(hi, lo + steps - 1)
    return lo, hi, steps


def block_counts(q_pos, kv_pos, q_seg, kv_seg, block_q: int, block_k: int,
                 causal: bool, window: int = 0):
    """(visited, grid): how many (query block, kv block) pairs a head of
    the forward computes for these host arrays, and how many its grid has."""
    lo, hi, steps = grid_ranges(q_pos, kv_pos, q_seg, kv_seg, block_q,
                                block_k, causal, window)
    return int(np.maximum(hi - lo + 1, 0).sum()), lo.size * steps


def _fwd_kernel(lo_ref, hi_ref,                        # scalar prefetch
                q_pos_ref, kv_pos_ref, q_seg_ref, kv_seg_ref,
                q_ref, k_ref, v_ref, *rest,
                scale: float, causal: bool, use_segments: bool,
                window: int = 0, has_sink: bool = False):
    # rest: [sink_ref,] o_ref, lse_ref, m_scr, l_scr, acc_scr.
    sink_ref = rest[0] if has_sink else None
    o_ref, lse_ref, m_scr, l_scr, acc_scr = rest[-5:]
    step = pl.program_id(3)
    lo = lo_ref[pl.program_id(0), pl.program_id(2)]
    hi = hi_ref[pl.program_id(0), pl.program_id(2)]
    # With a window the grid walks blocks FROM lo (grid_ranges says how
    # many), else every kv block.
    kv_idx = step + lo if window else step

    @pl.when(jnp.logical_and(hi < lo, step == 0))
    def _nothing_to_see():
        # No query of this block sees any key (a bucket's padded tail).
        o_ref[0, 0] = jnp.zeros(o_ref.shape[2:], o_ref.dtype)
        lse_ref[0, 0] = jnp.full(lse_ref.shape[2:], NEG_INF, lse_ref.dtype)

    @pl.when(jnp.logical_and(lo <= kv_idx, kv_idx <= hi))
    def _body():
        @pl.when(kv_idx == lo)
        def _init():
            m_scr[:] = jnp.full_like(m_scr, NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

        q = q_ref[0, 0].astype(jnp.float32)           # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)           # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)           # [bk, d]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [bq, bk]

        kp = kv_pos_ref[0][:1, :]                             # [1, bk]
        mask = kp < PAD_POS  # padding keys masked regardless of causality
        mask = jnp.broadcast_to(mask, s.shape)
        if causal:
            qp = q_pos_ref[0][:, :1]                          # [bq, 1]
            mask = jnp.logical_and(mask, kp <= qp)
        if window:
            mask = jnp.logical_and(mask, q_pos_ref[0][:, :1] - kp < window)
        if use_segments:
            qs = q_seg_ref[0][:, :1]
            ks = kv_seg_ref[0][:1, :]
            mask = jnp.logical_and(mask, qs == ks)
            mask = jnp.logical_and(mask, ks != 0)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:]                                     # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # Rows with no valid key yet keep m == NEG_INF; guard the exp shift.
        m_safe = jnp.where(m_new <= NEG_INF, 0.0, m_new)
        p = jnp.exp(s - m_safe)
        p = jnp.where(mask, p, 0.0)

        alpha = jnp.where(m_prev <= NEG_INF, 0.0, jnp.exp(m_prev - m_safe))
        l_new = alpha * l_scr[:] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new
        l_scr[:] = l_new

        @pl.when(kv_idx == hi)
        def _finalize():
            l = l_scr[:]
            m = m_scr[:]
            acc = acc_scr[:]
            if has_sink:
                # The sink joins the softmax here, as one more logit of
                # this head that gives no value: the running state moves
                # to max(m, sink) and the sum takes its term.
                sink = sink_ref[0][:1, :1]                    # [1, 1]
                m_all = jnp.maximum(m, sink)
                alpha = jnp.where(m <= NEG_INF, 0.0, jnp.exp(m - m_all))
                l = alpha * l + jnp.exp(sink - m_all)
                acc = acc * alpha
                m = m_all
            l_safe = jnp.where(l == 0.0, 1.0, l)          # fully-masked rows
            o_ref[0, 0] = (acc / l_safe).astype(o_ref.dtype)
            lse = jnp.where(l == 0.0, NEG_INF, m + jnp.log(l_safe))  # [bq,1]
            lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _pad_to(x, size, axis, value=0):
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def flash_fwd_qside(q, q_pos, q_seg, block_q):
    """Query-side kernel prep (layout transpose + padded lane broadcasts),
    split out so ring attention can hoist it OUT of its per-K/V-block scan
    — it is invariant across ring steps and XLA does not reliably hoist it
    from a while-loop body."""
    b, sq, h, d = q.shape
    bq = min(block_q, sq)
    sq_p = pl.cdiv(sq, bq) * bq
    # Layout [b, h, s, d] for kernel-friendly blocking. Padding queries
    # produce garbage rows that are sliced off.
    qT = _pad_to(jnp.swapaxes(q, 1, 2), sq_p, 2)
    q_pos_p = _pad_to(q_pos.astype(jnp.int32), sq_p, 1, value=0)
    use_segments = q_seg is not None
    q_seg_p = (_pad_to(q_seg.astype(jnp.int32), sq_p, 1, value=0)
               if use_segments else jnp.zeros_like(q_pos_p))
    return (qT, _bcast_lanes(q_pos_p), _bcast_lanes(q_seg_p), use_segments)


def _flash_fwd(q, k, v, q_pos, kv_pos, q_seg, kv_seg, scale, causal,
               block_q, block_k, out_dtype=None, qside=None, window=0,
               sink=None):
    b, sq, h, d = q.shape
    # Values may be narrower or wider than keys (latent attention expanded:
    # 192-wide q and k, 128-wide v): the accumulator and the output take
    # the value width.
    dv = v.shape[-1]
    sk = k.shape[1]
    kv_h = k.shape[2]
    n_rep = h // kv_h
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    sq_p = pl.cdiv(sq, block_q) * block_q
    sk_p = pl.cdiv(sk, block_k) * block_k

    if qside is None:
        qside = flash_fwd_qside(q, q_pos, q_seg, block_q)
    qT, q_pos_l, q_seg_l, use_segments = qside
    kT = _pad_to(jnp.swapaxes(k, 1, 2), sk_p, 2)
    vT = _pad_to(jnp.swapaxes(v, 1, 2), sk_p, 2)
    # Padding keys get segment 0 + positions beyond any query so that causal
    # and segment masks both kill them.
    kv_pos_p = _pad_to(kv_pos.astype(jnp.int32), sk_p, 1, value=PAD_POS)
    kv_seg_p = (_pad_to(kv_seg.astype(jnp.int32), sk_p, 1, value=0)
                if use_segments else jnp.zeros_like(kv_pos_p))
    lo, hi, kv_steps = grid_ranges(q_pos, kv_pos, q_seg, kv_seg, block_q,
                                   block_k, causal, window)

    def kv_block(bi, qi, ki, lo_ref, hi_ref):
        # Outside [lo, hi] the index repeats the nearest block inside it —
        # the same index as a neighbouring iteration, so Pallas issues no
        # DMA, and pl.when skips the compute. (An empty range points at
        # block 0.)
        first = lo_ref[bi, qi]
        if window:
            ki = ki + first
        return jnp.clip(ki, first, jnp.maximum(first, hi_ref[bi, qi]))

    def q_map(bi, hi, qi, ki, *_):
        return (bi, hi, qi, 0)

    def kv_map(bi, hi, qi, ki, *ranges):
        # GQA: q head hi reads kv head hi // n_rep — no repeated HBM copy.
        return (bi, hi // n_rep, kv_block(bi, qi, ki, *ranges), 0)

    def qrow_map(bi, hi, qi, ki, *_):
        return (bi, qi, 0)

    def krow_map(bi, hi, qi, ki, *ranges):
        return (bi, 0, kv_block(bi, qi, ki, *ranges))

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, use_segments=use_segments,
        window=window, has_sink=sink is not None)
    sink_specs, sink_args = [], ()
    if sink is not None:
        # One tile a head, the layout note at the top of the file.
        sink_specs = [pl.BlockSpec((1, SUBLANES, LANES),
                                   lambda bi, hi, qi, ki, *_: (hi, 0, 0))]
        sink_args = (jax.lax.broadcast_in_dim(
            sink.astype(jnp.float32), (h, SUBLANES, LANES), (0,)),)

    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                                # lo, hi
            grid=(b, h, sq_p // block_q, kv_steps),
            in_specs=[
                pl.BlockSpec((1, block_q, LANES), qrow_map),      # q_pos
                pl.BlockSpec((1, SUBLANES, block_k), krow_map),   # kv_pos
                pl.BlockSpec((1, block_q, LANES), qrow_map),      # q_seg
                pl.BlockSpec((1, SUBLANES, block_k), krow_map),   # kv_seg
                pl.BlockSpec((1, 1, block_q, d), q_map),          # q
                pl.BlockSpec((1, 1, block_k, d), kv_map),         # k
                pl.BlockSpec((1, 1, block_k, dv), kv_map),        # v
                *sink_specs,
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_q, dv), q_map),
                pl.BlockSpec((1, 1, block_q, LANES), q_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, dv), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq_p, dv), out_dtype or q.dtype),
            jax.ShapeDtypeStruct((b, h, sq_p, LANES), jnp.float32),
        ],
        interpret=_interpret(),
    )(lo, hi, q_pos_l, _bcast_sublanes(kv_pos_p),
      q_seg_l, _bcast_sublanes(kv_seg_p), qT, kT, vT, *sink_args)

    out = jnp.swapaxes(out[:, :, :sq], 1, 2)          # [b, sq, h, dv]
    return out, lse[:, :, :sq, 0]


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------

def _last_valid_kv(qi, block_q: int, block_k: int, num_kv):
    """Last kv-block index that can contain an unmasked key for q block qi,
    under causal masking with globally monotone positions (standard training
    layout, including contiguous packing: a later global index is either a
    future position or a later segment — masked either way)."""
    return jnp.minimum(num_kv - 1, ((qi + 1) * block_q - 1) // block_k)


def _bwd_dq_kernel(q_pos_ref, kv_pos_ref, q_seg_ref, kv_seg_ref,
                   q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr,
                   *, scale, causal, use_segments,
                   block_q, block_k, block_skip):
    kv_idx = pl.program_id(3)
    num_kv = pl.num_programs(3)
    if block_skip and causal:
        last_kv = _last_valid_kv(pl.program_id(2), block_q, block_k, num_kv)
    else:
        last_kv = num_kv - 1

    @pl.when(kv_idx <= last_kv)
    def _body():
        @pl.when(kv_idx == 0)
        def _init():
            dq_scr[:] = jnp.zeros_like(dq_scr)

        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, :1]                            # [bq, 1]
        delta = delta_ref[0, 0][:, :1]                        # [bq, 1]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = jnp.broadcast_to(kv_pos_ref[0][:1, :] < PAD_POS, s.shape)
        if causal:
            mask = jnp.logical_and(
                mask, kv_pos_ref[0][:1, :] <= q_pos_ref[0][:, :1])
        if use_segments:
            mask = jnp.logical_and(
                mask, q_seg_ref[0][:, :1] == kv_seg_ref[0][:1, :])
            mask = jnp.logical_and(mask, kv_seg_ref[0][:1, :] != 0)
        lse_safe = jnp.where(lse <= NEG_INF, 0.0, lse)
        p = jnp.where(mask, jnp.exp(s - lse_safe), 0.0)

        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_scr[:] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

        @pl.when(kv_idx == last_kv)
        def _finalize():
            dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _first_valid_q(ki, block_q: int, block_k: int, num_q):
    """First q-block index that can see any key in kv block ki (causal,
    globally monotone positions) — the mirror of _last_valid_kv. Clamped to
    num_q-1 so kv blocks entirely past the last q row (sk > sq) still run
    one fully-masked iteration and write true zeros to dk/dv."""
    return jnp.minimum(num_q - 1, (ki * block_k) // block_q)


def _bwd_dkv_kernel(q_pos_ref, kv_pos_ref, q_seg_ref, kv_seg_ref,
                    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale, causal, use_segments,
                    block_q, block_k, block_skip):
    q_idx = pl.program_id(3)
    num_q = pl.num_programs(3)
    if block_skip and causal:
        first_q = _first_valid_q(pl.program_id(2), block_q, block_k, num_q)
    else:
        first_q = 0

    @pl.when(q_idx >= first_q)
    def _body():
        @pl.when(q_idx == first_q)
        def _init():
            dk_scr[:] = jnp.zeros_like(dk_scr)
            dv_scr[:] = jnp.zeros_like(dv_scr)

        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = jnp.broadcast_to(kv_pos_ref[0][:1, :] < PAD_POS, s.shape)
        if causal:
            mask = jnp.logical_and(
                mask, kv_pos_ref[0][:1, :] <= q_pos_ref[0][:, :1])
        if use_segments:
            mask = jnp.logical_and(
                mask, q_seg_ref[0][:, :1] == kv_seg_ref[0][:1, :])
            mask = jnp.logical_and(mask, kv_seg_ref[0][:1, :] != 0)
        lse_safe = jnp.where(lse <= NEG_INF, 0.0, lse)
        p = jnp.where(mask, jnp.exp(s - lse_safe), 0.0)        # [bq, bk]

        dv_scr[:] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale                          # [bq, bk]
        dk_scr[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

        @pl.when(q_idx == num_q - 1)
        def _finalize():
            dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
            dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# Running the kernels under a multi-device mesh
# ---------------------------------------------------------------------------

class _ShardPlan(NamedTuple):
    mesh: Any
    batch: Any                 # mesh axes the batch dim shards over, or None
    heads: Optional[str]       # mesh axis the query heads shard over
    kv_heads: Optional[str]    # ... and the kv heads (None: replicated)


def _shard_plan(q, k) -> Optional[_ShardPlan]:
    """How to launch the kernels under the ambient mesh; None on a single
    device. Mosaic kernels cannot be partitioned by GSPMD ("Mosaic kernels
    cannot be automatically partitioned. Please wrap the call in a
    shard_map"), so on a multi-device mesh each device runs the kernel on
    its own shard: batch over (data, fsdp) and heads over tensor — the
    layout the surrounding projections already produce, so no resharding.
    Heads shard only when the GQA grouping survives it: kv heads divide
    too, or there is a single kv head every shard reads whole."""
    from runbooks_tpu.parallel.sharding import _current_mesh, spec_for_array

    mesh = _current_mesh()
    if mesh is None or mesh.size == 1:
        return None
    batch = spec_for_array(q.shape[:1], ("batch",), mesh)[0]
    tp = int(mesh.shape.get("tensor", 1))
    h, kv_h = q.shape[2], k.shape[2]
    heads = kv_heads = None
    if tp > 1 and h % tp == 0 and kv_h % tp == 0:
        heads = kv_heads = "tensor"
    elif tp > 1 and h % tp == 0 and kv_h == 1:
        heads = "tensor"
    return _ShardPlan(mesh, batch, heads, kv_heads)


def _per_shard(fn, plan: Optional[_ShardPlan], in_kinds, out_kinds):
    """fn as a shard_map over every (not already manual) mesh axis, its
    operands laid out by kind: "q" [b, s, h, d], "kv" [b, s, kv_h, d],
    "row" [b, s] (None operands pass through), "lse" [b, h, s]."""
    if plan is None:
        return fn
    spec = {"q": P(plan.batch, None, plan.heads, None),
            "kv": P(plan.batch, None, plan.kv_heads, None),
            "row": P(plan.batch, None),
            "lse": P(plan.batch, plan.heads, None)}
    return jax.shard_map(
        fn, mesh=plan.mesh,
        in_specs=tuple(spec[kind] for kind in in_kinds),
        out_specs=tuple(spec[kind] for kind in out_kinds),
        axis_names=(frozenset(plan.mesh.axis_names)
                    - frozenset(plan.mesh.manual_axes)),
        check_vma=False)


# ---------------------------------------------------------------------------
# Public op with custom VJP
# ---------------------------------------------------------------------------

def flash_attention(
    q: jax.Array,                      # [b, sq, h, d]
    k: jax.Array,                      # [b, sk, kv_h, d] (kv_h divides h)
    v: jax.Array,
    q_positions: jax.Array,            # [b, sq] int32
    kv_positions: jax.Array,           # [b, sk] int32
    q_segment_ids: Optional[jax.Array],   # [b, sq] or None
    kv_segment_ids: Optional[jax.Array],  # [b, sk] or None
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    block_skip: bool = True,
    window: int = 0,
    sink: Optional[jax.Array] = None,  # [h] float
) -> jax.Array:
    """window > 0: a query at position t sees only keys j with t - j <
    window (beside causality), the ranges follow it and the grid shrinks
    to the blocks a window can span (_flash_fwd). sink: one more logit a
    query head in the softmax, which takes weight and gives no value. Both
    are the FORWARD's alone: differentiating such a call raises
    WindowSinkBackward, and under a multi-device mesh it is refused.

    The forward needs no hint: it visits the kv blocks its positions
    and segment ids say a query can see (block_ranges), whatever the layout.

    block_skip is the BACKWARD kernels' alone: they skip above-diagonal
    blocks by GRID index, which is exact iff q storage index i holds the
    same global position as kv storage index i (q_positions[:, i] ==
    kv_positions[:, i] — standard training layout, including contiguous
    packing). Offset layouts (e.g. q rows that start at position P > 0)
    violate this; the skip auto-disables when sq != sk, and a caller that
    differentiates through aligned lengths but misaligned positions must
    pass block_skip=False.

    Structure: the fwd kernel runs OUTSIDE the custom_vjp, and its outputs
    (out, lse) — exactly the backward kernels' residuals — enter the vjp as
    stop_gradient'ed arguments tagged with checkpoint_name. Residuals
    nested inside a custom_vjp fwd are invisible to jax.checkpoint
    policies (verified: names in a vjp-fwd don't change compiled FLOPs);
    hoisting them to the caller's trace level makes
    remat_policy="save_attn_out" actually skip the O(s^2) fwd-kernel
    recompute in the backward pass instead of only the wo projection."""
    scale_v = scale if scale is not None else q.shape[-1] ** -0.5
    # Inputs are stop_gradient'ed so linearization treats this residual-
    # producing kernel as a constant (the pallas call has no JVP rule);
    # the differentiable path runs through _flash_core's custom vjp, whose
    # q/k/v args carry the real tangents.
    if window or sink is not None:
        if _shard_plan(q, k) is not None:
            raise NotImplementedError(
                "flash attention with a window or a sink runs on one "
                "device: its per-shard launch is not written")
        with jax.named_scope("flash.fwd"):
            out, _ = _flash_fwd(
                jax.lax.stop_gradient(q), jax.lax.stop_gradient(k),
                jax.lax.stop_gradient(v), q_positions, kv_positions,
                q_segment_ids, kv_segment_ids, scale_v, causal, block_q,
                block_k, window=window, sink=sink)
        return _forward_only(out, q, k, v)

    def fwd(q, k, v, q_pos, kv_pos, q_seg, kv_seg):
        return _flash_fwd(q, k, v, q_pos, kv_pos, q_seg, kv_seg,
                          scale_v, causal, block_q, block_k)

    with jax.named_scope("flash.fwd"):
        out, lse = _per_shard(
            fwd, _shard_plan(q, k),
            ("q", "kv", "kv", "row", "row", "row", "row"), ("q", "lse"))(
            jax.lax.stop_gradient(q), jax.lax.stop_gradient(k),
            jax.lax.stop_gradient(v), q_positions, kv_positions,
            q_segment_ids, kv_segment_ids)
    out = checkpoint_name(out, "attn_context")
    lse = checkpoint_name(lse, "attn_lse")
    return _flash_core(
        q, k, v, q_positions, kv_positions, q_segment_ids, kv_segment_ids,
        out, lse, causal, scale_v, block_q, block_k, block_skip)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11, 12, 13))
def _flash_core(q, k, v, q_pos, kv_pos, q_seg, kv_seg, out, lse,
                causal, scale, block_q, block_k, block_skip):
    return out


class WindowSinkBackward(NotImplementedError):
    """The backward kernels know neither a window nor a sink."""


@jax.custom_vjp
def _forward_only(out, q, k, v):
    return out


def _forward_only_fwd(out, q, k, v):
    raise WindowSinkBackward(
        "flash attention's backward kernels mask by causality and segments "
        "alone and normalise over the keys alone: a call with a window or "
        "a sink has a forward only (train such layers on the XLA path, "
        "attention_impl: xla)")


_forward_only.defvjp(_forward_only_fwd, lambda res, g: res)


class UnequalWidthsBackward(NotImplementedError):
    """The backward kernels are written for keys and values of one width."""


def _vjp_fwd(q, k, v, q_pos, kv_pos, q_seg, kv_seg, out, lse,
             causal, scale, block_q, block_k, block_skip):
    if v.shape[-1] != q.shape[-1]:
        raise UnequalWidthsBackward(
            f"flash attention's backward takes q, k and v of one width; got "
            f"{q.shape[-1]} and {v.shape[-1]} (the forward alone takes a "
            "value width of its own)")
    return out, (q, k, v, q_pos, kv_pos, q_seg, kv_seg, out, lse)


def _vjp_bwd(causal, scale, block_q, block_k, block_skip, res, g):
    q, k, v, q_pos, kv_pos, q_seg, kv_seg, out, lse = res
    plan = _shard_plan(q, k)

    def bwd(q, k, v, q_pos, kv_pos, q_seg, kv_seg, out, lse, g):
        dq, dk, dv = flash_attention_bwd(
            q, k, v, q_pos, kv_pos, q_seg, kv_seg, out, lse, g,
            causal=causal, scale=scale, block_q=block_q, block_k=block_k,
            block_skip=block_skip)
        if plan is not None and plan.heads != plan.kv_heads:
            # Multi-query under head sharding: each shard holds its query
            # heads' share of the one kv head's gradient.
            dk, dv = jax.lax.psum((dk, dv), plan.heads)
        return dq, dk, dv

    dq, dk, dv = _per_shard(
        bwd, plan, ("q", "kv", "kv", "row", "row", "row", "row", "q", "lse",
                    "q"), ("q", "kv", "kv"))(
        q, k, v, q_pos, kv_pos, q_seg, kv_seg, out, lse, g)
    # Zero cotangents for the hoisted residual args (out, lse): the real
    # attention gradient routes entirely through q/k/v, and the producers
    # are stop_gradient'ed at the call site so these zeros are dropped.
    return (dq, dk, dv, None, None, None, None,
            jnp.zeros_like(out), jnp.zeros_like(lse))


def flash_bwd_qside(q, g, out, lse, q_pos, q_seg, block_q):
    """Query-side backward prep: the delta reduction and the lane-broadcast
    [b, h, sq_p, LANES] f32 lse/delta buffers (see layout note at top of
    file) plus padded q/do transposes. Invariant across ring steps — ring
    attention hoists this out of its backward scan so the (n-1)-step ring
    pays the delta reduction and 128x broadcasts once, not per step."""
    b, sq, h, d = q.shape
    bq = min(block_q, sq)
    sq_p = pl.cdiv(sq, bq) * bq
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                # [b, sq, h]
    deltaT = jax.lax.broadcast_in_dim(
        _pad_to(jnp.swapaxes(delta, 1, 2), sq_p, 2),
        (b, h, sq_p, LANES), (0, 1, 2))
    lseT = jax.lax.broadcast_in_dim(
        _pad_to(lse, sq_p, 2, value=NEG_INF),
        (b, h, sq_p, LANES), (0, 1, 2))
    qT = _pad_to(jnp.swapaxes(q, 1, 2), sq_p, 2)
    doT = _pad_to(jnp.swapaxes(g, 1, 2), sq_p, 2)
    q_pos_p = _pad_to(q_pos.astype(jnp.int32), sq_p, 1, value=-(2**30))
    use_segments = q_seg is not None
    q_seg_p = (_pad_to(q_seg.astype(jnp.int32), sq_p, 1, value=0)
               if use_segments else jnp.zeros_like(q_pos_p))
    return (qT, doT, lseT, deltaT, _bcast_lanes(q_pos_p),
            _bcast_lanes(q_seg_p), use_segments)


def flash_attention_bwd(q, k, v, q_pos, kv_pos, q_seg, kv_seg, out, lse, g,
                        *, causal, scale, block_q, block_k, block_skip,
                        grad_dtype=None, qside=None):
    """Backward kernels (dq, dkv) given the GLOBAL (out, lse) for these
    queries. Besides serving flash_attention's vjp, this is the per-block
    building block of ring attention's backward pass: with global lse the
    per-block probabilities exp(s - lse) are exact global-softmax slices,
    so summing block dq (and ring-accumulating dk/dv) is the exact
    gradient (parallel/ring_attention.py). grad_dtype overrides the
    gradient dtype (ring accumulates partial grads in f32 across steps);
    qside takes a precomputed flash_bwd_qside result."""
    scale_v = scale  # always concrete: callers resolve None
    b, sq, h, d = q.shape
    sk = k.shape[1]
    kv_h = k.shape[2]
    n_rep = h // kv_h
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    sq_p = pl.cdiv(sq, block_q) * block_q
    sk_p = pl.cdiv(sk, block_k) * block_k

    if qside is None:
        qside = flash_bwd_qside(q, g, out, lse, q_pos, q_seg, block_q)
    qT, doT, lseT, deltaT, q_pos_l, q_seg_l, use_segments = qside
    kT = _pad_to(jnp.swapaxes(k, 1, 2), sk_p, 2)
    vT = _pad_to(jnp.swapaxes(v, 1, 2), sk_p, 2)
    kv_pos_p = _pad_to(kv_pos.astype(jnp.int32), sk_p, 1, value=PAD_POS)
    kv_seg_p = (_pad_to(kv_seg.astype(jnp.int32), sk_p, 1, value=0)
                if use_segments else jnp.zeros_like(kv_pos_p))

    kv_pos_s = _bcast_sublanes(kv_pos_p)
    kv_seg_s = _bcast_sublanes(kv_seg_p)

    # Grid-index skip is only exact when q index i and kv index i carry the
    # same global position; unequal lengths guarantee misalignment.
    skip = bool(block_skip and causal and sq == sk)
    num_kv = sk_p // block_k
    num_q = sq_p // block_q

    def clamp_k(i, j):  # dq pass: kv block j valid only up to the diagonal
        if skip:
            return jnp.minimum(j, _last_valid_kv(i, block_q, block_k, num_kv))
        return j

    def clamp_q(j, i):  # dkv pass: q block i valid only from the diagonal on
        if skip:
            return jnp.maximum(i, _first_valid_q(j, block_q, block_k, num_q))
        return i

    def qrow(bi, hi, i, j):
        return (bi, i, 0)

    def krow(bi, hi, i, j):
        return (bi, 0, clamp_k(i, j))

    def hq(bi, hi, i, j):
        return (bi, hi, i, 0)

    def hk(bi, hi, i, j):
        return (bi, hi // n_rep, clamp_k(i, j), 0)

    # dq: grid inner dim iterates kv blocks
    dq_kernel = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale_v, causal=causal,
                          use_segments=use_segments, block_q=block_q,
                          block_k=block_k, block_skip=skip),
        grid=(b, h, sq_p // block_q, sk_p // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, LANES), qrow),
            pl.BlockSpec((1, SUBLANES, block_k), krow),
            pl.BlockSpec((1, block_q, LANES), qrow),
            pl.BlockSpec((1, SUBLANES, block_k), krow),
            pl.BlockSpec((1, 1, block_q, d), hq),
            pl.BlockSpec((1, 1, block_k, d), hk),
            pl.BlockSpec((1, 1, block_k, d), hk),
            pl.BlockSpec((1, 1, block_q, d), hq),
            pl.BlockSpec((1, 1, block_q, LANES), hq),
            pl.BlockSpec((1, 1, block_q, LANES), hq),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), hq),
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, d),
                                       grad_dtype or q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=_interpret(),
    )
    with jax.named_scope("flash.dq"):
        dq = dq_kernel(q_pos_l, kv_pos_s, q_seg_l, kv_seg_s, qT, kT, vT,
                       doT, lseT, deltaT)

    # dk/dv: grid inner dim iterates q blocks
    def hq2(bi, hi, j, i):
        return (bi, hi, clamp_q(j, i), 0)

    def qrow2(bi, hi, j, i):
        return (bi, clamp_q(j, i), 0)

    def hk2_read(bi, hi, j, i):
        return (bi, hi // n_rep, j, 0)

    def hk2_write(bi, hi, j, i):
        return (bi, hi, j, 0)

    dkv_kernel = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale_v, causal=causal,
                          use_segments=use_segments, block_q=block_q,
                          block_k=block_k, block_skip=skip),
        grid=(b, h, sk_p // block_k, sq_p // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, LANES), qrow2),
            pl.BlockSpec((1, SUBLANES, block_k),
                         lambda bi, hi, j, i: (bi, 0, j)),
            pl.BlockSpec((1, block_q, LANES), qrow2),
            pl.BlockSpec((1, SUBLANES, block_k),
                         lambda bi, hi, j, i: (bi, 0, j)),
            pl.BlockSpec((1, 1, block_q, d), hq2),
            pl.BlockSpec((1, 1, block_k, d), hk2_read),
            pl.BlockSpec((1, 1, block_k, d), hk2_read),
            pl.BlockSpec((1, 1, block_q, d), hq2),
            pl.BlockSpec((1, 1, block_q, LANES), hq2),
            pl.BlockSpec((1, 1, block_q, LANES), hq2),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), hk2_write),
            pl.BlockSpec((1, 1, block_k, d), hk2_write),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sk_p, d), grad_dtype or k.dtype),
            jax.ShapeDtypeStruct((b, h, sk_p, d), grad_dtype or v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=_interpret(),
    )
    with jax.named_scope("flash.dkv"):
        dk, dv = dkv_kernel(q_pos_l, kv_pos_s, q_seg_l, kv_seg_s, qT, kT,
                            vT, doT, lseT, deltaT)

    dq = jnp.swapaxes(dq[:, :, :sq], 1, 2)
    # dk/dv come back at full q-head width; fold the n_rep group back onto
    # each kv head (sum over the query heads sharing it).
    dk = dk.reshape(b, kv_h, n_rep, sk_p, d).sum(axis=2)[:, :, :sk]
    dv = dv.reshape(b, kv_h, n_rep, sk_p, d).sum(axis=2)[:, :, :sk]
    dk = jnp.swapaxes(dk, 1, 2).astype(grad_dtype or k.dtype)
    dv = jnp.swapaxes(dv, 1, 2).astype(grad_dtype or v.dtype)
    return dq, dk, dv


_flash_core.defvjp(_vjp_fwd, _vjp_bwd)
