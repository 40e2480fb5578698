"""Pallas TPU flash attention (blockwise, O(seq) memory) with custom VJP.

Design (see /opt/skills/guides/pallas_guide.md):
- A grid step works on ONE KV head and a block of ``G`` of the query heads
  of its group (``n_rep = h // kv_h`` heads share a KV head). Forward and
  dq: grid (batch, kv_heads, q_blocks, head_blocks, kv_blocks); dkv: grid
  (batch, kv_heads, kv_blocks, q_blocks, head_blocks). TPU executes the grid
  sequentially with the last dimension innermost, so the forward keeps the
  softmax running state (m, l, acc) of its G heads across kv-block
  iterations in VMEM scratch with a leading G and finalizes on the last kv
  block it visits.
- What does not depend on the head is made once a step, not once a head:
  the mask of the (query block, kv block) pair (``_mask_bias``: padding,
  causality, window, segments, as a float32 bias of 0 / NEG_INF in
  scratch), the K and the V block (one DMA, one cast to float32 into
  scratch), the kv-side and the query-side rows (one DMA). The heads are a
  loop INSIDE the step (``jax.lax.fori_loop`` over the block's leading
  axis), so the live scores stay one head's ``[block_q, block_k]`` float32.
  Per head the mathematics and the precision are those of a step that held
  one head: same dots on the same operand types, same order of the running
  softmax, so the forward and dq are bit-equal for every G.
- ``G`` is a function of shapes alone (``head_block``), under a stated VMEM
  budget; a group of one gets G = 1. Where G does not divide the group
  (``falcon-7b``: 71, a prime) the group's LAST block is PARTIAL: the arrays
  are viewed as ``[b * kv_h, n_rep, s, d]`` and blocked ``(1, G, block, d)``,
  Pallas pads the overhanging block (what it reads there is unspecified,
  what is written there is dropped), and the loops over heads stop at the
  last real head (``_heads_here``), so the padding is never read. That was
  taken over padding the group with zero heads because it costs nothing: no
  padded copy of q / do in HBM, no cut of the outputs, no products for
  heads that are not there.
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
- A forward may take a per-query CHOICE of key blocks (``choice`` int8
  ``[b, kv_h, sq, blocks]``, ``choice_block`` keys a block; the sparse read
  of ops/block_sparse_attention.py): a query then sees, causally, its
  window AND the blocks it chose. The ranges are the causal ones (a chosen
  block lies anywhere before the window, so the grid walks every kv step
  up to ``hi``), the choice's block of a grid step is the query block's
  (the same for every kv step: copied in once a query block), and the
  step widens it to the kv block's columns ONCE for all its heads
  (``_chosen_keys``: one 0 / 1 product with "key c is of block n", exact
  in bfloat16) into the bias the heads already share. Without a choice
  nothing of the traced program differs (a Python ``if`` on ``None``).
- Backward: standard flash backward from saved logsumexp — one kernel for dq
  (kv blocks innermost) and one for dk/dv (query blocks, then head blocks
  innermost), both recomputing p blockwise. They still skip by GRID index
  (``block_skip``: exact only where q storage index i and kv storage index
  i hold the same position, the training layout), see ``flash_attention``.
- GQA-native: k/v stay at kv_heads width and no repeated k/v is ever
  materialized. The dkv kernel sums over the heads of the group in its
  float32 scratch — for each query block the heads in order, whatever G, so
  dk / dv are bit-equal for every G too — and writes dk, dv at KV-head
  width: no ``[b, h, sk, d]`` copies, no sum outside the kernel.

The block shape is a function of the call too (``block_shape``: which
kernel, the lengths, the group, the window), read from a sweep on the chip
(TPU v5 lite, PERF.md section 6, PR 39) of block_q x block_k at the calls
the benchmark's cells compile; ms a call with the transposes and the row
data around the kernel, median of 12, G by ``head_block``:
  forward                       128x128 256x256 256x512 512x512 256x1024 512x1024 1024x1024 512x2048
  LoRA [4,2048] 71 on 1, packed   13.97    9.72    7.11    7.44     6.26     6.12      6.23     7.31
  [8,2048] x 2049, 71 on 1 at 64  26.96   16.52   11.81   11.17    10.49     9.76     11.77    12.11
  [1,2048] x 2049, 71 on 1 at 64   4.20    3.01    2.29    2.24     2.04     2.04      2.14     2.27
  a 40b shard, 16 on 2 at 64       2.37    1.76    1.37    1.41     1.26     1.31      1.37     1.41
  latent expanded, 64 x 1, 192/128 7.21    4.17    3.46    3.25     3.27     2.96      3.10     3.40
  30 x 1 at 128                    2.79    1.81    1.48    1.45     1.40     1.34      1.50     1.61
  64 on 4 at 192 / 128             5.12    3.20    2.56    2.56     2.39     2.35      2.61     2.79
  48 on 8 at 128                   3.35    2.45    1.95    1.86     1.77     1.72      1.85     1.91
  64 on 8 at 128, window 512       2.96    2.26    1.93    1.95     1.87     1.87      2.16     2.24
  64 on 8, 192/128, window 128     2.46    2.21    2.08    2.22     2.12     2.19      2.60     2.77
  (sweep to sweep a reading moves 2-4 %.) Interleaved, 25 rounds, at
  512x1024 / 256x1024 / 512x512 / 256x512: the last row 2.130 / 2.092 /
  2.082 / 1.973, its widths at window 256 2.223 / 2.193 / 2.192 / 2.048;
  64 on 8 at 128: window 512 1.865 / 1.940 / 1.939 / 1.953, window 256
  1.778 / 1.786 / 1.856 / 1.759, window 128 1.785 / 1.746 / 1.842 / 1.755.
  forward under a choice (PR 46; 32 heads on 2 at 128, window 2048, 257
  blocks of 64 keys, 16 385 keys; ms a call WITH the choice's 20.2 / 10.5 /
  72.7 ms of select_blocks before it, median of 8, G = 16):
                                  256x512 512x512 1024x512 256x1024 512x1024 1024x1024 256x2048 512x2048
  [1, 16384], 14 592 real tokens    48.95   46.89    50.19    38.82    37.00     38.35    38.02    38.33
  [1, 8192]                         20.25   19.40    20.23    17.05    16.37     16.57    17.15    17.24
  [8, 8192]                             -  144.41        -   126.86   119.65         -        -        -
  (the XLA walk it replaced, with the same select: 110.65 / 40.97 / 297.22.)
  512 x 1024 again, so block_shape has no rule for a choice.
  backward, LoRA rows             256x256 256x512 512x256 512x512 512x1024 1024x512 1024x1024
  dq                                 9.39    7.83    8.64    7.65     7.65     7.79      8.92
  dkv                               12.99    9.39   10.77    7.74     8.13     8.20      8.27
  both in one call, interleaved: 13.107 at 512x1024, 12.830 at 512x512.
Finer blocks LOSE, with a group a step as without one: a 512 x 512 forward
step visits 19 % fewer scores of the packed rows than 512 x 1024 and takes
22 % longer. What a step shares (the step's fixed cost, the mask, K / V)
is no longer what a small block pays for; the loop over heads is: by the
compiler's own schedule a head and block costs 2644 bundles at 512 x 1024,
1925 at 512 x 512, 1610 at 512 x 256, 1519 at 256 x 1024 and 861 at
256 x 256 — about 1200 bundles a head that do not shrink with the key
block (the running softmax's chain from the scores' pop over max, exp and
sum to the value product and the accumulator's rescale, once a head and
key step, which nothing overlaps: the heads of a step run one after the
other). dq (3122 -> 1644 bundles) and dkv (4031 -> 2313) have no such
chain, so the backward takes the smaller key block for the blocks its
static skip then leaves out. Larger blocks lose what they visit beyond the
mask. So: the forward stays at 512 x 1024 whatever the group, but under a
window of up to 256 with a group a step (256 x 512: a key block that holds
a query block and its window); the backward runs at 512 x 512.

VMEM arithmetic behind ``head_block`` (every element counted at 4 bytes, a
block's last two dims rounded up to the (8, 128) tile; ``tile(r, c)``
below). At the forward's blocks 512 x 1024 and d = dv = 64, for dq, which
binds there:
  a step, whatever G   rows 2 x 2 x (tile(bq, 128) + tile(8, bk))  1.1 MiB
                       K, V blocks, two buffers each               2.0
                       their float32 casts                         1.0
                       the bias tile(bq, bk)                       2.0
                       live scores of ONE head, 4 x tile(bq, bk)   8.0
  a head               q, do, dq: two buffers each                 1.5
                       lse, delta lane-broadcast, two buffers      1.0
                       dq scratch                                  0.25
so G = (VMEM_BUDGET_BYTES - 14.1 MiB) // 2.75 MiB = 18 of 71 heads (dkv:
17.1 MiB a step with dk, dv and 2 MiB a head; the forward: 10.1 and 2.25).
Each kernel asks Mosaic for what is counted for it (``_ask_vmem``: at 18
heads 50.6 MiB the forward, 63.6 dq, 53.1 dkv; bf16 blocks take half of
what is counted for them, which is the compiler's room for what this sum
cannot see), and a step of one head asks for nothing and holds neither
the casts nor the bias in scratch. Asking is not free — a 2 ms forward
that asked for 96 MiB took 0.2-0.8 ms longer on the chip than under the
default, whatever it used — so nothing asks for more than it counts.
What more heads a step buy flattens early — on the chip the LoRA forward
alone reads 7.59 ms at one head a step, 6.70 at 4, 6.28 at 8, 6.06 at 16,
6.05 at 20 — because the loop over heads is bound by the MXU's slots (73 %
full in the forward's schedule, 94 % in dq's and dkv's, and the same with
bf16 operands), not by what a step shares (PERF.md section 6, PR 37).

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

# What head_block lets the buffers it can count take of a v5e core's 128 MiB
# of VMEM (every element at 4 bytes, so bf16 calls stay far below). A step
# of several heads asks Mosaic for what is counted for its kernel and no
# more; a step of one head asks for nothing (Mosaic's default of 16 MiB, as
# before there were blocks). The limit is not free: on the chip a forward
# of 2 ms that asked for 96 MiB took 0.2-0.8 ms longer than the same
# kernel under the default, whatever it then used (PERF.md section 6,
# PR 37).
VMEM_BUDGET_BYTES = 64 * 2 ** 20


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
# How many query heads a grid step holds
# ---------------------------------------------------------------------------

def _tile(rows: int, cols: int) -> int:
    """Bytes of a [rows, cols] block in VMEM at 4 bytes an element: the
    last two dims are rounded up to the (8, 128) tile (a bf16 block takes
    half, which the budget does not count on)."""
    return 4 * (-(-rows // SUBLANES) * SUBLANES) * (-(-cols // LANES) * LANES)


def vmem_by_kernel(g: int, block_q: int, block_k: int, d: int, dv: int,
                   sink: bool = False, choice_blocks: int = 0) -> dict:
    """{kernel: bytes}: what a grid step of ``g`` heads of the forward, dq
    and dkv holds in VMEM, as far as shapes say it. The module docstring
    has the sum in numbers. ``choice_blocks``: the width of the forward's
    block choice, 0 for none."""
    bq_d, bq_dv, bq_row = _tile(block_q, d), _tile(block_q, dv), \
        _tile(block_q, LANES)
    scores, kv = _tile(block_q, block_k), _tile(block_k, d) + _tile(block_k, dv)
    # Whatever g: the row data, K and V (two buffers and the float32 cast),
    # the bias.
    a_step = 2 * 2 * (bq_row + _tile(SUBLANES, block_k)) + 3 * kv + scores
    # The choice of a query block in two buffers, and while it is widened
    # to the key block's columns: which block a key is of, the widened
    # choice.
    chosen = (2 * _tile(block_q, choice_blocks)
              + _tile(choice_blocks, block_k) + scores) if choice_blocks else 0
    return {
        # One head's s and p live; a head's q, o, lse in two buffers, its
        # m, l, acc, its sink's tile.
        "fwd": a_step + chosen + 2 * scores + g * (
            2 * (bq_d + bq_dv + bq_row) + 2 * _tile(block_q, 1) + bq_dv
            + (2 * _tile(SUBLANES, LANES) if sink else 0)),
        # s, p, dp, ds live; a head's q, do, lse, delta, dq in two buffers
        # and dq's scratch.
        "dq": a_step + 4 * scores + g * (
            2 * (2 * bq_d + bq_dv + 2 * bq_row) + bq_d),
        # dk, dv (scratch and two out buffers) beside the scores; a head's
        # q, do, lse, delta in two buffers.
        "dkv": a_step + 4 * scores + 3 * kv + g * 2 * (
            bq_d + bq_dv + 2 * bq_row)}


def vmem_bytes(g: int, block_q: int, block_k: int, d: int, dv: int,
               sink: bool = False, window: int = 0,
               choice_blocks: int = 0) -> int:
    """The largest of vmem_by_kernel over the kernels the call has: a call
    with a sink, a window, a block choice or values of another width than
    keys has a forward only (flash_attention refuses its backward).
    ``window`` takes no VMEM (it shortens the grid)."""
    by_kernel = vmem_by_kernel(g, block_q, block_k, d, dv, sink,
                               choice_blocks)
    if sink or window or choice_blocks or d != dv:
        return by_kernel["fwd"]
    return max(by_kernel.values())


def _ask_vmem(kernel: str, g: int, *shape) -> pltpu.CompilerParams:
    """What a kernel asks Mosaic for: what is counted for a step of ``g``
    heads of it (vmem_by_kernel; head_block keeps that under the budget),
    or nothing for a step of one head."""
    if g == 1:
        return pltpu.CompilerParams()
    return pltpu.CompilerParams(
        vmem_limit_bytes=vmem_by_kernel(g, *shape)[kernel])


def head_block(n_rep: int, block_q: int, block_k: int, d: int, dv: int,
               sink: bool = False, window: int = 0,
               choice_blocks: int = 0) -> int:
    """G: how many of a KV head's ``n_rep`` query heads one grid step of
    the kernels holds. A pure function of shapes: the most heads that
    ``vmem_bytes`` puts under VMEM_BUDGET_BYTES, then evened out over the
    blocks the group needs (71 heads at most 18 a step are 4 blocks, three
    of 18 and a last one of 17; 32 heads at most 18 are 2 blocks of 16).
    One G for the kernels of a call at one block shape (the forward at
    its own, dq and dkv at theirs), sized by the kernel that needs most,
    so a program has one number to report. A group of one gets
    1, which is the kernel of one head a step. Never less than 1: blocks
    too large for the budget at G = 1 are the caller's to shrink, as
    before."""
    most = max((g for g in range(1, n_rep + 1)
                if vmem_bytes(g, block_q, block_k, d, dv, sink, window,
                              choice_blocks)
                <= VMEM_BUDGET_BYTES), default=1)
    return -(-n_rep // -(-n_rep // most))


# ---------------------------------------------------------------------------
# The block shape of a call
# ---------------------------------------------------------------------------

KERNELS = ("fwd", "bwd")       # the backward's two kernels share a shape


def block_shape(kernel: str, sq: int, sk: int, n_rep: int, window: int = 0,
                block_q: Optional[int] = None,
                block_k: Optional[int] = None) -> tuple:
    """(block_q, block_k) of a flash call, Python ints from what is static
    when the call is traced: which kernel ("fwd", or "bwd" for dq and
    dkv, which share the padded query side), the lengths, the group
    ``n_rep`` a grid step draws its heads from, the window. A given
    ``block_q`` / ``block_k`` is honoured (tests pin small ones); the
    answer is clamped to the lengths, which keeps it a multiple of the
    (8, 128) tile or the whole length. The table is the module
    docstring's chip sweep:
    - forward: 512 x 1024, the flat optimum of every call swept;
    - forward under a window, where a step holds a group and a key block
      of 512 holds a query block of 256 with its window (windows up to
      256): 256 x 512;
    - backward: 512 x 512 (the static skip by grid index visits 62.5 % of
      a 2048 x 2048 rectangle where 512 x 1024 visits 75 %)."""
    if kernel == "bwd":
        rule = (512, 512)
    elif window and n_rep > 1 and 256 + window <= 512:
        rule = (256, 512)
    else:
        rule = (512, 1024)
    return (min(rule[0] if block_q is None else block_q, sq),
            min(rule[1] if block_k is None else block_k, sk))


def blocks_of_call(kernel: str, q, k, block_q: Optional[int],
                   block_k: Optional[int], window: int = 0) -> tuple:
    """block_shape of the arrays of a call: q [b, sq, h, d], k
    [b, sk, kv_h, d]."""
    return block_shape(kernel, q.shape[1], k.shape[1],
                       q.shape[2] // k.shape[2], window, block_q, block_k)


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


def _chosen_keys(chosen_ref, kp, block: int):
    """A query block's choice of key blocks [bq, blocks] (0 / 1, a narrow
    type) widened to the columns of one kv block: [bq, bk] bool, whether a
    query chose the block of ``block`` keys that holds the key at position
    kp [1, bk]. One product with the 0 / 1 matrix "key c is of block n",
    exact in any type that holds 0 and 1: a key of no block the operand
    has (padding) is of none."""
    chosen = chosen_ref[0, 0]                                 # [bq, blocks]
    first = block * jax.lax.broadcasted_iota(
        jnp.int32, (chosen.shape[1], kp.shape[1]), 0)
    of_block = jnp.logical_and(first <= kp, kp < first + block)
    return jax.lax.dot_general(
        chosen.astype(jnp.bfloat16), of_block.astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32) > 0


def _mask_bias(q_pos_ref, kv_pos_ref, q_seg_ref, kv_seg_ref, shape, *,
               causal: bool, use_segments: bool, window: int = 0,
               choice=None):
    """The mask of one (query block, kv block) pair as what a score takes
    on: 0 where the query sees the key, NEG_INF where it does not (padding
    key, causality, window, another segment). It depends on no head, so a
    grid step makes it once for every head it holds. score + 0 is the
    score and score + NEG_INF is NEG_INF exactly in float32, so adding it
    is ``where(mask, score, NEG_INF)`` to the bit, and exp of a masked
    score minus any row maximum or logsumexp is exactly 0 with no second
    select. ``choice``: (the query block's choice of key blocks, a block's
    keys), which a query sees BESIDE its window."""
    kp = kv_pos_ref[0][:1, :]                                 # [1, bk]
    mask = jnp.broadcast_to(kp < PAD_POS, shape)   # padding keys, always
    if causal or window:
        qp = q_pos_ref[0][:, :1]                              # [bq, 1]
    if causal:
        mask = jnp.logical_and(mask, kp <= qp)
    if choice is not None:
        seen = _chosen_keys(choice[0], kp, choice[1])
        if window:
            seen = jnp.logical_or(seen, qp - kp < window)
        mask = jnp.logical_and(mask, seen)
    elif window:
        mask = jnp.logical_and(mask, qp - kp < window)
    if use_segments:
        ks = kv_seg_ref[0][:1, :]
        mask = jnp.logical_and(mask, q_seg_ref[0][:, :1] == ks)
        mask = jnp.logical_and(mask, ks != 0)
    return jnp.where(mask, 0.0, NEG_INF).astype(jnp.float32)


def _heads_here(block_axis: int, n_rep: int, g: int):
    """How many heads of the block a grid step holds are real: all ``g``,
    but in a group's last block where ``g`` does not divide the group.
    The rest of that block lies past the array's end: Pallas copies
    nothing in for it and nothing out, and the loops over heads stop
    before it, so what its VMEM holds is never read."""
    if n_rep % g == 0:
        return g
    return jnp.minimum(g, n_rep - pl.program_id(block_axis) * g)


def _for_heads(heads, g: int, head) -> None:
    """``head(i)`` for the step's real heads in turn; a step of one head
    (static) is that head's straight-line code, as before there were
    blocks."""
    if g == 1:
        head(0)
    else:
        jax.lax.fori_loop(0, heads, lambda i, carry: head(i), None)


def _shared(rows, q_ref, k_ref, v_ref, scratch, **mask):
    """What a step makes once for all its heads, as a function a head
    calls to read it: K and V in float32 and the bias of the mask
    (_mask_bias of the four row refs ``rows`` under the flags ``mask``). A
    step of several heads makes them into ``scratch`` (k, v, bias) and
    each head loads them; a step of one head has nothing to share, gets no
    scratch, and keeps them as values: the step of before there were
    blocks, under Mosaic's default VMEM limit."""
    if scratch:
        k_scr, v_scr, bias_scr = scratch
        k_scr[:] = k_ref[0, 0].astype(jnp.float32)            # [bk, d]
        v_scr[:] = v_ref[0, 0].astype(jnp.float32)            # [bk, dv]
        bias_scr[:] = _mask_bias(*rows, bias_scr.shape, **mask)  # [bq, bk]
        return lambda: (k_scr[:], v_scr[:], bias_scr[:])
    k, v = k_ref[0, 0].astype(jnp.float32), v_ref[0, 0].astype(jnp.float32)
    bias = _mask_bias(*rows, (q_ref.shape[2], k_ref.shape[2]), **mask)
    return lambda: (k, v, bias)


def _shared_scratch(g: int, block_q: int, block_k: int, d: int, dv: int):
    """_shared's scratch: none for a step of one head."""
    if g == 1:
        return []
    return [pltpu.VMEM((block_k, d), jnp.float32),                # k
            pltpu.VMEM((block_k, dv), jnp.float32),               # v
            pltpu.VMEM((block_q, block_k), jnp.float32)]          # bias


def _fwd_kernel(lo_ref, hi_ref,                        # scalar prefetch
                q_pos_ref, kv_pos_ref, q_seg_ref, kv_seg_ref,
                q_ref, k_ref, v_ref, *rest,
                scale: float, causal: bool, use_segments: bool,
                n_rep: int, window: int = 0, has_sink: bool = False,
                choice_block: int = 0):
    # rest: [sink_ref,] [chosen_ref,] o_ref, lse_ref, then scratch: the
    # running state a head (m, l, acc) and, for a step of several heads,
    # what it makes once for all of them (_shared).
    # Grid (b, kv_h, q blocks, head blocks, kv steps).
    rest = list(rest)
    sink_ref = rest.pop(0) if has_sink else None
    mask = dict(causal=causal, use_segments=use_segments, window=window)
    if choice_block:
        mask["choice"] = (rest.pop(0), choice_block)
    o_ref, lse_ref, m_scr, l_scr, acc_scr, *scratch = rest
    g = q_ref.shape[1]
    heads = _heads_here(3, n_rep, g)
    step = pl.program_id(4)
    lo = lo_ref[pl.program_id(0), pl.program_id(2)]
    hi = hi_ref[pl.program_id(0), pl.program_id(2)]
    # With a window the grid walks blocks FROM lo (grid_ranges says how
    # many), else every kv block: under a choice too, whose blocks lie
    # anywhere before the window.
    kv_idx = step + lo if window and not choice_block else step

    @pl.when(jnp.logical_and(hi < lo, step == 0))
    def _nothing_to_see():
        # No query of this block sees any key (a bucket's padded tail).
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)
        lse_ref[0] = jnp.full(lse_ref.shape[1:], NEG_INF, lse_ref.dtype)

    @pl.when(jnp.logical_and(lo <= kv_idx, kv_idx <= hi))
    def _body():
        @pl.when(kv_idx == lo)
        def _init():
            m_scr[:] = jnp.full_like(m_scr, NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

        shared = _shared(
            (q_pos_ref, kv_pos_ref, q_seg_ref, kv_seg_ref), q_ref, k_ref,
            v_ref, scratch, **mask)

        def head(i):
            k, v, bias = shared()
            q = q_ref[0, i].astype(jnp.float32)               # [bq, d]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale + bias

            m_prev = m_scr[i]                                 # [bq, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            # Rows with no valid key yet keep m == NEG_INF; guard the exp
            # shift.
            m_safe = jnp.where(m_new <= NEG_INF, 0.0, m_new)
            p = jnp.exp(s - m_safe)                # exactly 0 where masked

            alpha = jnp.where(m_prev <= NEG_INF, 0.0,
                              jnp.exp(m_prev - m_safe))
            l_scr[i] = alpha * l_scr[i] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[i] = acc_scr[i] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[i] = m_new

        _for_heads(heads, g, head)

        @pl.when(kv_idx == hi)
        def _finalize():
            def head(i):
                l = l_scr[i]
                m = m_scr[i]
                acc = acc_scr[i]
                if has_sink:
                    # The sink joins the softmax here, as one more logit
                    # of this head that gives no value: the running state
                    # moves to max(m, sink) and the sum takes its term.
                    sink = sink_ref[0, i][:1, :1]             # [1, 1]
                    m_all = jnp.maximum(m, sink)
                    alpha = jnp.where(m <= NEG_INF, 0.0, jnp.exp(m - m_all))
                    l = alpha * l + jnp.exp(sink - m_all)
                    acc = acc * alpha
                    m = m_all
                l_safe = jnp.where(l == 0.0, 1.0, l)      # fully-masked rows
                o_ref[0, i] = (acc / l_safe).astype(o_ref.dtype)
                lse = jnp.where(l == 0.0, NEG_INF, m + jnp.log(l_safe))
                lse_ref[0, i] = jnp.broadcast_to(lse, lse_ref.shape[2:])

            _for_heads(heads, g, head)


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
               block_q=None, block_k=None, out_dtype=None, qside=None,
               window=0, sink=None, choice=None, choice_block=0):
    b, sq, h, d = q.shape
    # Values may be narrower or wider than keys (latent attention expanded:
    # 192-wide q and k, 128-wide v): the accumulator and the output take
    # the value width.
    dv = v.shape[-1]
    sk = k.shape[1]
    kv_h = k.shape[2]
    n_rep = h // kv_h
    block_q, block_k = blocks_of_call("fwd", q, k, block_q, block_k, window)
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
    # A query's chosen blocks lie anywhere before its window: under a
    # choice the ranges are the causal ones.
    walk = window if choice is None else 0
    lo, hi, kv_steps = grid_ranges(q_pos, kv_pos, q_seg, kv_seg, block_q,
                                   block_k, causal, walk)

    def kv_block(bi, qi, ki, lo_ref, hi_ref):
        # Outside [lo, hi] the index repeats the nearest block inside it —
        # the same index as a neighbouring iteration, so Pallas issues no
        # DMA, and pl.when skips the compute. (An empty range points at
        # block 0.)
        first = lo_ref[bi, qi]
        if walk:
            ki = ki + first
        return jnp.clip(ki, first, jnp.maximum(first, hi_ref[bi, qi]))

    choice_specs, choice_args, choice_blocks = [], (), 0
    if choice is not None:
        # [b, kv_h, sq, blocks] as given, the blocks padded to whole lanes
        # (a block nobody chose). Its block of a grid step does not depend
        # on the kv step: it is copied in once a query block.
        choice_blocks = pl.cdiv(choice.shape[3], LANES) * LANES
        choice_specs = [pl.BlockSpec((1, 1, block_q, choice_blocks),
                                     lambda bi, kh, qi, hb, ki, *_:
                                     (bi, kh, qi, 0))]
        choice_args = (_pad_to(_pad_to(choice, sq_p, 2), choice_blocks, 3),)

    # The query heads as [b * kv_h, n_rep, ...]: a KV head's group is one
    # row, blocked G heads a step; G need not divide it (module docstring).
    g = head_block(n_rep, block_q, block_k, d, dv, sink is not None, window,
                   choice_blocks)

    def q_map(bi, kh, qi, hb, ki, *_):
        return (bi * kv_h + kh, hb, qi, 0)

    def kv_map(bi, kh, qi, hb, ki, *ranges):
        return (bi, kh, kv_block(bi, qi, ki, *ranges), 0)

    def qrow_map(bi, kh, qi, hb, ki, *_):
        return (bi, qi, 0)

    def krow_map(bi, kh, qi, hb, ki, *ranges):
        return (bi, 0, kv_block(bi, qi, ki, *ranges))

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, use_segments=use_segments,
        n_rep=n_rep, window=window, has_sink=sink is not None,
        choice_block=choice_block)
    sink_specs, sink_args = [], ()
    if sink is not None:
        # One tile a head, the layout note at the top of the file.
        sink_specs = [pl.BlockSpec((1, g, SUBLANES, LANES),
                                   lambda bi, kh, qi, hb, ki, *_:
                                   (kh, hb, 0, 0))]
        sink_args = (jax.lax.broadcast_in_dim(
            sink.astype(jnp.float32).reshape(kv_h, n_rep),
            (kv_h, n_rep, SUBLANES, LANES), (0, 1)),)

    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                                # lo, hi
            grid=(b, kv_h, sq_p // block_q, pl.cdiv(n_rep, g), kv_steps),
            in_specs=[
                pl.BlockSpec((1, block_q, LANES), qrow_map),      # q_pos
                pl.BlockSpec((1, SUBLANES, block_k), krow_map),   # kv_pos
                pl.BlockSpec((1, block_q, LANES), qrow_map),      # q_seg
                pl.BlockSpec((1, SUBLANES, block_k), krow_map),   # kv_seg
                pl.BlockSpec((1, g, block_q, d), q_map),          # q
                pl.BlockSpec((1, 1, block_k, d), kv_map),         # k
                pl.BlockSpec((1, 1, block_k, dv), kv_map),        # v
                *sink_specs,
                *choice_specs,
            ],
            out_specs=[
                pl.BlockSpec((1, g, block_q, dv), q_map),
                pl.BlockSpec((1, g, block_q, LANES), q_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((g, block_q, 1), jnp.float32),         # m
                pltpu.VMEM((g, block_q, 1), jnp.float32),         # l
                pltpu.VMEM((g, block_q, dv), jnp.float32),        # acc
                *_shared_scratch(g, block_q, block_k, d, dv),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b * kv_h, n_rep, sq_p, dv),
                                 out_dtype or q.dtype),
            jax.ShapeDtypeStruct((b * kv_h, n_rep, sq_p, LANES),
                                 jnp.float32),
        ],
        compiler_params=_ask_vmem("fwd", g, block_q, block_k, d, dv,
                                  sink is not None, choice_blocks),
        interpret=_interpret(),
    )(lo, hi, q_pos_l, _bcast_sublanes(kv_pos_p),
      q_seg_l, _bcast_sublanes(kv_seg_p),
      qT.reshape(b * kv_h, n_rep, sq_p, d), kT, vT, *sink_args,
      *choice_args)
    out = out.reshape(b, h, sq_p, dv)
    lse = lse.reshape(b, h, sq_p, LANES)

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
                   dq_ref, dq_scr, *scratch,
                   scale, causal, use_segments, n_rep,
                   block_q, block_k, block_skip):
    # Grid (b, kv_h, q blocks, head blocks, kv blocks).
    g = q_ref.shape[1]
    heads = _heads_here(3, n_rep, g)
    kv_idx = pl.program_id(4)
    num_kv = pl.num_programs(4)
    if block_skip and causal:
        last_kv = _last_valid_kv(pl.program_id(2), block_q, block_k, num_kv)
    else:
        last_kv = num_kv - 1

    @pl.when(kv_idx <= last_kv)
    def _body():
        @pl.when(kv_idx == 0)
        def _init():
            dq_scr[:] = jnp.zeros_like(dq_scr)

        shared = _shared(
            (q_pos_ref, kv_pos_ref, q_seg_ref, kv_seg_ref), q_ref, k_ref,
            v_ref, scratch, causal=causal, use_segments=use_segments)

        def head(i):
            k, v, bias = shared()
            q = q_ref[0, i].astype(jnp.float32)
            do = do_ref[0, i].astype(jnp.float32)
            lse = lse_ref[0, i][:, :1]                        # [bq, 1]
            delta = delta_ref[0, i][:, :1]                    # [bq, 1]

            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale + bias
            lse_safe = jnp.where(lse <= NEG_INF, 0.0, lse)
            p = jnp.exp(s - lse_safe)              # exactly 0 where masked

            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * scale
            dq_scr[i] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        _for_heads(heads, g, head)

        @pl.when(kv_idx == last_kv)
        def _finalize():
            dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _first_valid_q(ki, block_q: int, block_k: int, num_q):
    """First q-block index that can see any key in kv block ki (causal,
    globally monotone positions) — the mirror of _last_valid_kv. Clamped to
    num_q-1 so kv blocks entirely past the last q row (sk > sq) still run
    one fully-masked iteration and write true zeros to dk/dv."""
    return jnp.minimum(num_q - 1, (ki * block_k) // block_q)


def _bwd_dkv_kernel(q_pos_ref, kv_pos_ref, q_seg_ref, kv_seg_ref,
                    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *scratch,
                    scale, causal, use_segments, n_rep,
                    block_q, block_k, block_skip):
    # Grid (b, kv_h, kv blocks, q blocks, head blocks): dk_scr / dv_scr take
    # every head of the group and every query block before they are
    # written, once, at KV-head width — for each query block the heads in
    # order, so the float32 sum does not depend on G.
    g = q_ref.shape[1]
    heads = _heads_here(4, n_rep, g)
    q_idx, head_idx = pl.program_id(3), pl.program_id(4)
    num_q, num_head_blocks = pl.num_programs(3), pl.num_programs(4)
    if block_skip and causal:
        first_q = _first_valid_q(pl.program_id(2), block_q, block_k, num_q)
    else:
        first_q = 0

    @pl.when(q_idx >= first_q)
    def _body():
        @pl.when(jnp.logical_and(q_idx == first_q, head_idx == 0))
        def _init():
            dk_scr[:] = jnp.zeros_like(dk_scr)
            dv_scr[:] = jnp.zeros_like(dv_scr)

        shared = _shared(
            (q_pos_ref, kv_pos_ref, q_seg_ref, kv_seg_ref), q_ref, k_ref,
            v_ref, scratch, causal=causal, use_segments=use_segments)

        def head(i):
            k, v, bias = shared()
            q = q_ref[0, i].astype(jnp.float32)
            do = do_ref[0, i].astype(jnp.float32)
            lse = lse_ref[0, i][:, :1]
            delta = delta_ref[0, i][:, :1]

            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale + bias
            lse_safe = jnp.where(lse <= NEG_INF, 0.0, lse)
            p = jnp.exp(s - lse_safe)    # [bq, bk], exactly 0 where masked

            dv_scr[:] += jax.lax.dot_general(
                p, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * scale                      # [bq, bk]
            dk_scr[:] += jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        _for_heads(heads, g, head)

        @pl.when(jnp.logical_and(q_idx == num_q - 1,
                                 head_idx == num_head_blocks - 1))
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
    h, kv_h = heads_per_shard(q.shape[2], k.shape[2],
                              int(mesh.shape.get("tensor", 1)))
    return _ShardPlan(mesh, batch, "tensor" if h < q.shape[2] else None,
                      "tensor" if kv_h < k.shape[2] else None)


def heads_per_shard(h: int, kv_h: int, tp: int) -> tuple:
    """(query heads, kv heads) a device's launch of the kernels holds
    under a tensor mesh of ``tp``: both divided where both divide; a
    single kv head whole on every shard beside its share of the group
    (multi-query: head_block is handed that share); else nothing shards."""
    if tp > 1 and h % tp == 0 and kv_h % tp == 0:
        return h // tp, kv_h // tp
    if tp > 1 and h % tp == 0 and kv_h == 1:
        return h // tp, 1
    return h, kv_h


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
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    block_skip: bool = True,
    window: int = 0,
    sink: Optional[jax.Array] = None,  # [h] float
    choice: Optional[jax.Array] = None,   # [b, kv_h, sq, blocks] 0 / 1
    choice_block: int = 0,
) -> jax.Array:
    """block_q / block_k: None = block_shape's answer for the call (the
    forward's and the backward's may differ: the residual lse is a row,
    not a block); an integer is honoured, clamped to the lengths.

    window > 0: a query at position t sees only keys j with t - j <
    window (beside causality), the ranges follow it and the grid shrinks
    to the blocks a window can span (_flash_fwd). sink: one more logit a
    query head in the softmax, which takes weight and gives no value.
    choice: which blocks of ``choice_block`` keys (block n: the positions
    [n choice_block, (n + 1) choice_block)) each query reads BESIDE its
    window, one choice a KV head for all the heads of its group, in a
    narrow type that holds 0 and 1 (int8); still causal. The forward then
    visits the causal ranges and not the window's, and a grid step widens
    its query block's choice to the kv block's columns once for all its
    heads (_chosen_keys). All three are the FORWARD's alone:
    differentiating such a call raises WindowSinkBackward (a choice:
    BlockChoiceBackward), and under a multi-device mesh it is refused.

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
    if window or sink is not None or choice is not None:
        if _shard_plan(q, k) is not None:
            raise NotImplementedError(
                "flash attention with a window, a sink or a block choice "
                "runs on one device: its per-shard launch is not written")
        if choice is None:
            only, more = _forward_only, {}
        else:
            only = _chosen_forward_only
            more = dict(choice=choice, choice_block=choice_block)
        with jax.named_scope("flash.fwd"):
            out, _ = _flash_fwd(
                jax.lax.stop_gradient(q), jax.lax.stop_gradient(k),
                jax.lax.stop_gradient(v), q_positions, kv_positions,
                q_segment_ids, kv_segment_ids, scale_v, causal, block_q,
                block_k, window=window, sink=sink, **more)
        return only(out, q, k, v)

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


class BlockChoiceBackward(NotImplementedError):
    """The backward kernels know no block choice."""


def _forward_only_raising(error, why: str):
    """out -> out for a call that has a forward only: differentiating it
    raises ``error(why)``."""
    @jax.custom_vjp
    def _forward_only(out, q, k, v):
        return out

    def _forward_only_fwd(out, q, k, v):
        raise error(why)

    _forward_only.defvjp(_forward_only_fwd, lambda res, g: res)
    return _forward_only


_forward_only = _forward_only_raising(
    WindowSinkBackward,
    "flash attention's backward kernels mask by causality and segments "
    "alone and normalise over the keys alone: a call with a window or "
    "a sink has a forward only (train such layers on the XLA path, "
    "attention_impl: xla)")
_chosen_forward_only = _forward_only_raising(
    BlockChoiceBackward,
    "flash attention's backward kernels mask by causality and segments "
    "alone: a call whose queries choose their key blocks has a forward "
    "only (the choice is a step function of q and k: it has no gradient "
    "to give)")


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
                        *, causal, scale, block_q=None, block_k=None,
                        block_skip, grad_dtype=None, qside=None):
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
    block_q, block_k = blocks_of_call("bwd", q, k, block_q, block_k)
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

    g_heads = head_block(n_rep, block_q, block_k, d, d)
    head_blocks = pl.cdiv(n_rep, g_heads)
    # The query heads as [b * kv_h, n_rep, ...], as in the forward.
    qG, doG, lseG, deltaG = (x.reshape(b * kv_h, n_rep, *x.shape[2:])
                             for x in (qT, doT, lseT, deltaT))
    kernel_args = dict(scale=scale_v, causal=causal,
                       use_segments=use_segments, n_rep=n_rep,
                       block_q=block_q, block_k=block_k, block_skip=skip)
    shared_scratch = _shared_scratch(g_heads, block_q, block_k, d, d)

    def clamp_k(i, j):  # dq pass: kv block j valid only up to the diagonal
        if skip:
            return jnp.minimum(j, _last_valid_kv(i, block_q, block_k, num_kv))
        return j

    def qrow(bi, kh, i, hb, j):
        return (bi, i, 0)

    def krow(bi, kh, i, hb, j):
        return (bi, 0, clamp_k(i, j))

    def hq(bi, kh, i, hb, j):
        return (bi * kv_h + kh, hb, i, 0)

    def hk(bi, kh, i, hb, j):
        return (bi, kh, clamp_k(i, j), 0)

    # dq: grid inner dim iterates kv blocks
    dq_kernel = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **kernel_args),
        grid=(b, kv_h, num_q, head_blocks, num_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, LANES), qrow),
            pl.BlockSpec((1, SUBLANES, block_k), krow),
            pl.BlockSpec((1, block_q, LANES), qrow),
            pl.BlockSpec((1, SUBLANES, block_k), krow),
            pl.BlockSpec((1, g_heads, block_q, d), hq),
            pl.BlockSpec((1, 1, block_k, d), hk),
            pl.BlockSpec((1, 1, block_k, d), hk),
            pl.BlockSpec((1, g_heads, block_q, d), hq),
            pl.BlockSpec((1, g_heads, block_q, LANES), hq),
            pl.BlockSpec((1, g_heads, block_q, LANES), hq),
        ],
        out_specs=pl.BlockSpec((1, g_heads, block_q, d), hq),
        out_shape=jax.ShapeDtypeStruct((b * kv_h, n_rep, sq_p, d),
                                       grad_dtype or q.dtype),
        scratch_shapes=[pltpu.VMEM((g_heads, block_q, d), jnp.float32),
                        *shared_scratch],
        compiler_params=_ask_vmem("dq", g_heads, block_q, block_k, d, d),
        interpret=_interpret(),
    )
    with jax.named_scope("flash.dq"):
        dq = dq_kernel(q_pos_l, kv_pos_s, q_seg_l, kv_seg_s, qG, kT, vT,
                       doG, lseG, deltaG)

    # dk/dv: grid inner dims iterate q blocks, then the group's head blocks
    def q_step(j, i, hb):
        # Before the diagonal every step points at the first block that
        # runs, (first_q, head block 0): a repeated index issues no DMA.
        if skip:
            first = _first_valid_q(j, block_q, block_k, num_q)
            return jnp.maximum(i, first), jnp.where(i < first, 0, hb)
        return i, hb

    def hq2(bi, kh, j, i, hb):
        i, hb = q_step(j, i, hb)
        return (bi * kv_h + kh, hb, i, 0)

    def qrow2(bi, kh, j, i, hb):
        return (bi, q_step(j, i, hb)[0], 0)

    def krow2(bi, kh, j, i, hb):
        return (bi, 0, j)

    def hk2(bi, kh, j, i, hb):
        return (bi, kh, j, 0)

    dkv_kernel = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **kernel_args),
        grid=(b, kv_h, num_kv, num_q, head_blocks),
        in_specs=[
            pl.BlockSpec((1, block_q, LANES), qrow2),
            pl.BlockSpec((1, SUBLANES, block_k), krow2),
            pl.BlockSpec((1, block_q, LANES), qrow2),
            pl.BlockSpec((1, SUBLANES, block_k), krow2),
            pl.BlockSpec((1, g_heads, block_q, d), hq2),
            pl.BlockSpec((1, 1, block_k, d), hk2),
            pl.BlockSpec((1, 1, block_k, d), hk2),
            pl.BlockSpec((1, g_heads, block_q, d), hq2),
            pl.BlockSpec((1, g_heads, block_q, LANES), hq2),
            pl.BlockSpec((1, g_heads, block_q, LANES), hq2),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), hk2),
            pl.BlockSpec((1, 1, block_k, d), hk2),
        ],
        # At KV-head width: the kernel has summed over the group's heads.
        out_shape=[
            jax.ShapeDtypeStruct((b, kv_h, sk_p, d), grad_dtype or k.dtype),
            jax.ShapeDtypeStruct((b, kv_h, sk_p, d), grad_dtype or v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),                # dk
            pltpu.VMEM((block_k, d), jnp.float32),                # dv
            *shared_scratch,
        ],
        compiler_params=_ask_vmem("dkv", g_heads, block_q, block_k, d, d),
        interpret=_interpret(),
    )
    with jax.named_scope("flash.dkv"):
        dk, dv = dkv_kernel(q_pos_l, kv_pos_s, q_seg_l, kv_seg_s, qG, kT,
                            vT, doG, lseG, deltaG)

    dq = jnp.swapaxes(dq.reshape(b, h, sq_p, d)[:, :, :sq], 1, 2)
    dk = jnp.swapaxes(dk[:, :, :sk], 1, 2)
    dv = jnp.swapaxes(dv[:, :, :sk], 1, 2)
    return dq, dk, dv


_flash_core.defvjp(_vjp_fwd, _vjp_bwd)
