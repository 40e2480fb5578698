"""Kimi Delta Attention (KDA): the gated delta rule with a decay a CHANNEL
of the key, the token mixer of a ``linear_mixer: kda`` layer.

A head keeps a state S [d_k, d_v] float32. Per token, with k and q of unit
length (q further scaled by d_k^-1/2), a log-decay g in R^{d_k}, g <= 0
(alpha = exp(g) in (0, 1] a channel) and a write strength beta:

    S' = Diag(alpha_t) S_{t-1}
    S_t = S' - beta_t k_t (k_t^T S') + beta_t k_t v_t^T
    o_t = S_t^T q_t

(Kimi Linear, arXiv:2510.26692.) With every channel's decay equal it is
ops/gated_delta.py's rule, number for number. The short convolution, the
normalization and the blocked inverse are that module's.

Two forms of the same recurrence:

- ``kda_step``: one token a row, for decode; elementwise, float32.
- ``kda_chunked``: a whole sequence in chunks of 64 tokens, for prefill and
  the no-cache forward. With c_i in R^{d_k} the cumulated g of the chunk,
      A[i, j] = beta_i sum_d k_id k_jd exp(c_id - c_jd)        (j < i)
      T = (I + A)^-1, W = T (beta k * exp c), U = T (beta v)
      V' = U - W S
      O  = (q * exp c) S + tril(sum_d q_id k_jd exp(c_id - c_jd)) V'
      S' = Diag(exp c_last) S + (k * exp(c_last - c))^T V'
  The decay sits INSIDE the contraction over d_k, so A and q k^T are not
  a product k k^T times a [64, 64] matrix of decays, and the factored form
  (k exp c)(k exp -c)^T overflows: with A_log up to log 16 and a softplus of
  a few units a token's g reaches -50, a chunk's c -3000. What is
  exponentiated here is never positive, whatever g <= 0 is:
    * between two sub-blocks of 16 tokens, I behind J, the difference is
      split at b, c at the last token before sub-block I:
      (k_i * exp(c_i - b)) . (k_j * exp(b - c_j)), both exponents <= 0
      since c only falls along the chunk (b lies between c_j and c_i); a
      factor that underflows to 0 stands for a product that is below
      float32's range anyway;
    * inside a diagonal 16 x 16 block the difference c_i - c_j (j <= i) is
      taken BEFORE the exponential, a channel at a time, one column j of
      the block at a step: sum_d k_id k_jd exp(c_id - c_jd) on the vector
      unit, in float32;
    * exp(c), exp(c - c_start) and exp(c_last - c) have c <= 0 and
      c_last <= c.
  It holds for every finite g <= 0 (the tests take g to -60 a token); the
  one loss is float32's own: c carries an absolute error of about
  1e-7 |c|, so a difference of two cumulated decays of thousands is good
  to 1e-4, where the decay it stands for has long underflowed.

The chunked form is ONE Pallas kernel (``_kda_kernel``: Mosaic on a TPU,
the Pallas interpreter elsewhere) on a grid of (row, block of heads, group
of chunks), as ops/gated_delta.py's: the state [hb, d_k, d_v] stays in VMEM
from a row's first chunk to its last, T is made by that module's blocked
scheme (``_inv_unit_lower_vmem``) and the products with T are taken at
float32 accuracy on bfloat16 passes (``_exact``). It differs where the
decay forces it:

- the kernel makes its own operands from what the projections and the
  short convolution wrote, so no ``[b, s, H * d_k]`` array is produced,
  relaid and read back between them and the kernel (at ``[1, 16384]`` and
  32 heads of 128 the float32 decay alone was 268 MB a layer, relaid
  twice; `PERF.md` section 6, PR 49). It reads
    * q, k and v as the convolution left them, ``[b, s, 2 H d_k + H d_v]``:
      a grid step takes `hb` heads' columns of each by a column-block index
      map into that one array where the columns are whole tiles of 128
      lanes and the three offsets whole blocks (``_reads_in_place``, a pure
      function of the shapes; else three slices of it). A head's tiles of
      q and k are brought to unit length in float32 (q further times
      d_k^-1/2) and rounded to the activation type, as ``unit_qk`` does
      for the step;
    * the decay's low-rank inner product f = x W_f_down ``[b, s, rank]``
      with `hb` heads' columns of W_f_up, dt_bias and -exp(A_log): a chunk's
      g = -exp(A_log_h) softplus(f W_f_up + dt_bias) is one pass of the MXU
      and a float32 softplus (``kda_decay``'s arithmetic), cumulated by a
      product with a lower-triangular matrix of ones (exact by ``_exact``)
      whose columns of invalid tokens are zero: the mask costs nothing;
    * beta and the validity as rows ``[.., 1, 64]``.
  o is written as it lies, ``[b, s, H * d_v]``.
- the sub-block scheme above: three products [32, d_k] x [d_k, 64] for the
  off-diagonal strips (the rows of k and q of one sub-block against every
  earlier token) and sixteen elementwise steps for the four diagonal
  blocks together.
- the state's decay is a column [d_k, 1], made from the row exp(c_last)
  by a masked sum (no transpose).
- the launch shape is a pure function of the call's shapes
  (``kernel_shape``): `hb` = the largest divisor of the heads up to 4 whose
  columns are whole tiles of 128 lanes (else every head), `cs` = 16 / hb
  chunks a step for 2-byte activations.
- ``jax.grad`` goes through ``_chunked_plain``: the operands made in plain
  `jax.numpy` (``plain_operands``: ``kda_decay`` and ``unit_qk``, the ones
  the decode step is fed) and the same equations with the decay as a
  [64, 64, d_k] tensor a chunk: the kernel's differentiation rule and its
  oracle in the tests; nothing else calls it.
- under a multi-device mesh the call is a ``shard_map`` as the gated delta
  rule's (batch over the data axes, heads over `tensor` where it divides
  them: q, k, v, W_f_up and dt_bias by columns, A_log by heads, f whole).

Both forms take a per-token validity mask: an invalid token has g = 0 and
beta = 0, which leaves S exactly as it was.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from runbooks_tpu.ops import gated_delta
from runbooks_tpu.ops.gated_delta import (
    _BASE,
    _EXACT,
    _ONE_PASS,
    CHUNK,
    _exact,
    _inv_unit_lower,
    _inv_unit_lower_vmem,
    l2_normalize,
)

_STEP_HEADS = 4         # heads a grid step holds side by side, at most
_STEP_CHUNK_HEADS = 16  # chunks x heads a step holds at 2 bytes an element
_VMEM_BYTES = 64 * 1024 * 1024


def kda_decay(f, wf_up, dt_bias, a_log):
    """The log-decay a channel, g = -exp(A_log_h) softplus(f W_f_up +
    dt_bias): f [..., rank] in the activation type (x W_f_down), wf_up
    [rank, H * d_k] (cast to f's type), dt_bias [H * d_k], a_log [H].
    Returns g [..., H, d_k] float32, g <= 0. The step's and the plain
    form's; the kernel does the same arithmetic on its tiles."""
    f32 = jnp.float32
    pre = jnp.einsum("...k,ko->...o", f, wf_up.astype(f.dtype),
                     preferred_element_type=f32) + dt_bias.astype(f32)
    g = jax.nn.softplus(pre).reshape(pre.shape[:-1] + (a_log.shape[0], -1))
    return -jnp.exp(a_log.astype(f32))[:, None] * g


def unit_qk(q, k):
    """q and k [..., d_k] as the convolution left them, brought to unit
    length over d_k in float32 (q further times d_k^-1/2) and rounded to
    their own type: what every form of the rule is fed."""
    return ((l2_normalize(q) * q.shape[-1] ** -0.5).astype(q.dtype),
            l2_normalize(k).astype(k.dtype))


def plain_operands(qkv, f, wf_up, dt_bias, a_log):
    """``kda_chunked``'s operands as the step's and the recurrence's, made
    in plain `jax.numpy`: (q, k [.., H, d_k] normalized and scaled, v [.., H,
    d_v], g [.., H, d_k] float32)."""
    heads = a_log.shape[0]
    kd = wf_up.shape[1]

    def by_head(x):
        return x.reshape(x.shape[:-1] + (heads, -1))

    q, k = unit_qk(by_head(qkv[..., :kd]), by_head(qkv[..., kd:2 * kd]))
    return q, k, by_head(qkv[..., 2 * kd:]), kda_decay(f, wf_up, dt_bias,
                                                       a_log)


def kda_step(q, k, v, g, beta, state, valid=None):
    """One token a row. q, k [b, H, d_k] (already normalized and scaled),
    v [b, H, d_v], g [b, H, d_k] and beta [b, H] float32, state [b, H, d_k,
    d_v] float32, valid [b] bool or None. Returns (o [b, H, d_v] float32,
    new state)."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    if valid is not None:
        g = jnp.where(valid[:, None, None], g, 0.0)
        beta = jnp.where(valid[:, None], beta, 0.0)
    s = state * jnp.exp(g)[..., None]
    written = jnp.sum(k[..., :, None] * s, axis=-2)          # S^T k
    delta = (v - written) * beta[..., None]
    s = s + k[..., :, None] * delta[..., None, :]
    return jnp.sum(q[..., :, None] * s, axis=-2), s


def _chunked_plain(qkv, f, wf_up, dt_bias, a_log, beta, valid,
                   initial_state, chunk: int):
    """``_chunked`` in plain `jax.numpy`: the operands by
    ``plain_operands``, then the decay of a chunk as the tensor
    exp(c_i - c_j) [chunk, chunk, d_k], the difference taken before the
    exponential. The kernel's differentiation rule and its oracle in the
    tests."""
    f32 = jnp.float32
    q, k, v, g = plain_operands(qkv, f, wf_up, dt_bias, a_log)
    g = jnp.where(valid[..., None, None] > 0, g, 0.0)
    beta = jnp.where(valid[..., None] > 0, beta, 0.0)
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

    seen = jnp.tril(jnp.ones((chunk, chunk), bool))

    def mm(spec, x, y):
        return jnp.einsum(spec, x.astype(ad), y.astype(ad),
                          preferred_element_type=f32)

    def body(state, xs):
        q_i, k_i, v_i, g_i, beta_i = xs
        qf, kf = q_i.astype(f32), k_i.astype(f32)
        c = jnp.cumsum(g_i, axis=-2)                      # [b, H, chunk, d_k]
        decay = jnp.exp(jnp.where(
            seen[..., None], c[..., :, None, :] - c[..., None, :, :],
            -jnp.inf))                                    # [.., i, j, d_k]
        kk = jnp.einsum("...id,...jd,...ijd->...ij", kf, kf, decay,
                        precision=_EXACT)
        qk = jnp.einsum("...id,...jd,...ijd->...ij", qf, kf, decay,
                        precision=_EXACT)
        a = jnp.where(jnp.tril(seen, -1), kk * beta_i[..., None], 0.0)
        t_beta = _inv_unit_lower(a) * beta_i[..., None, :]
        u = jnp.einsum("...ij,...jv->...iv", t_beta, v_i.astype(f32),
                       precision=_EXACT)
        w = jnp.einsum("...ij,...jk->...ik", t_beta, kf * jnp.exp(c),
                       precision=_EXACT)
        c_last = c[..., -1:, :]
        v_new = u - mm("...ik,...kv->...iv", w, state)
        o = mm("...ik,...kv->...iv", qf * jnp.exp(c), state) \
            + mm("...ij,...jv->...iv", qk, v_new)
        state = state * jnp.exp(jnp.swapaxes(c_last, -1, -2)) \
            + mm("...ik,...iv->...kv", kf * jnp.exp(c_last - c), v_new)
        return state, o.astype(ad)

    state, o = jax.lax.scan(
        body, initial_state.astype(f32),
        (chunks(q), chunks(k), chunks(v), chunks(g.astype(f32)),
         chunks(beta.astype(f32))))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)   # [b, n, chunk, H, d_v]
    return o.reshape(b, n * chunk, heads, dv)[:, :s], state


# ---------------------------------------------------------------------------
# The chunked form as one Pallas kernel
# ---------------------------------------------------------------------------

def kernel_shape(s: int, heads: int, dk: int, dv: int, chunk: int = CHUNK,
                 itemsize: int = 2) -> Tuple[int, int, int]:
    """(chunks a grid step, heads a grid step, padded length) of the
    kernel's launch: a pure function of the call's shapes. As many heads
    side by side as divide the heads, up to 4, if their columns of q and v
    are whole tiles of 128 lanes, else every head (a block may always span
    an axis whole); then as many chunks as keep a step's blocks small
    beside the float32 values a chunk makes."""
    n = -(-s // chunk)
    fits = [d for d in range(1, min(heads, _STEP_HEADS) + 1)
            if heads % d == 0 and d * dk % 128 == 0 and d * dv % 128 == 0]
    hb = max(fits) if fits else heads
    cs = min(n, max(1, _STEP_CHUNK_HEADS * 2 // itemsize // hb))
    return cs, hb, -(-n // cs) * cs * chunk


def _reads_in_place(heads: int, dk: int, dv: int, hb: int) -> bool:
    """Whether a grid step's columns of q, k and v are blocks of the one
    array ``[.., 2 H d_k + H d_v]`` the convolution wrote: the columns of
    `hb` heads whole tiles of 128 lanes, and k's and v's offsets whole
    blocks of theirs. Else the launch slices the array in three."""
    return (hb * dk % 128 == 0 and hb * dv % 128 == 0
            and 2 * heads * dk % (hb * dv) == 0)


def _kda_kernel(q_ref, k_ref, v_ref, f_ref, wf_ref, bias_ref, rate_ref,
                beta_ref, valid_ref, s0_ref, o_ref, s_ref, *, chunk: int,
                cs: int, hb: int):
    """One grid step: `cs` chunks of `hb` heads of one row, the chunks in
    order and the heads side by side. q_ref, k_ref [1, cs * chunk, hb * d_k]
    as the convolution left them, v_ref, o_ref [1, cs * chunk, hb * d_v],
    f_ref [1, cs * chunk, rank], wf_ref [rank, hb * d_k], bias_ref (dt_bias)
    and rate_ref (-exp(A_log), a channel) [1, hb * d_k] float32, beta_ref
    [1, hb, cs, 1, chunk] and valid_ref [1, cs, 1, chunk] float32, s0_ref
    and s_ref [1, hb, d_k, d_v] float32. s_ref's block does not move along
    the grid's last (sequential) axis: it is the state, in VMEM from a
    row's first chunk to its last."""
    f32 = jnp.float32
    ad = v_ref.dtype
    dk, dv = s_ref.shape[2], s_ref.shape[3]
    base = min(chunk, _BASE)
    nb = chunk // base

    @pl.when(pl.program_id(2) == 0)
    def _load_state():
        s_ref[...] = s0_ref[...]

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tok = jax.lax.broadcasted_iota(jnp.int32, (chunk, dk), 0)
    krow = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
    kcol = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1)
    same_block = row // base == col // base

    def as_column(x, eye):  # [hb, 1, n] -> [hb, n, 1], no transpose
        return jnp.sum(jnp.where(eye, x, 0.0), axis=-1, keepdims=True)

    def mm(spec, x, y):
        # bfloat16 operands are one pass whatever precision the caller's
        # context asks of float32 ones (Mosaic refuses the combination).
        return jnp.einsum(spec, x.astype(ad), y.astype(ad),
                          precision=_ONE_PASS if ad == jnp.bfloat16 else None,
                          preferred_element_type=f32)

    def same_in_block(x, j):
        """[hb, chunk, d] -> row r holds x's row j of r's sub-block."""
        return jnp.concatenate(
            [jnp.broadcast_to(x[:, b * base + j:b * base + j + 1, :],
                              (hb, base, x.shape[-1])) for b in range(nb)],
            axis=1)

    def one_chunk(i, carry):
        at = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)

        def by_head(x, d):      # [chunk, hb * d] -> [hb, chunk, d]
            return jnp.stack([x[:, h * d:(h + 1) * d] for h in range(hb)])

        # The operands, as kda.unit_qk and kda.kda_decay make the step's.
        q, k = unit_qk(by_head(q_ref[0, at], dk), by_head(k_ref[0, at], dk))
        qf, kf = q.astype(f32), k.astype(f32)
        v = by_head(v_ref[0, at], dv)
        g = by_head(jax.nn.softplus(
            mm("ik,ko->io", f_ref[0, at], wf_ref[...]) + bias_ref[...])
            * rate_ref[...], dk)
        valid_row = valid_ref[0, i] > 0                      # [1, chunk]
        beta_row = jnp.where(valid_row, beta_ref[0, :, i], 0.0)
        beta_col = as_column(beta_row, row == col)           # [hb, chunk, 1]
        # Ones on and below the diagonal under the valid tokens: c = lower
        # @ g is g cumulated with an invalid token's taken as 0.
        lower = jnp.broadcast_to(
            jnp.where((row >= col) & valid_row, 1.0, 0.0),
            (hb, chunk, chunk)).astype(jnp.bfloat16)
        c = _exact(lower, g)                                 # [hb, chunk, dk]
        # c at the last token before each sub-block (0 before the first):
        # the point its rows' decays are split at.
        starts = [jnp.zeros((hb, 1, dk), f32)] + [
            c[:, b * base - 1:b * base, :] for b in range(1, nb)]
        rel = jnp.exp(c - jnp.concatenate(
            [jnp.broadcast_to(s, (hb, base, dk)) for s in starts], axis=1))
        k_rel, q_rel = kf * rel, qf * rel
        # Off-diagonal strips: a sub-block's rows of k and q against every
        # earlier token, each side decayed towards the split point.
        nothing = jnp.zeros((hb, base, chunk), f32)
        a_rows, qk_rows = [nothing], [nothing]
        for blk in range(1, nb):
            lo = blk * base
            k_to = kf * jnp.exp(jnp.where(tok < lo, starts[blk] - c,
                                          -jnp.inf))
            strip = mm("nik,njk->nij", jnp.concatenate(
                [k_rel[:, lo:lo + base], q_rel[:, lo:lo + base]], axis=1),
                k_to)
            a_rows.append(strip[:, :base])
            qk_rows.append(strip[:, base:])
        a = jnp.concatenate(a_rows, axis=1)
        qk = jnp.concatenate(qk_rows, axis=1)
        # Diagonal blocks, a column of all of them a step: the difference
        # of the decays before the exponential, a channel at a time.
        for j in range(base):
            k_j = same_in_block(kf, j) * jnp.exp(jnp.where(
                tok % base >= j, c - same_in_block(c, j), -jnp.inf))
            here = same_block & (col % base == j)
            a = jnp.where(here, jnp.sum(kf * k_j, -1, keepdims=True), a)
            qk = jnp.where(here, jnp.sum(qf * k_j, -1, keepdims=True), qk)
        t_beta = _inv_unit_lower_vmem(
            jnp.where(row > col, a * beta_col, 0.0)) * beta_row
        decayed = jnp.exp(c)
        u = _exact(t_beta, v)
        w = _exact(t_beta, kf * decayed).astype(ad)
        q_in = (qf * decayed).astype(ad)
        c_last = c[:, chunk - 1:, :]                         # [hb, 1, dk]
        k_out = (kf * jnp.exp(c_last - c)).astype(ad)
        state = s_ref[0]
        # W and q exp(c) meet the state in one pass over it.
        from_state = mm("nik,nkv->niv", jnp.concatenate([w, q_in], 1),
                        state)
        v_new = u - from_state[:, :chunk]
        o = from_state[:, chunk:] + mm(
            "nij,njv->niv", jnp.where(row >= col, qk, 0.0), v_new)
        for h in range(hb):
            o_ref[0, at, h * dv:(h + 1) * dv] = o[h].astype(o_ref.dtype)
        s_ref[0] = state * jnp.exp(as_column(c_last, krow == kcol)) \
            + mm("nik,niv->nkv", k_out, v_new)
        return carry

    jax.lax.fori_loop(0, cs, one_chunk, 0)


def _launch(qkv, f, wf_up, dt_bias, a_log, beta, valid, state, *,
            chunk: int):
    """The kernel on the operands one device holds, padded to whole grid
    steps (padding is invalid tokens)."""
    f32 = jnp.float32
    b, s, _ = qkv.shape
    heads, kd = a_log.shape[0], wf_up.shape[1]
    dk, dv = kd // heads, (qkv.shape[-1] - 2 * kd) // heads
    cs, hb, padded = kernel_shape(s, heads, dk, dv, chunk,
                                  qkv.dtype.itemsize)
    n = padded // chunk

    def rows(x):            # [b, s, ...] -> [b, ..., n, 1, chunk] float32
        x = jnp.pad(x.astype(f32),
                    ((0, 0), (0, padded - s)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x, 1, -1).reshape(x.shape[:1] + x.shape[2:]
                                              + (n, 1, chunk))

    def tokens(width, first=0):
        return pl.BlockSpec((1, cs * chunk, width),
                            lambda r, h, c: (r, c, first + h))

    def columns(depth):
        return pl.BlockSpec((depth, hb * dk), lambda r, h, c: (0, h))

    qkv, f = (jnp.pad(x, ((0, 0), (0, padded - s), (0, 0)))
              for x in (qkv, f))
    if _reads_in_place(heads, dk, dv, hb):
        q = k = v = qkv
        k_first, v_first = heads // hb, 2 * kd // (hb * dv)
    else:
        q, k, v = qkv[..., :kd], qkv[..., kd:2 * kd], qkv[..., 2 * kd:]
        k_first = v_first = 0
    whole = pl.BlockSpec((1, hb, dk, dv), lambda r, h, c: (r, h, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_kda_kernel, chunk=chunk, cs=cs, hb=hb),
        grid=(b, heads // hb, n // cs),
        in_specs=[
            tokens(hb * dk), tokens(hb * dk, k_first),
            tokens(hb * dv, v_first),
            pl.BlockSpec((1, cs * chunk, f.shape[-1]),
                         lambda r, h, c: (r, c, 0)),
            columns(wf_up.shape[0]), columns(1), columns(1),
            pl.BlockSpec((1, hb, cs, 1, chunk),
                         lambda r, h, c: (r, h, c, 0, 0)),
            pl.BlockSpec((1, cs, 1, chunk), lambda r, h, c: (r, c, 0, 0)),
            whole],
        out_specs=[tokens(hb * dv), whole],
        out_shape=[jax.ShapeDtypeStruct((b, padded, heads * dv), qkv.dtype),
                   jax.ShapeDtypeStruct((b, heads, dk, dv), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=gated_delta._interpret(),
        name="kda_chunked",
    )(q, k, v, f, wf_up.astype(f.dtype), dt_bias.astype(f32)[None],
      jnp.repeat(-jnp.exp(a_log.astype(f32)), dk)[None], rows(beta),
      rows(valid), state)
    return o[:, :s].reshape(b, s, heads, dv), state


_launch_jit = jax.jit(_launch, static_argnames="chunk")


def _chunked_kernel(qkv, f, wf_up, dt_bias, a_log, beta, valid,
                    initial_state, chunk: int):
    """``_launch`` on a single device; under a multi-device mesh a
    shard_map of it, as ops/gated_delta._chunked_kernel's."""
    from runbooks_tpu.ops.flash_attention import _shard_plan

    # Jitted: the layers of a program that call it at one shape share one
    # trace of the kernel's body.
    fn = functools.partial(_launch_jit, chunk=chunk)
    operands = (qkv, f, wf_up, dt_bias, a_log, beta, valid, initial_state)
    kd = wf_up.shape[1]
    by_head = jax.ShapeDtypeStruct(beta.shape + (kd // beta.shape[2],),
                                   qkv.dtype)
    plan = _shard_plan(by_head, by_head)
    if plan is None:
        return fn(*operands)
    # A device's heads are columns of q, of k and of v, not of the array
    # that holds the three: it is cut for the mesh and joined a device.
    columns = P(plan.batch, None, plan.heads)
    state = P(plan.batch, plan.heads, None, None)
    return jax.shard_map(
        lambda q, k, v, *rest: fn(jnp.concatenate([q, k, v], -1), *rest),
        mesh=plan.mesh,
        in_specs=(columns, columns, columns, P(plan.batch, None, None),
                  P(None, plan.heads), P(plan.heads), P(plan.heads), columns,
                  P(plan.batch, None), state),
        out_specs=(P(plan.batch, None, plan.heads, None), state),
        axis_names=(frozenset(plan.mesh.axis_names)
                    - frozenset(plan.mesh.manual_axes)),
        check_vma=False)(qkv[..., :kd], qkv[..., kd:2 * kd],
                         qkv[..., 2 * kd:], *operands[1:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _chunked(qkv, f, wf_up, dt_bias, a_log, beta, valid, initial_state,
             chunk):
    return _chunked_kernel(qkv, f, wf_up, dt_bias, a_log, beta, valid,
                           initial_state, chunk)


def _chunked_fwd(*args):
    return _chunked_kernel(*args), args[:-1]


def _chunked_bwd(chunk, operands, cotangents):
    # The kernel has no backward of its own: the plain form's is taken.
    _, vjp = jax.vjp(
        functools.partial(_chunked_plain, chunk=chunk), *operands)
    return vjp(cotangents)


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def kda_chunked(qkv, f, wf_up, dt_bias, a_log, beta, initial_state=None,
                mask=None, chunk: int = CHUNK):
    """A sequence, chunk by chunk, from what the projections and the short
    convolution wrote. qkv [b, s, 2 H d_k + H d_v]: q, k and v side by
    side, NOT normalized; f [b, s, rank], wf_up [rank, H * d_k], dt_bias
    [H * d_k] and a_log [H]: the decay's (``kda_decay``); beta [b, s, H]
    float32, initial_state [b, H, d_k, d_v] float32 (zeros when None),
    mask [b, s] bool (all valid when None). Returns (o [b, s, H, d_v] in
    qkv's dtype, final state float32). Any s: the sequence is padded to
    whole chunks with invalid tokens."""
    f32 = jnp.float32
    b, s, width = qkv.shape
    heads, kd = a_log.shape[0], wf_up.shape[1]
    if initial_state is None:
        initial_state = jnp.zeros(
            (b, heads, kd // heads, (width - 2 * kd) // heads), f32)
    valid = jnp.ones((b, s), f32) if mask is None else mask.astype(f32)
    return _chunked(qkv, f, wf_up, dt_bias, a_log, beta.astype(f32), valid,
                    initial_state.astype(f32), chunk)


def kda_reference(q, k, v, g, beta, initial_state=None, mask=None):
    """The recurrence token by token (a scan of ``kda_step``): the oracle
    of the tests, same arguments and results as the chunked form."""
    b, s, heads, dk = q.shape
    if initial_state is None:
        initial_state = jnp.zeros((b, heads, dk, v.shape[-1]), jnp.float32)
    valid = (jnp.ones((s, b), bool) if mask is None else mask.T)

    def body(state, xs):
        q_t, k_t, v_t, g_t, b_t, ok = xs
        o, state = kda_step(q_t, k_t, v_t, g_t, b_t, state, ok)
        return state, o

    t_first = lambda x: jnp.moveaxis(x, 1, 0)  # noqa: E731
    state, o = jax.lax.scan(
        body, initial_state,
        (t_first(q), t_first(k), t_first(v), t_first(g.astype(jnp.float32)),
         t_first(beta.astype(jnp.float32)), valid))
    return jnp.moveaxis(o, 0, 1).astype(v.dtype), state
