"""Overlapped collective matmuls: ring all-gather / reduce-scatter tensor
parallelism for the transformer projections.

GSPMD tensor parallelism leaves the per-layer collectives *exposed*: the
row-parallel o_proj/down_proj dots finish, then a blocking all-reduce runs,
then the next op starts (the exposed-communication wall described for TPU
pods in arxiv 2011.03641 / 1909.09756). This module decomposes those
collectives into ``lax.ppermute`` ring steps interleaved with per-shard
partial dots inside a manual ``jax.shard_map`` region, so each hop's comms
hide behind the previous hop's compute — the same treatment the codebase
already gives attention (parallel/ring_attention.py), applied to the other
half of per-layer FLOPs (and the dominant latency term in small-batch
decode).

Two primitives over the ``tensor`` mesh axis (size ``tp``):

- ``ring_ag_matmul`` (column-parallel q/k/v/gate/up): ``y = x @ w`` with
  ``w [in, out]`` column-sharded (each device holds ``[in, out/tp]``) and
  ``x [b, s, in]`` entering *contraction-sharded* (``[b, s, in/tp]`` per
  device — the residual stream stays tensor-sharded between layers, see
  below). Weight-stationary: the x shards circulate around the ring; each
  step contracts the resident shard against the matching ``in/tp`` row
  block of the local weight while the next shard is in flight. Equivalent
  to all-gather(x) @ w_local with the all-gather hidden behind the dots.
  ``bidirectional=True`` circulates shards both ways, halving hop count.

- ``matmul_reduce_scatter`` (row-parallel o_proj/down_proj): ``x [b, s, m]``
  sharded on ``m`` (heads/mlp), ``w [m, out]`` row-sharded. Each step
  computes the partial product destined for one output shard and
  ppermute-accumulates it toward its owner — after ``tp`` steps every
  device holds the fully-summed ``out/tp`` slice it owns. The post-dot
  all-reduce is *eliminated*: its reduce-scatter half hides behind the
  partial dots here, and its all-gather half hides behind the next
  layer's ``ring_ag_matmul``.

Between the two, the residual stream is sharded over ``tensor`` on the
hidden axis (models/transformer.py patches the ``act_embed`` rule when the
ring path is on); norms on the sharded stream cost one tiny [b, s]
all-reduce of partial sums, inserted by GSPMD.

Custom VJPs: the transpose of an all-gather-matmul is a matmul-reduce-
scatter and vice versa, so both backward passes are themselves overlapped
rings (dx ppermute-accumulates; dw forms chunk-by-chunk as the saved
activations re-circulate — no O(tp) activation residuals are kept).

A dequant-fused variant accepts ``QuantizedArray`` int8/int4 weight shards
(ops/quantization.py): integer blocks enter the per-chunk einsum directly
and the blockwise scales apply post-dot, so the quantized serving tier
overlaps too (forward-only — quantized weights are a serving artifact).

Implementation note: the region is manual over ALL mesh axes: activations
enter sharded batch-over-(data, fsdp) / seq-over-sequence exactly as GSPMD
lays them out (specs via parallel/sharding.spec_for_array, so mesh axes
the array doesn't divide degrade to replicated at the boundary), and the
fsdp (ZeRO-3) weight gather happens at the shard_map boundary exactly
where GSPMD would have placed it. It was written full-manual because
partial-manual shard_map crashed the partitioner of jaxlib 0.4.x; on
jaxlib 0.9.0 partial-manual works (the pipeline's stage-manual region
runs), so manual-over-tensor-only is now possible. Which layout to keep
is part of the ring-vs-GSPMD race (ROADMAP Speed carry / D6), not decided
here.

The GSPMD path stays the default reference; ``ring_supported`` is the
per-weight gate (falls back on any divisibility mismatch) and tests assert
numerical equivalence plus ppermute-in-jaxpr evidence.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from runbooks_tpu.ops.quantization import QuantizedArray, unpack_int4
from runbooks_tpu.parallel.sharding import (
    DEFAULT_RULES,
    _current_mesh,
    spec_for_array,
)

AXIS = "tensor"

# Logical rule set for the ring boundary: batch/seq follow the standard
# table; the circulating/contracted dim shards over the tensor axis.
_CM_RULES = {**DEFAULT_RULES, "_ring": AXIS}


def mesh_tensor_size(mesh=None) -> int:
    mesh = mesh if mesh is not None else _current_mesh()
    if mesh is None:
        return 1
    return int(mesh.shape.get(AXIS, 1))


def _quant_dims(w: QuantizedArray) -> Tuple[int, int]:
    """(in_dim, out_dim) of the logical weight."""
    return w.in_dim, w.values.shape[-1]


def ring_supported(kind: str, x_shape, w, mesh=None) -> bool:
    """Can `kind` ("ag" column-parallel | "rs" row-parallel) run as a ring
    for this x/w on this mesh? False falls back to the GSPMD matmul —
    callers never need to special-case shapes."""
    tp = mesh_tensor_size(mesh)
    if tp <= 1:
        return False
    quant = isinstance(w, QuantizedArray)
    if quant:
        if w.values.ndim != 2:
            return False
        in_dim, out_dim = _quant_dims(w)
    else:
        if w.ndim != 2:
            return False
        in_dim, out_dim = w.shape
    if x_shape[-1] != in_dim or len(x_shape) != 3:
        return False
    if in_dim % tp or out_dim % tp:
        return False
    if quant:
        if kind == "ag":
            # The ring slices in/tp row chunks out of the packed values +
            # scales; chunks must align to whole quantization blocks (int4
            # evenness is implied: blocks are even for packed weights).
            if (in_dim // tp) % w.block_size:
                return False
        else:
            # Row-parallel shards the contraction (= quantized) axis over
            # tensor; each local shard must hold whole blocks.
            if (in_dim // tp) % w.block_size:
                return False
    return True


# ---------------------------------------------------------------------------
# Ring schedules (run inside the manual shard_map region)
# ---------------------------------------------------------------------------

def _perm_up(tp):
    """Send i -> i+1 (accumulators flow toward their owners)."""
    return [(i, (i + 1) % tp) for i in range(tp)]


def _perm_down(tp):
    """Send i -> i-1, i.e. receive from i+1 (x shards circulate so the
    resident shard index walks up: after t hops device d holds shard
    (d + t) % tp)."""
    return [(i, (i - 1) % tp) for i in range(tp)]


@jax.named_scope("ring.ag")
def _ag_ring(x_l, tp, contract, bidirectional):
    """All-gather-matmul inner loop: contract(shard, global_chunk_index)
    accumulates while shards circulate. Returns the summed result."""
    my = jax.lax.axis_index(AXIS)
    acc = contract(x_l, my)
    if tp == 1:
        return acc
    if bidirectional and tp > 2:
        fwd = bwd = x_l
        steps = (tp - 1) // 2
        for t in range(1, steps + 1):
            fwd = jax.lax.ppermute(fwd, AXIS, _perm_down(tp))
            bwd = jax.lax.ppermute(bwd, AXIS, _perm_up(tp))
            acc = acc + contract(fwd, jax.lax.rem(my + t, tp))
            acc = acc + contract(bwd, jax.lax.rem(my - t + tp, tp))
        if tp % 2 == 0:
            fwd = jax.lax.ppermute(fwd, AXIS, _perm_down(tp))
            acc = acc + contract(fwd, jax.lax.rem(my + steps + 1, tp))
        return acc
    xs = x_l
    for t in range(1, tp):
        xs = jax.lax.ppermute(xs, AXIS, _perm_down(tp))
        acc = acc + contract(xs, jax.lax.rem(my + t, tp))
    return acc


@jax.named_scope("ring.rs")
def _rs_ring(tp, partial_for, bidirectional):
    """Reduce-scatter-matmul inner loop: partial_for(chunk_idx, half)
    computes this device's contribution to output chunk `chunk_idx`
    (half = None | 0 | 1 selects the full chunk or its halves for the
    bidirectional variant); accumulators ppermute toward their owners.
    Returns this device's fully-summed output chunk."""
    my = jax.lax.axis_index(AXIS)
    if bidirectional and tp > 2:
        acc_a = acc_b = None
        for t in range(tp):
            ca = jax.lax.rem(my + (tp - 1) - t, tp)
            cb = jax.lax.rem(my - (tp - 1) + t + 2 * tp, tp)
            pa = partial_for(ca, 0)
            pb = partial_for(cb, 1)
            acc_a = pa if acc_a is None else acc_a + pa
            acc_b = pb if acc_b is None else acc_b + pb
            if t < tp - 1:
                acc_a = jax.lax.ppermute(acc_a, AXIS, _perm_up(tp))
                acc_b = jax.lax.ppermute(acc_b, AXIS, _perm_down(tp))
        return jnp.concatenate([acc_a, acc_b], axis=-1)
    acc = None
    for t in range(tp):
        c = jax.lax.rem(my + (tp - 1) - t, tp)
        p = partial_for(c, None)
        acc = p if acc is None else acc + p
        if t < tp - 1:
            acc = jax.lax.ppermute(acc, AXIS, _perm_up(tp))
    return acc


# ---------------------------------------------------------------------------
# Chunk contractions
# ---------------------------------------------------------------------------

def _contract_rows(x_c, w_rows, compute_dtype):
    """x_c [..., chunk] @ w_rows [chunk, out] in compute dtype, f32 acc."""
    return jnp.einsum("bsk,ko->bso", x_c.astype(compute_dtype),
                      w_rows.astype(compute_dtype),
                      preferred_element_type=jnp.float32)


def _contract_rows_quant(x_c, vals, scales, bits, block, compute_dtype):
    """Dequant-fused chunk contraction, identical math to
    ops.quantization.quantized_matmul restricted to one in-chunk: integer
    blocks enter the einsum in compute dtype with f32 accumulation and the
    blockwise scales multiply POST-dot, so the bf16 weight chunk is never
    materialized."""
    q = unpack_int4(vals) if bits == 4 else vals
    in_dim, out = q.shape
    nb = in_dim // block
    xb = x_c.astype(compute_dtype).reshape(*x_c.shape[:-1], nb, block)
    wb = q.astype(compute_dtype).reshape(nb, block, out)
    partial = jnp.einsum("bsnk,nko->bsno", xb, wb,
                         preferred_element_type=jnp.float32)
    return jnp.sum(partial * scales, axis=-2)


# ---------------------------------------------------------------------------
# Boundary specs
# ---------------------------------------------------------------------------

def _act_spec(shape, mesh) -> P:
    """[b, s, f] activation spec at the region boundary: batch over
    (data, fsdp), seq over sequence, feature over tensor — each degrading
    to replicated when the mesh lacks the axis or the dim doesn't divide
    (spec_for_array), which keeps the boundary a pure local slice for
    arrays GSPMD already lays out this way."""
    return spec_for_array(shape, ("batch", "seq", "_ring"), mesh, _CM_RULES)


def _batch_axes(spec: P) -> Tuple[str, ...]:
    """Mesh axes the activation's batch/seq dims are REALIZED on (absent
    or non-dividing axes already degraded out of the spec). The weight
    cotangent contracts over batch and seq, so it must psum over exactly
    these — no more (a degraded axis means every shard already holds the
    full extent; psumming it would overcount by the axis size)."""
    axes = []
    for entry in tuple(spec)[:2]:
        if entry is None:
            continue
        axes.extend((entry,) if isinstance(entry, str) else entry)
    return tuple(axes)


# ---------------------------------------------------------------------------
# ring all-gather matmul (column-parallel)
# ---------------------------------------------------------------------------

def ring_ag_matmul(x: jax.Array, w, *, mesh=None,
                   compute_dtype=jnp.bfloat16,
                   bidirectional: bool = True) -> jax.Array:
    """``x [b, s, in] @ w [in, out] -> f32 [b, s, out]`` with the
    all-gather of the contraction-sharded x decomposed into ppermute ring
    steps hidden behind per-chunk dots. w may be a ``QuantizedArray``
    (dequant-fused, forward-only). Check ``ring_supported("ag", ...)``
    first; this raises on unsupported shapes."""
    mesh = mesh if mesh is not None else _current_mesh()
    if not ring_supported("ag", x.shape, w, mesh):
        raise ValueError(
            f"ring_ag_matmul unsupported for x{x.shape} w"
            f"{getattr(w, 'shape', None) or _quant_dims(w)} on this mesh; "
            "gate with ring_supported")
    tp = mesh_tensor_size(mesh)
    if isinstance(w, QuantizedArray):
        return _ag_quant(x, w, mesh, tp, compute_dtype, bidirectional)
    return _ag_dense(x, w, mesh, tp, compute_dtype, bidirectional)


def _ag_dense(x, w, mesh, tp, compute_dtype, bidirectional):
    in_dim, out_dim = w.shape
    chunk = in_dim // tp
    xspec = _act_spec(x.shape, mesh)
    wspec = P(None, AXIS)
    ospec = _act_spec(x.shape[:-1] + (out_dim,), mesh)

    def fwd_local(x_l, w_l):
        def contract(xs, idx):
            rows = jax.lax.dynamic_slice_in_dim(w_l, idx * chunk, chunk,
                                                axis=0)
            return _contract_rows(xs, rows, compute_dtype)

        return _ag_ring(x_l, tp, contract, bidirectional)

    @jax.named_scope("ring.ag_bwd")
    def bwd_local(x_l, w_l, dy_l):
        # dx: transpose of the all-gather-matmul is a matmul-reduce-scatter
        # — partial dy @ w^T chunks ppermute-accumulate toward their
        # owners. dw: the saved x shards re-circulate (no O(tp) residuals
        # were kept) and each arrival fills its in/tp row block. One loop,
        # two opposite-direction ppermute streams, all hops behind dots.
        my = jax.lax.axis_index(AXIS)
        dwl = jnp.zeros(w_l.shape, jnp.float32)
        xs = x_l
        acc = None
        for t in range(tp):
            c = jax.lax.rem(my + (tp - 1) - t, tp)
            w_rows = jax.lax.dynamic_slice_in_dim(w_l, c * chunk, chunk,
                                                  axis=0)
            p = jnp.einsum("bso,ko->bsk", dy_l, w_rows,
                           preferred_element_type=jnp.float32)
            acc = p if acc is None else acc + p
            i = jax.lax.rem(my + t, tp)
            dw_rows = jnp.einsum("bsk,bso->ko", xs, dy_l,
                                 preferred_element_type=jnp.float32)
            dwl = jax.lax.dynamic_update_slice(
                dwl, dw_rows, (i * chunk, jnp.zeros((), jnp.int32)))
            if t < tp - 1:
                acc = jax.lax.ppermute(acc, AXIS, _perm_up(tp))
                xs = jax.lax.ppermute(xs, AXIS, _perm_down(tp))
        # dw contracts over batch and seq, which are sharded across these
        # mesh axes inside the manual region — the f32 psum here is the
        # gradient reduction GSPMD inserts on its own path.
        reduce_axes = _batch_axes(xspec)
        if reduce_axes:
            dwl = jax.lax.psum(dwl, reduce_axes)
        return acc.astype(x_l.dtype), dwl.astype(w_l.dtype)

    def primal(x, w):
        return jax.shard_map(fwd_local, mesh=mesh, in_specs=(xspec, wspec),
                             out_specs=ospec, check_vma=False)(x, w)

    @jax.custom_vjp
    def ag(x, w):
        return primal(x, w)

    def ag_fwd(x, w):
        return primal(x, w), (x, w)

    def ag_bwd(res, dy):
        x, w = res
        dx, dw = jax.shard_map(
            bwd_local, mesh=mesh, in_specs=(xspec, wspec, ospec),
            out_specs=(xspec, wspec), check_vma=False)(x, w, dy)
        return dx, dw

    ag.defvjp(ag_fwd, ag_bwd)
    return ag(x, w)


def _ag_quant(x, w: QuantizedArray, mesh, tp, compute_dtype, bidirectional):
    in_dim, out_dim = _quant_dims(w)
    chunk = in_dim // tp
    block = w.block_size
    packed = 2 if w.bits == 4 else 1
    xspec = _act_spec(x.shape, mesh)
    vspec = P(None, AXIS)
    sspec = P(None, AXIS)
    ospec = _act_spec(x.shape[:-1] + (out_dim,), mesh)

    def fwd_local(x_l, vals_l, scales_l):
        def contract(xs, idx):
            v = jax.lax.dynamic_slice_in_dim(
                vals_l, idx * (chunk // packed), chunk // packed, axis=0)
            s = jax.lax.dynamic_slice_in_dim(
                scales_l, idx * (chunk // block), chunk // block, axis=0)
            return _contract_rows_quant(xs, v, s, w.bits, block,
                                        compute_dtype)

        return _ag_ring(x_l, tp, contract, bidirectional)

    out = jax.shard_map(fwd_local, mesh=mesh,
                        in_specs=(xspec, vspec, sspec), out_specs=ospec,
                        check_vma=False)(x, w.values, w.scales)
    return out


# ---------------------------------------------------------------------------
# matmul reduce-scatter (row-parallel)
# ---------------------------------------------------------------------------

def matmul_reduce_scatter(x: jax.Array, w, *, mesh=None,
                          compute_dtype=jnp.bfloat16,
                          bidirectional: bool = True) -> jax.Array:
    """``x [b, s, m] @ w [m, out] -> f32 [b, s, out]`` with x sharded on
    the contraction (heads/mlp) axis and w row-sharded: partial products
    are computed per destination shard and ppermute-accumulated, so the
    post-dot all-reduce never exists. The result leaves the region sharded
    over tensor on its last dim (the residual-stream layout the next
    ``ring_ag_matmul`` consumes). w may be a ``QuantizedArray``
    (dequant-fused, forward-only)."""
    mesh = mesh if mesh is not None else _current_mesh()
    if not ring_supported("rs", x.shape, w, mesh):
        raise ValueError(
            f"matmul_reduce_scatter unsupported for x{x.shape} on this "
            "mesh; gate with ring_supported")
    tp = mesh_tensor_size(mesh)
    if isinstance(w, QuantizedArray):
        return _rs_quant(x, w, mesh, tp, compute_dtype, bidirectional)
    return _rs_dense(x, w, mesh, tp, compute_dtype, bidirectional)


def _rs_halves(chunk):
    """(offset, width) pairs for the bidirectional half-chunks."""
    half = chunk // 2
    return {None: (0, chunk), 0: (0, half), 1: (half, chunk - half)}


def _rs_dense(x, w, mesh, tp, compute_dtype, bidirectional):
    m_dim, out_dim = w.shape
    chunk = out_dim // tp
    halves = _rs_halves(chunk)
    xspec = _act_spec(x.shape, mesh)
    wspec = P(AXIS, None)
    ospec = _act_spec(x.shape[:-1] + (out_dim,), mesh)

    def fwd_local(x_l, w_l):
        def partial_for(c, half):
            off, width = halves[half]
            cols = jax.lax.dynamic_slice(
                w_l, (jnp.zeros((), jnp.int32), c * chunk + off),
                (w_l.shape[0], width))
            return _contract_rows(x_l, cols, compute_dtype)

        return _rs_ring(tp, partial_for, bidirectional)

    @jax.named_scope("ring.rs_bwd")
    def bwd_local(x_l, w_l, do_l):
        # Transpose of the matmul-reduce-scatter is an all-gather-matmul:
        # the output-shard cotangents circulate; each arriving chunk both
        # contracts against the matching local weight columns (dx) and
        # outer-products with the saved local x into its dw column block.
        my = jax.lax.axis_index(AXIS)
        dwl = jnp.zeros(w_l.shape, jnp.float32)
        dx = None
        dos = do_l
        for t in range(tp):
            i = jax.lax.rem(my + t, tp)
            cols = jax.lax.dynamic_slice(
                w_l, (jnp.zeros((), jnp.int32), i * chunk),
                (w_l.shape[0], chunk))
            p = jnp.einsum("bsc,kc->bsk", dos, cols,
                           preferred_element_type=jnp.float32)
            dx = p if dx is None else dx + p
            dw_cols = jnp.einsum("bsk,bsc->kc", x_l, dos,
                                 preferred_element_type=jnp.float32)
            dwl = jax.lax.dynamic_update_slice(
                dwl, dw_cols, (jnp.zeros((), jnp.int32), i * chunk))
            if t < tp - 1:
                dos = jax.lax.ppermute(dos, AXIS, _perm_down(tp))
        reduce_axes = _batch_axes(xspec)
        if reduce_axes:
            dwl = jax.lax.psum(dwl, reduce_axes)
        return dx.astype(x_l.dtype), dwl.astype(w_l.dtype)

    def primal(x, w):
        return jax.shard_map(fwd_local, mesh=mesh, in_specs=(xspec, wspec),
                             out_specs=ospec, check_vma=False)(x, w)

    @jax.custom_vjp
    def rs(x, w):
        return primal(x, w)

    def rs_fwd(x, w):
        return primal(x, w), (x, w)

    def rs_bwd(res, do):
        x, w = res
        dx, dw = jax.shard_map(
            bwd_local, mesh=mesh, in_specs=(xspec, wspec, ospec),
            out_specs=(xspec, wspec), check_vma=False)(x, w, do)
        return dx, dw

    rs.defvjp(rs_fwd, rs_bwd)
    return rs(x, w)


def _rs_quant(x, w: QuantizedArray, mesh, tp, compute_dtype, bidirectional):
    m_dim, out_dim = _quant_dims(w)
    chunk = out_dim // tp
    halves = _rs_halves(chunk)
    block = w.block_size
    xspec = _act_spec(x.shape, mesh)
    # Row-parallel shards the contraction axis, which is the quantized
    # axis: values AND scales shard their leading dim over tensor (whole
    # blocks per shard — ring_supported checked), so the local contraction
    # is exactly quantized_matmul on the local rows.
    vspec = P(AXIS, None)
    sspec = P(AXIS, None)
    ospec = _act_spec(x.shape[:-1] + (out_dim,), mesh)

    def fwd_local(x_l, vals_l, scales_l):
        def partial_for(c, half):
            off, width = halves[half]
            v = jax.lax.dynamic_slice(
                vals_l, (jnp.zeros((), jnp.int32), c * chunk + off),
                (vals_l.shape[0], width))
            s = jax.lax.dynamic_slice(
                scales_l, (jnp.zeros((), jnp.int32), c * chunk + off),
                (scales_l.shape[0], width))
            return _contract_rows_quant(x_l, v, s, w.bits, block,
                                        compute_dtype)

        return _rs_ring(tp, partial_for, bidirectional)

    return jax.shard_map(fwd_local, mesh=mesh,
                         in_specs=(xspec, vspec, sspec), out_specs=ospec,
                         check_vma=False)(x, w.values, w.scales)
