"""Flash-attention kernel vs the XLA reference attention (the numerical
oracle), forward and backward, in Pallas interpreter mode on CPU."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbooks_tpu.ops.attention import dot_product_attention, make_attention_mask
from runbooks_tpu.ops.flash_attention import (
    NEG_INF,
    PAD_POS,
    _flash_fwd,
    _last_valid_kv,
    block_counts,
    block_ranges,
    flash_attention,
)


def make_inputs(b=2, sq=128, sk=128, h=2, d=32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, sk, h, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, sk, h, d), jnp.float32)
    q_pos = jnp.broadcast_to(jnp.arange(sq, dtype=jnp.int32)[None], (b, sq))
    kv_pos = jnp.broadcast_to(jnp.arange(sk, dtype=jnp.int32)[None], (b, sk))
    return q, k, v, q_pos, kv_pos


def oracle(q, k, v, q_pos, kv_pos, q_seg=None, kv_seg=None, causal=True):
    mask = make_attention_mask(q_pos, kv_pos, q_seg, kv_seg, causal=causal)
    return dot_product_attention(q, k, v, mask=mask)


@pytest.mark.parametrize("block", [64, 128])
def test_forward_matches_oracle_causal(block):
    q, k, v, q_pos, kv_pos = make_inputs()
    ref = oracle(q, k, v, q_pos, kv_pos)
    got = flash_attention(q, k, v, q_pos, kv_pos, None, None, True, None,
                          block, block)
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_forward_non_divisible_seq():
    q, k, v, q_pos, kv_pos = make_inputs(sq=100, sk=100)
    ref = oracle(q, k, v, q_pos, kv_pos)
    got = flash_attention(q, k, v, q_pos, kv_pos, None, None, True, None,
                          64, 64)
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_forward_with_segments():
    b, s = 2, 128
    q, k, v, q_pos, kv_pos = make_inputs(sq=s, sk=s)
    # Two packed docs + padding tail; positions restart per segment.
    seg = np.ones((b, s), np.int32)
    seg[:, 48:96] = 2
    seg[:, 96:] = 0
    pos = np.concatenate([np.arange(48), np.arange(48), np.arange(32)])
    pos = np.broadcast_to(pos, (b, s)).astype(np.int32)
    seg, pos = jnp.asarray(seg), jnp.asarray(pos)
    ref = oracle(q, k, v, pos, pos, seg, seg)
    got = flash_attention(q, k, v, pos, pos, seg, seg, True, None, 64, 64)
    # Padding rows (seg 0) are fully masked: oracle zeroes them; flash
    # zeroes them too via the l==0 guard.
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_noncausal_with_padding_keys():
    # Regression: with causal=False, zero-padded keys (sk not a block
    # multiple) must still be masked out of the softmax denominator.
    q, k, v, q_pos, kv_pos = make_inputs(sq=100, sk=100)
    ref = oracle(q, k, v, q_pos, kv_pos, causal=False)
    got = flash_attention(q, k, v, q_pos, kv_pos, None, None, False, None,
                          64, 64)
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# The forward visits only the kv blocks its positions and segment ids say a
# query can see (block_ranges). It must be exact for ANY layout.
# ---------------------------------------------------------------------------

def _rows(b, s, lengths, start=0, pad=-1):
    """[b, s] positions: row i holds start..start+lengths[i]-1, then pad."""
    pos = np.full((b, s), pad, np.int32)
    for i, n in enumerate(lengths):
        pos[i, :n] = start + np.arange(n)
    return pos


def _layout(name):
    """(sq, sk, h, kv_h, q_pos, kv_pos, q_seg, kv_seg, causal) of a case."""
    b, seg, causal, h, kv_h = 3, None, True, 2, 2
    if name in ("cached_prefill", "parked_at_trash", "gqa", "mqa"):
        # The engine's prefill: a 256 bucket against a 257-slot scratch
        # row; rows shorter than the bucket; padding parked at the trash
        # slot (256), which _cached_attention hands over as -1.
        sq, sk = 256, 257
        pad = 256 if name == "parked_at_trash" else -1
        q_pos = _rows(b, sq, (100, 256, 37), pad=pad)
        kv_pos = np.broadcast_to(np.arange(sk, dtype=np.int32), (b, sk))
        if name in ("gqa", "mqa"):
            h, kv_h = 4, 2 if name == "gqa" else 1
    elif name == "prefix_offset":
        # A spliced prefix: the suffix's queries start at its length.
        sq, sk = 128, 257
        q_pos = _rows(b, sq, (128, 60, 1), start=96)
        kv_pos = np.broadcast_to(np.arange(sk, dtype=np.int32), (b, sk))
    elif name in ("packed_on_edge", "packed_off_edge"):
        # Packed documents, positions restarting; a boundary on a block
        # edge (128) or off it (100), and a padding tail (segment 0).
        sq = sk = 256
        cut = 128 if name == "packed_on_edge" else 100
        ends = (cut, 200, 230)
        seg = np.zeros((b, sq), np.int32)
        q_pos = np.zeros((b, sq), np.int32)
        lo = 0
        for i, hi in enumerate(ends):
            seg[:, lo:hi] = i + 1
            q_pos[:, lo:hi] = np.arange(hi - lo)
            lo = hi
        kv_pos = q_pos
    elif name == "permuted":
        # Non-monotone storage order on both sides.
        sq, sk = 192, 200
        rng = np.random.default_rng(5)
        q_pos = np.stack([rng.permutation(sk)[:sq] for _ in range(b)])
        kv_pos = np.stack([rng.permutation(sk) for _ in range(b)])
    elif name == "noncausal_padding":
        # Not causal; keys that are no multiple of a block, some of them
        # marked as padding by position.
        sq, sk, causal = 100, 200, False
        q_pos = _rows(b, sq, (100, 100, 100))
        kv_pos = np.broadcast_to(np.arange(sk, dtype=np.int32), (b, sk)).copy()
        kv_pos[:, 150:] = PAD_POS
    else:
        raise ValueError(name)
    q_pos, kv_pos = jnp.asarray(q_pos, jnp.int32), jnp.asarray(kv_pos,
                                                               jnp.int32)
    seg = None if seg is None else jnp.asarray(seg)
    return sq, sk, h, kv_h, q_pos, kv_pos, seg, seg, causal


LAYOUTS = ("cached_prefill", "parked_at_trash", "prefix_offset",
           "packed_on_edge", "packed_off_edge", "permuted",
           "noncausal_padding", "gqa", "mqa")


@pytest.mark.parametrize("blocks", [(64, 128), (128, 64)],
                         ids=["64x128", "128x64"])
@pytest.mark.parametrize("name", LAYOUTS)
def test_forward_is_exact_for_every_layout(name, blocks):
    sq, sk, h, kv_h, q_pos, kv_pos, q_seg, kv_seg, causal = _layout(name)
    b, d = q_pos.shape[0], 16
    ks = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, sk, kv_h, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, sk, kv_h, d), jnp.float32)
    # Without causality the oracle knows no padding key: give it the real.
    real = 150 if name == "noncausal_padding" else sk
    ref = oracle(q, k[:, :real], v[:, :real], q_pos, kv_pos[:, :real], q_seg,
                 kv_seg, causal=causal)
    got = flash_attention(q, k, v, q_pos, kv_pos, q_seg, kv_seg, causal,
                          None, *blocks)
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)
    # Part of the grid goes wherever the layout has blocks no query sees
    # (shuffled positions and keys without causality have none).
    visited, grid = block_counts(
        np.asarray(q_pos), np.asarray(kv_pos),
        None if q_seg is None else np.asarray(q_seg),
        None if kv_seg is None else np.asarray(kv_seg), *blocks, causal)
    assert 0 < visited <= grid
    if name not in ("permuted", "noncausal_padding"):
        assert visited < grid, (visited, grid)


def test_a_query_block_of_padding_visits_nothing_and_writes_zeros():
    """Rows 64.. of row 0 are all padding: their blocks have an empty range,
    the kernel writes exact zeros and NEG_INF statistics without visiting a
    key, and nothing is NaN."""
    sq, sk, h, kv_h, q_pos, kv_pos, *_ = _layout("cached_prefill")
    q_pos = q_pos.at[0, 37:].set(-1)
    q, k, v = (jax.random.normal(jax.random.key(i), (3, s, 2, 16))
               for i, s in ((1, sq), (2, sk), (3, sk)))
    lo, hi = block_ranges(q_pos, kv_pos, None, None, 64, 128, True)
    assert np.asarray(lo)[0].tolist() == [0, 0, 0, 0]
    assert np.asarray(hi)[0].tolist() == [0, -1, -1, -1]
    out, lse = _flash_fwd(q, k, v, q_pos, kv_pos, None, None, 0.25, True,
                          64, 128)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_array_equal(np.asarray(out[0, 37:]), 0.0)
    np.testing.assert_array_equal(np.asarray(lse[0, :, 37:]),
                                  np.float32(NEG_INF))
    assert (np.asarray(lse[0, :, :37]) > NEG_INF).all()


@pytest.mark.parametrize("prompt,bucket,keys,want", [
    (1460, 2048, 2049, (4, 12)),   # doc_flood's mean prompt: 1 + 1 + 2 + 0
    (1700, 2048, 2049, (6, 12)),   # 1 + 1 + 2 + 2, never the trash block
    (1024, 2048, 2049, (2, 12)),
    (160, 256, 1025, (1, 2)),      # chat: the second kv block goes
], ids=["doc1460", "doc1700", "doc1024", "chat160"])
def test_block_counts_of_a_served_prefill(prompt, bucket, keys, want):
    """The cells' shapes at the configured 512 x 1024 blocks, as the engine
    counts them (serve/engine._count_flash_blocks)."""
    q_pos = _rows(1, bucket, (prompt,))
    kv_pos = np.arange(keys, dtype=np.int32)[None]
    assert block_counts(q_pos, kv_pos, None, None, 512, 1024, True) == want


@pytest.mark.parametrize("ends,want", [
    ((700, 1700, 2048), 6),   # documents straddle both block edges
    ((300, 1500, 2048), 5),   # one document from query block 0 into kv block
                              # 1, which starts another at position 0:
                              # block 0 still stays out of kv block 1
    ((700, 1200, 2048), 5),   # the last query block holds one document,
                              # none of it in kv block 0
    ((1024, 2048), 4),        # a boundary on row 1024
], ids=["straddling", "long_document", "late_document", "on_the_edge"])
def test_block_counts_of_a_packed_training_row(ends, want):
    """The LoRA cell's layout (sq == sk, aligned, packed, positions
    restarting): the ranges never visit more than the grid-index rule's 6 of
    8 blocks a head (the backward kernels still use that rule), and fewer
    where a block's documents lie wholly in later blocks."""
    s, bq, bk = 2048, 512, 1024
    seg, pos, lo = np.zeros((1, s), np.int32), np.zeros((1, s), np.int32), 0
    for i, hi in enumerate(ends):
        seg[:, lo:hi], pos[:, lo:hi], lo = i + 1, np.arange(hi - lo), hi
    static = sum(int(_last_valid_kv(qi, bq, bk, s // bk)) + 1
                 for qi in range(s // bq))
    assert static == 6 >= want
    assert block_counts(pos, pos, seg, seg, bq, bk, True) == (want, 8)


@pytest.mark.parametrize("seed", range(12))
def test_block_ranges_never_drop_a_block_with_an_unmasked_pair(seed):
    """Random lengths, blocks, positions (repeated, non-monotone, negative,
    padding) and segments: every block that holds an unmasked (query, key)
    pair lies inside [lo, hi], and jax.numpy gives what NumPy gives."""
    rng = np.random.default_rng(seed)
    b = 3
    sq, sk = (int(x) for x in rng.integers(1, 70, 2))
    bq, bk = (int(x) for x in rng.choice([8, 16, 32, 128], 2))
    causal = bool(seed % 3)
    q_pos = rng.integers(-2, 40, (b, sq)).astype(np.int32)
    kv_pos = rng.integers(0, 40, (b, sk)).astype(np.int32)
    kv_pos[rng.random((b, sk)) < 0.2] = PAD_POS
    if seed % 2:
        q_seg = rng.integers(0, 4, (b, sq)).astype(np.int32)
        kv_seg = rng.integers(0, 4, (b, sk)).astype(np.int32)
    else:
        q_seg = kv_seg = None
    lo, hi = block_ranges(q_pos, kv_pos, q_seg, kv_seg, bq, bk, causal)
    assert isinstance(lo, np.ndarray) and lo.dtype == np.int32

    mask = np.broadcast_to(kv_pos[:, None, :] < PAD_POS, (b, sq, sk)).copy()
    if causal:
        mask &= kv_pos[:, None, :] <= q_pos[:, :, None]
    if q_seg is not None:
        mask &= (q_seg[:, :, None] == kv_seg[:, None, :]) \
            & (kv_seg[:, None, :] != 0)
    bq, bk = min(bq, sq), min(bk, sk)
    assert lo.shape == (b, -(-sq // bq))
    for r, i, j in zip(*np.nonzero(mask)):
        assert lo[r, i // bq] <= j // bk <= hi[r, i // bq], (r, i, j)
    # Nothing needed, nothing visited; a hull is never wider than the grid.
    for r in range(b):
        for qi in range(lo.shape[1]):
            if not mask[r, qi * bq:(qi + 1) * bq].any():
                assert (lo[r, qi], hi[r, qi]) == (0, -1)
    assert (hi < -(-sk // bk)).all() and (lo >= 0).all()

    jlo, jhi = block_ranges(*(None if x is None else jnp.asarray(x)
                              for x in (q_pos, kv_pos, q_seg, kv_seg)),
                            bq, bk, causal)
    np.testing.assert_array_equal(np.asarray(jlo), lo)
    np.testing.assert_array_equal(np.asarray(jhi), hi)


def test_forward_bf16_close():
    q, k, v, q_pos, kv_pos = make_inputs()
    ref = oracle(q, k, v, q_pos, kv_pos)
    got = flash_attention(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                          v.astype(jnp.bfloat16), q_pos, kv_pos, None, None)
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref))) < 0.05


def test_gqa_forward_and_grads():
    b, s, h, kv_h, d = 1, 64, 4, 2, 16
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kv_h, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kv_h, d), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, pos, pos, None, None, True, None, 32, 32)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(oracle(q, k, v, pos, pos)))

    np.testing.assert_allclose(loss_flash(q, k, v), loss_ref(q, k, v),
                               rtol=1e-5)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(a, b_, rtol=1e-4, atol=1e-4,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("kv_h,mesh_axes,plan", [
    (2, dict(data=2, fsdp=2, tensor=2), ("tensor", "tensor")),
    # Multi-query (Falcon-7B's shape): query heads shard, the one kv head
    # is read whole by every shard and its gradient is summed over them.
    (1, dict(fsdp=2, tensor=4), ("tensor", None)),
    # A GQA grouping the tensor axis would break stays replicated.
    (3, dict(fsdp=4, tensor=2), (None, None)),
], ids=["gqa", "mqa", "indivisible"])
def test_kernels_run_per_shard_under_a_mesh(kv_h, mesh_axes, plan):
    """On a multi-device mesh the kernels launch inside a shard_map (a TPU
    refuses to partition a Mosaic kernel); value and gradients must still
    match the oracle for every head layout."""
    from runbooks_tpu.ops.flash_attention import _shard_plan
    from runbooks_tpu.parallel.mesh import MeshConfig, make_mesh

    h = 6 if kv_h == 3 else 8
    q, _, _, q_pos, kv_pos = make_inputs(b=4, sq=64, sk=64, h=h, d=16)
    k, v = (jax.random.normal(jax.random.key(i), (4, 64, kv_h, 16))
            for i in (7, 8))

    def loss(fn, q, k, v):
        return (fn(q, k, v) ** 2).sum()

    want = jax.value_and_grad(
        lambda q, k, v: loss(lambda q, k, v: oracle(
            q, jnp.repeat(k, h // kv_h, axis=2),
            jnp.repeat(v, h // kv_h, axis=2), q_pos, kv_pos), q, k, v),
        argnums=(0, 1, 2))(q, k, v)
    with jax.set_mesh(make_mesh(MeshConfig(**mesh_axes))):
        assert (_shard_plan(q, k).heads, _shard_plan(q, k).kv_heads) == plan
        got = jax.jit(jax.value_and_grad(
            lambda q, k, v: loss(lambda q, k, v: flash_attention(
                q, k, v, q_pos, kv_pos, None, None, True, None, 32, 32),
                q, k, v), argnums=(0, 1, 2)))(q, k, v)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)


def test_gradients_match_oracle():
    q, k, v, q_pos, kv_pos = make_inputs(b=1, sq=96, sk=96, h=2, d=16)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, q_pos, kv_pos, None, None, True, None,
                            32, 32)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(q, k, v):
        o = oracle(q, k, v, q_pos, kv_pos)
        return jnp.sum(o * jnp.cos(o))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(a, b_, rtol=1e-4, atol=1e-4,
                                   err_msg=f"d{name}")


def test_gradients_with_segments():
    b, s = 1, 64
    q, k, v, _, _ = make_inputs(b=b, sq=s, sk=s, h=2, d=16, seed=3)
    seg = np.ones((b, s), np.int32)
    seg[:, 40:] = 0  # padding tail
    pos = np.broadcast_to(np.arange(s), (b, s)).astype(np.int32)
    seg, pos = jnp.asarray(seg), jnp.asarray(pos)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, pos, pos, seg, seg, True, None, 32, 32)
        return jnp.sum(o)

    def loss_ref(q, k, v):
        return jnp.sum(oracle(q, k, v, pos, pos, seg, seg))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(a, b_, rtol=1e-4, atol=1e-4,
                                   err_msg=f"d{name}")


def test_gradients_kv_longer_than_q_causal():
    """sk > sq with causal block skip: kv blocks entirely past the last q
    block must produce dk/dv == 0, not stale scratch from the previous
    block (regression: _first_valid_q lacked the num_q-1 clamp)."""
    q, k, v, q_pos, kv_pos = make_inputs(b=1, sq=32, sk=128, h=2, d=16)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, q_pos, kv_pos, None, None, True, None,
                            32, 32)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(q, k, v):
        o = oracle(q, k, v, q_pos, kv_pos)
        return jnp.sum(o * jnp.cos(o))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    # Keys at positions > max q position get exactly zero gradient.
    np.testing.assert_array_equal(np.asarray(gf[1][:, 32:]), 0.0)
    np.testing.assert_array_equal(np.asarray(gf[2][:, 32:]), 0.0)
    for a, b_, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(a, b_, rtol=1e-4, atol=1e-4,
                                   err_msg=f"d{name}")


def _count_pallas_calls(jaxpr, n=0):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):
                n = _count_pallas_calls(v.jaxpr, n)
            elif hasattr(v, "eqns"):
                n = _count_pallas_calls(v, n)
    return n


def test_save_attn_out_skips_fwd_kernel_recompute():
    """remat_policy="save_attn_out" must eliminate the O(s^2) fwd-kernel
    re-run in the backward pass: the kernel's residuals (out, lse) are
    hoisted to the caller's trace level (ops/flash_attention.py) exactly so
    the checkpoint policy can save them. nothing_saveable: fwd x2 (primal +
    recompute) + dq + dkv = 4 pallas calls; save_attn_out: 3."""
    import dataclasses

    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.models.transformer import forward, init_params

    base = dataclasses.replace(
        get_config("debug"), attention_impl="flash",
        flash_block_q=64, flash_block_k=64)
    tokens = jnp.zeros((1, 128), jnp.int32)
    counts = {}
    for policy in ("nothing_saveable", "save_attn_out"):
        cfg = dataclasses.replace(base, remat_policy=policy)
        params = init_params(cfg, jax.random.key(0))

        def loss(p, cfg=cfg):
            logits, _ = forward(cfg, p, tokens, remat=True)
            return jnp.mean(logits)

        jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
        counts[policy] = _count_pallas_calls(jaxpr.jaxpr)
    assert counts["nothing_saveable"] == 4, counts
    assert counts["save_attn_out"] == 3, counts


# --------------------------------------------------------------------------
# A grid step holds a block of a KV head's group of query heads
# --------------------------------------------------------------------------

def _group_case(h, kv_h, layout, s=48, d=16):
    """(q, k, v, q_pos, kv_pos, q_seg, kv_seg, extra keywords) of one
    layout at `h` query heads on `kv_h` KV heads; two rows."""
    dv = d
    sq = sk = s
    over = {}
    rng = np.random.default_rng(h * 131 + kv_h)
    q_seg = kv_seg = None
    q_pos = kv_pos = np.broadcast_to(np.arange(s, dtype=np.int32), (2, s))
    if layout == "packed segments":
        # Documents behind one another, a padded tail in row 1.
        lengths = [20, 9, s - 29], [17, 25, s - 42]
        q_seg = kv_seg = np.stack([np.repeat([1, 2, 3], lengths[0]),
                                   np.repeat([1, 2, 0], lengths[1])
                                   ]).astype(np.int32)
        q_pos = kv_pos = np.stack([np.concatenate(
            [np.arange(n) for n in row]) for row in lengths]).astype(np.int32)
    elif layout == "cache view":
        # 24 queries at positions 17 .. 40 against a cache of 56 slots of
        # which 41 are written (sk != sq; the static skip is off).
        sq, sk = 24, 56
        q_pos = np.broadcast_to(np.arange(17, 41, dtype=np.int32), (2, sq))
        kv_pos = np.broadcast_to(np.where(np.arange(sk) < 41, np.arange(sk),
                                          PAD_POS).astype(np.int32), (2, sk))
    elif layout == "window and sink":
        over = dict(window=7, sink=jnp.asarray(rng.normal(size=h),
                                               jnp.float32))
    elif layout == "192 / 128 widths":
        d, dv = 24, 16
    ks = jax.random.split(jax.random.key(h + kv_h), 3)
    q = jax.random.normal(ks[0], (2, sq, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (2, sk, kv_h, d), jnp.float32)
    v = jax.random.normal(ks[2], (2, sk, kv_h, dv), jnp.float32)
    give = (lambda a: None if a is None else jnp.asarray(a))
    return (q, k, v, give(q_pos), give(kv_pos), give(q_seg), give(kv_seg),
            over)


GROUP_LAYOUTS = ["causal prompt", "packed segments", "cache view",
                 "window and sink", "192 / 128 widths"]


@pytest.mark.parametrize("layout", GROUP_LAYOUTS)
@pytest.mark.parametrize("h,kv_h", [(71, 1), (16, 2), (8, 8), (5, 1)])
def test_a_step_of_a_block_of_heads_is_exact(monkeypatch, h, kv_h, layout):
    """Every ratio of heads (a prime group, two groups of 8, groups of
    one, a small odd group) in every layout a caller has: the output and,
    where the call has a backward, dq, dk, dv equal the XLA path at this
    file's tolerances, and equal the G = 1 kernel BIT FOR BIT — at the G
    head_block gives (the whole group at these sizes) and at 3 heads a
    step, whose last block is partial for groups of 71 (2 heads), 8 (2)
    and 5 (2). Bit for bit and not within an ulp, because a head's
    arithmetic does not depend on which heads share its step, and dkv adds
    the heads of a group in one order whatever G (for each query block the
    heads in turn)."""
    import runbooks_tpu.ops.flash_attention as fa

    q, k, v, q_pos, kv_pos, q_seg, kv_seg, over = _group_case(h, kv_h, layout)
    has_bwd = not over and v.shape[-1] == q.shape[-1]
    weights = jax.random.normal(jax.random.key(3),
                                (*q.shape[:3], v.shape[-1]), jnp.float32)

    def run(attend):
        def loss(q, k, v):
            out = attend(q, k, v)
            return jnp.sum(out * weights), out
        if not has_bwd:
            return (jax.jit(attend)(q, k, v),)
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return (out, *grads)

    def flash(q, k, v):
        return flash_attention(q, k, v, q_pos, kv_pos, q_seg, kv_seg, True,
                               None, 16, 32, **over)

    def xla(q, k, v):
        mask = make_attention_mask(q_pos, kv_pos, q_seg, kv_seg)
        if "window" in over:
            mask &= (q_pos[:, None, :, None] - kv_pos[:, None, None, :]
                     < over["window"])
        return dot_product_attention(q, k, v, mask=mask,
                                     sink=over.get("sink"))

    n_rep = h // kv_h
    assert fa.head_block(n_rep, 16, 32, q.shape[-1], v.shape[-1]) == n_rep
    whole = run(flash)
    steps = {}
    for g in (1, 3):
        monkeypatch.setattr(fa, "head_block",
                            lambda n_rep, *a, g=g: min(n_rep, g))
        steps[g] = run(flash)
    want = run(xla)
    assert dk_width_is(whole, k) and dk_width_is(steps[3], k)
    for name, one, three, all_, ref in zip(
            ("out", "dq", "dk", "dv"), steps[1], steps[3], whole, want):
        np.testing.assert_array_equal(np.asarray(three), np.asarray(one),
                                      err_msg=f"{name}: 3 a step")
        np.testing.assert_array_equal(np.asarray(all_), np.asarray(one),
                                      err_msg=f"{name}: the whole group")
        tol = 2e-5 if name == "out" else 1e-4
        np.testing.assert_allclose(np.asarray(one), np.asarray(ref),
                                   rtol=tol, atol=tol, err_msg=name)


def dk_width_is(results, k):
    return len(results) == 1 or (results[2].shape == k.shape
                                 and results[3].shape == k.shape)


def test_dk_and_dv_leave_the_kernel_at_kv_head_width():
    """The dkv kernel sums over the heads of a group itself: its two
    results are [b, kv_h, sk, d] — one shape, as the benchmark tells the
    kernel by — dq keeps [b * kv_h, n_rep, sq, d], and nothing after the
    kernels adds anything up."""
    from runbooks_tpu.ops.flash_attention import flash_attention_bwd

    b, s, h, kv_h, d = 2, 64, 10, 2, 16
    q = jnp.zeros((b, s, h, d))
    k = v = jnp.zeros((b, s, kv_h, d))
    pos = jnp.zeros((b, s), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda q, k, v: flash_attention_bwd(
        q, k, v, pos, pos, None, None, q, jnp.zeros((b, h, s)), q,
        causal=True, scale=1.0, block_q=32, block_k=32,
        block_skip=True))(q, k, v)
    calls = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert [[o.aval.shape for o in e.outvars] for e in calls] == [
        [(b * kv_h, h // kv_h, s, d)], [(b, kv_h, s, d)] * 2]
    after = jaxpr.eqns[jaxpr.eqns.index(calls[1]) + 1:]
    assert not [e for e in after if e.primitive.name.startswith("reduce")]


def test_head_block_is_a_function_of_shapes_under_the_budget():
    """G = 1 for a group of one; never over the VMEM budget where one head
    fits at all; never more than the group; the blocks of a group are
    evened out; not larger at larger blocks or widths; a forward-only call
    (a sink, a window, 192 / 128) is sized by the forward alone."""
    from runbooks_tpu.ops.flash_attention import (
        VMEM_BUDGET_BYTES,
        _ask_vmem,
        head_block,
        vmem_by_kernel,
        vmem_bytes,
    )

    # A step of one head asks Mosaic for nothing; a step of several for
    # what is counted for its kernel, which head_block keeps in budget.
    assert _ask_vmem("fwd", 1, 512, 1024, 64, 64).vmem_limit_bytes is None
    asked = [_ask_vmem(kernel, 18, 512, 1024, 64, 64).vmem_limit_bytes
             for kernel in ("fwd", "dq", "dkv")]
    assert asked == list(vmem_by_kernel(18, 512, 1024, 64, 64).values())
    assert max(asked) == vmem_bytes(18, 512, 1024, 64, 64) \
        <= VMEM_BUDGET_BYTES
    sizes = [(bq, bk, d) for bq in (128, 256, 512, 1024)
             for bk in (128, 512, 1024, 2048) for d in (64, 128, 192)]
    for bq, bk, d in sizes:
        assert head_block(1, bq, bk, d, d) == 1
        for n_rep in (2, 5, 8, 16, 32, 71, 128):
            g = head_block(n_rep, bq, bk, d, d)
            assert 1 <= g <= n_rep
            if vmem_bytes(1, bq, bk, d, d) <= VMEM_BUDGET_BYTES:
                assert vmem_bytes(g, bq, bk, d, d) <= VMEM_BUDGET_BYTES
            # Evened out: one head fewer a step would need another block.
            assert g == 1 or -(-n_rep // (g - 1)) > -(-n_rep // g)
            for bq2, bk2, d2 in ((2 * bq, bk, d), (bq, 2 * bk, d),
                                 (bq, bk, d + 64)):
                assert head_block(n_rep, bq2, bk2, d2, d2) <= g
    # The model's blocks: falcon-7b's 71 on 1, a falcon-40b shard's 16,
    # mimo-v2-flash's window (8, a sink) and full (16) layers, a group of 1.
    assert head_block(71, 512, 1024, 64, 64) == 18      # 18, 18, 18, 17
    assert head_block(16, 512, 1024, 64, 64) == 16
    assert head_block(8, 512, 1024, 192, 128, True, 128) == 8
    assert head_block(16, 512, 1024, 192, 128) == 16
    assert head_block(1, 512, 1024, 192, 128) == 1
    assert head_block(16, 512, 1024, 192, 192) == 8     # it has a backward


@pytest.mark.parametrize("model,tp,q_len,kv_len,want", [
    # 71 on 1: three blocks of 18 and a partial one of 17.
    ("falcon-7b", 1, 2048, 2049, {"full_attention": 18}),
    # The chat buckets: smaller query blocks leave room for more heads.
    ("falcon-7b", 1, 256, 1025, {"full_attention": 36}),
    # 128 on 8, four ways: a shard holds 2 KV heads with their 16 each.
    ("falcon-40b", 4, 2048, 2049, {"full_attention": 16}),
    # Multi-query under a tensor mesh gives a shard a slice of the group —
    # where the heads divide (71 does not: nothing shards).
    ("falcon-7b", 71, 2048, 2049, {"full_attention": 1}),
    ("falcon-7b", 4, 2048, 2049, {"full_attention": 18}),
    # Groups of one: the step of one head.
    ("sarvam-105b", 1, 2048, 2049, {"latent_attention": 1}),
    ("olmo-hybrid-7b", 1, 2048, 2049, {"full_attention": 1}),
    # 64 on 4 (full) and on 8 with a sink under a window (forward only).
    ("mimo-v2-flash", 1, 2048, 2049,
     {"full_attention": 16, "sliding_attention": 8}),
])
def test_heads_a_step_of_the_published_models(model, tp, q_len, kv_len, want):
    """What the engine's census, /metrics and the trainer's start-up line
    report for the benchmark's configurations (the published widths; depth
    does not enter)."""
    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.models.transformer import flash_heads_per_step

    assert flash_heads_per_step(get_config(model), q_len, kv_len, tp) == want


# --------------------------------------------------------------------------
# The block shape of a call comes from its shapes (block_shape)
# --------------------------------------------------------------------------

def test_block_shape_is_a_pure_function_of_static_shapes():
    """Python ints, multiples of the (8, 128) tile or the whole length,
    never over the lengths, the same for the same shapes; a given size is
    honoured and clamped; a group of one keeps 512 x 1024 under a window
    too (nothing shares its step's fixed cost)."""
    from runbooks_tpu.ops.flash_attention import (
        KERNELS,
        LANES,
        SUBLANES,
        block_shape,
    )

    for kernel in KERNELS:
        for sq in (1, 24, 128, 300, 512, 2048, 8192):
            for sk in (sq, sq + 1, 2049):
                for n_rep in (1, 8, 71):
                    for window in (0, 128, 256, 512, 4096):
                        got = block_shape(kernel, sq, sk, n_rep, window)
                        bq, bk = got
                        assert type(bq) is int and type(bk) is int
                        assert 0 < bq <= sq and 0 < bk <= sk
                        assert bq == sq or bq % SUBLANES == 0
                        assert bk == sk or bk % LANES == 0
                        assert got == block_shape(kernel, sq, sk, n_rep,
                                                  window)
    assert block_shape("fwd", 40, 48, 4, 0, 16, 32) == (16, 32)
    assert block_shape("bwd", 40, 48, 4, 0, 64, None) == (40, 48)
    assert block_shape("fwd", 2048, 2048, 1, 128) == (512, 1024)
    assert block_shape("fwd", 2048, 2048, 8, 128) == (256, 512)
    assert block_shape("fwd", 2048, 2048, 8, 384) == (512, 1024)


@pytest.mark.parametrize("model,tp,q_len,kv_len,backward,want", [
    # The LoRA step, [4, 2048] packed rows: the backward's own shape.
    ("falcon-7b", 1, 2048, 2048, True,
     {"full_attention": {"fwd": [512, 1024], "bwd": [512, 512]}}),
    # doc_flood's prefill of one row against the cache view.
    ("falcon-7b", 1, 2048, 2049, False,
     {"full_attention": {"fwd": [512, 1024]}}),
    # A chat bucket: clamped to the queries.
    ("falcon-7b", 1, 128, 1025, False,
     {"full_attention": {"fwd": [128, 1024]}}),
    ("falcon-40b", 4, 2048, 2049, False,
     {"full_attention": {"fwd": [512, 1024]}}),
    # Groups of one.
    ("sarvam-105b", 1, 2048, 2049, False,
     {"latent_attention": {"fwd": [512, 1024]}}),
    ("olmo-hybrid-7b", 1, 2048, 2049, False,
     {"full_attention": {"fwd": [512, 1024]}}),
    # Window 128 with a group of 8: a key block that holds a query block
    # and its window. Window 512: nothing to gain below 512 x 1024.
    ("mimo-v2-flash", 1, 2048, 2049, False,
     {"full_attention": {"fwd": [512, 1024]},
      "sliding_attention": {"fwd": [256, 512]}}),
    ("laguna-xs.2", 1, 2048, 2049, False,
     {"full_attention": {"fwd": [512, 1024]},
      "sliding_attention": {"fwd": [512, 1024]}}),
])
def test_block_shapes_of_the_published_models(model, tp, q_len, kv_len,
                                              backward, want):
    """The table of the chip sweep (the module docstring), as the engine's
    census, /metrics and the trainer's start-up line report it for the
    benchmark's configurations. No preset sets a block size."""
    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.models.transformer import flash_blocks

    cfg = get_config(model)
    assert cfg.flash_block_q is None and cfg.flash_block_k is None
    assert flash_blocks(cfg, q_len, kv_len, tp, backward) == want


def _rule_case(layout):
    """A call long enough for block_shape's own answers to give several
    blocks each way: (q, k, v, q_pos, kv_pos, q_seg, kv_seg, keywords)."""
    rng = np.random.default_rng(len(layout))
    h, kv_h, d, dv, sq, sk, over = 4, 2, 16, 16, 1100, 1100, {}
    q_seg = kv_seg = None
    if layout == "cached prefill at an offset, parked tails":
        # 640 queries of which 600 are real, at positions 700 .. 1299 of a
        # cache view of 1301 slots; parked tokens at -1 (the engine's).
        sq, sk = 640, 1301
        q_pos = np.where(np.arange(sq) < 600, np.arange(sq) + 700, -1)
        kv_pos = np.arange(sk)
    elif layout.startswith("window"):
        # A group of 4 a step; window 128 takes the rule's 256 x 512, 512
        # its 512 x 1024. Parked tail as _window_attention hands it over.
        d, dv = 24, 16
        over = dict(window=int(layout.split()[1]))
        if "sink" in layout:
            over["sink"] = jnp.asarray(rng.normal(size=h), jnp.float32)
        q_pos = np.where(np.arange(sq) < 1000, np.arange(sq), -1)
        kv_pos = np.where(np.arange(sk) < 1000, np.arange(sk), PAD_POS)
    elif layout.startswith("packed"):
        # Documents behind one another; a boundary ON the forward's query
        # block edge (512) and the backward's key block edge, or off both.
        cuts = [512, 1024] if "on" in layout else [300, 777]
        lengths = np.diff([0, *cuts, sq - 60])
        seg = np.concatenate([np.repeat(np.arange(1, 4), lengths),
                              np.zeros(60, np.int64)])
        q_seg = kv_seg = seg
        q_pos = kv_pos = np.concatenate(
            [np.arange(n) for n in (*lengths, 60)])
    elif layout == "MQA 71, a partial head block":
        h, kv_h, sq, sk = 71, 1, 520, 1030
        q_pos, kv_pos = np.arange(sq) + 500, np.arange(sk)
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (1, sq, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (1, sk, kv_h, d), jnp.float32)
    v = jax.random.normal(ks[2], (1, sk, kv_h, dv), jnp.float32)
    row = (lambda a: None if a is None
           else jnp.asarray(np.asarray(a, np.int32)[None]))
    return (q, k, v, row(q_pos), row(kv_pos), row(q_seg), row(kv_seg), over)


def _xla(q, k, v, q_pos, kv_pos, q_seg, kv_seg, over):
    mask = make_attention_mask(q_pos, kv_pos, q_seg, kv_seg)
    if "window" in over:
        mask &= (q_pos[:, None, :, None] - kv_pos[:, None, None, :]
                 < over["window"])
    return dot_product_attention(q, k, v, mask=mask, sink=over.get("sink"))


RULE_LAYOUTS = ["cached prefill at an offset, parked tails",
                "window 128 and a sink", "window 512",
                "packed on a block edge", "packed off a block edge",
                "MQA 71, a partial head block"]


@pytest.mark.parametrize("layout", RULE_LAYOUTS)
def test_forward_at_the_shapes_the_rule_answers(monkeypatch, layout):
    """With no block size given the forward runs at block_shape's answer
    for the call (several blocks each way at these lengths) and equals the
    XLA path on every real row; parked rows come out exactly 0."""
    import runbooks_tpu.ops.flash_attention as fa

    *call, over = _rule_case(layout)
    q, k, _, q_pos = call[:4]
    n_rep = q.shape[2] // k.shape[2]
    want_blocks = {"window 128 and a sink": (256, 512)}.get(
        layout, (512, 1024))
    assert fa.block_shape("fwd", q.shape[1], k.shape[1], n_rep,
                          over.get("window", 0)) == want_blocks
    if n_rep == 71:
        # 71 = 3 x 18 + 17 at the published widths; at these toy widths
        # the budget is shrunk until the group's last block is partial.
        monkeypatch.setattr(fa, "VMEM_BUDGET_BYTES", 24 * 2 ** 20)
        g = fa.head_block(71, 512, 1024, 16, 16)
        assert 1 < g < 71 and 71 % g
    got = jax.jit(lambda *a: flash_attention(*a, True, None, **over))(*call)
    want = _xla(*call, over)
    real = np.asarray(q_pos >= 0)[:, :, None, None]
    if call[5] is not None:
        real = real & np.asarray(call[5] != 0)[:, :, None, None]
    np.testing.assert_allclose(np.where(real, got, 0),
                               np.where(real, want, 0),
                               rtol=2e-5, atol=2e-5)
    assert not np.asarray(got)[~np.broadcast_to(real, got.shape)].any()


@pytest.mark.parametrize("layout", ["packed on a block edge",
                                    "packed off a block edge"])
def test_gradients_at_the_backwards_own_shape(layout):
    """jax.grad through a call with no block size given: the forward at
    512 x 1024, dq and dkv at 512 x 512 with the static skip on (three
    blocks each way at 1100 packed tokens), against the XLA path."""
    import runbooks_tpu.ops.flash_attention as fa

    *call, _ = _rule_case(layout)
    q, k, v, *rows = call
    assert fa.block_shape("bwd", q.shape[1], k.shape[1], 2) == (512, 512)
    weights = jax.random.normal(jax.random.key(3), q.shape, jnp.float32)

    def grads(attend):
        return jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(attend(q, k, v) * weights),
            argnums=(0, 1, 2)))(q, k, v)

    got = grads(lambda q, k, v: flash_attention(q, k, v, *rows, True, None))
    want = grads(lambda q, k, v: _xla(q, k, v, *rows, {}))
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4, err_msg=name)
