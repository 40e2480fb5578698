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
