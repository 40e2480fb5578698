"""Flash-attention kernel vs the XLA reference attention (the numerical
oracle), forward and backward, in Pallas interpreter mode on CPU."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbooks_tpu.ops.attention import dot_product_attention, make_attention_mask
from runbooks_tpu.ops.flash_attention import flash_attention


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
