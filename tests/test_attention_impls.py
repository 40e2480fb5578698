"""Model-level equivalence of attention implementations: xla vs flash vs
ring (sequence-parallel over the mesh)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding

from runbooks_tpu.models.config import get_config
from runbooks_tpu.models.transformer import forward, init_params
from runbooks_tpu.ops.attention import (
    alibi_slopes,
    dot_product_attention,
    make_attention_mask,
)
from runbooks_tpu.parallel.mesh import MeshConfig, make_mesh
from runbooks_tpu.parallel.sharding import spec_for_array


def cfg_with(impl):
    return dataclasses.replace(
        get_config("llama2-7b"), vocab_size=128, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, max_seq_len=128, dtype="float32",
        attention_impl=impl,
    )


def test_flash_impl_matches_xla():
    cfg_x, cfg_f = cfg_with("xla"), cfg_with("flash")
    params = init_params(cfg_x, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg_x.vocab_size)
    lx, _ = forward(cfg_x, params, toks)
    lf, _ = forward(cfg_f, params, toks)
    np.testing.assert_allclose(lx, lf, rtol=2e-4, atol=2e-4)


def test_flash_impl_with_packing():
    cfg_x, cfg_f = cfg_with("xla"), cfg_with("flash")
    params = init_params(cfg_x, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg_x.vocab_size)
    segs = jnp.asarray(np.repeat([[1, 2, 3, 0]], 16, axis=1).reshape(1, 64)
                       .repeat(2, 0))
    pos = jnp.asarray(np.tile(np.arange(16), 4)[None].repeat(2, 0),
                      jnp.int32)
    lx, _ = forward(cfg_x, params, toks, positions=pos, segment_ids=segs)
    lf, _ = forward(cfg_f, params, toks, positions=pos, segment_ids=segs)
    # Compare only non-pad rows (pad logits differ: oracle zeroes them).
    valid = np.asarray(segs) != 0
    np.testing.assert_allclose(np.asarray(lx)[valid], np.asarray(lf)[valid],
                               rtol=2e-4, atol=2e-4)


def test_ring_impl_matches_xla_on_sequence_mesh():
    cfg_x, cfg_r = cfg_with("xla"), cfg_with("ring")
    params = init_params(cfg_x, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg_x.vocab_size)
    mesh = make_mesh(MeshConfig(data=2, fsdp=1, sequence=4, tensor=1))

    lx, _ = forward(cfg_x, params, toks)

    @jax.jit
    def f(params, toks):
        logits, _ = forward(cfg_r, params, toks)
        return logits

    with jax.set_mesh(mesh):
        lr = f(params, toks)
    np.testing.assert_allclose(lx, np.asarray(lr), rtol=2e-4, atol=2e-4)


def test_ring_impl_gradients_match():
    cfg_x, cfg_r = cfg_with("xla"), cfg_with("ring")
    params = init_params(cfg_x, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg_x.vocab_size)
    mesh = make_mesh(MeshConfig(data=1, fsdp=1, sequence=4, tensor=2))

    def loss(cfg):
        def inner(params):
            logits, _ = forward(cfg, params, toks)
            return jnp.mean(jax.nn.log_softmax(logits) ** 2)
        return inner

    gx = jax.grad(loss(cfg_x))(params)
    with jax.set_mesh(mesh):
        gr = jax.jit(jax.grad(loss(cfg_r)))(params)
    for a, b in zip(jax.tree.leaves(gx), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_ring_flash_inner_matches_xla_inner():
    """SPxflash composition (r4 verdict #5): the flash-kernel-per-block
    ring (out/lse merge fwd, hand-written ring bwd with global lse) must
    match the autodiff XLA-inner ring and the single-device oracle."""
    cfg_x = cfg_with("xla")
    cfg_rf = dataclasses.replace(cfg_with("ring"), ring_flash_inner=True,
                                 flash_block_q=16, flash_block_k=16)
    params = init_params(cfg_x, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg_x.vocab_size)
    mesh = make_mesh(MeshConfig(data=2, fsdp=1, sequence=4, tensor=1))

    lx, _ = forward(cfg_x, params, toks)
    with jax.set_mesh(mesh):
        lr = jax.jit(lambda p, t: forward(cfg_rf, p, t)[0])(params, toks)
    np.testing.assert_allclose(lx, np.asarray(lr), rtol=2e-4, atol=2e-4)


def test_ring_flash_inner_gradients_match():
    cfg_x = cfg_with("xla")
    cfg_rf = dataclasses.replace(cfg_with("ring"), ring_flash_inner=True,
                                 flash_block_q=16, flash_block_k=16)
    params = init_params(cfg_x, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg_x.vocab_size)
    mesh = make_mesh(MeshConfig(data=1, fsdp=1, sequence=4, tensor=2))

    def loss(cfg):
        def inner(params):
            logits, _ = forward(cfg, params, toks)
            return jnp.mean(jax.nn.log_softmax(logits) ** 2)
        return inner

    gx = jax.grad(loss(cfg_x))(params)
    with jax.set_mesh(mesh):
        gr = jax.jit(jax.grad(loss(cfg_rf)))(params)
    for a, b in zip(jax.tree.leaves(gx), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_ring_flash_inner_with_packing():
    """Packed segments cross shard boundaries; the flash inner must mask
    identically to the XLA inner under rotation."""
    cfg_r = cfg_with("ring")
    cfg_rf = dataclasses.replace(cfg_r, ring_flash_inner=True,
                                 flash_block_q=16, flash_block_k=16)
    params = init_params(cfg_r, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg_r.vocab_size)
    segs = jnp.asarray(np.repeat([[1, 2, 3, 0]], 16, axis=1).reshape(1, 64)
                       .repeat(2, 0))
    pos = jnp.asarray(np.tile(np.arange(16), 4)[None].repeat(2, 0),
                      jnp.int32)
    mesh = make_mesh(MeshConfig(data=2, fsdp=1, sequence=4, tensor=1))
    with jax.set_mesh(mesh):
        l_xla = jax.jit(lambda p, t: forward(
            cfg_r, p, t, positions=pos, segment_ids=segs)[0])(params, toks)
        l_fl = jax.jit(lambda p, t: forward(
            cfg_rf, p, t, positions=pos, segment_ids=segs)[0])(params, toks)
    valid = np.asarray(segs) != 0
    np.testing.assert_allclose(np.asarray(l_xla)[valid],
                               np.asarray(l_fl)[valid],
                               rtol=2e-4, atol=2e-4)


def test_ring_flash_save_attn_out_skips_fwd_ring_recompute():
    """The ring's (out, lse) are tagged OUTSIDE the custom_vjp and the
    shard_map (names nested in either are invisible to checkpoint
    policies), so save_attn_out must drop the forward-ring re-run from
    the backward pass. Pallas call SITES in the grad jaxpr:
    nothing_saveable = 8 (fwd local+scan, recomputed fwd local+scan,
    bwd local dq+dkv, bwd scan dq+dkv); save_attn_out = 6."""
    from tests.test_flash_attention import _count_pallas_calls

    base = dataclasses.replace(cfg_with("ring"), ring_flash_inner=True,
                               flash_block_q=16, flash_block_k=16)
    tokens = jnp.zeros((2, 64), jnp.int32)
    mesh = make_mesh(MeshConfig(data=1, fsdp=2, sequence=4, tensor=1))
    counts = {}
    with jax.set_mesh(mesh):
        for policy in ("nothing_saveable", "save_attn_out"):
            cfg = dataclasses.replace(base, remat_policy=policy)
            params = init_params(cfg, jax.random.key(0))

            def loss(p, cfg=cfg):
                logits, _ = forward(cfg, p, tokens, remat=True)
                return jnp.mean(logits)

            jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
            counts[policy] = _count_pallas_calls(jaxpr.jaxpr)
    assert counts["nothing_saveable"] == 8, counts
    assert counts["save_attn_out"] == 6, counts


# ---------------------------------------------------------------------------
# dot_product_attention contracts each KV head against its whole group of
# query heads; K/V are never repeated per query head (ops/attention.py).
# ---------------------------------------------------------------------------

KV_LEN = 64


def _repeat_kv(x, n_rep):
    """[b, s, kv_heads, d] -> [b, s, kv_heads*n_rep, d]: head h*n_rep + r
    is a copy of KV head h."""
    b, s, h, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d)


def _repeat_oracle(q, k, v, mask=None, bias=None, logit_softcap=None):
    """The explicit-repeat form the grouped contraction replaced: widen K/V
    to one copy per query head, then plain multi-head attention."""
    n_rep = q.shape[2] // k.shape[2]
    k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision="highest") * q.shape[-1] ** -0.5
    if logit_softcap is not None:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)
    if bias is not None:
        logits = logits + bias
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    if mask is not None:
        probs = jnp.where(jnp.any(mask, axis=-1, keepdims=True), probs, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision="highest")


def _qkv(heads, kv_heads, q_len, b=2, d=16, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(jax.random.key(heads * 100 + q_len), 3)
    q = jax.random.normal(kq, (b, q_len, heads, d), dtype)
    k = jax.random.normal(kk, (b, KV_LEN, kv_heads, d), dtype)
    v = jax.random.normal(kv, (b, KV_LEN, kv_heads, d), dtype)
    return q, k, v


def _decode_mask(b, q_len):
    """The cache's mask: the q_len queries are the last positions."""
    q_pos = jnp.broadcast_to(
        jnp.arange(KV_LEN - q_len, KV_LEN, dtype=jnp.int32)[None], (b, q_len))
    kv_pos = jnp.broadcast_to(
        jnp.arange(KV_LEN, dtype=jnp.int32)[None], (b, KV_LEN))
    return make_attention_mask(q_pos, kv_pos), q_pos, kv_pos


@pytest.mark.parametrize("variant",
                         ["mask", "mask_alibi", "softcap", "masked_row"])
@pytest.mark.parametrize("q_len", [1, 5, 64])
@pytest.mark.parametrize("heads,kv_heads", [(4, 4), (8, 2), (71, 1)])
def test_grouped_attention_matches_repeat_oracle(heads, kv_heads, q_len,
                                                 variant):
    q, k, v = _qkv(heads, kv_heads, q_len)
    mask, q_pos, kv_pos = _decode_mask(q.shape[0], q_len)
    kwargs = {"mask": mask}
    if variant == "mask_alibi":
        # Per-head bias [1, H, q, k]: a head landing on the wrong KV group
        # or the wrong slope shows.
        dist = (kv_pos[0][None, :] - q_pos[0][:, None]).astype(jnp.float32)
        kwargs["bias"] = (alibi_slopes(heads)[:, None, None] * dist)[None]
    elif variant == "softcap":
        kwargs["logit_softcap"] = 5.0
    elif variant == "masked_row":
        # A row that may attend nothing (padding) must come out as zeros;
        # a per-head mask [b, H, q, k] also takes the reshape of its heads.
        mask = jnp.broadcast_to(mask, (q.shape[0], heads, q_len, KV_LEN))
        kwargs["mask"] = mask.at[1, :, 0].set(False).at[0, heads // 2].set(
            False)
    got = dot_product_attention(q, k, v, **kwargs)
    want = _repeat_oracle(q, k, v, **kwargs)
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    if variant == "masked_row":
        assert not np.any(np.asarray(got[1, 0]))
        assert not np.any(np.asarray(got[0, :, heads // 2]))


def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_eqns(sub)


@pytest.mark.parametrize("heads,kv_heads", [(8, 2), (71, 1)])
def test_decode_attention_program_stays_at_kv_width(heads, kv_heads):
    """A property of the traced program, not of a run: no intermediate has
    K/V's length at the query heads' width, and each KV head is a batch
    dimension of both products (so its group is the matrix's rows)."""
    q, k, v = _qkv(heads, kv_heads, 1, dtype=jnp.bfloat16)
    mask, _, _ = _decode_mask(q.shape[0], 1)
    jaxpr = jax.make_jaxpr(dot_product_attention)(q, k, v, mask).jaxpr
    widened = (q.shape[0], KV_LEN, heads, q.shape[-1])
    dots = []
    for eqn in _all_eqns(jaxpr):
        for var in eqn.outvars:
            assert tuple(var.aval.shape) != widened, eqn
        if eqn.primitive.name == "dot_general":
            dots.append(eqn)
    assert len(dots) == 2
    for eqn in dots:
        (_, _), (lhs_batch, rhs_batch) = eqn.params["dimension_numbers"]
        lhs, rhs = (x.aval.shape for x in eqn.invars)
        assert eqn.params["preferred_element_type"] == jnp.float32
        assert len(lhs_batch) == 2, eqn        # (row, KV head)
        assert sorted(lhs[i] for i in lhs_batch) == sorted(
            (q.shape[0], kv_heads)), eqn
        assert sorted(rhs[i] for i in rhs_batch) == sorted(
            (q.shape[0], kv_heads)), eqn


@pytest.mark.parametrize("heads,kv_heads", [(8, 2), (4, 1)])
def test_grouped_attention_under_tensor_mesh_moves_no_kv(heads, kv_heads):
    """tensor=2: with 2 KV heads each device keeps one KV head and its four
    query heads; with 1 KV head K/V are replicated and each device takes
    half the group. Neither needs a collective."""
    mesh = make_mesh(MeshConfig(data=1, fsdp=4, tensor=2))
    q, k, v = _qkv(heads, kv_heads, 1, b=4)
    mask, _, _ = _decode_mask(q.shape[0], 1)
    heads_axes = ("batch", "seq", "act_heads", None)

    def place(x, logical):
        return jax.device_put(x, NamedSharding(
            mesh, spec_for_array(x.shape, logical, mesh)))

    args = (place(q, heads_axes), place(k, heads_axes), place(v, heads_axes),
            place(mask, ("batch", None, None, None)))
    with jax.set_mesh(mesh):
        f = jax.jit(dot_product_attention, out_shardings=args[0].sharding)
        hlo = f.lower(*args).compile().as_text()
        got = f(*args)
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute", "reduce-scatter"):
        assert collective not in hlo, collective
    assert got.sharding.spec[2] == "tensor"
    np.testing.assert_allclose(got, _repeat_oracle(q, k, v, mask),
                               rtol=1e-5, atol=1e-5)
