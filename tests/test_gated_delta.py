"""ops/gated_delta.py: the chunked (WY) form against the token-by-token
recurrence, and the short convolution's tail."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbooks_tpu.ops.gated_delta import (
    CHUNK,
    _chunked_plain,
    _inv_unit_lower,
    _inv_unit_lower_vmem,
    causal_conv,
    gated_delta_chunked,
    gated_delta_reference,
    gated_delta_step,
    kernel_shape,
    l2_normalize,
)


def inputs(b, s, heads=3, dk=24, dv=40, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 6)
    # SiLU of shifted normals: keys that share a direction, as the
    # model's do, so that (I + A) is far from the identity.
    q = l2_normalize(jax.nn.silu(
        jax.random.normal(ks[0], (b, s, heads, dk)) + 0.5)) * dk ** -0.5
    k = l2_normalize(jax.nn.silu(
        jax.random.normal(ks[1], (b, s, heads, dk)) + 0.5))
    v = jax.random.normal(ks[2], (b, s, heads, dv))
    g = -jnp.exp(jax.random.uniform(ks[3], (b, s, heads), minval=-6.0,
                                    maxval=0.5))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, heads)))
    state = jax.random.normal(ks[5], (b, heads, dk, dv))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta,
            state)


@pytest.mark.parametrize("s", [1, 16, 50, 64, 130, 256])
@pytest.mark.parametrize("with_state_and_mask", [False, True])
def test_chunked_form_is_the_recurrence(s, with_state_and_mask):
    q, k, v, g, beta, state = inputs(2, s)
    mask = init = None
    if with_state_and_mask:
        init = state
        # Row 0 whole, row 1 valid for its first half only.
        mask = jnp.arange(s)[None, :] < jnp.array([s, s // 2])[:, None]
    got_o, got_s = jax.jit(gated_delta_chunked)(q, k, v, g, beta, init, mask)
    want_o, want_s = jax.jit(gated_delta_reference)(q, k, v, g, beta, init,
                                                    mask)
    seen = jnp.ones((2, s), bool) if mask is None else mask
    diff = jnp.where(seen[..., None, None], got_o - want_o, 0.0)
    # float32 round-off of sums taken in another order.
    assert float(jnp.max(jnp.abs(diff))) < 5e-6
    assert float(jnp.max(jnp.abs(got_s - want_s))) < 2e-5


def test_a_fully_masked_row_keeps_its_state_bit_for_bit():
    q, k, v, g, beta, state = inputs(2, 70)
    mask = jnp.stack([jnp.ones(70, bool), jnp.zeros(70, bool)])
    _, new = jax.jit(gated_delta_chunked)(q, k, v, g, beta, state, mask)
    assert jnp.array_equal(new[1], state[1])
    assert not jnp.array_equal(new[0], state[0])
    _, new = gated_delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                              beta[:, 0], state, jnp.array([True, False]))
    assert jnp.array_equal(new[1], state[1])
    assert not jnp.array_equal(new[0], state[0])


def test_bfloat16_operands_stay_close_to_the_recurrence():
    q, k, v, g, beta, _ = inputs(2, 256, dk=96, dv=192, dtype=jnp.bfloat16)
    got_o, got_s = jax.jit(gated_delta_chunked)(q, k, v, g, beta)
    want_o, want_s = jax.jit(gated_delta_reference)(q, k, v, g, beta)
    rel = lambda a, b: float(  # noqa: E731
        jnp.linalg.norm((a - b).astype(jnp.float32))
        / jnp.linalg.norm(b.astype(jnp.float32)))
    # bfloat16 has 8 bits of mantissa (2^-9 = 2e-3 a rounding); products
    # of rounded operands accumulated in float32 land at a few of those.
    assert rel(got_o, want_o) < 1e-2 and rel(got_s, want_s) < 1e-2


@pytest.mark.parametrize("n", [8, 16, 32, 64])
def test_unit_lower_inverse(n):
    # Entries of the size beta (k_i . k_j) takes: larger ones make the
    # inverse itself grow with n, whatever computes it.
    a = 0.3 * jnp.tril(jax.random.normal(jax.random.key(n), (3, n, n)), -1)
    t = _inv_unit_lower(a)
    eye = jnp.eye(n)
    assert float(jnp.max(jnp.abs(t @ (eye + a) - eye))) < 1e-3 * float(
        jnp.max(jnp.abs(t)))
    assert jnp.array_equal(jnp.triu(t, 1), jnp.zeros_like(t))


@pytest.mark.parametrize("n", [16, 32, 64])
def test_the_kernels_inverse_is_the_plain_one(n):
    # The same blocked scheme on the values a kernel holds (it runs as
    # plain jax.numpy outside one): float32 round-off apart.
    a = 0.3 * jnp.tril(jax.random.normal(jax.random.key(n), (3, n, n)), -1)
    got, want = _inv_unit_lower_vmem(a), _inv_unit_lower(a)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6 * float(
        jnp.max(jnp.abs(want)))
    assert jnp.array_equal(jnp.triu(got, 1), jnp.zeros_like(got))


def plain(q, k, v, g, beta, state, mask=None):
    """The kernel's oracle, shape for shape: the chunked form in plain
    jax.numpy, fed what the public entry feeds the kernel."""
    if mask is not None:
        g = jnp.where(mask[..., None], g, 0.0)
        beta = jnp.where(mask[..., None], beta, 0.0)
    return _chunked_plain(q, k, v, g, beta, state, CHUNK)


@pytest.mark.parametrize("heads,dk,dv", [(3, 24, 40), (30, 96, 192)])
def test_kernel_is_the_plain_chunked_form(heads, dk, dv):
    q, k, v, g, beta, state = inputs(2, 130, heads, dk, dv, seed=1)
    mask = jnp.arange(130)[None, :] < jnp.array([130, 77])[:, None]
    got_o, got_s = jax.jit(gated_delta_chunked)(q, k, v, g, beta, state,
                                                mask)
    want_o, want_s = jax.jit(plain)(q, k, v, g, beta, state, mask)
    diff = jnp.where(mask[..., None, None], got_o - want_o, 0.0)
    assert float(jnp.max(jnp.abs(diff))) < 5e-6
    assert float(jnp.max(jnp.abs(got_s - want_s))) < 2e-5


@pytest.mark.parametrize("s,valid", [(100, 70), (64, 1), (1, 1)])
def test_a_prompt_may_end_inside_a_chunk(s, valid):
    # A bucket of s tokens holding a prompt of `valid`: the state is the
    # recurrence's after `valid` tokens, whatever the padding holds.
    q, k, v, g, beta, state = inputs(1, s, seed=2)
    mask = jnp.arange(s)[None, :] < valid
    got_o, got_s = jax.jit(gated_delta_chunked)(q, k, v, g, beta, state,
                                                mask)
    cut = lambda x: x[:, :valid]  # noqa: E731
    want_o, want_s = jax.jit(gated_delta_reference)(
        cut(q), cut(k), cut(v), cut(g), cut(beta), state)
    assert float(jnp.max(jnp.abs(got_o[:, :valid] - want_o))) < 5e-6
    assert float(jnp.max(jnp.abs(got_s - want_s))) < 2e-5


def test_gradients_are_the_plain_forms():
    # The kernel has no backward of its own: jax.grad through the public
    # entry runs the plain form's.
    q, k, v, g, beta, state = inputs(2, 70, seed=3)
    mask = jnp.arange(70)[None, :] < jnp.array([70, 40])[:, None]
    weigh = jax.random.normal(jax.random.key(9), (2, 70, 3, 40))

    def scalar(fn):
        def of(q, k, v, g, beta, state):
            o, s = fn(q, k, v, g, beta, state, mask)
            return jnp.sum(o * weigh) + jnp.sum(jnp.square(s))
        return of

    every = tuple(range(6))
    got = jax.jit(jax.grad(scalar(gated_delta_chunked), every))(
        q, k, v, g, beta, state)
    want = jax.jit(jax.grad(scalar(plain), every))(q, k, v, g, beta, state)
    for name, a, b in zip("q k v g beta state".split(), got, want):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5 * scale, name
    # An invalid token's decay and write strength move nothing.
    assert not bool(jnp.any(got[3][1, 40:])) and not bool(
        jnp.any(got[4][1, 40:]))


@pytest.mark.parametrize("bucket,shape", [
    (2048, (4, 10, 2048)), (1024, (4, 10, 1024)), (16, (1, 10, 64))])
def test_launch_shape_of_the_cells_prefill_buckets(bucket, shape):
    # olmo-hybrid-7b: 30 linear heads, buckets 16 .. 2048. (chunks a grid
    # step, heads a grid step, padded tokens): a rule of shapes alone.
    assert kernel_shape(bucket, 30) == shape
    cs, hb, padded = shape
    assert padded % (cs * CHUNK) == 0 and 30 % hb == 0 and padded >= bucket


@pytest.mark.parametrize("heads,mesh_axes", [
    (4, {"fsdp": 2, "tensor": 2}),      # batch and heads both shard
    (3, {"fsdp": 2, "tensor": 2}),      # heads do not divide: whole a shard
])
def test_kernel_runs_per_shard_under_a_mesh(heads, mesh_axes):
    # A TPU refuses to partition a Mosaic kernel: under a multi-device
    # mesh the launch is a shard_map, and its results are the unsharded
    # call's.
    from runbooks_tpu.parallel.mesh import MeshConfig, make_mesh

    q, k, v, g, beta, state = inputs(2, 70, heads=heads, seed=4)
    mask = jnp.arange(70)[None, :] < jnp.array([70, 33])[:, None]
    want_o, want_s = jax.jit(gated_delta_chunked)(q, k, v, g, beta, state,
                                                  mask)
    with jax.set_mesh(make_mesh(MeshConfig(**mesh_axes),
                                devices=jax.devices()[:4])):
        got_o, got_s = jax.jit(gated_delta_chunked)(q, k, v, g, beta, state,
                                                    mask)
    diff = jnp.where(mask[..., None, None], got_o - want_o, 0.0)
    assert float(jnp.max(jnp.abs(diff))) < 1e-6
    assert float(jnp.max(jnp.abs(got_s - want_s))) < 1e-6


def test_conv_tail_is_taken_at_the_last_valid_token():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(3, 10, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    tail0 = jnp.asarray(rng.normal(size=(3, 3, 6)), jnp.float32)
    n_valid = jnp.array([10, 4, 0])
    y, tail = causal_conv(x, w, tail0, n_valid)
    whole = jnp.concatenate([tail0, x], axis=1)
    for t in range(10):     # y_t sees inputs t-3 .. t, w[-1] on the newest
        want = jax.nn.silu(sum(whole[:, t + j] * w[j] for j in range(4)))
        np.testing.assert_allclose(y[:, t], want, rtol=1e-5, atol=1e-6)
    assert jnp.array_equal(tail[0], x[0, 7:])          # all ten valid
    assert jnp.array_equal(tail[1], x[1, 1:4])         # tokens 1, 2, 3
    assert jnp.array_equal(tail[2], tail0[2])          # nothing valid
    # Feeding a sequence in two pieces is feeding it whole.
    y1, t1 = causal_conv(x[:, :6], w)
    y2, _ = causal_conv(x[:, 6:], w, t1)
    y_all, _ = causal_conv(x, w)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y_all,
                               rtol=1e-6, atol=1e-6)
