"""ops/gated_delta.py: the chunked (WY) form against the token-by-token
recurrence, and the short convolution's tail."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbooks_tpu.ops.gated_delta import (
    _inv_unit_lower,
    causal_conv,
    gated_delta_chunked,
    gated_delta_reference,
    gated_delta_step,
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
