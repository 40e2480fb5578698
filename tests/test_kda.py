"""ops/kda.py: Kimi Delta Attention's chunked form (the interpreted kernel
and the plain form) against the token-by-token recurrence, at decays down
to the seeded recipe's most negative, and against ops/gated_delta.py where
every channel decays alike."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbooks_tpu.ops import gated_delta
from runbooks_tpu.ops.gated_delta import l2_normalize
from runbooks_tpu.ops.kda import (
    CHUNK,
    _chunked_plain,
    kda_chunked,
    kda_reference,
    kda_step,
    kernel_shape,
)

# A_log up to log 16 under a softplus of a few units: a token's g at the
# recipe's most negative; 64 of them cumulate to -3840, and exp(3840) is no
# float32.
G_MIN = -60.0


def inputs(b, s, heads=3, dk=24, dv=40, seed=0, dtype=jnp.float32,
           g_min=None):
    ks = jax.random.split(jax.random.key(seed), 7)
    # SiLU of shifted normals: keys that share a direction, as the
    # model's do, so that (I + A) is far from the identity.
    q = l2_normalize(jax.nn.silu(
        jax.random.normal(ks[0], (b, s, heads, dk)) + 0.5)) * dk ** -0.5
    k = l2_normalize(jax.nn.silu(
        jax.random.normal(ks[1], (b, s, heads, dk)) + 0.5))
    v = jax.random.normal(ks[2], (b, s, heads, dv))
    g = -jnp.exp(jax.random.uniform(ks[3], (b, s, heads, dk), minval=-6.0,
                                    maxval=0.5))
    if g_min is not None:
        # A fifth of the channels at the floor, among channels that hardly
        # decay: both ends of the range in one contraction.
        g = jnp.where(jax.random.uniform(ks[6], g.shape) < 0.2, g_min, g)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, heads)))
    state = jax.random.normal(ks[5], (b, heads, dk, dv))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta,
            state)


def gaps(got, want, mask=None):
    (got_o, got_s), (want_o, want_s) = got, want
    seen = jnp.ones(got_o.shape[:2], bool) if mask is None else mask
    diff = jnp.where(seen[..., None, None],
                     got_o.astype(jnp.float32) - want_o.astype(jnp.float32),
                     0.0)
    return (float(jnp.max(jnp.abs(diff))),
            float(jnp.max(jnp.abs(got_s - want_s))))


@pytest.mark.parametrize("s,g_min", [(1, None), (64, G_MIN), (150, None),
                                     (150, G_MIN)])
@pytest.mark.parametrize("form", ["kernel", "plain"])
def test_chunked_form_is_the_recurrence(s, g_min, form):
    """Prompts of unequal length in one bucket (row 1 valid for its first
    half only), from a state carried in."""
    q, k, v, g, beta, state = inputs(2, s, g_min=g_min)
    mask = jnp.arange(s)[None, :] < jnp.array([s, s // 2])[:, None]
    if form == "kernel":
        got = jax.jit(kda_chunked)(q, k, v, g, beta, state, mask)
    else:
        got = jax.jit(_chunked_plain, static_argnums=6)(
            q, k, v, jnp.where(mask[..., None, None], g, 0.0),
            jnp.where(mask[..., None], beta, 0.0), state, CHUNK)
    want = jax.jit(kda_reference)(q, k, v, g, beta, state, mask)
    o_gap, s_gap = gaps(got, want, mask)
    # float32 round-off of sums taken in another order; at the floor the
    # cumulated decay of a chunk runs to thousands, whose float32 spacing
    # (2.4e-4) the differences inherit.
    assert o_gap < (5e-6 if g_min is None else 2e-5)
    assert s_gap < (2e-5 if g_min is None else 1e-4)
    assert np.isfinite(np.asarray(got[0], np.float32)).all()


def test_a_state_carried_across_two_calls_is_one_call():
    q, k, v, g, beta, state = inputs(2, 140, g_min=G_MIN)
    cut = 76            # inside a chunk; both calls pad to 128
    run = jax.jit(kda_chunked)
    whole = run(q, k, v, g, beta, state)
    first_o, mid = run(q[:, :cut], k[:, :cut], v[:, :cut], g[:, :cut],
                       beta[:, :cut], state)
    second_o, last = run(q[:, cut:], k[:, cut:], v[:, cut:], g[:, cut:],
                         beta[:, cut:], mid)
    o_gap, s_gap = gaps((jnp.concatenate([first_o, second_o], 1), last),
                        whole)
    assert o_gap < 2e-5 and s_gap < 1e-4


def test_a_parked_row_keeps_its_state_bit_for_bit():
    q, k, v, g, beta, state = inputs(2, 70)
    mask = jnp.stack([jnp.ones(70, bool), jnp.zeros(70, bool)])
    _, got = jax.jit(kda_chunked)(q, k, v, g, beta, state, mask)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(state[1]))
    _, stepped = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                          state, jnp.array([True, False]))
    np.testing.assert_array_equal(np.asarray(stepped[1]),
                                  np.asarray(state[1]))
    assert float(jnp.max(jnp.abs(stepped[0] - state[0]))) > 1e-3


def test_equal_decays_are_the_gated_delta_rule():
    """Every channel of a head at one decay: KDA is ops/gated_delta.py's
    rule. The step and the recurrence bit for bit; the chunked forms, whose
    sums run in another order, to float32 round-off."""
    q, k, v, g, beta, state = inputs(2, 130, g_min=None)
    g1 = g[..., 0]
    wide = jnp.broadcast_to(g1[..., None], g.shape)
    beta2 = 2 * beta    # the gated delta rule's range
    o_kda, s_kda = kda_step(q[:, 0], k[:, 0], v[:, 0], wide[:, 0],
                            beta2[:, 0], state)
    o_gd, s_gd = gated_delta.gated_delta_step(
        q[:, 0], k[:, 0], v[:, 0], g1[:, 0], beta2[:, 0], state)
    np.testing.assert_array_equal(np.asarray(o_kda), np.asarray(o_gd))
    np.testing.assert_array_equal(np.asarray(s_kda), np.asarray(s_gd))
    want = gated_delta.gated_delta_reference(q, k, v, g1, beta2, state)
    got = kda_reference(q, k, v, wide, beta2, state)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    chunked_gd = jax.jit(gated_delta.gated_delta_chunked)(q, k, v, g1, beta2,
                                                          state)
    o_gap, s_gap = gaps(jax.jit(kda_chunked)(q, k, v, wide, beta2, state),
                        chunked_gd)
    assert o_gap < 5e-6 and s_gap < 2e-5


def test_bfloat16_operands_stay_close_to_the_recurrence():
    q, k, v, g, beta, state = inputs(2, 192, heads=2, dk=32, dv=32,
                                     dtype=jnp.bfloat16, g_min=G_MIN)
    o_gap, s_gap = gaps(jax.jit(kda_chunked)(q, k, v, g, beta, state),
                        kda_reference(q, k, v, g, beta, state))
    # bfloat16's spacing at outputs of order 1.
    assert o_gap < 3e-2 and s_gap < 3e-2


def test_gradients_are_the_plain_forms():
    q, k, v, g, beta, state = inputs(1, 70, heads=2, dk=16, dv=16)

    def loss(fn, *xs):
        o, s = fn(*xs)
        return jnp.sum(o * o) + jnp.sum(s)

    got = jax.jit(jax.grad(lambda *xs: loss(kda_chunked, *xs),
                           argnums=range(6)))(q, k, v, g, beta, state)
    want = jax.jit(jax.grad(lambda *xs: loss(kda_reference, *xs),
                            argnums=range(6)))(q, k, v, g, beta, state)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


@pytest.mark.parametrize("s,heads,dk,want", [
    (16384, 32, 128, (4, 4, 16384)),     # the cell's longest prefill
    (2048, 32, 128, (4, 4, 2048)),
    (100, 32, 128, (2, 4, 128)),
    (300, 4, 32, (4, 4, 512)),           # the toy model: 4 x 32 = a tile
    (300, 3, 24, (5, 3, 320)),           # no tile: every head a step
])
def test_launch_shape_is_a_function_of_the_call(s, heads, dk, want):
    assert kernel_shape(s, heads, dk, dk) == want


def test_kernel_runs_per_shard_under_a_mesh():
    from runbooks_tpu.parallel.mesh import MeshConfig, make_mesh

    q, k, v, g, beta, state = inputs(2, 70, heads=4, dk=32, dv=32)
    want = jax.jit(kda_chunked)(q, k, v, g, beta, state)
    mesh = make_mesh(MeshConfig(data=2, tensor=2), devices=jax.devices()[:4])
    with jax.set_mesh(mesh):
        got = jax.jit(kda_chunked)(q, k, v, g, beta, state)
    o_gap, s_gap = gaps(got, want)
    assert o_gap < 1e-6 and s_gap < 1e-6
