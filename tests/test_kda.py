"""ops/kda.py: Kimi Delta Attention's chunked form (the interpreted kernel
and the plain form) against the token-by-token recurrence, at decays down
to the seeded recipe's most negative, and against ops/gated_delta.py where
every channel decays alike. The chunked form is fed what the projections
and the short convolution write (q and k NOT normalized, the decay's
low-rank operands); the recurrence is fed what ``kda_decay`` and
``unit_qk`` make of them in plain XLA, as the decode step is."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbooks_tpu.ops import gated_delta
from runbooks_tpu.ops.kda import (
    CHUNK,
    _chunked_plain,
    _reads_in_place,
    kda_chunked,
    kda_decay,
    kda_reference,
    kda_step,
    kernel_shape,
    unit_qk,
)

# A_log up to log 16 under a softplus of a few units: a token's g at the
# recipe's most negative; 64 of them cumulate to -3840, and exp(3840) is no
# float32.
G_MIN = -60.0
TOY = dict(heads=3, dk=24, dv=40)       # no tile: every head a step, slices
TILES = dict(heads=8, dk=32, dv=64)     # two blocks of 4 heads, read in place


def inputs(b, s, heads=3, dk=24, dv=40, rank=8, seed=0, dtype=jnp.float32,
           g_min=None, one_decay_a_head=False):
    """(the chunked form's operands (qkv, f, wf_up, dt_bias, a_log, beta),
    a state carried in)."""
    ks = jax.random.split(jax.random.key(seed), 10)
    # SiLU of shifted normals, as the convolution leaves them: keys that
    # share a direction, so that (I + A) is far from the identity.
    q = jax.nn.silu(jax.random.normal(ks[0], (b, s, heads * dk)) + 0.5)
    k = jax.nn.silu(jax.random.normal(ks[1], (b, s, heads * dk)) + 0.5)
    v = jax.random.normal(ks[2], (b, s, heads * dv))
    f = jax.random.normal(ks[3], (b, s, rank))
    wf_up = jax.random.normal(ks[7], (rank, heads * dk)) * rank ** -0.5
    # The seeded recipe's ranges: a token's g from about -2 to -1e-3.
    a_log = jnp.log(jax.random.uniform(ks[8], (heads,), minval=1.0,
                                       maxval=16.0))
    dt = jnp.exp(jax.random.uniform(ks[9], (heads * dk,),
                                    minval=jnp.log(1e-3),
                                    maxval=jnp.log(1e-1)))
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))
    if g_min is not None:
        # A fifth of the channels at the floor, among channels that hardly
        # decay: both ends of the range in one contraction.
        floor = jax.random.uniform(ks[6], (heads * dk,)) < 0.2
        dt_bias = jnp.where(floor, jnp.repeat(-g_min / jnp.exp(a_log), dk),
                            dt_bias)
        wf_up = jnp.where(floor, 0.0, wf_up)
    if one_decay_a_head:
        first = lambda x: jnp.repeat(  # noqa: E731
            x.reshape(x.shape[:-1] + (heads, dk))[..., :1], dk,
            -1).reshape(x.shape)
        wf_up, dt_bias = first(wf_up), first(dt_bias)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, heads)))
    state = jax.random.normal(ks[5], (b, heads, dk, dv))
    qkv = jnp.concatenate([q, k, v], -1).astype(dtype)
    return (qkv, f.astype(dtype), wf_up.astype(dtype), dt_bias, a_log,
            beta), state


def old_road(ops, tokens=slice(None)):
    """What the recurrence and the step are fed: q and k to unit length, g
    by the shared helper, in plain XLA. (q, k, v, g, beta) of `tokens`."""
    qkv, f, wf_up, dt_bias, a_log, beta = ops
    heads, kd = a_log.shape[0], wf_up.shape[1]
    by_head = lambda x: x.reshape(x.shape[:2] + (heads, -1))  # noqa: E731
    q, k = unit_qk(by_head(qkv[..., :kd]), by_head(qkv[..., kd:2 * kd]))
    g = kda_decay(f, wf_up, dt_bias, a_log)
    return tuple(x[:, tokens] for x in (q, k, by_head(qkv[..., 2 * kd:]), g,
                                        beta))


def cut(ops, tokens):
    """The chunked form's operands for a stretch of the tokens."""
    qkv, f, wf_up, dt_bias, a_log, beta = ops
    return (qkv[:, tokens], f[:, tokens], wf_up, dt_bias, a_log,
            beta[:, tokens])


def gaps(got, want, mask=None):
    (got_o, got_s), (want_o, want_s) = got, want
    seen = jnp.ones(got_o.shape[:2], bool) if mask is None else mask
    diff = jnp.where(seen[..., None, None],
                     got_o.astype(jnp.float32) - want_o.astype(jnp.float32),
                     0.0)
    return (float(jnp.max(jnp.abs(diff))),
            float(jnp.max(jnp.abs(got_s - want_s))))


def test_the_floor_is_reached_through_the_operands():
    ops, _ = inputs(2, 64, g_min=G_MIN)
    g = np.asarray(old_road(ops)[3])
    at_floor = g < 0.9 * G_MIN
    assert 0.1 < at_floor.mean() < 0.3 and g.min() > 1.1 * G_MIN
    assert (g[~at_floor] > -10).all() and (g < 0).all()


@pytest.mark.parametrize("s,g_min", [(1, None), (64, G_MIN), (150, None),
                                     (150, G_MIN), (256, G_MIN)])
@pytest.mark.parametrize("form", ["kernel", "kernel_in_place", "plain"])
def test_chunked_form_is_the_recurrence(s, g_min, form):
    """Prompts of unequal length in one bucket (row 1 valid for its first
    half only), from a state carried in; lengths that are whole grid steps
    (64: one, 256: two and four) and that are not."""
    widths = TILES if form == "kernel_in_place" else TOY
    assert _reads_in_place(**widths, hb=kernel_shape(s, **widths)[1]) \
        == (form == "kernel_in_place")
    ops, state = inputs(2, s, g_min=g_min, **widths)
    mask = jnp.arange(s)[None, :] < jnp.array([s, s // 2])[:, None]
    if form == "plain":
        got = jax.jit(_chunked_plain, static_argnums=8)(
            *ops, mask.astype(jnp.float32), state, CHUNK)
    else:
        got = jax.jit(kda_chunked)(*ops, state, mask)
    want = jax.jit(kda_reference)(*old_road(ops), state, mask)
    o_gap, s_gap = gaps(got, want, mask)
    # float32 round-off of sums taken in another order; at the floor the
    # cumulated decay of a chunk runs to thousands, whose float32 spacing
    # (2.4e-4) the differences inherit.
    assert o_gap < (5e-6 if g_min is None else 2e-5)
    assert s_gap < (2e-5 if g_min is None else 1e-4)
    assert np.isfinite(np.asarray(got[0], np.float32)).all()


def test_a_state_carried_across_two_calls_is_one_call():
    ops, state = inputs(2, 140, g_min=G_MIN)
    at = 76             # inside a chunk; both calls pad to 128
    run = jax.jit(kda_chunked)
    whole = run(*ops, state)
    first_o, mid = run(*cut(ops, slice(None, at)), state)
    second_o, last = run(*cut(ops, slice(at, None)), mid)
    o_gap, s_gap = gaps((jnp.concatenate([first_o, second_o], 1), last),
                        whole)
    assert o_gap < 2e-5 and s_gap < 1e-4


@pytest.mark.parametrize("s", [63, 128])
def test_a_prefill_then_a_step_is_a_prefill_of_one_more(s):
    """The kernel's own operands and the step's, made apart, are the same
    numbers: s tokens through the kernel and token s + 1 through
    ``kda_step`` on ``kda_decay``'s g and ``unit_qk``'s q and k."""
    ops, state = inputs(2, s + 1, g_min=G_MIN, **TILES)
    run = jax.jit(kda_chunked)
    whole_o, whole_s = run(*ops, state)
    _, mid = run(*cut(ops, slice(None, s)), state)
    o, last = kda_step(*(x[:, 0] for x in old_road(ops, slice(s, None))),
                       mid)
    assert float(jnp.max(jnp.abs(o - whole_o[:, s]))) < 2e-5
    assert float(jnp.max(jnp.abs(last - whole_s))) < 1e-4


def test_a_parked_row_keeps_its_state_bit_for_bit():
    ops, state = inputs(2, 70)
    mask = jnp.stack([jnp.ones(70, bool), jnp.zeros(70, bool)])
    _, got = jax.jit(kda_chunked)(*ops, state, mask)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(state[1]))
    _, stepped = kda_step(*(x[:, 0] for x in old_road(ops)), state,
                          jnp.array([True, False]))
    np.testing.assert_array_equal(np.asarray(stepped[1]),
                                  np.asarray(state[1]))
    assert float(jnp.max(jnp.abs(stepped[0] - state[0]))) > 1e-3


def test_equal_decays_are_the_gated_delta_rule():
    """Every channel of a head at one decay: KDA is ops/gated_delta.py's
    rule. The step and the recurrence bit for bit; the chunked forms, whose
    sums run in another order, to float32 round-off."""
    ops, state = inputs(2, 130, one_decay_a_head=True)
    q, k, v, wide, beta = old_road(ops)
    g1 = wide[..., 0]
    np.testing.assert_array_equal(
        np.asarray(wide), np.asarray(jnp.broadcast_to(g1[..., None],
                                                      wide.shape)))
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
    o_gap, s_gap = gaps(jax.jit(kda_chunked)(*ops[:5], beta2, state),
                        chunked_gd)
    assert o_gap < 5e-6 and s_gap < 2e-5


def test_bfloat16_operands_stay_close_to_the_recurrence():
    ops, state = inputs(2, 192, heads=2, dk=32, dv=32, dtype=jnp.bfloat16,
                        g_min=G_MIN)
    o_gap, s_gap = gaps(jax.jit(kda_chunked)(*ops, state),
                        kda_reference(*old_road(ops), state))
    # bfloat16's spacing at outputs of order 1.
    assert o_gap < 3e-2 and s_gap < 3e-2


def test_gradients_are_the_plain_forms():
    ops, state = inputs(1, 70, heads=2, dk=16, dv=16)

    def loss(fn, *xs):
        o, s = fn(*xs)
        return jnp.sum(o * o) + jnp.sum(s)

    def recurrence(*xs):
        return kda_reference(*old_road(xs[:6]), xs[6])

    got = jax.jit(jax.grad(lambda *xs: loss(kda_chunked, *xs),
                           argnums=range(7)))(*ops, state)
    want = jax.jit(jax.grad(lambda *xs: loss(recurrence, *xs),
                            argnums=range(7)))(*ops, state)
    for a, b in zip(got, want):
        assert float(jnp.max(jnp.abs(b))) > 1e-3
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

    ops, state = inputs(2, 70, heads=4, dk=32, dv=32)
    want = jax.jit(kda_chunked)(*ops, state)
    mesh = make_mesh(MeshConfig(data=2, tensor=2), devices=jax.devices()[:4])
    with jax.set_mesh(mesh):
        got = jax.jit(kda_chunked)(*ops, state)
    o_gap, s_gap = gaps(got, want)
    assert o_gap < 1e-6 and s_gap < 1e-6


@pytest.mark.parametrize("s", [128, 1])
def test_the_block_hands_the_kernel_what_the_convolution_wrote(s):
    """The mechanism itself, in the jaxpr of a KDA layer's mixer with a
    cache: at a prefill shape q, k and v of the one Pallas call are the
    short convolution's output as it lies, and up to the call no value is
    float32 with b s H d_k elements (the decay g, or its pre-activation)
    and none is brought to unit length (no rsqrt): the kernel makes both.
    At s = 1 the step's road: no kernel, g [b, 1, H, d_k] and two
    normalizations in plain XLA."""
    from runbooks_tpu.models import transformer
    from runbooks_tpu.models.config import get_config

    cfg = get_config("debug-kimi-linear", dtype="bfloat16")
    b, H, dk = 2, cfg.linear_num_heads, cfg.linear_key_head_dim
    p = jax.eval_shape(lambda: jax.tree.map(
        lambda a: a[0], transformer._init_kda_mixer(
            cfg, iter(jax.random.split(jax.random.key(0), 16)), 1)))
    cache = transformer.LayerCache(
        {"state": jax.ShapeDtypeStruct((1, b, H, dk, dk), jnp.float32),
         "conv": jax.ShapeDtypeStruct((1, b, 3, cfg.linear_conv_dim),
                                      jnp.bfloat16)}, 0, None, None, None)

    def block(p, x, mask, leaves):
        return transformer._kda_block(cfg, p, x, mask,
                                      cache._replace(leaves=leaves))

    jaxpr = jax.make_jaxpr(block)(
        p, jax.ShapeDtypeStruct((b, s, cfg.hidden_size), jnp.bfloat16),
        jax.ShapeDtypeStruct((b, s), jnp.bool_), cache.leaves).jaxpr

    def flat(jaxpr):    # trace order, through calls, not into the kernel
        for eqn in jaxpr.eqns:
            inner = [v for v in eqn.params.values()
                     if isinstance(v, (jax.extend.core.ClosedJaxpr,
                                       jax.extend.core.Jaxpr))]
            if eqn.primitive.name == "pallas_call" or not inner:
                yield eqn
            for sub in inner:
                yield from flat(getattr(sub, "jaxpr", sub))

    eqns = list(flat(jaxpr))
    names = [e.primitive.name for e in eqns]

    def wide_f32(eqn):  # under kda.gates / kda.core (a projection's
        # float32 accumulator is the matmul's own, rounded as it is written)
        scope = str(eqn.source_info.name_stack)
        return [v.aval.shape for v in eqn.outvars
                if ("kda.gates" in scope or "kda.core" in scope)
                and v.aval.dtype == jnp.float32
                and v.aval.size == b * s * H * dk]

    if s == 1:
        assert "pallas_call" not in names and names.count("rsqrt") == 3
        assert (b, 1, H, dk) in [sh for e in eqns for sh in wide_f32(e)]
        return
    assert names.count("pallas_call") == 1
    call = names.index("pallas_call")
    q, k, v = eqns[call].invars[:3]
    assert q is k is v and q.aval.shape == (b, s, cfg.linear_conv_dim)
    assert "rsqrt" not in names[:call]      # the output norm's comes after
    assert [sh for e in eqns[:call] for sh in wide_f32(e)] == []
