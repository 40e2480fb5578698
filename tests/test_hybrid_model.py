"""A layer pattern in models/transformer.py: the program's forward against
the benchmark's plain reference on a tiny seeded hybrid, the seeded
weights of both, and what the pattern must leave alone."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.cache_paths import (
    MODE_VIEW_IDS,
    MODES_AND_VIEWS,
    greedy_chunk,
    worst_gap,
)
from tests.hybrid_fixture import (
    AS_RUN,
    load_reference,
    seeded_params,
    tiny_config,
)
from runbooks_tpu.models.config import ModelConfig, get_config
from runbooks_tpu.models.transformer import (
    KVCache,
    forward,
    init_params,
    param_logical_axes,
)


@pytest.fixture(scope="module")
def ref():
    return load_reference()


def test_preset_has_the_published_sizes():
    cfg = get_config("olmo-hybrid-7b")
    assert (cfg.hidden_size, cfg.num_heads, cfg.head_dim,
            cfg.intermediate_size, cfg.vocab_size, cfg.num_layers) == (
                3840, 30, 128, 11008, 100352, 32)
    assert cfg.layer_pattern == ("linear_attention",) * 3 + (
        "full_attention",)
    assert (cfg.linear_num_heads, cfg.linear_key_head_dim,
            cfg.linear_value_head_dim, cfg.linear_conv_kernel) == (
                30, 96, 192, 4)
    assert cfg.layers_of("linear_attention") == 24 and cfg.num_periods == 8
    # ISSUE 26's reckoning: 215.6 M a linear layer, 185.8 M a full one,
    # 770.7 M embedding + head; 4.10 G at 16 layers.
    half = get_config("olmo-hybrid-7b", num_layers=16)
    assert round(half.num_params / 1e9, 2) == 4.10
    assert get_config("falcon-7b").layer_pattern == ("full_attention",)


def test_counts_follow_the_pattern():
    cfg = tiny_config()
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)) \
        == cfg.num_params
    axes = param_logical_axes(cfg)
    is_axes = lambda x: isinstance(x, tuple)  # noqa: E731
    jax.tree.map(lambda a, ax: len(a.shape) == len(ax) or 1 / 0, params,
                 axes, is_leaf=is_axes)
    assert params["layers"]["attn"]["wq"].shape[0] == 2      # full layers
    # One stack [periods, …] for each of the period's three positions.
    assert [t["mixer"]["wq"].shape[0]
            for t in params["linear_layers"]] == [2, 2, 2]
    assert cfg.flops_per_token(128) > 0


@pytest.mark.parametrize("bad", [
    dict(layer_types=("windowed_attention",)),
    dict(layer_types=("linear_attention",) * 3 + ("full_attention",),
         num_layers=6, linear_num_heads=2, linear_key_head_dim=4,
         linear_value_head_dim=4),
    dict(layer_types=("linear_attention",), num_layers=2,
         linear_num_heads=2, linear_key_head_dim=4, linear_value_head_dim=4),
    dict(layer_types=("full_attention", "full_attention"), num_layers=2),
    dict(norm_position="sandwich"),
    dict(norm_position="post", parallel_block=True),
])
def test_config_refuses_a_pattern_it_cannot_run(bad):
    with pytest.raises(ValueError):
        ModelConfig(**bad)


def test_reference_weights_are_the_programs(ref):
    cfg = tiny_config(param_dtype="bfloat16")
    params = seeded_params(cfg, 11)
    w = ref.init_weights(AS_RUN, 11)
    # Linear layer l lies at position l % 3 of period l // 3: back into
    # layer order, as the reference holds them.
    full = params["layers"]
    lin = jax.tree.map(
        lambda *pos: jnp.stack(pos, 1).reshape((-1,) + pos[0].shape[1:]),
        *params["linear_layers"])
    pairs = {
        "embed": params["embed"], "head": params["head"],
        "wq": full["attn"]["wq"], "wk": full["attn"]["wk"],
        "wv": full["attn"]["wv"], "wo": full["attn"]["wo"],
        "mlp_down": full["mlp"]["wo"], "mlp_gate": full["mlp"]["wi_gate"],
        "mlp_up": full["mlp"]["wi_up"],
        "lin_mlp_down": lin["mlp"]["wo"],
        "lin_mlp_gate": lin["mlp"]["wi_gate"],
        "lin_mlp_up": lin["mlp"]["wi_up"],
        **{"lin_" + n: lin["mixer"][n] for n in (
            "wq", "wk", "wv", "wg", "wo", "wa", "wb", "conv", "a_log",
            "dt_bias")}}
    for name, theirs in pairs.items():
        assert jnp.array_equal(theirs, w[name]), name
    # Every leaf of the program is accounted for: the rest are norms, 1.
    n_ones = sum(a.size for n, a in w.items() if n not in pairs)
    assert cfg.num_params == sum(a.size for a in pairs.values()) + n_ones
    # Decay neither 0 nor 1 under the seeded recipe.
    dt = jax.nn.softplus(w["lin_dt_bias"].astype(jnp.float32))
    alpha = jnp.exp(-jnp.exp(w["lin_a_log"].astype(jnp.float32)) * dt)
    assert 0.15 < float(alpha.min()) and float(alpha.max()) < 0.9995


# sha256 over every leaf of falcon-7b's seeded weights at a toy size (key
# paths and bytes), taken on the parent commit e69c1c3: the pattern's new
# leaves draw their keys beside init_params' split, not from a wider one.
FALCON_DIGEST = (
    "2bcc067a57c5d0923473f908e28a28781e7be41cb6c2d5357b7630fa91866770")


def test_falcon_seeded_weights_are_the_parents():
    cfg = get_config("falcon-7b", num_layers=2, vocab_size=512,
                     hidden_size=64, intermediate_size=256, num_heads=8,
                     num_kv_heads=1, head_dim=8, param_dtype="bfloat16")
    params = seeded_params(cfg, 7)
    digest = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        digest.update(jax.tree_util.keystr(path).encode())
        digest.update(np.asarray(leaf.astype(jnp.float32)).tobytes())
    assert digest.hexdigest() == FALCON_DIGEST
    assert "linear_layers" not in params


@pytest.mark.parametrize("dtype,limit", [
    # float32 round-off of sums taken in another order, through 8 layers
    # (read: 3.3e-6 and 4.3e-6 on two seeds).
    ("float32", 2e-5),
    # bfloat16 rounds every activation and product operand to 8 bits of
    # mantissa (4e-3 a rounding), and a reordered-norm block renormalises
    # each sub-layer's output to unit size, so 16 sub-layers' roundings
    # reach the logits undamped: read 6.1 % and 7.1 % of their norm on two
    # seeds. A wrong equation is off by order 1.
    ("bfloat16", 0.12),
])
def test_forward_without_a_cache_is_the_reference(ref, dtype, limit):
    cfg = tiny_config(param_dtype="bfloat16", dtype=dtype)
    params = seeded_params(cfg, 5)
    w = ref.init_weights(AS_RUN, 5)
    toks = np.random.default_rng(0).integers(1, 512, 150)
    got = jax.jit(lambda p, t: forward(cfg, p, t)[0])(
        params, jnp.asarray(toks)[None])[0]
    want = ref.logits_at(AS_RUN, w, toks, np.arange(150))
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert rel < limit, rel


def test_prefill_in_pieces_then_decode_is_the_whole_forward():
    cfg = tiny_config(dtype="float32")
    params = seeded_params(cfg, 3)
    toks = jnp.asarray(np.random.default_rng(1).integers(1, 512, (2, 100)))
    whole = jax.jit(lambda p, t: forward(cfg, p, t)[0])(params, toks)
    step = jax.jit(lambda p, t, c: forward(cfg, p, t, cache=c))
    cache = KVCache.create(cfg, 2, 128)
    assert cache.k.shape[0] == 2 and cache.state.shape == (6, 2, 4, 32, 64)
    assert cache.conv.shape == (6, 2, 3, 2 * 128 + 256)
    got = []
    for lo, hi in ((0, 40), (40, 90)) + tuple(
            (i, i + 1) for i in range(90, 100)):
        logits, cache = step(params, toks[:, lo:hi], cache)
        got.append(logits)
    assert float(jnp.max(jnp.abs(jnp.concatenate(got, 1) - whole))) < 5e-4
    assert int(cache.index) == 100


@pytest.mark.parametrize("int8_pool", [False, True],
                         ids=["float-pool", "int8-pool"])
@pytest.mark.parametrize("mode,view", MODES_AND_VIEWS, ids=MODE_VIEW_IDS)
def test_cache_write_modes_and_views_match_full_forward(mode, view,
                                                        int8_pool):
    """State and conv tail are read and written at period * 3 + i of the
    carried leaves, K/V at the period's number, in both write modes; in
    scatter mode row 1's padding is parked at the trash slot and masked out
    of its state. The int8 pool quantizes K/V alone (0.035 here)."""
    cfg = tiny_config(dtype="float32")
    params = seeded_params(cfg, 3)
    toks = jnp.asarray(np.random.default_rng(1).integers(1, 512, (2, 12)))
    gap = worst_gap(cfg, params, toks, mode, view, int8_pool)
    assert (2e-5 < gap < 0.1) if int8_pool else gap < 5e-5


def test_decode_chunk_greedy_tokens_are_the_parents():
    """As test_transformer.py's: recorded from the parent of PR 27."""
    cfg = tiny_config(dtype="float32")
    assert greedy_chunk(cfg, seeded_params(cfg, 3)) == [
        [66, 483], [444, 239], [232, 7], [373, 125], [165, 304], [46, 126],
        [46, 116], [172, 248]]


def test_cache_view_slices_only_keys_and_values():
    cfg = tiny_config(dtype="float32")
    params = seeded_params(cfg, 3)
    toks = jnp.asarray(np.random.default_rng(2).integers(1, 512, (2, 40)))
    outs = []
    for view in (None, 64):
        cache = KVCache.create(cfg, 2, 128)
        logits, cache = forward(cfg, params, toks, cache=cache,
                                cache_view=view)
        outs.append((logits, cache))
    assert float(jnp.max(jnp.abs(outs[0][0] - outs[1][0]))) < 1e-5
    assert jnp.array_equal(outs[0][1].state, outs[1][1].state)
    assert outs[1][1].k.shape[2] == 128


def test_token_mask_freezes_state_and_conv_tail():
    cfg = tiny_config(dtype="float32")
    params = seeded_params(cfg, 3)
    toks = jnp.asarray(np.random.default_rng(4).integers(1, 512, (2, 32)))
    # Row 1 holds 20 real tokens and 12 of padding, parked by position at
    # the last slot as the engine parks them.
    pos = jnp.stack([jnp.arange(32), jnp.where(jnp.arange(32) < 20,
                                               jnp.arange(32), 63)])
    mask = pos < 63
    _, padded = forward(cfg, params, toks, positions=pos, token_mask=mask,
                        cache=KVCache.create(cfg, 2, 64))
    _, short = forward(cfg, params, toks[1:, :20],
                       cache=KVCache.create(cfg, 1, 64))
    # Deeper layers see float32 round-off of the layers below (a batch of
    # another shape sums in another order).
    np.testing.assert_allclose(padded.conv[0, 1], short.conv[0, 0],
                               atol=1e-6)
    np.testing.assert_allclose(padded.state[0, 1], short.state[0, 0],
                               atol=1e-6)
    scale = float(jnp.max(jnp.abs(short.state)))
    np.testing.assert_allclose(padded.state[:, 1], short.state[:, 0],
                               atol=1e-4 * scale)
    np.testing.assert_allclose(padded.conv[:, 1], short.conv[:, 0],
                               atol=1e-4)


def test_what_a_hybrid_cannot_do_yet_is_refused_by_name():
    cfg = tiny_config(dtype="float32")
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    toks = jnp.zeros((1, 16), jnp.int32)
    with pytest.raises(NotImplementedError, match="document boundaries"):
        jax.eval_shape(lambda p: forward(
            cfg, p, toks, segment_ids=jnp.ones((1, 16), jnp.int32)), params)
    from runbooks_tpu.parallel.mesh import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(tensor=8))      # 8 does not divide 4 heads
    with jax.set_mesh(mesh), pytest.raises(NotImplementedError,
                                           match="does not divide"):
        jax.eval_shape(lambda p: forward(cfg, p, toks), params)
