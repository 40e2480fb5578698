"""Gated short-convolution layers beside one grouped-query layer in four
(which comes FIRST in its period), a leading conv layer with a dense FFN,
sparse layers that hold every expert: sigmoid router, a selection bias, the
chosen scores over their sum + 1e-6, against the plain reference
(benchmark/reference/lfm2_moe.py; docs/hybrid-models.md).

The toy preset `debug-lfm2` keeps the published SHAPE (a leading conv layer,
2 periods of 1 full + 3 conv layers, 4 query heads on 2 KV heads with a QK
norm a head, kernel 3, 8 experts of which 2 a token, a tied head). Seeded
random weights on the CPU; LOGITS are compared, never sampled tokens.
Activations run in float32 under "highest" matmul precision, weights are
the bfloat16 the recipe stores, so what separates program and reference is
the order of float32 sums: every tolerance below is 2e-4 absolute on logits
of order 1 for that reason, unless it says otherwise.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbooks_tpu.models.config import get_config
from runbooks_tpu.models.moe import route
from runbooks_tpu.models.transformer import (
    KVCache,
    forward,
    init_params,
    param_logical_axes,
)
from runbooks_tpu.ops.gated_delta import causal_conv

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
FULL, CONV = "full_attention", "conv"


def load_reference():
    path = os.path.join(ROOT, "benchmark", "reference", "lfm2_moe.py")
    spec = importlib.util.spec_from_file_location("ref_lfm2_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = load_reference()


@pytest.fixture(autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def toy(**over):
    kw = dict(dtype="float32", param_dtype="bfloat16")
    kw.update(over)
    return get_config("debug-lfm2", **kw)


def as_run_of(cfg) -> dict:
    """The reference's description of a ModelConfig of this family, under
    the published keys."""
    lead = cfg.leading_dense_layers
    return {
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "vocab_size": cfg.vocab_size, "norm_eps": cfg.norm_eps,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "num_hidden_layers": cfg.num_layers, "num_dense_layers": lead,
        "layer_types": [CONV] * lead
        + list(cfg.layer_pattern) * cfg.num_periods,
        "conv_L_cache": cfg.conv_kernel, "conv_bias": False,
        "rope_parameters": {"rope_theta": cfg.rope_theta,
                            "rope_type": "default"},
        "num_experts": cfg.moe_num_experts,
        "num_experts_per_tok": cfg.moe_top_k,
        "moe_intermediate_size": cfg.moe_width,
        "norm_topk_prob": True, "use_expert_bias": cfg.moe_router_bias,
        "routed_scaling_factor": cfg.moe_routed_scale,
        "router_bias_std": cfg.moe_router_bias_std}


def seeded(cfg, seed):
    """init_params as the server makes them: under jit."""
    return jax.jit(lambda key: init_params(cfg, key))(jax.random.key(seed))


def tokens_for(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, n).astype(np.int32)


def reference_logits(cfg, seed, toks, **as_run_over):
    """The reference's logits on the seeded weights of `cfg`; the
    overrides change the mathematics, never the draw."""
    as_run = as_run_of(cfg)
    w = ref.init_weights(as_run, seed)
    return np.asarray(ref.logits_at({**as_run, **as_run_over}, w, toks,
                                    np.arange(len(toks))))


# --------------------------------------------------------------------------
# The preset, the config's checks and counts, the seeded recipe
# --------------------------------------------------------------------------

def test_preset_holds_the_published_sizes():
    cfg = get_config("lfm2-24b-a2b")
    assert (cfg.hidden_size, cfg.head_dim, cfg.intermediate_size,
            cfg.vocab_size, cfg.norm_eps, cfg.max_seq_len) == (
        2048, 64, 11776, 65536, 1e-5, 128000)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.rope_theta, cfg.qk_norm,
            cfg.qk_norm_width, cfg.tie_embeddings) == (
        32, 8, 1e6, True, "head", True)
    assert cfg.layer_pattern == (FULL,) + (CONV,) * 3 \
        and cfg.conv_kernel == 3 and cfg.leading_dense_layers == 2 \
        and cfg.leading_layer_kind == CONV and cfg.num_layers == 38
    assert (cfg.moe_num_experts, cfg.moe_top_k, cfg.moe_width,
            cfg.moe_router, cfg.moe_router_bias, cfg.moe_shared_experts,
            cfg.moe_routed_scale, cfg.moe_router_eps) == (
        64, 4, 1536, "sigmoid", True, 0, 1.0, 1e-6)
    assert cfg.has_recurrent_state and cfg.has_short_conv \
        and not cfg.has_linear_attention and not cfg.has_window
    # ISSUE 40's arithmetic, by layer.
    h = 2048
    conv = 4 * h * h + 3 * h
    attn = 2 * h * h + 2 * h * 512 + 2 * 64
    sparse = 64 * 3 * h * 1536 + h * 64 + 64 + 2 * h
    dense = 3 * h * 11776 + 2 * h
    assert (conv, attn) == (16_783_360, 10_485_888)
    embed = 65536 * h + h
    assert cfg.num_params == embed + 2 * (conv + dense) \
        + 9 * (attn + sparse) + 27 * (conv + sparse)
    # The published 40 layers are a full and a conv layer more: 23.8 B.
    whole = cfg.num_params + attn + conv + 2 * sparse
    assert 23.8e9 < whole < 23.9e9
    # The benchmark's cut: 5.18 G parameters, 7 conv and 2 full layers.
    cut = get_config("lfm2-24b-a2b", num_layers=9, leading_dense_layers=1)
    assert cut.num_params == embed + conv + dense \
        + 2 * (attn + sparse) + 6 * (conv + sparse)
    assert 5.17e9 < cut.num_params < 5.19e9
    assert cut.layers_of(CONV) == 7 and cut.layers_of(FULL) == 2


def test_counts_shapes_and_axes():
    cfg = toy()
    p = init_params(cfg, jax.random.key(0))
    assert cfg.num_params == sum(a.size for a in jax.tree.leaves(p))
    axes = param_logical_axes(cfg)
    is_axes = lambda x: isinstance(x, tuple)  # noqa: E731
    assert jax.tree.structure(jax.tree.map(lambda a: 0, p)) \
        == jax.tree.structure(jax.tree.map(lambda a: 0, axes,
                                           is_leaf=is_axes))
    assert [a.ndim for a in jax.tree.leaves(p)] == [
        len(ax) for ax in jax.tree.leaves(axes, is_leaf=is_axes)]
    h, K = cfg.hidden_size, cfg.conv_kernel
    shapes = lambda t: {k: v.shape[1:] for k, v in t.items()}  # noqa: E731
    mixer = {"w_in": (h, 3 * h), "w_out": (h, h), "conv": (K, h)}
    assert shapes(p["leading_layers"]["mixer"]) == mixer \
        and "attn" not in p["leading_layers"] \
        and "mlp" in p["leading_layers"]
    assert len(p["conv_layers"]) == 3
    for pos in p["conv_layers"]:
        assert shapes(pos["mixer"]) == mixer \
            and pos["moe"]["wi_gate"].shape == (2, 8, h, 64)
    assert p["layers"]["attn"]["q_norm"].shape == (2, cfg.head_dim)
    # The cache: K/V hold the 2 full layers, the conv leaf the 7 conv
    # layers (the leading one first), and there is no state.
    cache = KVCache.create(cfg, 3, 16, trash_slot=True)
    assert cache.k.shape == (2, 3, 17, 2, 32) and cache.state is None \
        and cache.conv.shape == (7, 3, K - 1, h)
    # Doubling the context adds scores to the 2 full layers only.
    more = cfg.flops_per_token(256) - cfg.flops_per_token(128)
    assert more == 2 * 128 * 4 * 2 * cfg.head_dim * 2


@pytest.mark.parametrize("preset,over,text", [
    ("debug-lfm2", dict(leading_kind="linear_attention"), "leading_kind"),
    ("debug", dict(leading_kind="conv", leading_dense_layers=1),
     "leading_kind"),
    ("debug-lfm2", dict(conv_kernel=1), "conv_kernel"),
    ("debug-lfm2", dict(layer_types=(FULL, CONV, "linear_attention", CONV)),
     "conv leaf"),
    ("debug-lfm2", dict(layer_types=(CONV,) * 4), "exactly one"),
])
def test_config_refuses(preset, over, text):
    with pytest.raises(ValueError, match=text):
        get_config(preset, **over)


def test_seeded_weights_are_the_references_bit_for_bit():
    cfg = toy()
    p = seeded(cfg, 11)
    w = ref.init_weights(as_run_of(cfg), 11)
    n = cfg.layer_pattern.count(CONV)

    def in_layer_order(leaf_of):
        # Conv layer l of the periods, in layer order, is period l // n,
        # position l % n of the program's stacks.
        return jnp.stack([leaf_of(p["conv_layers"][l % n])[l // n]
                          for l in range(cfg.num_periods * n)])

    sparse = {"router": "router", "exp_gate": "wi_gate", "exp_up": "wi_up",
              "exp_down": "wo", "router_bias": "router_bias"}
    conv = {"w_in": "w_in", "w_out": "w_out", "kernel": "conv"}
    pairs = {"embed": p["embed"]}
    for name in ("wq", "wk", "wv", "wo"):
        pairs[name] = p["layers"]["attn"][name]
    for name, b in sparse.items():
        pairs[name] = p["layers"]["moe"][b]
        pairs["conv_" + name] = in_layer_order(lambda t: t["moe"][b])
    lead = p["leading_layers"]
    for name, b in conv.items():
        pairs["conv_" + name] = in_layer_order(lambda t: t["mixer"][b])
        pairs["lead_" + name] = lead["mixer"][b]
    pairs.update(lead_mlp_gate=lead["mlp"]["wi_gate"],
                 lead_mlp_up=lead["mlp"]["wi_up"],
                 lead_mlp_down=lead["mlp"]["wo"])
    assert set(pairs) == {k for k in w if "ln" not in k and "norm" not in k}
    for name, leaf in pairs.items():
        assert leaf.dtype == jnp.bfloat16 and w[name].dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(leaf.astype(jnp.float32)),
            np.asarray(w[name].astype(jnp.float32)), err_msg=name)
    # The selection bias is no constant: it moves choices.
    assert float(jnp.abs(pairs["conv_router_bias"].astype(
        jnp.float32)).mean()) > 0.5 * cfg.moe_router_bias_std


def test_other_presets_keep_their_seeded_weights_and_their_conv():
    """The conv layers draw from a split of their own (fold_in 4) and the
    leading conv mixer takes the leading layers' first keys only where the
    leading kind is conv: a hybrid's linear layers (fold_in 1) and a sparse
    model's leading attention (fold_in 2) are drawn from the keys they had.
    `olmo-hybrid-7b`'s convolution call (SiLU, the default) is bit-equal to
    the sums written out."""
    cfg = get_config("debug-hybrid")
    p = seeded(cfg, 3)
    keys = jax.random.split(jax.random.fold_in(jax.random.key(3), 1), 16)
    got = jnp.stack([p["linear_layers"][l]["mixer"]["wq"][0]
                     for l in range(3)])
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(jax.random.normal(keys[0], got.shape))
        * cfg.hidden_size ** -0.5, rtol=1e-6)
    cfg = get_config("debug-sparse-latent")
    p = seeded(cfg, 3)
    keys = jax.random.split(jax.random.fold_in(jax.random.key(3), 2), 16)
    got = p["leading_layers"]["attn"]["wq"]
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(jax.random.normal(keys[0], got.shape))
        * cfg.hidden_size ** -0.5, rtol=1e-6)

    x = jax.random.normal(jax.random.key(0), (2, 9, 12), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (4, 12), jnp.float32)
    tail = jax.random.normal(jax.random.key(2), (2, 3, 12), jnp.float32)
    n_valid = jnp.asarray([9, 4], jnp.int32)
    xc = jnp.concatenate([tail, x], axis=1)
    sums = sum(xc[:, j:j + 9] * w[j] for j in range(4))
    y, new_tail = causal_conv(x, w, tail, n_valid)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(jax.nn.silu(sums)))
    y_plain, tail_plain = causal_conv(x, w, tail, n_valid, activation=None)
    np.testing.assert_array_equal(np.asarray(y_plain), np.asarray(sums))
    np.testing.assert_array_equal(np.asarray(new_tail),
                                  np.asarray(tail_plain))
    np.testing.assert_array_equal(np.asarray(new_tail[1]),
                                  np.asarray(xc[1, 4:7]))


# --------------------------------------------------------------------------
# The forward pass against the reference
# --------------------------------------------------------------------------

@pytest.mark.parametrize("impl,over", [
    ("xla", {}), ("xla", dict(leading_dense_layers=2, num_layers=10)),
    ("flash", dict(num_kv_heads=1))],
    ids=["one-leading", "two-leading", "flash-groups-of-4"])
def test_forward_matches_reference(impl, over):
    """A leading conv layer with a dense FFN (or two), two periods of 1
    full + 3 conv layers with sparse FFNs, no cache. `flash`: the kernel
    (interpreted) at the published group, 4 query heads a KV head."""
    cfg = toy(attention_impl=impl, flash_block_q=16, flash_block_k=16,
              **over)
    toks = tokens_for(cfg, 40)
    p = seeded(cfg, 5)
    got, _ = forward(cfg, p, jnp.asarray(toks)[None])
    want = reference_logits(cfg, 5, toks)
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=TOL)


def test_each_term_is_seen():
    """Each of the model's terms changes the logits by far more than the
    tolerance, so a program that drops or misplaces one fails the
    comparison above: on the program's side where a field or a leaf says
    the term, on the reference's where none does."""
    cfg = toy()
    toks_np = tokens_for(cfg, 40)
    toks = jnp.asarray(toks_np)[None]
    p = seeded(cfg, 5)
    base, _ = forward(cfg, p, toks)

    def without(tree, group, *names):
        return {**tree, group: {k: v for k, v in tree[group].items()
                                if k not in names}}

    no_bias = {**p, "layers": without(p["layers"], "moe", "router_bias"),
               "conv_layers": [without(t, "moe", "router_bias")
                               for t in p["conv_layers"]]}
    no_norm = {**p, "layers": without(p["layers"], "attn", "q_norm",
                                      "k_norm")}
    changed = {
        "the selection bias b": forward(cfg, no_bias, toks)[0],
        "the QK norm": forward(dataclasses.replace(cfg, qk_norm=False),
                               no_norm, toks)[0],
        "the rotary base": forward(dataclasses.replace(
            cfg, rope_theta=10000.0), p, toks)[0],
        "the router kind": forward(dataclasses.replace(
            cfg, moe_router="softmax"), p, toks)[0]}
    for name, logits in changed.items():
        assert float(jnp.abs(logits - base).max()) > 100 * TOL, name
    # The reference's own terms, left out or read otherwise on ITS side,
    # are seen by the same margin (the program is compared with the whole
    # of it above).
    want = reference_logits(cfg, 5, toks_np)
    for name, over in (
            ("the C gate", {"conv_gate": False}),
            ("the order of W_in's thirds", {"in_proj_order": "BXC"}),
            ("the QK norm", {"qk_norm": False}),
            ("b left out", {"use_expert_bias": False}),
            ("b in the weights", {"expert_bias_in_weights": True})):
        other = reference_logits(cfg, 5, toks_np, **over)
        assert np.abs(other - want).max() > 20 * TOL, name


def test_the_router_adds_its_epsilon():
    """w_e = s_e / (sum + 1e-6): a relative 1e-6 on the weights, which the
    logits' tolerance cannot see and a float32 comparison of the weights
    can. The default form (max(sum, 1e-9)) differs from the reference by
    it; the model's form does not."""
    cfg = toy()
    p = jax.tree.map(lambda a: a[0], seeded(cfg, 0)["layers"]["moe"])
    x = jax.random.normal(jax.random.key(9), (64, cfg.hidden_size),
                          jnp.float32)
    dm = ref.dims(as_run_of(cfg))
    lw = {"router": p["router"].astype(jnp.float32),
          "router_bias": p["router_bias"].astype(jnp.float32)}
    chosen, want = ref.choose(dm, x, lw)
    _, idx, gate = route(cfg, p, x)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(chosen))
    np.testing.assert_allclose(np.asarray(gate), np.asarray(want),
                               rtol=2e-7)
    _, _, plain = route(dataclasses.replace(cfg, moe_router_eps=0.0), p, x)
    assert float(jnp.abs(plain / want - 1).mean()) > 5e-7
    # The weights are the UNBIASED scores of the experts the bias chose.
    scores = jax.nn.sigmoid(x @ lw["router"])
    assert not np.array_equal(
        np.asarray(jax.lax.top_k(scores, cfg.moe_top_k)[1]),
        np.asarray(chosen))


def test_every_expert_is_held_and_counted():
    """moe_experts_held 0 = all: the counts' last entry (assignments held
    elsewhere) is 0 and the held ones add up to tokens x top-k a layer."""
    cfg = toy()
    p = seeded(cfg, 2)
    toks = jnp.asarray(tokens_for(cfg, 24))[None]
    cache = KVCache.create(cfg, 1, 32)
    _, _, counts = forward(cfg, p, toks, cache=cache, with_moe_counts=True)
    assert counts.shape == (8, cfg.moe_num_experts + 1)
    assert np.asarray(counts[:, -1]).tolist() == [0] * 8
    assert np.asarray(counts[:, :-1].sum(-1)).tolist() == [24 * 2] * 8


def test_packed_sequences_and_adapters_are_refused_by_name():
    cfg = toy()
    p = seeded(cfg, 0)
    toks = jnp.asarray(tokens_for(cfg, 8))[None]
    with pytest.raises(NotImplementedError, match="short-convolution"):
        forward(cfg, p, toks, segment_ids=jnp.ones((1, 8), jnp.int32))
    with pytest.raises(NotImplementedError, match="adapter pools"):
        forward(cfg, p, toks, adapters=({}, jnp.zeros((1,), jnp.int32)))
