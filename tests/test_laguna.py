"""Window and full layers that differ in query heads, rotary and nothing
else of their shape, a per-head output gate on both, a softmax router
whose renormalised weights are scaled, a shared expert beside a share of
the routed ones, against the plain reference
(benchmark/reference/laguna.py; docs/window-full-models.md).

The toy preset `debug-laguna` keeps the published RATIOS (6 gated query
heads with half a YaRN rotary on full layers, 8 with a plain whole-head one
on window layers, 2 KV heads: groups of 3 and 4; window 8, 3 window layers
a full one, 16 experts of which 4 a token, one shared). Seeded random
weights on the CPU; LOGITS are compared, never sampled tokens. Activations
run in float32 under "highest" matmul precision, weights are the bfloat16
the recipe stores, so what separates program and reference is the order of
float32 sums: every tolerance below is 2e-4 absolute on logits of order 1
for that reason (the same program in bfloat16 activations differs by 1e-2
and more), unless it says otherwise.
"""

import dataclasses
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbooks_tpu.models.config import RING_MARGIN, get_config
from runbooks_tpu.models.moe import moe_block
from runbooks_tpu.models.transformer import (
    flash_heads_per_step,
    forward,
    init_params,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
FULL, SLIDING = "full_attention", "sliding_attention"


def load_reference():
    path = os.path.join(ROOT, "benchmark", "reference", "laguna.py")
    spec = importlib.util.spec_from_file_location("ref_laguna", path)
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
    return get_config("debug-laguna", **kw)


def as_run_of(cfg) -> dict:
    """The reference's description of a ModelConfig of this family, under
    the published keys. attention_factor is the published formula, 0.1
    ln(factor) + 1, worked out here and not taken from the program."""
    lead = cfg.leading_dense_layers
    kinds = [FULL] * lead + list(cfg.layer_pattern) * cfg.num_periods
    full, win = cfg.attn_shape(FULL), cfg.attn_shape(SLIDING)
    factor, original, fast, slow = cfg.rope_yarn[:4]
    heads = {FULL: full.heads, SLIDING: win.heads}
    return {
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "vocab_size": cfg.vocab_size, "rms_norm_eps": cfg.norm_eps,
        "head_dim": cfg.head_dim, "num_key_value_heads": cfg.num_kv_heads,
        "num_attention_heads": cfg.num_heads,
        "num_attention_heads_per_layer": [heads[k] for k in kinds],
        "layer_types": kinds,
        "mlp_layer_types": ["dense"] * lead
        + ["sparse"] * (cfg.num_layers - lead),
        "num_hidden_layers": cfg.num_layers, "first_k_dense_replace": lead,
        "sliding_window": cfg.sliding_window, "gating": cfg.attn_gate,
        "rope_parameters": {
            FULL: {"rope_type": "yarn", "rope_theta": cfg.rope_theta,
                   "factor": factor,
                   "original_max_position_embeddings": original,
                   "beta_fast": fast, "beta_slow": slow,
                   "attention_factor": 0.1 * math.log(factor) + 1.0,
                   "partial_rotary_factor": cfg.rotary_dim / cfg.head_dim},
            SLIDING: {"rope_type": "default",
                      "rope_theta": cfg.sliding_rope_theta,
                      "partial_rotary_factor": 1}},
        "num_experts_routed": cfg.moe_num_experts,
        "num_experts": cfg.moe_experts_here,
        "first_expert_held": cfg.moe_experts_first,
        "num_experts_per_tok": cfg.moe_top_k,
        "moe_intermediate_size": cfg.moe_width,
        "shared_expert_intermediate_size": cfg.moe_width
        * cfg.moe_shared_experts,
        "moe_routed_scaling_factor": cfg.moe_routed_scale,
        "router": cfg.moe_router}


def seeded(cfg, seed):
    """init_params as the server makes them: under jit (an eager draw
    rounds a few elements in 65 536 to the other bfloat16 neighbour)."""
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
    cfg = get_config("laguna-xs.2")
    assert (cfg.hidden_size, cfg.head_dim, cfg.intermediate_size,
            cfg.vocab_size, cfg.norm_eps, cfg.max_seq_len) == (
        2048, 128, 8192, 100352, 1e-6, 262144)
    yarn = (64.0, 4096, 64.0, 1.0, 1.0, 0.0)
    assert cfg.attn_shape(FULL) == (
        8, 500000.0, False, 0, 48, 64, yarn, 1.4158883083359672, True)
    assert cfg.attn_shape(SLIDING) == (
        8, 10000.0, False, 512, 64, 128, (), 1.0, True)
    assert cfg.layer_pattern == (SLIDING,) * 3 + (FULL,) \
        and cfg.leading_dense_layers == 1 and cfg.num_layers == 37
    assert (cfg.moe_num_experts, cfg.moe_top_k, cfg.moe_width,
            cfg.moe_router, cfg.moe_router_bias, cfg.moe_shared_experts,
            cfg.moe_routed_scale) == (256, 8, 512, "softmax", False, 1, 2.5)
    assert cfg.ring_len == 512 + RING_MARGIN and not cfg.qk_norm
    # ISSUE 38's arithmetic, by kind.
    h = 2048
    attn_full = h * (2 * 48 * 128 + 2 * 8 * 128 + 48)
    attn_win = h * (2 * 64 * 128 + 2 * 8 * 128 + 64)
    assert (attn_full, attn_win) == (29_458_432, 37_879_808)
    assert cfg._attn_params(FULL) == attn_full \
        and cfg._attn_params(SLIDING) == attn_win

    def sparse(held):
        return (held + 1) * 3 * h * 512 + h * 256 + 2 * h
    lead = attn_full + 3 * h * 8192 + 2 * h
    embed = 2 * 100352 * h + h
    whole_37 = embed + lead + 27 * (attn_win + sparse(256)) \
        + 9 * (attn_full + sparse(256))
    assert cfg.num_params == whole_37
    # The published 40 layers are three window layers more: 33.4 B.
    whole_40 = whole_37 + 3 * (attn_win + sparse(256))
    assert 33.40e9 < whole_40 < 33.48e9
    # The benchmark's cut: 5.17 G parameters.
    cut = get_config("laguna-xs.2", moe_experts_held=32, vocab_size=12544)
    want = (2 * 12544 * h + h + lead + 27 * (attn_win + sparse(32))
            + 9 * (attn_full + sparse(32)))
    assert cut.num_params == want and 5.17e9 < want < 5.18e9


def test_counts_and_shapes_are_by_kind():
    cfg = toy()
    p = init_params(cfg, jax.random.key(0))
    assert cfg.num_params == sum(a.size for a in jax.tree.leaves(p))
    h, d = cfg.hidden_size, cfg.head_dim
    shapes = lambda attn: {k: v.shape[1:] for k, v in attn.items()}  # noqa: E731
    assert shapes(p["layers"]["attn"]) == shapes(
        p["leading_layers"]["attn"]) == {
        "wq": (h, 6 * d), "wk": (h, 2 * d), "wv": (h, 2 * d),
        "wo": (6 * d, h), "wg": (h, 6)}
    for pos in p["window_layers"]:
        assert shapes(pos["attn"]) == {
            "wq": (h, 8 * d), "wk": (h, 2 * d), "wv": (h, 2 * d),
            "wo": (8 * d, h), "wg": (h, 8)}
    # Doubling the context adds scores to the 2 full layers' 6 heads only;
    # a window layer's 8 heads are charged the window.
    more = cfg.flops_per_token(256) - cfg.flops_per_token(128)
    assert more == 2 * 128 * 6 * 2 * d * cfg.layers_of(FULL)
    assert (cfg.flops_per_token(8) - cfg.flops_per_token(4)
            == 2 * 4 * 2 * d * (6 * 2 + 8 * 3))


@pytest.mark.parametrize("preset,over,text", [
    ("debug-laguna", dict(sliding_num_heads=7), "does not divide"),
    ("debug-laguna", dict(sliding_rotary_dim=7), "sliding_rotary_dim"),
    ("debug-laguna", dict(sliding_rotary_dim=18), "sliding_rotary_dim"),
    ("debug-laguna", dict(rope_yarn=(8.0, 32, 8.0, 1.0, 1.0, 1.0)),
     "mscale_all_dim"),
    ("debug-laguna", dict(rope_yarn=(8.0, 32)), "rope_yarn is"),
    ("debug", dict(sliding_num_heads=8), "layer pattern has none"),
    ("debug", dict(sliding_rotary_dim=8), "layer pattern has none"),
])
def test_config_refuses(preset, over, text):
    with pytest.raises(ValueError, match=text):
        get_config(preset, **over)


def test_seeded_weights_are_the_references_bit_for_bit():
    cfg = toy(moe_experts_held=4, moe_experts_first=8, num_layers=9)
    p = seeded(cfg, 11)
    w = ref.init_weights(as_run_of(cfg), 11)
    n = cfg.layer_pattern.count(SLIDING)
    names = {"wq": ("attn", "wq"), "wk": ("attn", "wk"),
             "wv": ("attn", "wv"), "wo": ("attn", "wo"),
             "wg": ("attn", "wg"), "router": ("moe", "router"),
             "exp_gate": ("moe", "wi_gate"), "exp_up": ("moe", "wi_up"),
             "exp_down": ("moe", "wo")}
    shared = {"shared_gate": "wi_gate", "shared_up": "wi_up",
              "shared_down": "wo"}

    def in_layer_order(leaf_of):
        # Window layer l of the reference's layer order is period l // n,
        # position l % n of the program's stacks.
        return jnp.stack([leaf_of(p["window_layers"][l % n])[l // n]
                          for l in range(cfg.layers_of(SLIDING))])

    pairs = {"embed": p["embed"], "head": p["head"]}
    for name, (a, b) in names.items():
        pairs[name] = p["layers"][a][b]
        pairs["win_" + name] = in_layer_order(lambda t: t[a][b])
    for name, b in shared.items():
        pairs[name] = p["layers"]["moe"]["shared"][b]
        pairs["win_" + name] = in_layer_order(
            lambda t: t["moe"]["shared"][b])
    lead = p["leading_layers"]
    for name in ("wq", "wk", "wv", "wo", "wg"):
        pairs["lead_" + name] = lead["attn"][name]
    pairs.update(lead_mlp_gate=lead["mlp"]["wi_gate"],
                 lead_mlp_up=lead["mlp"]["wi_up"],
                 lead_mlp_down=lead["mlp"]["wo"])
    assert set(pairs) == {k for k in w if "ln" not in k and "norm" not in k}
    for name, leaf in pairs.items():
        assert leaf.dtype == jnp.bfloat16 and w[name].dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(leaf.astype(jnp.float32)),
            np.asarray(w[name].astype(jnp.float32)), err_msg=name)
    # The gate is no constant: its logits have the spread of a projection.
    assert float(jnp.abs(pairs["win_wg"].astype(jnp.float32)).mean()) \
        > 0.5 * cfg.hidden_size ** -0.5


def test_other_presets_keep_their_seeded_weights():
    """The gate takes the key AFTER wq, wk, wv, wo (and a sink) of its
    stack, and only where a model has one: every other preset's leaves are
    drawn from the keys they had. The FFN's first key of three presets, by
    the position it had before this model came."""
    for name, over, leaf, k in (
            ("debug", {}, ("layers", "mlp", "wo"), 6),
            ("debug-sparse-latent", {}, ("layers", "moe", "router"), 6),
            ("debug-window-full", {}, ("layers", "moe", "router"), 6)):
        cfg = get_config(name, **over)
        assert not cfg.attn_gate and "wg" not in jax.eval_shape(
            lambda: init_params(cfg, jax.random.key(3)))["layers"]["attn"]
        p = seeded(cfg, 3)
        got = np.asarray(p[leaf[0]][leaf[1]][leaf[2]], np.float32)
        drawn = np.asarray(jax.random.normal(
            jax.random.split(jax.random.key(3), 16)[k], got.shape))
        fan_in = got.shape[-2]
        # (Eager against jitted: equal to a float32 rounding; the stored
        # leaf is the preset's parameter type.)
        np.testing.assert_allclose(
            got, np.asarray((drawn * fan_in ** -0.5).astype(
                cfg.parameter_dtype), np.float32), rtol=1e-6)
    # A window model's sink still takes the key behind wo, the window
    # layers' FFN the one behind it.
    cfg = get_config("debug-window-full")
    p = seeded(cfg, 3)
    keys = jax.random.split(jax.random.fold_in(jax.random.key(3), 3), 16)
    sink = jnp.stack([p["window_layers"][l]["attn"]["sink"][0]
                      for l in range(3)])
    np.testing.assert_allclose(
        np.asarray(sink, np.float32),
        np.asarray(jax.random.normal(keys[4], sink.shape)), rtol=1e-6)


# --------------------------------------------------------------------------
# The forward pass against the reference
# --------------------------------------------------------------------------

@pytest.mark.parametrize("share,impl,over", [
    ((0, 0), "xla", {}), ((2, 6), "xla", {}),
    ((0, 0), "xla", dict(moe_router="sigmoid")),
    ((8, 0), "flash", dict(num_heads=12, sliding_num_heads=16))],
    ids=["whole", "share-1-of-8", "sigmoid-router", "flash-groups-6-and-8"])
def test_forward_matches_reference(share, impl, over):
    """Leading dense layer, two periods of 3 window + 1 full layers, sparse
    FFNs with the shared expert, no cache: the window mask, both head
    counts and rotaries, the gate on both kinds. `share`: an eighth of the
    experts, the reference given the same share. `sigmoid-router`: the
    other reading of the missing scoring key, one field away on both
    sides. `flash`: the kernel (interpreted) with ranges from a window (key
    blocks of 16, 5 windows of context) at the published groups, 6 query
    heads a KV head on full layers and 8 on window layers."""
    held, first = share
    cfg = toy(moe_experts_held=held, moe_experts_first=first, num_layers=9,
              attention_impl=impl, flash_block_q=16, flash_block_k=16,
              **over)
    if impl == "flash":
        assert flash_heads_per_step(cfg, 40, 40) == {FULL: 6, SLIDING: 8}
    toks = tokens_for(cfg, 40)
    p = seeded(cfg, 5)
    got, _ = forward(cfg, p, jnp.asarray(toks)[None])
    want = reference_logits(cfg, 5, toks)
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=TOL)


def test_each_term_is_seen():
    """Each of the model's terms changes the logits by far more than the
    tolerance, so a program that drops or misplaces one fails the
    comparison above. On the program's side where a field says the term,
    on the reference's where none does."""
    cfg = toy(moe_experts_held=8)
    toks_np = tokens_for(cfg, 40)
    toks = jnp.asarray(toks_np)[None]
    p = seeded(cfg, 5)
    base, _ = forward(cfg, p, toks)
    m = cfg.attn_shape(FULL).rope_factor
    assert m == pytest.approx(0.1 * math.log(8.0) + 1.0)

    def no_gate(tree):
        return {k: ({a: b for a, b in v.items() if a != "wg"}
                    if k == "attn" else v) for k, v in tree.items()}

    ungated = {**p, "layers": no_gate(p["layers"]),
               "leading_layers": no_gate(p["leading_layers"]),
               "window_layers": [no_gate(t) for t in p["window_layers"]]}
    no_factor = dataclasses.replace(
        cfg, rope_yarn=cfg.rope_yarn[:4] + (0.0, 0.0))
    # The factor's square on the WHOLE score (the latent path's softmax
    # scale) is another equation once half the head does not rotate.
    on_the_scale = jax.tree.map(lambda a: a, p)
    for tree in (on_the_scale["layers"], on_the_scale["leading_layers"]):
        tree["attn"]["wq"] = (tree["attn"]["wq"].astype(jnp.float32)
                              * m * m).astype(jnp.bfloat16)
    changed = {
        "gate": forward(dataclasses.replace(cfg, attn_gate=False), ungated,
                        toks)[0],
        "factor on sin and cos": forward(no_factor, p, toks)[0],
        "factor on the rotated part only": forward(
            no_factor, on_the_scale, toks)[0],
        "half a rotary on full layers": forward(dataclasses.replace(
            cfg, rotary_dim=0), p, toks)[0],
        "a whole rotary on window layers": forward(dataclasses.replace(
            cfg, sliding_rotary_dim=0), p, toks)[0],
        "window rotary base": forward(dataclasses.replace(
            cfg, sliding_rope_theta=0.0), p, toks)[0],
        "window": forward(dataclasses.replace(cfg, sliding_window=64), p,
                          toks)[0],
        "2.5 on the router's weights": forward(dataclasses.replace(
            cfg, moe_routed_scale=1.0), p, toks)[0],
        "router kind": forward(dataclasses.replace(
            cfg, moe_router="sigmoid"), p, toks)[0]}
    for name, logits in changed.items():
        assert float(jnp.abs(logits - base).max()) > 100 * TOL, name
    # YaRN's blend of the frequencies, apart from its factor.
    plain = dataclasses.replace(cfg, rope_yarn=(1.0,) + cfg.rope_yarn[1:])
    assert plain.attn_shape(FULL).rope_factor == 1.0
    assert float(jnp.abs(forward(plain, p, toks)[0]
                         - forward(no_factor, p, toks)[0]).max()) > 100 * TOL
    # The reference's own terms, left out on ITS side, are seen by the
    # same margin (the program is compared with the whole of it above).
    want = reference_logits(cfg, 5, toks_np)
    rope = as_run_of(cfg)["rope_parameters"]
    for name, over in (
            ("attention_factor", {"rope_parameters": {
                **rope, FULL: {**rope[FULL], "attention_factor": 1.0}}}),
            ("blended frequencies", {"rope_parameters": {
                **rope, FULL: {**rope[FULL], "rope_type": "default"}}}),
            ("half a rotary", {"rope_parameters": {
                **rope, FULL: {**rope[FULL], "partial_rotary_factor": 1}}}),
            ("gate", {"gating": False}),
            ("2.5", {"moe_routed_scaling_factor": 1.0})):
        other = reference_logits(cfg, 5, toks_np, **over)
        assert np.abs(other - want).max() > 100 * TOL, name


# --------------------------------------------------------------------------
# The expert layer's shares (the model-configs guide, section 4)
# --------------------------------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """held = 2 of a 16-expert window layer's FFN at first = 0, 2 .. 14:
    the eight parts, the shared expert counted ONCE (by the first share),
    sum to the uncut reference's whole layer, and one share with it is the
    reference given the same share."""
    cfg = toy()
    p = jax.tree.map(lambda a: a[0],
                     seeded(cfg, 0)["window_layers"][1]["moe"])
    x = jax.random.normal(jax.random.key(100), (2, 12, cfg.hidden_size),
                          jnp.float32)

    def reference_layer(first, held, shared=True):
        dm = ref.dims(dict(as_run_of(cfg), num_experts=held,
                           first_expert_held=first))
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        lw = {"router": f32(p["router"]),
              "exp_gate": p["wi_gate"][first:first + held],
              "exp_up": p["wi_up"][first:first + held],
              "exp_down": p["wo"][first:first + held],
              "shared_gate": f32(p["shared"]["wi_gate"]),
              "shared_up": f32(p["shared"]["wi_up"]),
              "shared_down": f32(p["shared"]["wo"])}
        y, _ = ref.sparse_ffn(dm, x.reshape(-1, x.shape[-1]), lw, ref.matmul,
                              shared)
        return np.asarray(y).reshape(x.shape)

    total, held_counts = 0.0, 0
    for first in range(0, 16, 2):
        share = {**p, **{k: p[k][first:first + 2]
                         for k in ("wi_gate", "wi_up", "wo")}}
        y, _, counts = moe_block(cfg, share, x, held=first,
                                 shared=first == 0)
        total = total + np.asarray(y)
        held_counts += int(counts[:-1].sum())
        if first == 6:
            np.testing.assert_allclose(
                np.asarray(y), reference_layer(6, 2, shared=False),
                atol=TOL)
    whole = reference_layer(0, 16)
    np.testing.assert_allclose(total, whole, atol=TOL)
    # Counted eight times, the shared expert is seen.
    assert np.abs(whole - reference_layer(0, 16, shared=False)).max() \
        > 100 * TOL
    assert held_counts == 24 * cfg.moe_top_k
