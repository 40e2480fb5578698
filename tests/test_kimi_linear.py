"""Kimi-Linear (`debug-kimi-linear`: a leading KDA layer with a dense FFN,
then periods of KDA, KDA, latent attention without a rotary, KDA over a
share of the experts) against the plain reference
(benchmark/reference/kimi_linear.py; docs/hybrid-models.md,
docs/sparse-latent-models.md).

Seeded random weights at toy widths on the CPU; LOGITS are compared, never
sampled tokens. Activations run in float32 under "highest" matmul
precision, weights are the bfloat16 the recipe stores, so what separates
program and reference is the order of float32 sums over nine layers (the
latent and expert layers' own tests hold 2e-4 over three): every tolerance
below is 2e-3 absolute on logits of order 1 for that reason, unless it says
otherwise. A bfloat16 state, a rotated k_r or a term left out moves the
logits by 1e-2 and more (the last tests)."""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbooks_tpu.models.config import get_config
from runbooks_tpu.models.moe import moe_block
from runbooks_tpu.models.transformer import (
    KVCache,
    _latent_attention_block,
    cache_leaves,
    forward,
    init_params,
    param_logical_axes,
)
from runbooks_tpu.ops.attention import make_attention_mask

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-3
LINEAR, LATENT = "linear_attention", "latent_attention"


def load_reference():
    path = os.path.join(ROOT, "benchmark", "reference", "kimi_linear.py")
    spec = importlib.util.spec_from_file_location("ref_kimi_linear", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = load_reference()


@pytest.fixture(autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def toy(**over):
    kw = dict(dtype="float32", param_dtype="bfloat16", moe_experts_held=8)
    kw.update(over)
    return get_config("debug-kimi-linear", **kw)


def as_run_of(cfg) -> dict:
    """The reference's description of a ModelConfig of this family, under
    the published keys (and the configuration file's for what the row
    lacks)."""
    kinds = ([cfg.leading_layer_kind] * cfg.leading_dense_layers
             + list(cfg.layer_pattern) * cfg.num_periods)
    return {
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "vocab_size": cfg.vocab_size, "rms_norm_eps": cfg.norm_eps,
        "num_attention_heads": cfg.num_heads,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
        "q_lora_rank": None, "mla_use_nope": True,
        "num_hidden_layers": cfg.num_layers,
        "first_k_dense_replace": cfg.leading_dense_layers,
        "linear_attn_config": {
            "kda_layers": [l + 1 for l, k in enumerate(kinds)
                           if k == LINEAR],
            "full_attn_layers": [l + 1 for l, k in enumerate(kinds)
                                 if k == LATENT],
            "num_heads": cfg.linear_num_heads,
            "head_dim": cfg.linear_key_head_dim,
            "short_conv_kernel_size": cfg.linear_conv_kernel},
        "layer_period": ["kda" if k == LINEAR else "full"
                         for k in cfg.layer_pattern],
        "gate_low_rank": cfg.linear_gate_rank,
        "num_experts_routed": cfg.moe_num_experts,
        "num_experts": cfg.moe_experts_here,
        "first_expert_held": cfg.moe_experts_first,
        "num_experts_per_token": cfg.moe_top_k,
        "moe_intermediate_size": cfg.moe_width,
        "num_shared_experts": cfg.moe_shared_experts,
        "router_bias_std": cfg.moe_router_bias_std,
        "routed_scaling_factor": cfg.moe_routed_scale,
        "num_expert_group": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid"}


@functools.lru_cache(maxsize=None)
def seeded(cfg, seed: int):
    """The program's own seeded weights (jitted, as the server draws them:
    the reference's draw is a jitted one too)."""
    return jax.jit(functools.partial(init_params, cfg))(jax.random.key(seed))


def tokens_for(cfg, n: int, seed: int) -> np.ndarray:
    # numpy's generator: the engine tests count compiles under traffic.
    return np.random.default_rng(100 + seed).integers(
        0, cfg.vocab_size, n).astype(np.int32)


@functools.lru_cache(maxsize=None)
def reference_weights(cfg, seed: int):
    return ref.init_weights(as_run_of(cfg), seed)


def reference_logits(cfg, seed: int, tokens) -> np.ndarray:
    rows = np.arange(len(tokens))
    return np.asarray(ref.logits_at(as_run_of(cfg),
                                    reference_weights(cfg, seed),
                                    np.asarray(tokens), rows))


def test_seeded_weights_are_the_references():
    """Every leaf the program draws is the reference's, by its key: the
    three splits (16, 32 for the periods' KDA layers, 16 for the leading
    one), the layer order of the dealt stacks."""
    cfg = toy()
    p, w = seeded(cfg, 4), reference_weights(cfg, 4)
    same = lambda a, b: np.testing.assert_array_equal(  # noqa: E731
        np.asarray(a, np.float32), np.asarray(b, np.float32))
    same(p["embed"], w["embed"])
    same(p["head"], w["head"])
    for name in ref.MLA_LEAVES:
        same(p["layers"]["attn"][name], w["mla_" + name])
    moe = {"router": "router", "router_bias": "router_bias",
           "wi_gate": "exp_gate", "wi_up": "exp_up", "wo": "exp_down"}
    shared = {"wi_gate": "shared_gate", "wi_up": "shared_up",
              "wo": "shared_down"}
    for ours, theirs in moe.items():
        same(p["layers"]["moe"][ours], w["mla_" + theirs])
    for ours, theirs in shared.items():
        same(p["layers"]["moe"]["shared"][ours], w["mla_" + theirs])
    n = cfg.layer_pattern.count(LINEAR)
    for pos, stack in enumerate(p["linear_layers"]):
        for name in ref.KDA_LEAVES:     # layer l = period l // n, pos l % n
            same(stack["mixer"][name], w["kda_" + name][pos::n])
        for ours, theirs in moe.items():
            same(stack["moe"][ours], w["kda_" + theirs][pos::n])
    lead = p["leading_layers"]
    for name in ref.KDA_LEAVES:
        same(lead["mixer"][name], w["lead_" + name])
    for ours, theirs in (("wi_gate", "gate"), ("wi_up", "up"),
                         ("wo", "down")):
        same(lead["mlp"][ours], w["lead_mlp_" + theirs])
    assert sum(a.size for a in jax.tree.leaves(p)) == cfg.num_params
    assert jax.tree.structure(jax.tree.map(lambda a: 0, p)) == \
        jax.tree.structure(jax.tree.map(
            lambda a: 0, param_logical_axes(cfg),
            is_leaf=lambda x: isinstance(x, tuple)))


def test_forward_without_a_cache_matches_reference():
    cfg = toy()
    toks = tokens_for(cfg, 70, 1)
    p = seeded(cfg, 3)
    logits, _ = jax.jit(lambda t: forward(cfg, p, t))(
        jnp.asarray(toks)[None])
    np.testing.assert_allclose(np.asarray(logits[0]),
                               reference_logits(cfg, 3, toks), atol=TOL)


def test_the_latent_path_is_not_rotated_and_sarvams_still_is():
    """One latent layer alone on random inputs: position_type none passes
    q_rope and k_r as projected (the reference's latent_mixer), and is NOT
    what a rotary gives; the same block under position_type rope is the
    reference's rotated reading."""
    cfg = toy()
    dm = ref.dims(as_run_of(cfg))
    p = jax.tree.map(lambda a: a[0], seeded(cfg, 3)["layers"]["attn"])
    u = jax.random.normal(jax.random.key(2), (1, 40, cfg.hidden_size)) * 0.5
    pos = jnp.arange(40)[None]
    mask = make_attention_mask(pos, pos, causal=True)
    lw = {n: np.asarray(p[n], np.float32) for n in ref.MLA_LEAVES}
    lw["kv_norm"] = np.ones(cfg.kv_lora_rank, np.float32)
    plain = np.asarray(ref.latent_mixer(dm, u[0], lw, ref.matmul))
    turned = np.asarray(ref.latent_mixer(dm, u[0], lw, ref.matmul,
                                         rotated=True))
    assert np.abs(plain - turned).max() > 3e-2
    for c, want in ((cfg, plain),
                    (toy(position_type="rope"), turned)):
        got, _ = _latent_attention_block(c, p, u, pos, None, mask, None)
        np.testing.assert_allclose(np.asarray(got[0]), want, atol=2e-4)


def test_shares_of_the_experts_add_up_to_the_uncut_layer():
    """The guide's share test: the parts that the four shares of 8 experts
    give, the shared expert counted once, are the layer with all 32; in
    the program and in the reference, and the two agree."""
    whole_cfg = toy(moe_experts_held=0)
    p = jax.tree.map(lambda a: a[0], seeded(whole_cfg, 3)["layers"]["moe"])
    u = jax.random.normal(jax.random.key(5), (2, 24, whole_cfg.hidden_size))
    whole, _, counts = moe_block(whole_cfg, p, u)
    assert int(counts[:-1].sum()) == 2 * 24 * whole_cfg.moe_top_k
    dm = ref.dims(as_run_of(whole_cfg))
    lw = {"router": np.asarray(p["router"], np.float32),
          "router_bias": np.asarray(p["router_bias"], np.float32),
          "exp_gate": p["wi_gate"], "exp_up": p["wi_up"],
          "exp_down": p["wo"], "shared_gate": p["shared"]["wi_gate"],
          "shared_up": p["shared"]["wi_up"],
          "shared_down": p["shared"]["wo"]}
    flat = u.reshape(-1, whole_cfg.hidden_size)
    want = np.asarray(ref.sparse_ffn(dm, flat, lw, ref.matmul))
    np.testing.assert_allclose(np.asarray(whole).reshape(want.shape), want,
                               atol=2e-4)
    total, ref_total = 0.0, 0.0
    for share in range(4):
        cut = toy(moe_experts_first=8 * share)
        held = slice(8 * share, 8 * share + 8)
        part = {**p, **{n: p[n][held] for n in ("wi_gate", "wi_up", "wo")}}
        y, _, c = moe_block(cut, part, u, shared=share == 0)
        total = total + y
        ref_total = ref_total + ref.sparse_ffn(
            dm, flat, {**lw, **{n: lw[n][held] for n in
                                ("exp_gate", "exp_up", "exp_down")}},
            ref.matmul, first=8 * share, held=8, shared=share == 0)
        assert int(c[:-1].sum()) == int(counts[held].sum())
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(ref_total), want, atol=2e-4)


@pytest.mark.parametrize("broken", ["bfloat16_state", "decay_of_a_head",
                                    "no_selection_bias"])
def test_a_program_with_a_term_changed_fails(monkeypatch, broken):
    """What the comparison must catch: the state rounded to bfloat16 a
    token, the decay a head's mean instead of a channel's, the selection
    bias left out of the choice."""
    from runbooks_tpu.ops import kda

    cfg = toy()
    p = seeded(cfg, 3)
    if broken == "no_selection_bias":
        p = jax.tree.map(lambda a: a, p)
        for stack in [p["layers"]] + list(p["linear_layers"]):
            stack["moe"] = {**stack["moe"], "router_bias":
                            jnp.zeros_like(stack["moe"]["router_bias"])}
    else:
        def changed(qkv, f, wf_up, dt_bias, a_log, beta, valid, state,
                    chunk):
            # The recurrence token by token on the operands made in plain
            # XLA, with the one term changed.
            q, k, v, g = kda.plain_operands(qkv, f, wf_up, dt_bias, a_log)
            if broken == "decay_of_a_head":
                g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)

            def body(s, xs):
                o, s = kda.kda_step(*xs[:5], s, xs[5])
                if broken == "bfloat16_state":
                    s = s.astype(jnp.bfloat16).astype(jnp.float32)
                return s, o
            t = lambda x: jnp.moveaxis(x, 1, 0)  # noqa: E731
            s, o = jax.lax.scan(body, state, tuple(map(t, (q, k, v, g, beta,
                                                           valid > 0))))
            return jnp.moveaxis(o, 0, 1).astype(v.dtype), s
        monkeypatch.setattr(kda, "_chunked", changed)
    toks = tokens_for(cfg, 70, 2)
    logits, _ = jax.jit(lambda t: forward(cfg, p, t))(jnp.asarray(toks)[None])
    gap = np.abs(np.asarray(logits[0]) - reference_logits(cfg, 3, toks))
    assert gap.max() > 5 * TOL


def test_cache_leaves_of_the_configuration():
    """A state, a conv tail and a latent side by side; k and v hold no
    layer. The leading KDA layer is the first of the recurrent leaves."""
    cfg = toy()
    leaves = {leaf.name: leaf for leaf in cache_leaves(cfg)}
    assert set(leaves) == {"k", "v", "state", "conv", "latent"}
    cache = KVCache.create(cfg, 2, 64, trash_slot=True)
    assert cache.k.shape[0] == 0 and cache.v.shape[0] == 0
    assert cache.state.shape == (7, 2, 4, 32, 32) \
        and cache.state.dtype == jnp.float32
    assert cache.conv.shape == (7, 2, 3, 3 * 4 * 32)
    assert cache.latent.shape == (2, 2, 65, 64 + 16)
    assert [leaves[n].group for n in ("state", "conv", "latent")] == [
        "recurrent_state", "recurrent_state", "latent_cache"]
    with pytest.raises(NotImplementedError, match="latent"):
        cache_leaves(cfg, quantize_kv=True)


@pytest.mark.parametrize("over,text", [
    (dict(linear_gate_rank=0), "linear_gate_rank"),
    (dict(leading_kind="conv"), "leading_kind"),
    (dict(linear_mixer="delta"), "unknown linear_mixer"),
    (dict(linear_mixer="lightning", linear_conv_kernel=0),
     "leading lightning"),
])
def test_config_refuses_what_cannot_be(over, text):
    with pytest.raises(ValueError, match=text):
        toy(**over)


def test_preset_is_the_published_model_less_its_remainder():
    cfg = get_config("kimi-linear-48b-a3b")
    assert cfg.num_layers == 25 and cfg.num_periods == 6
    assert cfg.layers_of(LINEAR) == 19 and cfg.layers_of(LATENT) == 6
    assert cfg.latent_width == 576 and cfg.linear_conv_dim == 3 * 4096
    cut = get_config("kimi-linear-48b-a3b", num_layers=13,
                     moe_experts_held=32, vocab_size=20480)
    # The issue's arithmetic: 3.45 G parameters held.
    assert abs(cut.num_params / 1e9 - 3.45) < 0.01
    assert cut.layers_of(LINEAR) == 10 and cut.layers_of(LATENT) == 3


def test_packed_sequences_and_adapters_are_refused():
    cfg = toy()
    toks = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="recurrent state reset"):
        forward(cfg, seeded(cfg, 3), toks, segment_ids=jnp.ones((1, 8),
                                                                jnp.int32))
