"""Latent attention (MLA), the dropless expert layer held as a share, and
leading dense layers, against the plain reference
(benchmark/reference/sarvam_mla.py; docs/sparse-latent-models.md).

Seeded random weights at toy widths on the CPU; LOGITS are compared, never
sampled tokens. Activations run in float32 under "highest" matmul
precision, weights are the bfloat16 the recipe stores, so what separates
program and reference is the order of float32 sums: every tolerance below
is 2e-4 absolute on logits of order 1 for that reason, unless it says
otherwise.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbooks_tpu.models.config import get_config
from runbooks_tpu.models.moe import moe_block, route
from runbooks_tpu.models.transformer import KVCache, forward, init_params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4


def load_reference():
    path = os.path.join(ROOT, "benchmark", "reference", "sarvam_mla.py")
    spec = importlib.util.spec_from_file_location("ref_sarvam_mla", path)
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
    return get_config("debug-sparse-latent", **kw)


def as_run_of(cfg) -> dict:
    """The reference's description of a ModelConfig of this family."""
    factor, original, fast, slow, mscale, mscale_all = cfg.rope_yarn
    return {
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "vocab_size": cfg.vocab_size, "rms_norm_eps": cfg.norm_eps,
        "num_attention_heads": cfg.num_heads,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
        "use_qk_norm": cfg.qk_norm,
        "first_k_dense_replace": cfg.leading_dense_layers,
        "num_hidden_layers": cfg.num_layers,
        "num_experts_routed": cfg.moe_num_experts,
        "num_experts": cfg.moe_experts_here,
        "first_expert_held": cfg.moe_experts_first,
        "num_experts_per_tok": cfg.moe_top_k,
        "moe_intermediate_size": cfg.moe_width,
        "num_shared_experts": cfg.moe_shared_experts,
        "moe_router_enable_expert_bias": cfg.moe_router_bias,
        "router_bias_std": 0.05,
        "routed_scaling_factor": cfg.moe_routed_scale,
        "rope_theta": cfg.rope_theta,
        "rope_scaling": {
            "type": "deepseek_yarn", "factor": factor,
            "original_max_position_embeddings": original,
            "beta_fast": fast, "beta_slow": slow, "mscale": mscale,
            "mscale_all_dim": mscale_all}}


def seeded(cfg, seed):
    """init_params as the server makes them: under jit (an eager draw
    rounds a few elements in 65 536 to the other bfloat16 neighbour)."""
    return jax.jit(lambda key: init_params(cfg, key))(jax.random.key(seed))


def tokens_for(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, n).astype(np.int32)


def reference_logits(cfg, seed, toks):
    w = ref.init_weights(as_run_of(cfg), seed)
    return np.asarray(ref.logits_at(as_run_of(cfg), w, toks,
                                    np.arange(len(toks))))


# --------------------------------------------------------------------------
# The seeded recipe, the preset, the config's checks
# --------------------------------------------------------------------------

def test_preset_holds_the_published_sizes():
    cfg = get_config("sarvam-105b")
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads) == (32, 4096, 64)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.kv_lora_rank, cfg.head_dim) == (128, 64, 128, 512, 576)
    assert cfg.q_head_dim == 192 and cfg.latent_width == 576
    assert (cfg.intermediate_size, cfg.moe_width, cfg.moe_num_experts,
            cfg.moe_top_k, cfg.moe_shared_experts) == (16384, 2048, 128, 8, 1)
    assert cfg.leading_dense_layers == 1 and cfg.vocab_size == 262144
    assert cfg.moe_routed_scale == 2.5 and cfg.moe_router == "sigmoid"
    assert cfg.rope_yarn == (40.0, 4096, 32.0, 1.0, 1.0, 1.0)
    assert abs(cfg.yarn_attn_factor - 1.3689) < 1e-4
    assert 105e9 < cfg.num_params < 107e9
    cut = get_config("sarvam-105b", num_layers=6, moe_experts_held=32,
                     vocab_size=65536)
    # ISSUE 30's arithmetic: 296.0 M + 5 x 925.6 M + 536.9 M.
    assert abs(cut.num_params - 5.461e9) < 2e6


@pytest.mark.parametrize("field,value,text", [
    ("moe_router", "tanh", "moe_router"),
    ("moe_experts_held", 17, "not among"),
    ("leading_dense_layers", 3, "leading"),
    ("kv_lora_rank", 0, "latent_attention layers need"),
    ("rope_yarn", (40.0, 4096), "rope_yarn is"),
])
def test_config_refuses(field, value, text):
    with pytest.raises(ValueError, match=text):
        toy(**{field: value})


def test_seeded_weights_are_the_references_bit_for_bit():
    cfg = toy(moe_experts_held=4, moe_experts_first=8)
    p = seeded(cfg, 11)
    w = ref.init_weights(as_run_of(cfg), 11)
    pairs = {"embed": p["embed"], "head": p["head"],
             "router": p["layers"]["moe"]["router"],
             "router_bias": p["layers"]["moe"]["router_bias"],
             "exp_gate": p["layers"]["moe"]["wi_gate"],
             "exp_up": p["layers"]["moe"]["wi_up"],
             "exp_down": p["layers"]["moe"]["wo"],
             "shared_gate": p["layers"]["moe"]["shared"]["wi_gate"],
             "shared_up": p["layers"]["moe"]["shared"]["wi_up"],
             "shared_down": p["layers"]["moe"]["shared"]["wo"],
             "lead_mlp_gate": p["leading_layers"]["mlp"]["wi_gate"],
             "lead_mlp_up": p["leading_layers"]["mlp"]["wi_up"],
             "lead_mlp_down": p["leading_layers"]["mlp"]["wo"]}
    for pre, tree in (("", p["layers"]), ("lead_", p["leading_layers"])):
        for name in ("wq", "w_kva", "w_kvb", "wo"):
            pairs[pre + name] = tree["attn"][name]
    assert set(pairs) == {n for n in w if "norm" not in n and "ln" not in n}
    for name, leaf in pairs.items():
        assert leaf.dtype == jnp.bfloat16 and w[name].dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(leaf.astype(jnp.float32)),
            np.asarray(w[name].astype(jnp.float32)), err_msg=name)
    # The selection bias is not zero: leaving it out must be seen.
    assert float(jnp.abs(p["layers"]["moe"]["router_bias"]
                         .astype(jnp.float32)).mean()) > 0.01


def test_other_presets_keep_their_seeded_weights():
    """The new leaves take keys of their own: a dense preset's and the
    softmax MoE's leaves are drawn as before."""
    for name, over in (("debug", {}),
                       ("debug", dict(moe_num_experts=4, moe_top_k=2))):
        cfg = get_config(name, **over)
        p = seeded(cfg, 3)
        keys = jax.random.split(jax.random.key(3), 16)
        # (Eager against jitted: equal to a float32 rounding.)
        np.testing.assert_allclose(
            np.asarray(p["layers"]["attn"]["wq"]),
            np.asarray(jax.random.normal(
                keys[2], p["layers"]["attn"]["wq"].shape)
                * cfg.hidden_size ** -0.5), rtol=1e-6)


# --------------------------------------------------------------------------
# The forward pass against the reference
# --------------------------------------------------------------------------

@pytest.mark.parametrize("share", [(0, 0), (4, 8)], ids=["whole", "share"])
def test_forward_matches_reference(share):
    """Leading dense layer + sparse layers + latent attention, no cache
    (expanded attention on both sides; grouped product against the
    reference's expert-at-a-time loop)."""
    held, first = share
    cfg = toy(moe_experts_held=held, moe_experts_first=first)
    toks = tokens_for(cfg, 40)
    p = seeded(cfg, 5)
    got, _ = forward(cfg, p, jnp.asarray(toks)[None])
    want = reference_logits(cfg, 5, toks)
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=TOL)


def test_prefill_then_absorbed_decode_matches_reference():
    """Two rows of different lengths prefilled in one padded call
    (position-scatter mode, padding at the trash slot), then decoded a
    token at a time through a view shorter than the pool: every logit the
    absorbed path gives equals the reference's full expanded forward."""
    cfg = toy(moe_experts_held=8)
    p = seeded(cfg, 7)
    seqs = [tokens_for(cfg, 30, 1), tokens_for(cfg, 19, 2)]
    n_pre = [22, 11]
    max_len, bucket, view = 48, 32, 40
    cache = KVCache.create(cfg, 2, max_len, trash_slot=True)
    assert cache.k.shape[0] == 0 and cache.latent.shape == (
        3, 2, max_len + 1, cfg.latent_width)
    toks = np.zeros((2, bucket), np.int32)
    pos = np.full((2, bucket), max_len, np.int32)
    for r, (s, n) in enumerate(zip(seqs, n_pre)):
        toks[r, :n], pos[r, :n] = s[:n], np.arange(n)
    logits, cache = forward(cfg, p, jnp.asarray(toks),
                            positions=jnp.asarray(pos), cache=cache,
                            token_mask=jnp.asarray(pos < max_len))
    want = [reference_logits(cfg, 7, s) for s in seqs]
    for r, n in enumerate(n_pre):
        np.testing.assert_allclose(np.asarray(logits[r, :n]), want[r][:n],
                                   atol=TOL)
    step = jax.jit(lambda c, t, q: forward(
        cfg, p, t, positions=q, cache=c, cache_view=view))
    for i in range(8):
        at = np.array([n + i for n in n_pre], np.int32)
        t = np.array([[s[a]] for s, a in zip(seqs, at)], np.int32)
        logits, cache = step(cache, jnp.asarray(t), jnp.asarray(at[:, None]))
        for r in range(2):
            np.testing.assert_allclose(np.asarray(logits[r, 0]),
                                       want[r][at[r]], atol=TOL)


def test_flash_prefill_expands_from_the_cache():
    """The expanded cached path (the flash forward at key width 48, value
    width 32, block ranges from the scattered positions) equals the
    absorbed one a test above holds to the reference."""
    base = toy(moe_experts_held=8, flash_block_q=16, flash_block_k=16)
    p = init_params(base, jax.random.key(7))
    s = tokens_for(base, 24, 3)
    toks, pos = np.zeros((1, 32), np.int32), np.full((1, 32), 40, np.int32)
    toks[0, :24], pos[0, :24] = s, np.arange(24)
    out = {}
    for impl in ("xla", "flash"):
        cfg = dataclasses.replace(base, attention_impl=impl)
        out[impl], _ = forward(
            cfg, p, jnp.asarray(toks), positions=jnp.asarray(pos),
            cache=KVCache.create(cfg, 1, 40, trash_slot=True),
            token_mask=jnp.asarray(pos < 40))
    np.testing.assert_allclose(np.asarray(out["flash"][0, :24]),
                               np.asarray(out["xla"][0, :24]), atol=TOL)


def test_chunked_decode_program_serves_the_references_best_token():
    """make_prefill_fn + make_decode_fn (the chunk loop, a finished row
    parked): each greedy token is the reference's best at its position, or
    within TOL of it."""
    from runbooks_tpu.serve.engine import make_decode_fn, make_prefill_fn

    cfg = toy(moe_experts_held=8)
    p = seeded(cfg, 9)
    max_len, slots, chunk = 64, 2, 4
    prompts = [tokens_for(cfg, 21, 4), tokens_for(cfg, 9, 5)]
    pool = KVCache.create(cfg, slots, max_len, trash_slot=True)
    toks = np.zeros((slots, 32), np.int32)
    pos = np.full((slots, 32), max_len, np.int32)
    for r, s in enumerate(prompts):
        toks[r, :len(s)], pos[r, :len(s)] = s, np.arange(len(s))
    zeros, ones = jnp.zeros(slots), jnp.ones(slots)
    first, pool, rng, (counts, hits) = jax.jit(
        make_prefill_fn(cfg, max_len + 1))(
        p, pool, jnp.asarray(toks), jnp.asarray(pos),
        jnp.arange(slots, dtype=jnp.int32),
        jnp.asarray([len(s) - 1 for s in prompts], jnp.int32),
        jax.random.key(0), zeros, zeros.astype(jnp.int32), ones)
    # Real tokens only: 30 prompt tokens x top-4 x 2 sparse layers.
    assert int(counts.sum()) == 30 * 4 * 2
    assert counts.shape == (2, 9) and 0 < int(hits) <= 16
    served = [[int(t)] for t in first]
    decode = jax.jit(make_decode_fn(cfg, chunk, max_len, max_len, max_len))
    remaining = jnp.asarray([9, 3], jnp.int32)   # row 1 stops mid-chunk
    out, valid, _, pool, rng, (counts, hits) = decode(
        p, pool, first, jnp.asarray([len(s) for s in prompts], jnp.int32),
        rng, zeros, zeros.astype(jnp.int32), ones,
        jnp.full(slots, -1, jnp.int32), remaining, jnp.ones(slots, bool))
    out, valid = np.asarray(out), np.asarray(valid)
    assert valid[:, 0].all() and valid[:, 1].tolist() == [True] * 3 + [False]
    # A parked row is routed to no expert: 4 + 3 live tokens.
    assert int(counts.sum()) == 7 * 4 * 2
    for r in range(slots):
        served[r] += [int(out[k, r]) for k in range(chunk) if valid[k, r]]
        seq = np.concatenate([prompts[r], served[r]]).astype(np.int32)
        logits = reference_logits(cfg, 9, seq)
        rows = np.arange(len(prompts[r]) - 1, len(seq) - 1)
        gap = logits[rows].max(-1) - logits[rows, served[r]]
        assert gap.max() <= TOL, (r, gap)


# --------------------------------------------------------------------------
# The expert layer
# --------------------------------------------------------------------------

def layer_and_input(cfg, seed=0, tokens=24):
    p = jax.tree.map(lambda a: a[0],
                     seeded(cfg, seed)["layers"]["moe"])
    x = jax.random.normal(jax.random.key(seed + 100),
                          (2, tokens // 2, cfg.hidden_size), jnp.float32)
    return p, x


def reference_layer(cfg, p, x, first=0, held=None):
    held = cfg.moe_num_experts if held is None else held
    dm = ref.dims(dict(as_run_of(cfg), num_experts=held,
                       first_expert_held=first))
    lw = {"router": p["router"].astype(jnp.float32),
          "router_bias": p["router_bias"].astype(jnp.float32),
          "exp_gate": p["wi_gate"][first:first + held],
          "exp_up": p["wi_up"][first:first + held],
          "exp_down": p["wo"][first:first + held],
          "shared_gate": p["shared"]["wi_gate"],
          "shared_up": p["shared"]["wi_up"],
          "shared_down": p["shared"]["wo"]}
    y, _, _ = ref.sparse_ffn(dm, x.reshape(-1, x.shape[-1]), lw, ref.matmul)
    return np.asarray(y).reshape(x.shape)


def share_of(p, first, held):
    return {**p, **{k: p[k][first:first + held]
                    for k in ("wi_gate", "wi_up", "wo")}}


def test_the_four_shares_add_up_to_the_whole_layer():
    """held = 0, 4, 8, 12 of a 16-expert layer, the shared expert counted
    once: their sum is the uncut reference's whole layer, and one share
    is the reference given the same share."""
    cfg = toy()
    p, x = layer_and_input(cfg)
    whole = reference_layer(cfg, p, x)
    total, all_counts = 0.0, []
    for i, first in enumerate((0, 4, 8, 12)):
        y, _, counts = moe_block(cfg, share_of(p, first, 4), x, held=first,
                                 shared=(i == 0))
        total = total + np.asarray(y)
        all_counts.append(np.asarray(counts))
        if i == 1:
            one = reference_layer(cfg, p, x, first, 4)
            shared_only = reference_layer(cfg, p, x, 0, 0)
            np.testing.assert_allclose(np.asarray(y), one - shared_only,
                                       atol=TOL)
    np.testing.assert_allclose(total, whole, atol=TOL)
    # Every assignment is held by exactly one share.
    n = x.shape[0] * x.shape[1] * cfg.moe_top_k
    assert sum(int(c[:-1].sum()) for c in all_counts) == n
    assert all(int(c.sum()) == n for c in all_counts)
    # The whole layer in one call gives the same sum.
    y, _, counts = moe_block(cfg, p, x)
    np.testing.assert_allclose(np.asarray(y), whole, atol=TOL)
    assert int(counts[-1]) == 0


def test_dropless_a_token_does_not_depend_on_its_batch_mates():
    """Routing that sends most tokens to one expert (what a capacity
    would drop): a token's output alone equals its output in the batch."""
    cfg = toy()
    p, x = layer_and_input(cfg, tokens=64)
    router = np.asarray(p["router"].astype(jnp.float32)) * 0.01
    router[:, 3] = 0.0
    p = {**p, "router": jnp.asarray(router),
         "router_bias": p["router_bias"].at[3].set(1.0)}
    y, _, counts = moe_block(cfg, p, x)
    assert int(counts[3]) == 64          # every token chose expert 3
    for b, s in ((0, 0), (1, 17)):
        alone, _, _ = moe_block(cfg, p, x[b:b + 1, s:s + 1])
        np.testing.assert_allclose(np.asarray(alone[0, 0]),
                                   np.asarray(y[b, s]), atol=TOL)
    np.testing.assert_allclose(np.asarray(y), reference_layer(cfg, p, x),
                               atol=TOL)


def test_bias_changes_the_choice_and_not_the_weights():
    cfg = toy()
    p, x = layer_and_input(cfg)
    xt = x.reshape(-1, cfg.hidden_size)
    scores, idx, gate = route(cfg, p, xt)
    pushed = {**p, "router_bias": p["router_bias"].at[5].set(10.0)}
    scores2, idx2, gate2 = route(cfg, pushed, xt)
    np.testing.assert_array_equal(np.asarray(scores), np.asarray(scores2))
    assert (np.asarray(idx2) == 5).any(axis=-1).all()
    assert not (np.asarray(idx) == 5).any(axis=-1).all()
    # The weights are the UNBIASED scores of the chosen, over their sum,
    # times routed_scaling_factor, once.
    chosen = np.take_along_axis(np.asarray(scores2), np.asarray(idx2), -1)
    np.testing.assert_allclose(
        np.asarray(gate2), 2.5 * chosen / chosen.sum(-1, keepdims=True),
        rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gate2).sum(-1), 2.5, rtol=1e-6)


def test_routed_scaling_factor_is_applied_once():
    cfg = toy(moe_shared_experts=0)
    p, x = layer_and_input(cfg)
    y, _, _ = moe_block(cfg, p, x)
    y1, _, _ = moe_block(dataclasses.replace(cfg, moe_routed_scale=1.0), p, x)
    np.testing.assert_allclose(np.asarray(y), 2.5 * np.asarray(y1),
                               atol=TOL)


def test_softmax_router_renormalises_the_chosen():
    cfg = get_config("debug", moe_num_experts=4, moe_top_k=2,
                     dtype="float32")
    p = jax.tree.map(lambda a: a[0],
                     init_params(cfg, jax.random.key(0))["layers"]["moe"])
    xt = jax.random.normal(jax.random.key(1), (10, cfg.hidden_size))
    scores, idx, gate = route(cfg, p, xt)
    np.testing.assert_allclose(np.asarray(scores).sum(-1), 1.0, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gate).sum(-1), 1.0, rtol=1e-5)
    assert (np.asarray(idx)[:, 0]
            == np.asarray(scores).argmax(-1)).all()


def test_masked_tokens_are_routed_nowhere_and_long_inputs_are_chunked(
        monkeypatch):
    from runbooks_tpu.models import moe

    cfg = toy(moe_experts_held=8)
    p, x = layer_and_input(cfg, tokens=40)
    p = share_of(p, 0, 8)
    mask = jnp.arange(20)[None, :] < jnp.asarray([[13], [20]])
    y, _, counts = moe_block(cfg, p, x, token_mask=mask)
    assert int(counts.sum()) == 33 * cfg.moe_top_k
    full, _, full_counts = moe_block(cfg, p, x)
    real = np.asarray(mask)
    np.testing.assert_allclose(np.asarray(y)[real], np.asarray(full)[real],
                               atol=TOL)
    monkeypatch.setattr(moe, "TOKEN_CHUNK", 16)     # 40 tokens: 3 chunks
    chunked, _, chunk_counts = moe_block(cfg, p, x)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(full),
                               atol=TOL)
    np.testing.assert_array_equal(np.asarray(chunk_counts),
                                  np.asarray(full_counts))


def test_grouped_matmul_on_the_whole_stack_is_the_layers_own():
    """The serving path hands the product the whole [layers, experts, k, n]
    stack and the layer's number; the layer's groups among zero-sized ones
    give what the layer's own slice gives, rows past the groups apart."""
    from runbooks_tpu.models.moe import grouped_matmul

    ks = jax.random.split(jax.random.key(0), 2)
    lhs = jax.random.normal(ks[0], (24, 16), jnp.float32)
    stack = jax.random.normal(ks[1], (3, 4, 16, 8), jnp.float32)
    sizes = jnp.asarray([5, 0, 9, 4], jnp.int32)        # 6 rows in no group
    for layer in range(3):
        whole = grouped_matmul(lhs, stack, sizes, layer=jnp.int32(layer))
        own = grouped_matmul(lhs, stack[layer], sizes)
        np.testing.assert_allclose(np.asarray(whole[:18]),
                                   np.asarray(own[:18]), atol=1e-5)
        want = np.concatenate([
            np.asarray(lhs[a:b] @ stack[layer, e])
            for e, (a, b) in enumerate(((0, 5), (5, 5), (5, 14), (14, 18)))])
        np.testing.assert_allclose(np.asarray(own[:18]), want, atol=1e-5)


# --------------------------------------------------------------------------
# The way to the experts and back: one ranking, windows of the held rows
# --------------------------------------------------------------------------

def routed_to(p, favourites):
    """The layer with a router whose bias decides: {expert: bias}; scores
    are near ties otherwise, so the favourites are every token's choice."""
    router = np.asarray(p["router"].astype(jnp.float32)) * 0.01
    bias = np.zeros(p["router_bias"].shape, np.float32)
    for expert, push in favourites.items():
        bias[expert] = push
    return {**p, "router": jnp.asarray(router),
            "router_bias": jnp.asarray(bias)}


def stacked(p, first, held, layer, layers=3):
    """The share's expert weights as the layer `layer` of whole stacks, as
    the serving forward hands them over: the other layers hold noise."""
    out = dict(p)
    for i, name in enumerate(("wi_gate", "wi_up", "wo")):
        own = p[name][first:first + held]
        noise = jax.random.normal(jax.random.key(70 + i),
                                  (layers,) + own.shape, own.dtype)
        out[name] = noise.at[layer].set(own)
    return out


def counts_by_hand(cfg, p, x, first, held, mask=None):
    """What a bincount of the held experts' assignments gives: the counts
    the layer returned before it ranked."""
    _, idx, _ = route(cfg, p, x.reshape(-1, cfg.hidden_size))
    idx = np.asarray(idx)
    if mask is not None:
        idx = idx[np.asarray(mask).reshape(-1)]
    local = idx.reshape(-1) - first
    local = np.where((local >= 0) & (local < held), local, held)
    return np.bincount(local, minlength=held + 1)


# name: (tokens, first expert held, experts held, {expert: router bias} or
# None for the seeded router, real tokens or None, TOKEN_CHUNK or None,
# windows the held rows take: None where all rows are one window).
# 16 experts, top-4, row tiles of 16: a share of 4 expects tokens rows.
WINDOW_CASES = {
    "a share": (64, 4, 4, None, None, None, None),
    "the whole layer": (64, 0, 16, None, None, None, None),
    "every assignment held here": (
        64, 4, 4, dict.fromkeys((4, 5, 6, 7), 10.0), None, None, 4),
    "all on one held expert": (
        64, 4, 4, {6: 10.0, **dict.fromkeys((9, 10, 11), 5.0)}, None, None,
        1),
    "none held": (64, 4, 4, dict.fromkeys((0, 1, 2, 3), 10.0), None, None,
                  0),
    "a masked bucket": (64, 4, 4, None, 37, None, None),
    "longer than TOKEN_CHUNK": (80, 4, 4, None, None, 32, None),
    "masked and chunked": (80, 4, 4, None, 50, 32, None),
    # 2 of 16 held and 96 tokens: windows of 48 rows; one held choice a
    # real token, so the real tokens are the held rows.
    "one row under a window": (
        96, 4, 2, {4: 10.0, **dict.fromkeys((9, 10, 11), 5.0)}, 47, None, 1),
    "a window full": (
        96, 4, 2, {4: 10.0, **dict.fromkeys((9, 10, 11), 5.0)}, 48, None, 1),
    "one row over a window": (
        96, 4, 2, {4: 10.0, **dict.fromkeys((9, 10, 11), 5.0)}, 49, None, 2),
    "two windows full": (
        96, 4, 2, {4: 10.0, **dict.fromkeys((9, 10, 11), 5.0)}, 96, None, 2),
}


@pytest.mark.parametrize("name", WINDOW_CASES)
def test_windows_of_held_rows_match_the_reference(name, monkeypatch):
    """The cached forward's layer (whole stacks and a layer's number: the
    held rows a window at a time) and, in three of the cases, the forward
    without a cache (all rows at once) against the plain float32 reference
    and a bincount, at the routings that strain a bound on the rows."""
    from runbooks_tpu.models import moe

    tokens, first, held, favourites, real, chunk, windows = \
        WINDOW_CASES[name]
    monkeypatch.setattr(moe, "GMM_ROW_TILES", (16,))
    if chunk:
        monkeypatch.setattr(moe, "TOKEN_CHUNK", chunk)
    cfg = toy()
    p, x = layer_and_input(cfg, seed=3, tokens=tokens)
    if favourites:
        p = routed_to(p, favourites)
    mask = None
    if real is not None:
        mask = (jnp.arange(tokens) < real).reshape(x.shape[:2])
    want = reference_layer(cfg, p, x, first, held) \
        - reference_layer(cfg, p, x, 0, 0)
    want_counts = counts_by_hand(cfg, p, x, first, held, mask)
    k = cfg.moe_top_k
    assert moe.row_window(min(tokens, chunk or tokens) * k, held, 16) \
        == (min(tokens, chunk or tokens) * k * held // 16)
    paths = [(stacked(p, first, held, layer=1), jnp.int32(1))]
    if name in ("a share", "every assignment held here", "a masked bucket"):
        paths.append((share_of(p, first, held), None))
    for params, layer in paths:
        y, _, counts = jax.jit(
            lambda p_, x_, l_: moe_block(cfg, p_, x_, held=first,
                                         shared=False, token_mask=mask,
                                         layer=l_))(params, x, layer)
        np.testing.assert_array_equal(np.asarray(counts), want_counts)
        y = np.asarray(y)
        if mask is None:
            np.testing.assert_allclose(y, want, atol=TOL)
        else:
            keep = np.asarray(mask)
            np.testing.assert_allclose(y[keep], want[keep], atol=TOL)
            if layer is not None and held < 16:
                assert not y[~keep].any()       # nobody's: nothing added
    # What the engine's counter makes of the counts, on the host.
    moved = moe.rows_moved(toy(moe_experts_held=held % 16), tokens,
                           int(want_counts[:-1].sum()))
    if windows is not None:
        assert moved == (windows * tokens * k * held // 16, False)
    if name == "the whole layer":
        assert moved == (tokens * k, True)


def held_part_by_two_sorts(cfg, p, xt, idx, gate, first):
    """The layer's held part as it was before it ranked: a stable argsort
    by expert, a bincount, a gather of all tokens x top_k rows, and a
    second argsort for the way back."""
    from runbooks_tpu.models.moe import grouped_matmul
    from runbooks_tpu.models.transformer import _activation

    T, k = idx.shape
    n_held = p["wi_gate"].shape[0]
    local = idx.reshape(-1) - first
    here = (local >= 0) & (local < n_held)
    group = jnp.where(here, local, n_held)
    order = jnp.argsort(group, stable=True)
    counts = jnp.bincount(group, length=n_held + 1).astype(jnp.int32)
    xs = xt[(jnp.arange(T * k, dtype=jnp.int32) // k)[order]]
    sizes = counts[:n_held]
    hidden = _activation(cfg, grouped_matmul(xs, p["wi_gate"], sizes)) \
        * grouped_matmul(xs, p["wi_up"], sizes)
    out = grouped_matmul(hidden, p["wo"], sizes)
    rows = out[jnp.argsort(order)].reshape(T, k, -1)
    held_here = here.reshape(T, k)
    y = jnp.einsum("tkh,tk->th", jnp.where(held_here[..., None], rows, 0),
                   jnp.where(held_here, gate, 0.0),
                   preferred_element_type=jnp.float32)
    return y, counts


@pytest.mark.parametrize("first,held", [(0, 16), (4, 4)],
                         ids=["whole", "share"])
def test_ranking_gives_the_two_sorts_results_and_gradients(first, held):
    """The forward without a cache, which training differentiates: the
    ranked layer's result, counts and gradients (by the input, the gate
    weights and the expert weights) are those of the layer that sorted
    twice — the order of the rows is the stable sort's, so bit for bit."""
    from runbooks_tpu.models.moe import _held_part, _rank

    cfg = toy()
    p, x = layer_and_input(cfg, seed=5, tokens=40)
    p = share_of(p, first, held)
    xt = x.reshape(-1, cfg.hidden_size)
    _, idx, gate = route(cfg, p, xt)
    experts = {name: p[name].astype(jnp.float32)
               for name in ("wi_gate", "wi_up", "wo")}

    def loss(part, xt_, gate_, experts_):
        y, counts = part(cfg, {**p, **experts_}, xt_, idx, gate_, first)
        return (y * jnp.cos(jnp.arange(y.size).reshape(y.shape))).sum(), \
            (y, counts)

    got, want = (jax.jit(jax.value_and_grad(
        lambda *a, part=part: loss(part, *a), argnums=(0, 1, 2),
        has_aux=True))(xt, gate, experts)
        for part in (_held_part, held_part_by_two_sorts))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # The ranking alone, at a length no block divides.
    group = jnp.asarray(np.random.default_rng(0).integers(0, 7, 333),
                        jnp.int32)
    counts, place, _ = _rank(group, 7)
    np.testing.assert_array_equal(
        np.asarray(place), np.argsort(np.argsort(group, kind="stable")))
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.bincount(group, minlength=7))


def primitives_and_shapes(jaxpr):
    """(primitive name, shapes of its results) of every equation of a
    jaxpr, the bodies of its loops and calls included."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, [getattr(v.aval, "shape", ())
                                   for v in eqn.outvars]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from primitives_and_shapes(sub)


def test_cached_share_neither_sorts_nor_moves_all_the_rows():
    """The mechanism, held by the program's structure: a cached forward
    that holds 32 of 256 experts (a share cell: 2048 tokens, top-8) has no
    sort in it, and no value of tokens x top_k rows of the hidden size —
    its gathers, its products and the way back are of a window's rows."""
    from runbooks_tpu.models.moe import _held_part, row_window

    T, k, h, f, n_held = 2048, 8, 256, 128, 32
    cfg = toy(hidden_size=h, moe_num_experts=256, moe_top_k=k,
              moe_experts_held=n_held, moe_intermediate_size=f)
    assert row_window(T * k, n_held, 256) == 2048

    def like(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)

    p = {"wi_gate": like(2, n_held, h, f), "wi_up": like(2, n_held, h, f),
         "wo": like(2, n_held, f, h)}
    found = list(primitives_and_shapes(jax.make_jaxpr(
        lambda p_, xt, idx, gate: _held_part(cfg, p_, xt, idx, gate, 0,
                                             layer=jnp.int32(1)))(
        p, like(T, h), like(T, k, dtype=jnp.int32), like(T, k)).jaxpr))
    names = {name for name, _ in found}
    assert "sort" not in names and "while" in names
    assert not [(name, s) for name, shapes in found for s in shapes
                if s and s[-1] == h and np.prod(s[:-1]) >= T * k]
    # The forward without a cache keeps all its rows, and sorts no more.
    p = {name: like(*a.shape[1:]) for name, a in p.items()}
    found = list(primitives_and_shapes(jax.make_jaxpr(
        lambda p_, xt, idx, gate: _held_part(cfg, p_, xt, idx, gate, 0))(
        p, like(T, h), like(T, k, dtype=jnp.int32), like(T, k)).jaxpr))
    assert "sort" not in {name for name, _ in found}
    assert [s for _, shapes in found for s in shapes if s == (T * k, h)]


# The calls the four sparse cells make of the grouped product (doc_flood:
# a 2048-token prefill chunk, a decode step of 8 rows), and the tile each
# compiles with: name -> (preset, tokens, (m, h, f), gate / up, down). m is
# the window of the order by expert that a call holds (moe.row_window):
# what the held share can expect of the chunk's tokens x top_k rows, all
# of them where every expert is held and in a decode step.
GMM_CALLS = {
    "lfm2 prefill": ("lfm2-24b-a2b", 2048, (8192, 2048, 1536),
                     (256, 2048, 768), (256, 1536, 1024)),
    "lfm2 decode": ("lfm2-24b-a2b", 8, (32, 2048, 1536),
                    (32, 1024, 1024), (32, 1024, 1024)),
    "sarvam prefill": ("sarvam-105b", 2048, (4096, 4096, 2048),
                       (256, 4096, 512), (256, 2048, 1024)),
    "sarvam decode": ("sarvam-105b", 8, (64, 4096, 2048),
                      (64, 1024, 1024), (64, 1024, 1024)),
    "mimo prefill": ("mimo-v2-flash", 2048, (2048, 4096, 2048),
                     (256, 4096, 512), (256, 2048, 1024)),
    "mimo decode": ("mimo-v2-flash", 8, (64, 4096, 2048),
                    (64, 1024, 1024), (64, 1024, 1024)),
    "laguna prefill": ("laguna-xs.2", 2048, (2048, 2048, 512),
                       (256, 2048, 512), (256, 512, 2048)),
    "laguna decode": ("laguna-xs.2", 8, (64, 2048, 512),
                      (64, 1024, 512), (64, 512, 1024)),
}


@pytest.mark.parametrize("name", GMM_CALLS)
def test_gmm_tiling_of_the_cells_calls(name, monkeypatch):
    """The chooser at the eight call shapes of the four sparse cells: the
    tile is the sweep's (models/moe.py's docstring), divides the problem,
    stays inside the VMEM the docstring reckons, is one tile of all the
    rows in decode — and is what gmm_tilings publishes for the preset."""
    import runbooks_tpu.utils.hw as hw
    from runbooks_tpu.models import moe

    preset, tokens, (m, h, f), gate_up, down = GMM_CALLS[name]
    for (k, n), want in (((h, f), gate_up), ((f, h), down)):
        tm, tk, tn = tile = moe._gmm_tiling(m, k, n)
        assert tile == want
        assert tm == min(m, 256) and m % tm == 0
        if tm < m:      # a prefill chunk: k whole, tiles that divide
            assert tk == k and n % tn == 0
        else:           # a decode step: the tiles every call used to get
            assert (tk, tn) == (min(k, 1024), min(n, 1024))
        assert moe._gmm_vmem_bytes(tm, tk, tn, 2) <= moe.GMM_VMEM_BYTES
    # The share cells hold 32 experts a chip; lfm2's holds all 64.
    cfg = get_config(preset, moe_experts_held=0 if "lfm2" in preset else 32)
    assert (moe.row_window(cfg.moe_top_k * min(tokens, moe.TOKEN_CHUNK),
                           cfg.moe_experts_here, cfg.moe_num_experts),
            cfg.hidden_size, cfg.moe_width) == (m, h, f)
    assert moe.gmm_tilings(cfg, tokens) == {}          # ragged_dot off the TPU
    monkeypatch.setattr(hw, "on_tpu", lambda: True)
    assert moe.gmm_tilings(cfg, tokens) == {"gate_up": list(gate_up),
                                            "down": list(down)}
    # A burst prefill is chunked to TOKEN_CHUNK tokens: the chunk's tile.
    assert moe.gmm_tilings(cfg, 8 * 2048) == moe.gmm_tilings(cfg, 2048)


@pytest.mark.parametrize("call,want", [
    # float32 activations (a scratch check on the chip): 4-byte tiles.
    ((16384, 4096, 2048, 4), (256, 2048, 256)),
    ((256, 4096, 2048, 4), (256, 512, 1024)),
    # A k no 2 MiB row tile holds whole: its largest divisor that does.
    ((512, 8192, 1024), (256, 4096, 512)),
    # Rows no tile divides, a width that is not whole lanes: ragged_dot.
    ((24, 4096, 2048), None),
    ((64, 4096, 100), None),
    ((64, 100, 4096), None),
])
def test_gmm_tiling_at_the_edges(call, want):
    from runbooks_tpu.models.moe import (
        GMM_VMEM_BYTES,
        _gmm_tiling,
        _gmm_vmem_bytes,
    )

    assert _gmm_tiling(*call) == want
    if want is not None:
        assert _gmm_vmem_bytes(*want, (call + (2,))[3]) <= GMM_VMEM_BYTES


def drawn_sizes(rng, tokens, top_k, scored, held):
    """Group sizes of a prefill chunk: `tokens` real tokens choose top_k of
    `scored` experts with a skewed popularity; the first `held` are here."""
    p = np.exp(0.7 * rng.standard_normal(scored))
    return rng.multinomial(tokens * top_k, p / p.sum())[:held]


@pytest.mark.parametrize("cell,top_k,scored,held", [
    ("lfm2moe_doc", 4, 64, 64), ("sarvam105b_doc", 8, 128, 32),
    ("mimov2flash_doc", 8, 256, 32), ("lagunaxs2_doc", 8, 256, 32)])
def test_tile_visits_counts_the_tiles_groups_touch(cell, top_k, scored,
                                                   held):
    """tile_visits (the V of the cost model) against a count row by row,
    over seeded group sizes of a doc_flood prefill (about 1460 real tokens
    of a 2048 bucket); and the chosen row tile computes fewer rows than the
    512-row tile it replaces: visits x rows a visit."""
    from runbooks_tpu.models.moe import _gmm_tiling, tile_visits

    rng = np.random.default_rng(41)
    for tokens in (1460, 1024, 1900, 7):
        sizes = drawn_sizes(rng, tokens, top_k, scored, held)
        group_of_row = np.repeat(np.arange(held), sizes)
        for tm in (16, 64, 128, 256, 512):
            brute = len({(g, r // tm) for r, g in enumerate(group_of_row)})
            assert tile_visits(sizes, tm) == brute
    assert tile_visits([0, 0], 256) == 0 and tile_visits([5, 0, 9, 4], 8) == 5
    sizes = drawn_sizes(rng, 1460, top_k, scored, held)
    tm = _gmm_tiling(2048 * top_k, 2048, 2048)[0]
    assert tile_visits(sizes, tm) * tm < 0.7 * tile_visits(sizes, 512) * 512


def test_megablox_at_a_chosen_tiling_is_ragged_dot():
    """The Pallas kernel (interpreted off the TPU) at the tiling the
    chooser gives a toy call, a whole stack with the layer's groups among
    zeros as grouped_matmul hands it over, against jax.lax.ragged_dot on
    the layer's own slice: the rows that belong to a group."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    from runbooks_tpu.models.moe import _gmm_tiling

    ks = jax.random.split(jax.random.key(1), 2)
    lhs = jax.random.normal(ks[0], (64, 256), jnp.float32)
    stack = jax.random.normal(ks[1], (2, 4, 256, 384), jnp.float32)
    sizes = jnp.asarray([5, 0, 30, 11], jnp.int32)      # 18 rows in no group
    tiling = _gmm_tiling(64, 256, 384, 4)
    assert tiling == (64, 256, 384)
    for tiling in (tiling, (16, 128, 128)):             # and several tiles
        whole = gmm(lhs, stack.reshape(8, 256, 384),
                    jnp.zeros(8, jnp.int32).at[4:].set(sizes),
                    preferred_element_type=jnp.float32, tiling=tiling,
                    interpret=True)
        own = jax.lax.ragged_dot(lhs, stack[1], sizes,
                                 preferred_element_type=jnp.float32)
        np.testing.assert_allclose(np.asarray(whole[:46]),
                                   np.asarray(own[:46]), atol=1e-4)


def test_decode_never_expands_the_cache_to_heads():
    """The absorbed path's lowered program holds no tensor with a (view,
    heads) pair of axes at the key or value width: nothing per-head is
    made of the cache."""
    import re

    cfg = toy(moe_experts_held=8)
    p = jax.eval_shape(lambda: seeded(cfg, 0))
    cache = jax.eval_shape(lambda: KVCache.create(cfg, 2, 71,
                                                  trash_slot=True))
    i32 = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    text = jax.jit(lambda p, c, t, q: forward(
        cfg, p, t, positions=q, cache=c, cache_view=56)).lower(
        p, cache, i32, i32).as_text()
    H, widths = cfg.num_heads, (cfg.qk_nope_head_dim, cfg.v_head_dim,
                                cfg.q_head_dim,
                                cfg.qk_nope_head_dim + cfg.v_head_dim)
    shapes = set(re.findall(r"tensor<([0-9x]+)x[a-z]", text))
    for shape in shapes:
        dims_ = [int(d) for d in shape.split("x")]
        per_head = (56 in dims_ or 72 in dims_) and H in dims_ \
            and dims_[-1] in widths and len(dims_) >= 4
        assert not per_head, shape
    # ... while the latent view itself is there.
    assert f"2x56x{cfg.latent_width}" in " ".join(shapes)


# --------------------------------------------------------------------------
# The flash forward with a value width of its own
# --------------------------------------------------------------------------

def test_flash_forward_value_width_against_xla():
    """(192, 192, 128), block ranges from positions that start past 0 and
    leave a padded tail; against ops/attention."""
    from runbooks_tpu.ops.attention import (
        dot_product_attention,
        make_attention_mask,
    )
    from runbooks_tpu.ops.flash_attention import (
        UnequalWidthsBackward,
        flash_attention,
    )

    b, sq, sk, h = 2, 64, 96, 2
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, sq, h, 192), jnp.float32)
    k = jax.random.normal(ks[1], (b, sk, h, 192), jnp.float32)
    v = jax.random.normal(ks[2], (b, sk, h, 128), jnp.float32)
    q_pos = jnp.asarray(np.stack([np.arange(20, 20 + sq),
                                  np.where(np.arange(sq) < 40,
                                           np.arange(sq), -1)]), jnp.int32)
    kv_pos = jnp.broadcast_to(jnp.arange(sk, dtype=jnp.int32), (b, sk))
    scale = 192 ** -0.5 * 1.3689 ** 2
    got = flash_attention(q, k, v, q_pos, kv_pos, None, None, True, scale,
                          16, 32)
    assert got.shape == (b, sq, h, 128)
    want = dot_product_attention(
        q, k, v, mask=make_attention_mask(q_pos, kv_pos), scale=scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert float(jnp.abs(got[1, 40:]).max()) == 0.0     # sees no key
    with pytest.raises(UnequalWidthsBackward, match="one width"):
        jax.grad(lambda q_: flash_attention(
            q_, k, v, q_pos, kv_pos, None, None, True, scale, 16,
            32).sum())(q)


def test_flash_forward_equal_widths_still_differentiates():
    from runbooks_tpu.ops.flash_attention import flash_attention

    ks = jax.random.split(jax.random.key(1), 3)
    q, k, v = (jax.random.normal(kk, (1, 32, 2, 64), jnp.float32)
               for kk in ks)
    pos = jnp.arange(32, dtype=jnp.int32)[None]
    g = jax.grad(lambda q_: flash_attention(
        q_, k, v, pos, pos, None, None, True, None, 16, 16).sum())(q)
    assert np.isfinite(np.asarray(g)).all() and float(jnp.abs(g).max()) > 0


# --------------------------------------------------------------------------
# The engine
# --------------------------------------------------------------------------

def greedy_reference(cfg, seed, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        logits = reference_logits(cfg, seed, np.asarray(toks, np.int32))
        toks.append(int(logits[-1].argmax()))
    return toks[len(prompt):]


def test_engine_slots_at_different_lengths_and_a_reused_slot():
    from runbooks_tpu.serve.engine import InferenceEngine, Request

    cfg = toy(moe_experts_held=8)
    p = seeded(cfg, 13)
    eng = InferenceEngine(cfg, p, max_slots=2, max_seq_len=64,
                          decode_chunk=4)
    prompts = [tokens_for(cfg, 7, 6).tolist(), tokens_for(cfg, 25, 7).tolist(),
               tokens_for(cfg, 12, 8).tolist()]   # the third reuses a slot
    reqs = [Request(prompt_tokens=list(q), max_tokens=m, temperature=0.0)
            for q, m in zip(prompts, (3, 9, 5))]
    eng.generate(reqs)
    for q, r in zip(prompts, reqs):
        seq = np.asarray(q + r.output_tokens, np.int32)
        logits = reference_logits(cfg, 13, seq)
        rows = np.arange(len(q) - 1, len(seq) - 1)
        gap = logits[rows].max(-1) - logits[rows, r.output_tokens]
        assert len(r.output_tokens) == r.max_tokens and gap.max() <= TOL
    stats = eng.moe_stats()
    here, elsewhere = sum(stats["expert_tokens"]), stats["elsewhere"]
    # Real tokens only: prompts + every decoded token but each request's
    # last (sampled, never fed back), x top-4 x 2 sparse layers.
    fed = sum(len(q) for q in prompts) + sum(m - 1 for m in (3, 9, 5))
    assert here + elsewhere == fed * 4 * 2
    assert 0.3 < here / (here + elsewhere) < 0.7      # 8 of 16 experts
    assert stats["hits"]["decode"] <= stats["calls"]["decode"]
    # Rows to the experts and back: toy rows are under one row tile, so
    # every forward's window is all its rows — a prefill bucket's tokens
    # x top-4, a decode step's 2 slots x top-4 — a sparse layer.
    forwards = {"prefill": stats["all_rows"]["prefill"] // 2,
                "decode": stats["all_rows"]["decode"] // 2}
    assert forwards["prefill"] == 3 and forwards["decode"] % 4 == 0
    assert stats["rows_moved"]["decode"] == forwards["decode"] * 2 * 2 * 4
    assert stats["rows_moved"]["prefill"] == sum(
        eng._bucket_for(len(q)) for q in prompts) * 4 * 2
    occ = eng.kv_occupancy()
    assert occ["latent_cache_bytes"] == occ["kv_pool_bytes"] > 0
    groups = eng.memory_groups()
    assert groups["latent_cache"].shape == (3, 2, 65, cfg.latent_width)
    assert groups["kv_cache"].latent is None


@pytest.mark.parametrize("preset,held,chunk,bucket16,burst16,step", [
    ("laguna-xs.2", 32, 2048, 128, 256, 64),
    ("mimo-v2-flash", 32, 2048, 128, 256, 64),
    ("sarvam-105b", 32, 4096, 128, 256, 64),
    ("lfm2-24b-a2b", 0, 8192, 64, 512, 32)])
def test_census_names_the_rows_a_window_holds(preset, held, chunk, bucket16,
                                              burst16, step):
    """engine.moe_row_window (warmup_census, GET /debug/programs) at the
    cells' shapes: a prefill chunk's window is what the held share can
    expect of 2048 tokens x top_k, whatever the burst's rows (one row tile
    of a burst of eight buckets of 16); a bucket of 16 tokens and a decode
    step of 8 slots are one window of all their rows; nothing for a dense
    model. And the engine's count of the rows
    moved follows from a dispatch's counts: the held rows in whole
    windows, all rows where the window is all."""
    import types

    from runbooks_tpu.models.moe import rows_moved
    from runbooks_tpu.serve.engine import InferenceEngine

    def census(cfg):
        from runbooks_tpu.serve.engine import dispatch_shapes

        # A budget of two windows: every bucket has both row counts.
        return InferenceEngine.moe_row_window.func(types.SimpleNamespace(
            cfg=cfg, max_slots=8, view_buckets=(512,),
            dispatch_shapes=dispatch_shapes((16, 2048), 4096, 8)))

    cfg = get_config(preset, moe_experts_held=held)
    assert census(cfg) == {
        "prefill_b16r1": bucket16, "prefill_b16r8": burst16,
        "prefill_b2048r1": chunk, "prefill_b2048r8": chunk,
        "decode_v512": step}
    assert census(get_config("falcon-7b")) == {}
    all_rows = 2048 * cfg.moe_top_k
    eng = types.SimpleNamespace(
        cfg=cfg, _moe_counts=np.zeros(cfg.moe_experts_here + 1, np.int64),
        _moe_peak=0, _moe_hits={"prefill": [0, 0], "decode": [0, 0]},
        _moe_rows={"prefill": [0, 0], "decode": [0, 0]})
    # Two sparse layers of one [1, 2048] prefill: one held row over a
    # window, and none.
    counts = np.zeros((2, cfg.moe_experts_here + 1), np.int32)
    counts[0, 0], counts[0, 1] = chunk // 2 + 1, chunk // 2
    InferenceEngine._count_moe(eng, "prefill", [(counts, 2)], 2048)
    if chunk == all_rows:
        assert eng._moe_rows["prefill"] == [2 * all_rows, 2]
    else:
        assert eng._moe_rows["prefill"] == [2 * chunk, 0]
        assert rows_moved(cfg, 2048, chunk) == (chunk, False)
        assert rows_moved(cfg, 2048, all_rows) == (all_rows, False)
    # A decode dispatch of 4 steps: all 8 slots' rows a step and layer.
    InferenceEngine._count_moe(eng, "decode", [(counts, 2)], 8, steps=4)
    assert eng._moe_rows["decode"] == [4 * 2 * step, 4 * 2]


@pytest.mark.parametrize("options,text", [
    (dict(speculative="ngram"), "speculative decoding"),
    (dict(adapter_pool=2), "adapter pool"),
    (dict(quantize_kv=True), "quantize_kv"),
])
def test_engine_refuses_by_mechanism(options, text):
    from runbooks_tpu.serve.engine import InferenceEngine

    cfg = toy(moe_experts_held=8)
    p = seeded(cfg, 0)
    with pytest.raises(ValueError, match=f"{text}.*latent"):
        InferenceEngine(cfg, p, max_slots=2, max_seq_len=64, **options)


def test_engine_refuses_paging_prefixes_and_a_tensor_mesh():
    from runbooks_tpu.parallel.mesh import MeshConfig, make_mesh
    from runbooks_tpu.serve.engine import InferenceEngine
    from runbooks_tpu.serve.paging import PagedInferenceEngine

    cfg = toy(moe_experts_held=8)
    p = seeded(cfg, 0)
    with pytest.raises(ValueError, match="kv_paging: paged.*latent"):
        PagedInferenceEngine(cfg, p, max_slots=2, max_seq_len=64)
    eng = InferenceEngine(cfg, p, max_slots=2, max_seq_len=64)
    with pytest.raises(ValueError, match="prefix registration.*latent"):
        eng.register_prefix(list(range(40)))
    with pytest.raises(ValueError, match="prefix registration.*latent"):
        eng.warmup(rows=(1,), prefix_build=True)
    mesh = make_mesh(MeshConfig(data=4, tensor=2, fsdp=1))
    with pytest.raises(ValueError, match="tensor mesh axis.*latent"):
        InferenceEngine(cfg, p, max_slots=2, max_seq_len=64, mesh=mesh)
    with jax.set_mesh(mesh):
        with pytest.raises(NotImplementedError, match="tensor mesh axis"):
            forward(cfg, p, jnp.zeros((2, 8), jnp.int32))
    with pytest.raises(NotImplementedError, match="no head axis"):
        KVCache.create(cfg, 2, 32, quantize_kv=True)
