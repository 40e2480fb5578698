"""Window layers with a sink and a ring cache beside full layers of another
KV head count, keys wider than values, a rotary over part of a head, a
leading dense layer and experts held as a share, against the plain
reference (benchmark/reference/mimo_v2_flash.py;
docs/window-full-models.md).

The toy preset `debug-window-full` keeps the published RATIOS (8 query
heads on 4 window / 2 full KV heads, keys 24 wide of which 8 rotate on
values 16 wide, window 8, 3 window layers a full one). Seeded random
weights on the CPU; LOGITS are compared, never sampled tokens.
Activations run in float32 under "highest" matmul precision, weights are
the bfloat16 the recipe stores, so what separates program and reference is
the order of float32 sums: every tolerance below is 2e-4 absolute on
logits of order 1 for that reason, unless it says otherwise.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbooks_tpu.models.config import RING_MARGIN, get_config
from runbooks_tpu.models.moe import moe_block
from runbooks_tpu.models.transformer import KVCache, forward, init_params
from runbooks_tpu.ops.flash_attention import (
    PAD_POS,
    WindowSinkBackward,
    block_counts,
    block_ranges,
    flash_attention,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4


def load_reference():
    path = os.path.join(ROOT, "benchmark", "reference", "mimo_v2_flash.py")
    spec = importlib.util.spec_from_file_location("ref_mimo_v2_flash", path)
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
    return get_config("debug-window-full", **kw)


def as_run_of(cfg) -> dict:
    """The reference's description of a ModelConfig of this family."""
    period = [1 if k == "sliding_attention" else 0
              for k in cfg.layer_pattern]
    lead = cfg.leading_dense_layers
    return {
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "vocab_size": cfg.vocab_size, "layernorm_epsilon": cfg.norm_eps,
        "num_attention_heads": cfg.num_heads, "head_dim": cfg.head_dim,
        "v_head_dim": cfg.value_head_dim, "rotary_dim": cfg.rotary_dim,
        "num_key_value_heads": cfg.num_kv_heads,
        "swa_num_key_value_heads": cfg.sliding_num_kv_heads,
        "rope_theta": cfg.rope_theta, "swa_rope_theta": cfg.sliding_rope_theta,
        "add_full_attention_sink_bias": False,
        "add_swa_attention_sink_bias": cfg.sliding_sink,
        "sliding_window": cfg.sliding_window,
        "attention_value_scale": cfg.attn_value_scale,
        "hybrid_layer_pattern": [0] * lead + period * cfg.num_periods,
        "moe_layer_freq": [0] * lead + [1] * (cfg.num_layers - lead),
        "num_hidden_layers": cfg.num_layers, "first_k_dense_replace": lead,
        "num_experts_routed": cfg.moe_num_experts,
        "num_experts": cfg.moe_experts_here,
        "first_expert_held": cfg.moe_experts_first,
        "num_experts_per_tok": cfg.moe_top_k,
        "moe_intermediate_size": cfg.moe_width,
        "router_bias_std": cfg.moe_router_bias_std,
        "routed_scaling_factor": None}


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
# The preset, the config's checks and counts, the seeded recipe
# --------------------------------------------------------------------------

def test_preset_holds_the_published_sizes():
    cfg = get_config("mimo-v2-flash")
    assert (cfg.hidden_size, cfg.num_heads, cfg.head_dim, cfg.v_head_dim,
            cfg.rotary_dim, cfg.intermediate_size, cfg.vocab_size) == (
        4096, 64, 192, 128, 64, 16384, 152576)
    assert cfg.attn_shape("full_attention")[:4] == (4, 5000000.0, False, 0)
    assert cfg.attn_shape("sliding_attention")[:4] == (8, 10000.0, True, 128)
    assert cfg.layer_pattern == ("sliding_attention",) * 5 + (
        "full_attention",) and cfg.leading_dense_layers == 1
    assert (cfg.moe_num_experts, cfg.moe_top_k, cfg.moe_width,
            cfg.moe_router, cfg.moe_router_bias, cfg.moe_shared_experts,
            cfg.moe_routed_scale) == (256, 8, 2048, "sigmoid", True, 0, 1.0)
    assert cfg.attn_value_scale == 0.707 and cfg.norm_eps == 1e-5
    assert cfg.ring_len == 128 + RING_MARGIN
    # The benchmark's cut: ISSUE 32's arithmetic, 5.85 G parameters.
    cut = get_config("mimo-v2-flash", num_layers=7, moe_experts_held=32,
                     vocab_size=19072)
    attn_full = 4096 * 12288 + 4096 * 4 * (192 + 128) + 8192 * 4096
    attn_win = 4096 * 12288 + 4096 * 8 * (192 + 128) + 8192 * 4096 + 64
    sparse = 32 * 3 * 4096 * 2048 + 4096 * 256 + 256 + 2 * 4096
    want = (2 * 19072 * 4096 + 4096
            + attn_full + 3 * 4096 * 16384 + 2 * 4096
            + 5 * (attn_win + sparse) + attn_full + sparse)
    assert cut.num_params == want and 5.84e9 < want < 5.86e9


def test_counts_are_by_kind_and_a_window_is_not_charged_the_context():
    cfg = toy()
    p = init_params(cfg, jax.random.key(0))
    assert cfg.num_params == sum(a.size for a in jax.tree.leaves(p))
    # Doubling the context adds scores to the 2 full layers only.
    h, d, dv = cfg.num_heads, cfg.head_dim, cfg.value_head_dim
    more = cfg.flops_per_token(256) - cfg.flops_per_token(128)
    assert more == 2 * 128 * h * (d + dv) * cfg.layers_of("full_attention")


@pytest.mark.parametrize("over,text", [
    (dict(layer_types=("sliding_attention",) * 2), "exactly one"),
    (dict(sliding_window=0), "sliding_window >= 1"),
    (dict(sliding_num_kv_heads=3), "does not divide"),
    (dict(rotary_dim=7), "rotary_dim"),
    (dict(num_layers=6), "whole number of periods"),
])
def test_config_refuses(over, text):
    with pytest.raises(ValueError, match=text):
        toy(**over)


def test_seeded_weights_are_the_references_bit_for_bit():
    cfg = toy(moe_experts_held=4, moe_experts_first=8, num_layers=9)
    p = seeded(cfg, 11)
    w = ref.init_weights(as_run_of(cfg), 11)
    n = cfg.layer_pattern.count("sliding_attention")
    names = {"wq": ("attn", "wq"), "wk": ("attn", "wk"),
             "wv": ("attn", "wv"), "wo": ("attn", "wo"),
             "router": ("moe", "router"),
             "router_bias": ("moe", "router_bias"),
             "exp_gate": ("moe", "wi_gate"), "exp_up": ("moe", "wi_up"),
             "exp_down": ("moe", "wo")}
    pairs = {"embed": p["embed"], "head": p["head"]}
    for name, (a, b) in names.items():
        pairs[name] = p["layers"][a][b]
        # Window layer l of the reference's layer order is period l // n,
        # position l % n of the program's stacks.
        pairs["win_" + name] = jnp.stack(
            [p["window_layers"][l % n][a][b][l // n]
             for l in range(cfg.layers_of("sliding_attention"))])
    pairs["win_sink"] = jnp.stack(
        [p["window_layers"][l % n]["attn"]["sink"][l // n]
         for l in range(cfg.layers_of("sliding_attention"))])
    lead = p["leading_layers"]
    for name in ("wq", "wk", "wv", "wo"):
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
    # Neither the sinks nor the selection bias are zero: leaving either
    # out must be seen.
    assert float(jnp.abs(pairs["win_sink"].astype(jnp.float32)).mean()) > 0.3
    assert float(jnp.abs(pairs["win_router_bias"]
                         .astype(jnp.float32)).mean()) > 0.005


def test_other_presets_keep_their_seeded_weights():
    """The window layers take keys of their own (fold_in 3), and the FFN's
    keys are dealt in the order they were: a dense preset's, the softmax
    MoE's and the sparse-latent preset's leaves are drawn as before."""
    for name, over, leaf in (
            ("debug", {}, ("mlp", "wi_up", 8)),
            ("debug", dict(moe_num_experts=4, moe_top_k=2),
             ("moe", "wo", 9)),
            ("debug-sparse-latent", {}, ("moe", "router_bias", 13))):
        cfg = get_config(name, **over)
        p = seeded(cfg, 3)
        keys = jax.random.split(jax.random.key(3), 16)
        a, b, k = leaf
        got = np.asarray(p["layers"][a][b], np.float32)
        drawn = np.asarray(jax.random.normal(keys[k], got.shape))
        scale = {"wi_up": cfg.hidden_size ** -0.5,
                 "wo": cfg.moe_width ** -0.5, "router_bias": 0.05}[b]
        # (Eager against jitted: equal to a float32 rounding.)
        np.testing.assert_allclose(got, drawn * scale, rtol=1e-6)


# --------------------------------------------------------------------------
# The forward pass against the reference
# --------------------------------------------------------------------------

@pytest.mark.parametrize("share,impl", [((0, 0), "xla"), ((4, 8), "xla"),
                                        ((8, 0), "flash")],
                         ids=["whole", "share", "flash"])
def test_forward_matches_reference(share, impl):
    """Leading dense layer, two periods of 3 window + 1 full layers, sparse
    FFNs, no cache: the window mask, the sink, both KV head counts, 24 / 16
    widths and the partial rotary on both sides. `flash`: the kernel with
    ranges from a window (key blocks of 16, 5 windows of context)."""
    held, first = share
    cfg = toy(moe_experts_held=held, moe_experts_first=first, num_layers=9,
              attention_impl=impl, flash_block_q=16, flash_block_k=16)
    toks = tokens_for(cfg, 40)
    p = seeded(cfg, 5)
    got, _ = forward(cfg, p, jnp.asarray(toks)[None])
    want = reference_logits(cfg, 5, toks)
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=TOL)


def test_the_sink_the_window_and_the_value_scale_are_seen():
    """Each of the new mechanisms changes the logits by far more than the
    tolerance: a program that drops one fails the comparison above."""
    cfg = toy(moe_experts_held=8)
    toks = jnp.asarray(tokens_for(cfg, 40))[None]
    p = seeded(cfg, 5)
    base, _ = forward(cfg, p, toks)
    no_sink = jax.tree.map(lambda a: a, p)
    for pos in no_sink["window_layers"]:
        pos["attn"]["sink"] = jnp.full_like(pos["attn"]["sink"], -1e30)
    no_bias = jax.tree.map(lambda a: a, p)
    for tree in no_bias["window_layers"] + [no_bias["layers"]]:
        tree["moe"]["router_bias"] = jnp.zeros_like(
            tree["moe"]["router_bias"])
    changed = {
        "sink": forward(cfg, no_sink, toks)[0],
        "selection bias": forward(cfg, no_bias, toks)[0],
        "window": forward(dataclasses.replace(cfg, sliding_window=64), p,
                          toks)[0],
        "value scale": forward(dataclasses.replace(
            cfg, attn_value_scale=1.0), p, toks)[0],
        "partial rotary": forward(dataclasses.replace(
            cfg, rotary_dim=0), p, toks)[0],
        "window rotary base": forward(dataclasses.replace(
            cfg, sliding_rope_theta=0.0), p, toks)[0]}
    for name, logits in changed.items():
        assert float(jnp.abs(logits - base).max()) > 100 * TOL, name


def test_prefill_then_decode_through_the_ring_matches_reference():
    """Two rows of different lengths prefilled in one padded call
    (position-scatter mode, padding parked), then decoded a token at a
    time for more than two turns of the 16-slot ring: every logit equals
    the reference's full forward without a cache. The prefill of 22 and 11
    tokens is longer than the ring (row 0) and shorter (row 1)."""
    cfg = toy(moe_experts_held=8)
    p = seeded(cfg, 7)
    seqs = [tokens_for(cfg, 62, 1), tokens_for(cfg, 51, 2)]
    n_pre = [22, 11]
    max_len, bucket, view = 72, 32, 64
    cache = KVCache.create(cfg, 2, max_len, trash_slot=True)
    ring = cfg.sliding_window + RING_MARGIN
    assert cache.ring_k.shape == (3, 2, ring, 4, 24)
    assert cache.ring_v.shape == (3, 2, ring, 4, 16)
    assert cache.k.shape == (2, 2, max_len + 1, 2, 24)
    assert cache.v.shape == (2, 2, max_len + 1, 2, 16)
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
    for i in range(40):
        at = np.array([n + i for n in n_pre], np.int32)
        t = np.array([[s[a]] for s, a in zip(seqs, at)], np.int32)
        logits, cache = step(cache, jnp.asarray(t), jnp.asarray(at[:, None]))
        for r in range(2):
            np.testing.assert_allclose(np.asarray(logits[r, 0]),
                                       want[r][at[r]], atol=TOL)


@pytest.mark.parametrize("n,exact", [(RING_MARGIN + 1, True),
                                     (RING_MARGIN + 2, False)],
                         ids=["margin", "beyond"])
def test_ring_margin_at_its_edge(n, exact):
    """A call of n tokens behind 30 cached ones (append-at-index mode).
    RING_MARGIN + 1 tokens go through the ring and are exact: the last of
    them overwrites the slot a window below the first one's oldest key.
    One token more is taken for a row's FIRST tokens (KVCache): the call
    attends its own keys alone, which is another answer — what the engine
    refuses a spliced prefix and a second prefill chunk for."""
    cfg = toy(moe_experts_held=8)
    p = seeded(cfg, 7)
    seq = tokens_for(cfg, 30 + n, 1)
    want = reference_logits(cfg, 7, seq)
    cache = KVCache.create(cfg, 1, 64)
    _, cache = forward(cfg, p, jnp.asarray(seq[:30])[None], cache=cache)
    got, cache = forward(cfg, p, jnp.asarray(seq[30:])[None], cache=cache)
    gap = np.abs(np.asarray(got[0]) - want[30:]).max()
    assert gap <= TOL if exact else gap > 100 * TOL
    assert int(cache.index) == 30 + n


def test_flash_prefill_attends_its_own_keys_and_fills_the_ring():
    """The cached prefill on the flash path (window layers: the call's own
    keys, ranges from a window; full layers: the cache view) equals the
    XLA one, logits and ring alike, and the ring holds the row's last 16
    real tokens and nothing of its padding."""
    base = toy(moe_experts_held=8, flash_block_q=16, flash_block_k=16)
    p = init_params(base, jax.random.key(7))
    s = tokens_for(base, 27, 3)
    toks, pos = np.zeros((1, 32), np.int32), np.full((1, 32), 40, np.int32)
    toks[0, :27], pos[0, :27] = s, np.arange(27)
    out, rings = {}, {}
    for impl in ("xla", "flash"):
        cfg = dataclasses.replace(base, attention_impl=impl)
        cache = KVCache.create(cfg, 1, 40, trash_slot=True)
        cache = dataclasses.replace(cache,
                                    ring_k=jnp.full_like(cache.ring_k, 77.0))
        out[impl], cache = forward(
            cfg, p, jnp.asarray(toks), positions=jnp.asarray(pos),
            cache=cache, token_mask=jnp.asarray(pos < 40))
        rings[impl] = np.asarray(cache.ring_k)
    np.testing.assert_allclose(out["flash"][0, :27], out["xla"][0, :27],
                               atol=TOL)
    np.testing.assert_allclose(rings["flash"], rings["xla"], atol=TOL)
    # Positions 11 .. 26 were written (slot = position mod 16); nothing of
    # the ring still holds the 77 it started with, and no slot took the
    # padding's key twice.
    assert not (rings["xla"] == 77.0).any()


def test_chunked_decode_program_with_a_slot_freed_and_readmitted():
    """make_prefill_fn + make_decode_fn (the chunk loop, a finished row
    parked), then the freed slot prefilled with ANOTHER, shorter prompt
    while its ring still holds the first occupant's tokens: each greedy
    token is the reference's best at its position, or within TOL of it."""
    from runbooks_tpu.serve.engine import make_decode_fn, make_prefill_fn

    cfg = toy(moe_experts_held=8)
    p = seeded(cfg, 9)
    max_len, slots, chunk = 64, 2, 4
    prefill = jax.jit(make_prefill_fn(cfg, max_len + 1))
    decode = jax.jit(make_decode_fn(cfg, chunk, max_len, max_len, max_len))
    zeros, ones = jnp.zeros(slots), jnp.ones(slots)
    pool = KVCache.create(cfg, slots, max_len, trash_slot=True)

    def admit(pool, prompts, rows, rng):
        toks = np.zeros((len(rows), 32), np.int32)
        pos = np.full((len(rows), 32), max_len, np.int32)
        for r, s in enumerate(prompts):
            toks[r, :len(s)], pos[r, :len(s)] = s, np.arange(len(s))
        n = len(rows)
        first, pool, rng, (counts, _) = prefill(
            p, pool, jnp.asarray(toks), jnp.asarray(pos),
            jnp.asarray(rows, jnp.int32),
            jnp.asarray([len(s) - 1 for s in prompts], jnp.int32), rng,
            zeros[:n], zeros[:n].astype(jnp.int32), ones[:n])
        # Real tokens only, x top-4 x 4 sparse layers (3 window + 1 full).
        assert int(counts.sum()) == sum(map(len, prompts)) * 4 * 4
        assert counts.shape == (4, 9)
        return first, pool, rng

    def check(prompt, served):
        seq = np.concatenate([prompt, served]).astype(np.int32)
        logits = reference_logits(cfg, 9, seq)
        rows = np.arange(len(prompt) - 1, len(seq) - 1)
        gap = logits[rows].max(-1) - logits[rows, served]
        assert gap.max() <= TOL, gap

    prompts = [tokens_for(cfg, 29, 4), tokens_for(cfg, 21, 5)]
    first, pool, rng = admit(pool, prompts, [0, 1], jax.random.key(0))
    served = [[int(t)] for t in first]
    lengths = jnp.asarray([len(s) for s in prompts], jnp.int32)
    for remaining in ([9, 3], [5, 0]):     # row 1 stops mid-chunk, parks
        alive = jnp.asarray([r > 0 for r in remaining])
        out, valid, (tok, lengths, _, _), pool, rng, _ = decode(
            p, pool, first, lengths, rng, zeros, zeros.astype(jnp.int32),
            ones, jnp.full(slots, -1, jnp.int32),
            jnp.asarray(remaining, jnp.int32), alive)
        out, valid, first = np.asarray(out), np.asarray(valid), tok
        for r in range(slots):
            served[r] += [int(out[k, r]) for k in range(chunk)
                          if valid[k, r]]
    assert [len(s) for s in served] == [9, 4]
    for r in range(slots):
        check(prompts[r], served[r])
    # Slot 1 changes hands: 7 tokens, fewer than a window, under a ring
    # that holds 16 of the first occupant's.
    again = tokens_for(cfg, 7, 6)
    first1, pool, rng = admit(pool, [again], [1], rng)
    out, valid, _, pool, rng, _ = decode(
        p, pool, jnp.asarray([0, int(first1[0])], jnp.int32),
        jnp.asarray([0, 7], jnp.int32), rng, zeros,
        zeros.astype(jnp.int32), ones, jnp.full(slots, -1, jnp.int32),
        jnp.asarray([0, 4], jnp.int32), jnp.asarray([False, True]))
    check(again, [int(first1[0])] + [int(t) for t in np.asarray(out)[:, 1]])


# --------------------------------------------------------------------------
# The flash forward: window, sink, ranges
# --------------------------------------------------------------------------

def _qkv(b=2, s=96, h=8, kvh=4, d=24, dv=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (b, s, h, d)),
            jax.random.normal(ks[1], (b, s, kvh, d)),
            jax.random.normal(ks[2], (b, s, kvh, dv)),
            jax.random.normal(ks[3], (h,)))


@pytest.mark.parametrize("how", ["plain", "window hides nothing",
                                 "sink weighs nothing"])
def test_flash_without_window_or_sink_is_the_plain_forward_bit_for_bit(how):
    """A call that passes neither takes the kernel the parent had (no sink
    operand, the whole kv grid): its jaxpr names no sink tile. And the new
    paths add nothing of their own: a window wider than the sequence, or a
    sink of -1e30, give the plain call's output bit for bit."""
    q, k, v, _ = _qkv()
    pos = jnp.broadcast_to(jnp.arange(96)[None], (2, 96))
    args = (q, k, v, pos, pos, None, None, True, None, 32, 16)
    plain = flash_attention(*args)
    if how == "plain":
        text = str(jax.make_jaxpr(lambda *a: flash_attention(
            *a, pos, pos, None, None, True, None, 32, 16))(q, k, v))
        assert "f32[4,2,8,128]" not in text      # the sinks' tiles
        # (b, kv heads, query blocks, head blocks of a group of 2, kv blocks)
        assert "(2, 4, 3, 1, 6)" in text
        return
    over = (dict(window=4096) if how == "window hides nothing"
            else dict(sink=jnp.full((8,), -1e30)))
    np.testing.assert_array_equal(
        np.asarray(flash_attention(*args, **over)), np.asarray(plain))


@pytest.mark.parametrize("blocks", [(16, 8), (32, 16), (16, 32), (96, 96)])
def test_flash_window_and_sink_against_xla(blocks):
    """Offset queries (a cached prefill's layout: 64 queries at positions
    32 .. 95 against 96 keys), a padded tail on both sides, KV-head groups
    of 2, unequal widths, a sink: the kernel equals the XLA path."""
    from runbooks_tpu.ops.attention import (
        dot_product_attention,
        make_attention_mask,
    )

    q, k, v, sink = _qkv()
    q = q[:, 32:]
    q_pos = np.broadcast_to(np.arange(32, 96)[None], (2, 64)).copy()
    kv_pos = np.broadcast_to(np.arange(96)[None], (2, 96)).copy()
    q_pos[1, 50:], kv_pos[1, 82:] = -1, PAD_POS        # row 1 is shorter
    q_pos, kv_pos = jnp.asarray(q_pos), jnp.asarray(kv_pos)
    got = flash_attention(q, k, v, q_pos, kv_pos, None, None, True, None,
                          *blocks, window=8, sink=sink)
    seen = make_attention_mask(q_pos, kv_pos) & (
        (q_pos[:, None, :, None] - kv_pos[:, None, None, :]) < 8)
    want = dot_product_attention(q, k, v, mask=seen, sink=sink)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    # A parked query sees nothing and comes out exactly 0.
    assert not np.asarray(got[1, 50:]).any()


@pytest.mark.parametrize("xp", [np, jnp], ids=["numpy", "jax"])
@pytest.mark.parametrize("seed", range(6))
def test_block_ranges_with_a_window_never_drop_a_visible_pair(xp, seed):
    """Random layouts (offsets, several documents a row with positions
    that restart, padding, sk != sq, block sizes that divide nothing): for
    every (query, key) pair the window mask lets through, the key's block
    lies inside the query block's range."""
    rng = np.random.default_rng(seed)
    b, sq, sk = 3, int(rng.integers(20, 70)), int(rng.integers(20, 70))
    bq, bk, window = (int(rng.choice([8, 16, 24])),
                      int(rng.choice([8, 16, 24])), int(rng.integers(1, 20)))
    segments = seed % 2 == 1
    if segments:
        sk = sq
        seg = np.sort(rng.integers(1, 4, (b, sq)), axis=1)
        seg[:, -int(rng.integers(0, 6)) or sq:] = 0          # padded tail
        pos = np.stack([np.concatenate(
            [np.arange(n) for n in np.bincount(row)[np.unique(row)]])
            for row in seg])
        q_pos = kv_pos = pos.astype(np.int32)
        q_seg = kv_seg = seg.astype(np.int32)
    else:
        q_pos = (rng.integers(0, 40, (b, 1)) + np.arange(sq)).astype(np.int32)
        kv_pos = np.broadcast_to(np.arange(sk), (b, sk)).astype(np.int32)
        kv_pos = np.where(np.arange(sk) >= rng.integers(sk // 2, sk + 1,
                                                        (b, 1)),
                          PAD_POS, kv_pos).astype(np.int32)
        q_seg = kv_seg = None
    give = (lambda a: None if a is None else xp.asarray(a))
    lo, hi = block_ranges(give(q_pos), give(kv_pos), give(q_seg),
                          give(kv_seg), bq, bk, True, window)
    assert isinstance(lo, np.ndarray) == (xp is np)
    lo, hi = np.asarray(lo), np.asarray(hi)
    age = q_pos[:, :, None].astype(np.int64) - kv_pos[:, None, :]
    seen = (age >= 0) & (age < window) & (kv_pos[:, None, :] < PAD_POS)
    if segments:
        seen &= (q_seg[:, :, None] == kv_seg[:, None, :]) \
            & (kv_seg[:, None, :] != 0)
    rows, qs, ks = np.nonzero(seen)
    bq, bk = min(bq, sq), min(bk, sk)
    assert len(rows) and (lo[rows, qs // bq] <= ks // bk).all() \
        and (ks // bk <= hi[rows, qs // bq]).all()
    # And a window never widens what causality alone visits.
    lo0, hi0 = map(np.asarray, block_ranges(
        give(q_pos), give(kv_pos), give(q_seg), give(kv_seg), bq, bk, True))
    assert (np.maximum(hi - lo + 1, 0) <= np.maximum(hi0 - lo0 + 1, 0)).all()


def test_block_counts_follow_the_window():
    """A 2048-token prompt under a window of 128: at key blocks of 1024
    the window saves nothing; at 128 the forward visits 2 blocks a query
    block of 128 (5 of 512), on a grid that walks 3 (6) and not 16."""
    pos = np.arange(2048, dtype=np.int32)[None]
    assert block_counts(pos, pos, None, None, 512, 1024, True, 128)[0] == \
        block_counts(pos, pos, None, None, 512, 1024, True)[0] - 1
    assert block_counts(pos, pos, None, None, 128, 128, True, 128) == (
        31, 16 * 3)
    assert block_counts(pos, pos, None, None, 512, 128, True, 128) == (
        4 + 5 * 3, 4 * 6)


# --------------------------------------------------------------------------
# The expert layer's shares (the model-configs guide, section 4)
# --------------------------------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """held = 2 of a 16-expert window layer's FFN at first = 0, 2 .. 14:
    the eight parts sum to the uncut reference's whole layer (no shared
    expert to count once), and one share is the reference given the same
    share."""
    cfg = toy()
    p = jax.tree.map(lambda a: a[0],
                     seeded(cfg, 0)["window_layers"][1]["moe"])
    x = jax.random.normal(jax.random.key(100), (2, 12, cfg.hidden_size),
                          jnp.float32)

    def reference_layer(first, held):
        dm = ref.dims(dict(as_run_of(cfg), num_experts=held,
                           first_expert_held=first))
        lw = {"router": p["router"].astype(jnp.float32),
              "router_bias": p["router_bias"].astype(jnp.float32),
              "exp_gate": p["wi_gate"][first:first + held],
              "exp_up": p["wi_up"][first:first + held],
              "exp_down": p["wo"][first:first + held]}
        y, _ = ref.sparse_ffn(dm, x.reshape(-1, x.shape[-1]), lw, ref.matmul)
        return np.asarray(y).reshape(x.shape)

    total, held_counts = 0.0, 0
    for first in range(0, 16, 2):
        share = {**p, **{k: p[k][first:first + 2]
                         for k in ("wi_gate", "wi_up", "wo")}}
        y, _, counts = moe_block(cfg, share, x, held=first)
        total = total + np.asarray(y)
        held_counts += int(counts[:-1].sum())
        if first == 6:
            np.testing.assert_allclose(np.asarray(y), reference_layer(6, 2),
                                       atol=TOL)
    np.testing.assert_allclose(total, reference_layer(0, 16), atol=TOL)
    assert held_counts == 24 * cfg.moe_top_k


# --------------------------------------------------------------------------
# The engine
# --------------------------------------------------------------------------

def test_engine_slots_at_different_lengths_and_a_reused_slot():
    from runbooks_tpu.obs import metrics as obs_metrics
    from runbooks_tpu.serve.engine import InferenceEngine, Request

    cfg = toy(moe_experts_held=8, attention_impl="flash", flash_block_q=16,
              flash_block_k=16)
    p = seeded(cfg, 13)
    eng = InferenceEngine(cfg, p, max_slots=2, max_seq_len=64,
                          decode_chunk=4)
    prompts = [tokens_for(cfg, n, seed).tolist()   # the third reuses a slot
               for n, seed in ((17, 6), (29, 7), (20, 8))]
    reqs = [Request(prompt_tokens=list(q), max_tokens=m, temperature=0.0)
            for q, m in zip(prompts, (3, 21, 5))]
    before = obs_metrics.REGISTRY.render()
    eng.generate(reqs)
    for q, r in zip(prompts, reqs):
        seq = np.asarray(q + r.output_tokens, np.int32)
        logits = reference_logits(cfg, 13, seq)
        rows = np.arange(len(q) - 1, len(seq) - 1)
        gap = logits[rows].max(-1) - logits[rows, r.output_tokens]
        assert len(r.output_tokens) == r.max_tokens and gap.max() <= TOL
    occ = eng.kv_occupancy()
    ring_bytes = 2 * 3 * 2 * 16 * 4 * (24 + 16) * 4 // 2   # k and v, f32
    assert occ["kv_ring_bytes"] == 3 * 2 * 16 * 4 * (24 + 16) * 4 \
        and ring_bytes and occ["kv_pool_bytes"] > occ["kv_ring_bytes"]
    # The window layers' flash forward, counted on the host: every prompt
    # sits in a 32-token bucket of two 16-key blocks; a query block sees
    # its own block and the one before it.
    fams = obs_metrics.REGISTRY.render()
    total = lambda text, name: sum(  # noqa: E731
        float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
        if line.startswith(name + "{"))
    # Since this engine's requests: the registry is the process's.
    read = lambda name: total(fams, name) - total(before, name)  # noqa: E731
    assert read("serve_window_blocks_visited_total") == 3 * 3
    assert read("serve_window_blocks_grid_total") == 3 * 2 * 2
    assert read("serve_window_scores_visited_total") == 9 * 16 * 16
    assert read("serve_window_scores_needed_total") == sum(
        min(t + 1, 8) for q in prompts for t in range(len(q)))


@pytest.mark.parametrize("options,text", [
    (dict(speculative="ngram"), "speculative decoding"),
    (dict(adapter_pool=2), "adapter pool"),
    (dict(quantize_kv=True), "quantize_kv"),
    ("paged", "kv_paging: paged"),
    ("tensor", "tensor mesh axis"),
    ("prefix", "prefix registration"),
    ("warm_prefix", "prefix registration"),
])
def test_engine_refuses_by_mechanism(options, text):
    from runbooks_tpu.serve.engine import InferenceEngine

    cfg = toy(moe_experts_held=8)
    p = seeded(cfg, 0)
    kw = dict(max_slots=2, max_seq_len=64)
    with pytest.raises(ValueError, match=f"{text}.*sliding"):
        if options == "paged":
            from runbooks_tpu.serve.paging import PagedInferenceEngine

            PagedInferenceEngine(cfg, p, **kw)
        elif options == "tensor":
            from runbooks_tpu.parallel.mesh import MeshConfig, make_mesh

            InferenceEngine(cfg, p, **kw, mesh=make_mesh(
                MeshConfig(data=4, tensor=2, fsdp=1)))
        elif options == "prefix":
            InferenceEngine(cfg, p, **kw).register_prefix(list(range(40)))
        elif options == "warm_prefix":
            InferenceEngine(cfg, p, **kw).warmup(rows=(1,),
                                                 prefix_build=True)
        else:
            InferenceEngine(cfg, p, **kw, **options)


@pytest.mark.parametrize("what", ["flash backward", "tensor mesh",
                                  "adapters", "int8 ring", "convert"])
def test_forward_and_tools_refuse_by_name(what):
    cfg = toy(moe_experts_held=8)
    toks = jnp.zeros((2, 16), jnp.int32)
    if what == "flash backward":
        q, k, v, sink = _qkv(s=32)
        pos = jnp.broadcast_to(jnp.arange(32)[None], (2, 32))
        for over in (dict(window=8), dict(sink=sink)):
            with pytest.raises(WindowSinkBackward, match="forward only"):
                jax.grad(lambda q: flash_attention(
                    q, k, v, pos, pos, None, None, True, None, 16, 16,
                    **over).sum())(q)
    elif what == "tensor mesh":
        from runbooks_tpu.parallel.mesh import MeshConfig, make_mesh

        p = seeded(cfg, 0)
        with jax.set_mesh(make_mesh(MeshConfig(data=4, tensor=2, fsdp=1))):
            with pytest.raises(NotImplementedError,
                               match="tensor mesh axis.*sliding"):
                forward(cfg, p, toks)
    elif what == "adapters":
        p = seeded(cfg, 0)
        with pytest.raises(NotImplementedError, match="adapter pools"):
            forward(cfg, p, toks, adapters=({}, jnp.zeros(2, jnp.int32)))
    elif what == "int8 ring":
        with pytest.raises(NotImplementedError, match="ring"):
            KVCache.create(cfg, 2, 32, quantize_kv=True)
    else:
        from runbooks_tpu.models.convert import convert

        with pytest.raises(NotImplementedError, match="sliding_attention"):
            convert(cfg, {})


def test_a_program_reports_the_heads_a_step_its_kernels_got(monkeypatch):
    """What the engine and the trainer publish (flash_heads_per_step, from
    a configuration and two lengths) is what head_block answers while the
    program is traced: a cached prefill of 32 tokens on 41 cache slots,
    full layers 8 heads on 2 (the cache view's keys), window layers 8 on 4
    with a sink (the call's own keys). The budget is shrunk until a full
    layer's group of 4 no longer fits a step."""
    import runbooks_tpu.ops.flash_attention as fa
    from runbooks_tpu.models.transformer import flash_heads_per_step

    cfg = toy(moe_experts_held=8, flash_block_q=16, flash_block_k=16,
              attention_impl="flash")
    monkeypatch.setattr(fa, "VMEM_BUDGET_BYTES", 300 * 1024)
    want = flash_heads_per_step(cfg, 32, 41)
    assert want == {"full_attention": 2, "sliding_attention": 2}
    asked, answer = [], fa.head_block
    monkeypatch.setattr(fa, "head_block", lambda *a: asked.append(
        (a, answer(*a))) or asked[-1][1])
    p = jax.eval_shape(lambda: init_params(cfg, jax.random.key(7)))
    cache = jax.eval_shape(lambda: KVCache.create(cfg, 1, 40,
                                                  trash_slot=True))
    toks = jax.ShapeDtypeStruct((1, 32), jnp.int32)
    jax.eval_shape(
        lambda p, cache, toks, pos: forward(
            cfg, p, toks, positions=pos, cache=cache, token_mask=pos < 40),
        p, cache, toks, toks)
    # (group, block_q, block_k, d, dv, sink, window) -> G, once a layer.
    assert {(a[0], a[5], a[6]): g for a, g in asked} == {
        (4, False, 0): want["full_attention"],
        (2, True, cfg.sliding_window): want["sliding_attention"]}


def test_a_program_reports_the_blocks_its_kernels_got(monkeypatch):
    """flash_blocks (what the engine and the trainer publish, from a
    configuration and two lengths) is what block_shape answers while the
    program is traced, by kind of layer: a cached prefill of 32 tokens on
    41 cache slots with no size given — full layers the cache view's keys
    (the lengths clamp 512 x 1024 to 32 x 41), window layers the call's
    own keys under the window's rule (256 x 512 clamped to 32 x 32)."""
    import runbooks_tpu.ops.flash_attention as fa
    from runbooks_tpu.models.transformer import flash_blocks

    cfg = toy(moe_experts_held=8, attention_impl="flash")
    want = flash_blocks(cfg, 32, 41)
    assert want == {"full_attention": {"fwd": [32, 41]},
                    "sliding_attention": {"fwd": [32, 32]}}
    assert flash_blocks(cfg, 1024, 1025)["sliding_attention"] == {
        "fwd": [256, 512]}
    asked, answer = [], fa.block_shape
    monkeypatch.setattr(fa, "block_shape", lambda *a: asked.append(
        (a, answer(*a))) or asked[-1][1])
    p = jax.eval_shape(lambda: init_params(cfg, jax.random.key(7)))
    cache = jax.eval_shape(lambda: KVCache.create(cfg, 1, 40,
                                                  trash_slot=True))
    toks = jax.ShapeDtypeStruct((1, 32), jnp.int32)
    jax.eval_shape(
        lambda p, cache, toks, pos: forward(
            cfg, p, toks, positions=pos, cache=cache, token_mask=pos < 40),
        p, cache, toks, toks)
    # (kernel, sq, sk, n_rep, window, given q, given k) -> blocks.
    assert {(a[0], a[4]): list(b) for a, b in asked} == {
        ("fwd", 0): want["full_attention"]["fwd"],
        ("fwd", cfg.sliding_window): want["sliding_attention"]["fwd"]}


def test_metrics_and_the_census_carry_the_heads_a_step():
    """`serve_flash_heads_per_step{program, kind}` on /metrics and
    `flash_head_block` of the engine (what warmup_census and
    /debug/programs repeat): one entry a prefill bucket that takes the
    flash path, the whole groups at these sizes (4 full, 2 window). (The
    XLA path's empty census entry: tests/test_engine_weight_layout.py.)"""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from runbooks_tpu.serve.api import create_server
    from runbooks_tpu.train.data import ByteTokenizer

    cfg = toy(moe_experts_held=8, attention_impl="flash")
    params = init_params(cfg, jax.random.key(1))
    app = create_server(cfg, params, ByteTokenizer(), max_slots=2,
                        max_seq_len=64, warmup=False)
    engine = app["worker"].engine
    want = {f"prefill_b{b}": {"full_attention": 4, "sliding_attention": 2}
            for b in engine.prefill_buckets if b >= 16}
    assert want and engine.flash_head_block == want

    async def scrape():
        async with TestClient(TestServer(app)) as client:
            return await (await client.get("/metrics")).text()

    try:
        text = asyncio.run(scrape())
    finally:
        app["worker"].stop()
    lines = {ln.rsplit(" ", 1)[0]: float(ln.rsplit(" ", 1)[1])
             for ln in text.splitlines()
             if ln.startswith("serve_flash_heads_per_step{")}
    assert lines == {
        f'serve_flash_heads_per_step{{kind="{kind}",program="{program}"}}':
        float(g) for program, kinds in want.items()
        for kind, g in kinds.items()}
    # ... and the block shape each was compiled with, a side a line: the
    # lengths clamp the rule's answers at these buckets.
    blocks = {f"prefill_b{b}": {
        "full_attention": {"fwd": [b, 65]},
        "sliding_attention": {"fwd": [b, b]}}
        for b in engine.prefill_buckets if b >= 16}
    assert engine.flash_blocks == blocks
    shapes = {ln.rsplit(" ", 1)[0]: float(ln.rsplit(" ", 1)[1])
              for ln in text.splitlines()
              if ln.startswith("serve_flash_block_shape{")}
    assert shapes == {
        f'serve_flash_block_shape{{kind="{kind}",program="{program}",'
        f'side="{side}"}}': float(block)
        for program, kinds in blocks.items()
        for kind, kernels in kinds.items()
        for side, block in zip("qk", kernels["fwd"])}
