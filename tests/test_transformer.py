"""Model forward-pass correctness tests.

Mirrors the reference's test philosophy (SURVEY.md §4: hermetic, no cloud/
hardware deps) — everything runs on the 8-device virtual CPU platform from
conftest.py.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbooks_tpu.models.config import get_config
from runbooks_tpu.models.transformer import KVCache, forward, init_params
from tests.cache_paths import (
    MODE_VIEW_IDS,
    MODES_AND_VIEWS,
    greedy_chunk,
    worst_gap,
)


def tiny(family: str, **over):
    base = get_config(family)
    return dataclasses.replace(base, **{**dict(
        vocab_size=256, hidden_size=64,
        intermediate_size=128 if not base.gated_mlp else 96,
        num_layers=2, num_heads=4,
        num_kv_heads=2 if base.num_kv_heads < base.num_heads else 4,
        head_dim=16, max_seq_len=64,
        dtype="float32",  # exact-math tests; bf16 noise tested separately
    ), **over})


FAMILIES = ["llama2-7b", "falcon-7b", "opt-125m"]


@pytest.mark.parametrize("family", FAMILIES)
def test_forward_shapes_finite(family):
    cfg = tiny(family)
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    logits, cache = forward(cfg, params, tokens)
    assert cache is None
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("family", FAMILIES)
def test_causality(family):
    cfg = tiny(family)
    params = init_params(cfg, jax.random.key(0))
    t1 = jax.random.randint(jax.random.key(1), (1, 12), 0, cfg.vocab_size)
    t2 = t1.at[0, -1].set((t1[0, -1] + 1) % cfg.vocab_size)
    l1, _ = forward(cfg, params, t1)
    l2, _ = forward(cfg, params, t2)
    np.testing.assert_allclose(l1[0, :-1], l2[0, :-1], rtol=2e-4, atol=2e-4)
    assert not np.allclose(l1[0, -1], l2[0, -1])


@pytest.mark.parametrize("family", FAMILIES)
def test_kv_cache_matches_full_forward(family):
    cfg = tiny(family)
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 10), 0, cfg.vocab_size)

    full_logits, _ = forward(cfg, params, tokens)

    # Chunked prefill (6 tokens) + token-by-token decode.
    cache = KVCache.create(cfg, batch=2, max_len=16)
    logits_pre, cache = forward(cfg, params, tokens[:, :6], cache=cache)
    got = [logits_pre]
    for i in range(6, 10):
        step_logits, cache = forward(cfg, params, tokens[:, i:i + 1], cache=cache)
        got.append(step_logits)
    cached_logits = jnp.concatenate(got, axis=1)
    np.testing.assert_allclose(full_logits, cached_logits, rtol=2e-5, atol=2e-5)
    assert int(cache.index) == 10


# One KV head for all query heads, and two for four: the two ratios the
# cache paths are driven at (tests/cache_paths.py; the hybrid's and the
# int8 pool's cases are in test_hybrid_model.py and test_quantization.py).
KV_RATIOS = {"mqa": lambda: tiny("falcon-7b", num_kv_heads=1),
             "gqa": lambda: tiny("llama2-7b")}


@pytest.mark.parametrize("mode,view", MODES_AND_VIEWS, ids=MODE_VIEW_IDS)
@pytest.mark.parametrize("kv", list(KV_RATIOS))
def test_cache_write_modes_and_views_match_full_forward(kv, mode, view):
    cfg = KV_RATIOS[kv]()
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 12), 0,
                                cfg.vocab_size)
    assert worst_gap(cfg, params, tokens, mode, view) < 2e-5


# Recorded from the parent of PR 27 (ed1d630: cache leaves scanned as
# xs/ys), float32, this machine's CPU: threading the leaves through the
# scan's carry instead moves bytes differently and computes the same.
PARENT_GREEDY = {
    "mqa": [[220, 30], [143, 61], [116, 61], [116, 1], [41, 30],
            [132, 199], [132, 199], [132, 199]],
    "gqa": [[171, 147], [212, 87], [87, 8], [109, 16], [109, 74],
            [148, 94], [109, 141], [147, 0]],
}


@pytest.mark.parametrize("kv", list(KV_RATIOS))
def test_decode_chunk_greedy_tokens_are_the_parents(kv):
    cfg = KV_RATIOS[kv]()
    params = init_params(cfg, jax.random.key(0))
    assert greedy_chunk(cfg, params) == PARENT_GREEDY[kv]


def test_packed_segments_are_isolated():
    cfg = tiny("llama2-7b")
    params = init_params(cfg, jax.random.key(0))
    a = jax.random.randint(jax.random.key(1), (1, 5), 0, cfg.vocab_size)
    b = jax.random.randint(jax.random.key(2), (1, 7), 0, cfg.vocab_size)

    packed = jnp.concatenate([a, b], axis=1)
    segs = jnp.asarray([[1] * 5 + [2] * 7], jnp.int32)
    positions = jnp.asarray([list(range(5)) + list(range(7))], jnp.int32)
    lp, _ = forward(cfg, params, packed, positions=positions, segment_ids=segs)

    la, _ = forward(cfg, params, a)
    lb, _ = forward(cfg, params, b)
    np.testing.assert_allclose(lp[0, :5], la[0], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(lp[0, 5:], lb[0], rtol=2e-5, atol=2e-5)


def test_padding_segment_zero_is_masked():
    cfg = tiny("llama2-7b")
    params = init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (1, 8), 0, cfg.vocab_size)
    segs = jnp.asarray([[1, 1, 1, 1, 0, 0, 0, 0]], jnp.int32)
    l1, _ = forward(cfg, params, toks, segment_ids=segs)
    # Changing padding tokens must not change real-token logits.
    toks2 = toks.at[0, 5].set((toks[0, 5] + 3) % cfg.vocab_size)
    l2, _ = forward(cfg, params, toks2, segment_ids=segs)
    np.testing.assert_allclose(l1[0, :4], l2[0, :4], rtol=1e-5, atol=1e-5)


def test_remat_matches_no_remat():
    cfg = tiny("llama2-7b")
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (1, 8), 0, cfg.vocab_size)
    l1, _ = forward(cfg, params, tokens)
    l2, _ = forward(cfg, params, tokens, remat=True)
    np.testing.assert_allclose(l1, l2, rtol=1e-6, atol=1e-6)


def test_save_attn_out_policy_matches_full_remat():
    # The selective policy (save only the named attn_out tensor) must not
    # change numerics — forward or gradients — vs full remat and no remat.
    cfg = tiny("llama2-7b")
    sel = dataclasses.replace(cfg, remat_policy="save_attn_out")
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab_size)

    def loss(c, p):
        logits, _ = forward(c, p, tokens, remat=True)
        return jnp.mean(jax.nn.log_softmax(logits)[..., 0])

    l1, g1 = jax.value_and_grad(lambda p: loss(cfg, p))(params)
    l2, g2 = jax.value_and_grad(lambda p: loss(sel, p))(params)
    np.testing.assert_allclose(l1, l2, rtol=1e-6, atol=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_unknown_remat_policy_raises():
    cfg = dataclasses.replace(tiny("llama2-7b"), remat_policy="bogus")
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (1, 8), 0, cfg.vocab_size)
    with pytest.raises(ValueError, match="unknown remat_policy"):
        forward(cfg, params, tokens, remat=True)


def test_bf16_forward_close_to_fp32():
    cfg32 = tiny("llama2-7b")
    cfg16 = dataclasses.replace(cfg32, dtype="bfloat16")
    params = init_params(cfg32, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (1, 8), 0, cfg32.vocab_size)
    l32, _ = forward(cfg32, params, tokens)
    l16, _ = forward(cfg16, params, tokens)
    # bf16 activations should track fp32 within a few percent on a tiny model.
    assert float(jnp.max(jnp.abs(l32 - l16))) < 0.15


def test_param_count_matches_config():
    from runbooks_tpu.models.config import ModelConfig

    for family in FAMILIES:
        cfg = tiny(family)
        params = init_params(cfg, jax.random.key(0))
        n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
        assert n == cfg.num_params, f"{family}: {n} != {cfg.num_params}"
