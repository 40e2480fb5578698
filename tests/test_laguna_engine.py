"""The cache and the engine under `debug-laguna` (tests/test_laguna.py has
the model against its reference and says what the tolerances mean): prefill
then decode through the ring and the K/V leaves at 6 and 8 heads on 2 KV
heads, the flash cached prefill, the serving engine's slots, census,
counters and refusals. A file of its own so that the suite's workers share
the load."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbooks_tpu.models.config import RING_MARGIN
from runbooks_tpu.models.transformer import KVCache, forward, init_params
from tests.test_laguna import (
    FULL,
    SLIDING,
    TOL,
    reference_logits,
    seeded,
    tokens_for,
    toy,
)


@pytest.fixture(autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def test_prefill_then_decode_through_the_ring_matches_reference():
    """Two rows of different lengths prefilled in one padded call
    (position-scatter mode, padding parked), then decoded a token at a
    time for more than two turns of the 16-slot ring: every logit equals
    the reference's full forward without a cache. Full layers read 6 heads
    on the K/V leaves, window layers 8 on the ring, both on 2 KV heads."""
    cfg = toy(moe_experts_held=8)
    p = seeded(cfg, 7)
    seqs = [tokens_for(cfg, 62, 1), tokens_for(cfg, 51, 2)]
    n_pre = [22, 11]
    max_len, bucket, view = 72, 32, 64
    cache = KVCache.create(cfg, 2, max_len, trash_slot=True)
    ring = cfg.sliding_window + RING_MARGIN
    assert cache.ring_k.shape == cache.ring_v.shape == (3, 2, ring, 2, 16)
    assert cache.k.shape == cache.v.shape == (2, 2, max_len + 1, 2, 16)
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


def test_flash_prefill_equals_the_xla_one_and_fills_the_ring():
    """The cached prefill on the flash path (window layers: the call's own
    keys at groups of 4; full layers: the cache view at groups of 3) equals
    the XLA one, logits and ring alike."""
    base = toy(moe_experts_held=8, flash_block_q=16, flash_block_k=16)
    p = init_params(base, jax.random.key(7))
    s = tokens_for(base, 27, 3)
    toks, pos = np.zeros((1, 32), np.int32), np.full((1, 32), 40, np.int32)
    toks[0, :27], pos[0, :27] = s, np.arange(27)
    out, rings = {}, {}
    for impl in ("xla", "flash"):
        cfg = dataclasses.replace(base, attention_impl=impl)
        cache = KVCache.create(cfg, 1, 40, trash_slot=True)
        out[impl], cache = forward(
            cfg, p, jnp.asarray(toks), positions=jnp.asarray(pos),
            cache=cache, token_mask=jnp.asarray(pos < 40))
        rings[impl] = np.asarray(cache.ring_k)
    np.testing.assert_allclose(out["flash"][0, :27], out["xla"][0, :27],
                               atol=TOL)
    np.testing.assert_allclose(rings["flash"], rings["xla"], atol=TOL)


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
    # The census is by kind: whole groups, 3 heads a step on full layers
    # and 4 on window layers.
    assert eng.flash_head_block and all(
        kinds == {FULL: 3, SLIDING: 4}
        for kinds in eng.flash_head_block.values())
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
    assert occ["kv_ring_bytes"] == 2 * 3 * 2 * 16 * 2 * 16 * 4
    # The window counters are a head and window layer, whatever the kind's
    # head count (a reader multiplies by the kind's heads): every prompt
    # sits in a 32-token bucket of two 16-key blocks; a query block sees
    # its own block and the one before it.
    fams = obs_metrics.REGISTRY.render()
    total = lambda text, name: sum(  # noqa: E731
        float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
        if line.startswith(name + "{"))
    # Since this engine's requests: the registry is the process's.
    read = lambda name: total(fams, name) - total(before, name)  # noqa: E731
    assert read("serve_window_blocks_visited_total") == 3 * 3
    assert read("serve_window_scores_visited_total") == 9 * 16 * 16
    assert read("serve_window_scores_needed_total") == sum(
        min(t + 1, 8) for q in prompts for t in range(len(q)))


@pytest.mark.parametrize("options,text", [
    (dict(speculative="ngram"), "speculative decoding"),
    (dict(adapter_pool=2), "adapter pool"),
    (dict(quantize_kv=True), "quantize_kv"),
    ("paged", "kv_paging: paged"),
    ("prefix", "prefix registration"),
])
def test_engine_refusals_hold_for_this_model_too(options, text):
    from runbooks_tpu.serve.engine import InferenceEngine

    cfg = toy(moe_experts_held=8)
    p = seeded(cfg, 0)
    kw = dict(max_slots=2, max_seq_len=64)
    with pytest.raises(ValueError, match=f"{text}.*sliding"):
        if options == "paged":
            from runbooks_tpu.serve.paging import PagedInferenceEngine

            PagedInferenceEngine(cfg, p, **kw)
        elif options == "prefix":
            InferenceEngine(cfg, p, **kw).register_prefix(list(range(40)))
        else:
            InferenceEngine(cfg, p, **kw, **options)
